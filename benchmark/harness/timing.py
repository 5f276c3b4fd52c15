"""Clock arithmetic the end-to-end metrics rest on."""

import os
import time


def process_age_s():
  """Seconds since this process was started (not since Python came up)."""
  try:
    with open('/proc/self/stat', encoding='ascii') as f:
      start_ticks = int(f.read().rsplit(')', 1)[1].split()[19])
    with open('/proc/uptime', encoding='ascii') as f:
      uptime_s = float(f.read().split()[0])
    return uptime_s - start_ticks / os.sysconf('SC_CLK_TCK')
  except (OSError, ValueError, IndexError):
    return time.perf_counter() - _IMPORTED_AT


_IMPORTED_AT = time.perf_counter()


def whole_step_rate(steps, examples_per_step, first_sync_s, last_sync_s,
                    chips):
  """examples/s/chip over WHOLE steps: ``steps`` steps were dispatched after
  the device sync at ``first_sync_s`` and had all finished at the sync at
  ``last_sync_s``. No step is counted in part, so the rate does not move in
  quanta of one step over the window."""
  if steps <= 0 or last_sync_s <= first_sync_s or chips <= 0:
    raise ValueError('no whole step was timed')
  return steps * examples_per_step / (last_sync_s - first_sync_s) / chips


def window_closed(now_s, first_sync_s, seconds):
  """The run ends at the first step boundary at or after ``seconds``."""
  return now_s - first_sync_s >= seconds
