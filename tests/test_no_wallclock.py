"""The monotonic-clock invariant, enforced instead of remembered.

PR 2/3 established the discipline: every deadline, duration, steps/sec
window, and rate limit in the trainer/reliability/observability layers
uses ``time.perf_counter``/``time.monotonic``, because ``time.time()``
jumps (NTP step, DST) and a jumped clock turns a 30 s checkpoint wait
into an instant timeout — or a negative steps/sec. Until now that
invariant was a code-review convention; this test makes it a failing
build.

``time.time()`` IS still legitimate for *timestamps that cross process
boundaries* (telemetry.jsonl record times, heartbeat files, TensorBoard
event wall_time, file-mtime comparisons): those must interoperate with
other hosts' wall clocks. Each such call site must carry the literal
marker ``wall-clock`` in a comment ON THE SAME LINE — the annotation is
the reviewer-visible claim "this is a timestamp, not a duration". Any
unannotated ``time.time()`` in the scanned trees fails this test with
the offending file:line list.
"""

import os

# 'data' joined the scan with the pipeline X-ray instrumentation (ISSUE
# 7): the stage busy/idle accounting in pipeline.py / input_generators.py
# / device_feed.py / native_loader.py is all durations, which must come
# from time.perf_counter (the C++ twin uses std::chrono::steady_clock).
# 'serving' joined with ISSUE 8: batching deadlines, SLO latencies, and
# report windows are durations — a wall-clock jump must not dispatch an
# under-age batch or fabricate a p99.
# ISSUE 9's fleet module (observability/fleet.py + fleet_sim.py) is
# covered by the existing 'observability' entry; its heartbeat-age and
# recovery-marker comparisons are genuine cross-process timestamps and
# carry the annotation, while the recovery PHASES (restore, first step)
# stay perf_counter durations measured within one process.
# 'replay' joined with ISSUE 11: sample deadlines, report windows, and
# client retry/wait budgets are durations; the only timestamps it emits
# go through TelemetryLogger (already annotated).
# 'envs' + 'rl' joined with ISSUE 12: the acting-step timing, report
# windows, swap-poll cadence and run deadlines of the closed
# actor<->learner loop (rl/loop.py) are all durations — a wall-clock
# jump must not fabricate an acting-step regression or end a run early;
# the vectorized envs are pure functions and must stay clock-free.
# 'compile' joined with ISSUE 13: the CompiledArtifact load/compile
# timings and the coldstart time-to-first-step measurement are
# durations a wall-clock jump must not corrupt — a fabricated
# negative compile_ms would poison the cold-start trajectory table.
# ISSUE 14's fleet modules (serving/router.py, serving/fleet.py) ride
# the existing 'serving' entry: replica
# heartbeat ages, ejection staleness, scale-up time-to-ready and the
# fleet report windows are ALL durations (monotonic by construction —
# an NTP step must not eject a healthy replica or fake a scale-up
# latency); fleet telemetry timestamps go through TelemetryLogger
# (already annotated).
# 'elastic' joined with ISSUE 15: lease-renewal pacing, boundary-segment
# deadlines and the shrink-ladder phase timings are durations (a
# wall-clock jump must not lapse a healthy host's lease); only the
# lease/plan STAMPS that cross process boundaries are wall-clock, and
# they carry the annotation.
SCANNED_PACKAGES = ('trainer', 'reliability', 'observability', 'data',
                    'serving', 'replay', 'envs', 'rl', 'compile',
                    'elastic')
MARKER = 'wall-clock'

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
PACKAGE_ROOT = os.path.join(REPO_ROOT, 'tensor2robot_tpu')


def _python_files():
  for package in SCANNED_PACKAGES:
    root = os.path.join(PACKAGE_ROOT, package)
    assert os.path.isdir(root), 'scanned package vanished: {}'.format(root)
    for dirpath, _, filenames in os.walk(root):
      for filename in sorted(filenames):
        if filename.endswith('.py'):
          yield os.path.join(dirpath, filename)


def _code_portion(line: str) -> str:
  """The executable part of a source line (everything before '#').

  Good enough here: none of the scanned files embed '#' inside string
  literals on a time.time() line, and a false positive fails loudly
  with the line text so the fix is obvious either way.
  """
  return line.split('#', 1)[0]


def test_no_unannotated_wallclock_reads():
  offenders = []
  for path in _python_files():
    with open(path, encoding='utf-8') as f:
      for lineno, line in enumerate(f, start=1):
        if 'time.time()' not in _code_portion(line):
          continue  # comment/docstring mention, or no call at all
        if MARKER in line:
          continue  # annotated timestamp: allowed by contract
        rel = os.path.relpath(path, REPO_ROOT)
        offenders.append('{}:{}: {}'.format(rel, lineno, line.strip()))
  assert not offenders, (
      'time.time() in duration/deadline code (use time.perf_counter / '
      'time.monotonic, or annotate a genuine cross-process timestamp '
      "with a '# wall-clock' comment on the same line):\n  "
      + '\n  '.join(offenders))


def test_scanner_sees_the_annotated_sites():
  """Guards the scanner itself: the known timestamp sites must be found
  (an over-eager refactor that stops scanning, or a marker typo, would
  otherwise turn the whole check into a silent no-op)."""
  annotated = 0
  for path in _python_files():
    with open(path, encoding='utf-8') as f:
      for line in f:
        if 'time.time()' in _code_portion(line) and MARKER in line:
          annotated += 1
  # telemetry_file.py (record + heartbeat), metrics.py (event wall_time +
  # filename stamp), doctor.py (heartbeat age), autoprofiler.py (mtime
  # filter), fleet.py (heartbeat-age observation, fleet summary,
  # recovery marker stamp + recovery total) — at least these ten exist
  # today.
  assert annotated >= 10, (
      'expected >= 10 annotated wall-clock sites, found {} — scanner or '
      'markers broken'.format(annotated))
