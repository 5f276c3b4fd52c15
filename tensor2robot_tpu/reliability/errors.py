"""Exception classes of the fault-tolerance layer.

One module with no intra-package imports so retry/, fault_injection/,
quarantine/ and the wired-up layers (trainer/, data/, predictors/) can all
share the same types without cycles.

The classification that matters operationally:

  * transient (retry): ``InjectedFault`` and real ``OSError``/``TimeoutError``
    from flaky filesystems — bounded retry with backoff, then ``RetryError``.
  * data-local (skip + budget): ``CorruptRecordError`` — quarantine the
    record (or the rest of the file when framing is lost) and keep going
    until ``CorruptionBudgetExceeded``.
  * run-level (stop or roll back): ``NonFiniteLossError``,
    ``TrainingPreempted``.
"""

from __future__ import annotations

from typing import Optional


class InjectedFault(IOError):
  """A failure forced by the FaultInjector at a named site.

  Subclasses IOError so the default RetryPolicy treats injected faults as
  the transient I/O errors they simulate.
  """

  def __init__(self, site: str, call_index: int):
    super().__init__(
        'Injected fault at site {!r} (call #{})'.format(site, call_index))
    self.site = site
    self.call_index = call_index


class RetryError(IOError):
  """All retry attempts exhausted; ``last`` holds the final cause."""

  def __init__(self, site: Optional[str], attempts: int,
               last: BaseException):
    super().__init__(
        'Gave up after {} attempt(s){}: {}'.format(
            attempts, ' at site {!r}'.format(site) if site else '', last))
    self.site = site
    self.attempts = attempts
    self.last = last


class CorruptRecordError(IOError):
  """One unreadable record (bad CRC, truncation, injected corruption)."""

  def __init__(self, path: str, reason: str,
               record_index: Optional[int] = None):
    at = '' if record_index is None else ' (record #{})'.format(record_index)
    super().__init__('Corrupt TFRecord {} in {}{}'.format(reason, path, at))
    self.path = path
    self.reason = reason
    self.record_index = record_index


class CorruptionBudgetExceeded(IOError):
  """skip_corrupt_records ran out of budget — fail loudly, name the file."""

  def __init__(self, path: str, scope: str, limit: int):
    super().__init__(
        'Corrupt-record budget exhausted: more than {} corrupt record(s) '
        '{} — last offender: {}. The data is damaged beyond the configured '
        'tolerance; repair or exclude it.'.format(
            limit, 'in one file' if scope == 'file' else 'across the run',
            path))
    self.path = path
    self.scope = scope
    self.limit = limit


class CorruptCheckpointError(IOError):
  """A checkpoint step whose on-disk state is visibly damaged
  (half-written commit, retention GC mid-read). Transient from the
  caller's perspective: skip to another step or wait for the next one."""

  def __init__(self, directory: str, step: int, detail: str):
    super().__init__(
        'Checkpoint step {} in {} is damaged ({}).'.format(
            step, directory, detail))
    self.directory = directory
    self.step = step


class NonFiniteLossError(RuntimeError):
  """The train loss went NaN/Inf and the policy says stop (or the
  rollback budget ran out)."""

  def __init__(self, step: int, detail: str = ''):
    super().__init__(
        'Non-finite train loss at step {}{}'.format(
            step, ': ' + detail if detail else ''))
    self.step = step


class TrainingPreempted(Exception):
  """SIGTERM/SIGINT received; an emergency checkpoint was committed
  before this was raised."""

  def __init__(self, signum: int, step: int):
    super().__init__(
        'Training preempted by signal {} at step {} (emergency checkpoint '
        'committed).'.format(signum, step))
    self.signum = signum
    self.step = step


# What the retrying wrappers treat as transient by default. IOError is an
# alias of OSError (and FileNotFoundError/InjectedFault subclass it);
# TimeoutError is separate on some paths.
TRANSIENT_IO_ERRORS = (OSError, TimeoutError)

# What a checkpoint CONSUMER may skip past (fall back to an older step,
# keep polling): transient restore failures come out of the retrying
# CheckpointManager exclusively as these two. Deliberately narrower than
# TRANSIENT_IO_ERRORS — a bare OSError out of an eval/data path (missing
# dataset, exhausted corruption budget) is NOT a checkpoint problem and
# must propagate.
CHECKPOINT_SKIP_ERRORS = (RetryError, CorruptCheckpointError)
