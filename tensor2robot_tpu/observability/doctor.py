"""Ranked run diagnosis from telemetry.jsonl + forensics reports.

The reading half of the forensics loop, for the operator who just got
paged: ``t2r_telemetry doctor <model_dir>`` answers "what is wrong with
this run" from the files alone — no jax import, no live process, works
on any box that sees the filesystem (the same contract as the rest of
``bin/t2r_telemetry``).

Evidence consumed, in rough severity order:

  * heartbeat.json age (watchdog staleness thresholds);
  * the run's last lifecycle record (``run_abort`` / ``preempted``);
  * the latest goodput split, with the data-loss case attributed across
    HISTORY — "prefetch queue empty in 81% of samples" needs the gauge
    series the trainer embeds in every ``train`` record, not one sample;
  * recompile + shape-signature gauges (the device_feed invariant);
  * device/host memory gauge trends across train records;
  * ``anomaly`` records the in-process watchdog wrote;
  * the newest forensics report's top op + occupancy;
  * the newest roofline attribution (report or ``roofline`` record):
    under the MFU floor the verdict names the gating memory-bound op
    family and its fusion headroom (CRITICAL on a live run).

``diagnose`` returns ``Finding`` dicts ranked most-severe-first; the CLI
prints them and exits non-zero only on CRITICAL findings so the command
can gate automation without lying about missing telemetry (missing
files are a diagnosis, not an error).
"""

from __future__ import annotations

import os
import time
from typing import Dict, List, Optional

from tensor2robot_tpu.observability import fleet as fleet_lib
from tensor2robot_tpu.observability import forensics as forensics_lib
from tensor2robot_tpu.observability import telemetry_file
from tensor2robot_tpu.observability import watchdog as watchdog_lib

__all__ = ['CRITICAL', 'WARNING', 'INFO', 'OK', 'diagnose',
           'format_findings']

CRITICAL = 'critical'
WARNING = 'warning'
INFO = 'info'
OK = 'ok'

_SEVERITY_RANK = {CRITICAL: 0, WARNING: 1, INFO: 2, OK: 3}

# Goodput losses below this fraction are not worth a finding.
_GOODPUT_FLOOR = 0.10

# MFU below this on a device with a peaks entry earns a roofline
# verdict naming the gating memory-bound family (a healthy run should
# not be under 25%).
_MFU_FLOOR = 0.25


def _finding(severity: str, message: str, **detail) -> Dict[str, object]:
  return {'severity': severity, 'message': message, 'detail': detail}


def _queue_empty_fraction(trains: List[Dict[str, object]]
                          ) -> Optional[float]:
  """Share of train samples whose prefetch queues were ALL empty."""
  sampled = 0
  empty = 0
  for record in trains:
    gauges = record.get('gauges') or {}
    depths = [value for tag, value in gauges.items()
              if tag.startswith('data/prefetch_queue_depth')]
    if not depths:
      continue
    sampled += 1
    if all(value <= 0.0 for value in depths):
      empty += 1
  return (empty / sampled) if sampled else None


def _memory_trend(trains: List[Dict[str, object]], prefix: str
                  ) -> Dict[str, List[float]]:
  series: Dict[str, List[float]] = {}
  for record in trains:
    gauges = record.get('gauges') or {}
    for tag, value in gauges.items():
      if tag.startswith(prefix):
        series.setdefault(tag, []).append(float(value))
  return series


def diagnose(model_dir: str,
             now: Optional[float] = None,
             heartbeat_stale_secs: float = 300.0
             ) -> List[Dict[str, object]]:
  """All findings for one model_dir, ranked most-severe first."""
  if now is None:
    now = time.time()  # wall-clock: compared to heartbeat timestamps
  findings: List[Dict[str, object]] = []

  # Primary lifecycle stream: the lowest-index host per discover_hosts,
  # which applies the indexed-wins rule — in a model_dir holding BOTH a
  # leftover single-process telemetry.jsonl and a fleet's
  # telemetry.0.jsonl, the fleet's stream is the live one, and judging
  # run_ended from the old run would suppress live fleet CRITICALs.
  telemetry_path = os.path.join(model_dir,
                                telemetry_file.TELEMETRY_FILENAME)
  host_files = telemetry_file.discover_hosts(model_dir)
  for host in sorted(host_files):
    if host_files[host].get('telemetry'):
      telemetry_path = host_files[host]['telemetry']
      break
  records: List[Dict[str, object]] = []
  if not os.path.exists(telemetry_path) or \
      os.path.getsize(telemetry_path) == 0:
    findings.append(_finding(
        INFO, 'no telemetry.jsonl under {} — run never started its '
        'telemetry, or metrics are disabled'.format(model_dir)))
  else:
    try:
      records = telemetry_file.read_telemetry(telemetry_path)
    except ValueError as e:
      findings.append(_finding(
          WARNING, 'telemetry.jsonl is corrupt mid-file: {}'.format(e)))

  # Whole-run staleness judges the FRESHEST heartbeat across hosts: the
  # run is alive if any host is; one host gone quiet while others beat
  # is the fleet section's host_dead verdict, not a wedged run.
  beat = telemetry_file.read_heartbeat(model_dir)
  for host in sorted(host_files):
    candidate = telemetry_file.read_heartbeat(model_dir,
                                              process_index=host)
    if candidate and (beat is None or
                      candidate.get('time', 0) > beat.get('time', 0)):
      beat = candidate
  # 'serving_stop'/'replay_stop'/'rl_stop'/'serving_fleet_stop' count
  # as orderly ends: a PolicyServer, ReplayService, RL loop or serving
  # fleet that closed cleanly stops heartbeating by design, which is
  # not a wedged process. An elastic 'leave' event (ISSUE 15) is the
  # same: the host departed orderly and stopped writing by design.
  run_ended = bool(records) and (
      records[-1].get('kind') in (
          'run_end', 'run_abort', 'preempted', 'serving_stop',
          'replay_stop', 'rl_stop', 'serving_fleet_stop')
      or (records[-1].get('kind') == 'elastic'
          and records[-1].get('event') == 'leave'))
  if run_ended and beat is not None:
    findings.append(_finding(
        INFO, 'run finished ({}); heartbeat age not meaningful'.format(
            records[-1].get('kind'))))
  else:
    for anomaly in watchdog_lib.check_heartbeat(
        beat, now, stale_secs=heartbeat_stale_secs):
      findings.append(_finding(
          CRITICAL if beat is not None else INFO, anomaly.message,
          **anomaly.detail))

  trains = [r for r in records if r.get('kind') == 'train']
  last = records[-1] if records else None
  if last is not None and last.get('kind') == 'run_abort':
    findings.append(_finding(
        CRITICAL, 'run aborted at step {} with {}'.format(
            last.get('step'), last.get('error'))))
  elif last is not None and last.get('kind') == 'preempted':
    findings.append(_finding(
        WARNING, 'run was preempted at step {} (signal {}) and has not '
        'resumed'.format(last.get('step'), last.get('signum'))))

  # Goodput: rank the lost categories of the newest split, attributing
  # the data case across the whole history.
  goodput_records = [r for r in records
                     if r.get('kind') in ('train', 'run_end')
                     and r.get('goodput')]
  if goodput_records:
    latest = goodput_records[-1]
    for category, fraction in sorted(latest['goodput'].items(),
                                     key=lambda kv: -kv[1]):
      if category == 'productive' or fraction < _GOODPUT_FLOOR:
        continue
      message = 'goodput lost to {} {:.0%}'.format(category, fraction)
      if category == 'data':
        empty = _queue_empty_fraction(trains)
        if empty is not None:
          message += ' -> prefetch queue empty in {:.0%} of samples'.format(
              empty)
          if empty > 0.5:
            message += ' (host decode is the bottleneck; scale the input '
            message += 'pipeline, not the model)'
      findings.append(_finding(WARNING, message, category=category,
                               fraction=fraction))

  # Recompiles + the device_feed shape-stability invariant.
  latest_gauges: Dict[str, float] = {}
  for record in trains:
    latest_gauges.update(record.get('gauges') or {})
  recompiles = latest_gauges.get(watchdog_lib.RECOMPILE_GAUGE, 0.0)
  if recompiles > 1.0:
    findings.append(_finding(
        WARNING, 'train step compiled {:g} times — a shape-unstable batch '
        'reached the jitted step (expected exactly 1; see '
        'data/device_feed.py)'.format(recompiles), recompiles=recompiles))
  shapes = latest_gauges.get(watchdog_lib.FEED_SHAPES_GAUGE, 0.0)
  if shapes > 1.0:
    findings.append(_finding(
        WARNING, 'device feed emitted {:g} distinct batch shape '
        'signatures (must be 1)'.format(shapes)))

  # Memory trends across the sampled history.
  for tag, values in _memory_trend(
      trains, watchdog_lib.DEVICE_BYTES_GAUGE).items():
    if len(values) >= 4 and all(b > a for a, b in zip(values, values[1:])):
      findings.append(_finding(
          WARNING, '{} grew monotonically across {} samples '
          '({:.1f} -> {:.1f} MiB): leak signature'.format(
              tag, len(values), values[0] / 2**20, values[-1] / 2**20)))

  # Pipeline X-ray: the latest t2r.pipeline.v1 attribution + stalls.
  pipelines = [r for r in records if r.get('kind') == 'pipeline']
  if pipelines:
    latest = pipelines[-1]
    bottleneck = latest.get('bottleneck')
    headroom = latest.get('headroom_vs_device')
    if bottleneck and bottleneck != 'device' and headroom is not None \
        and headroom < 0.5:
      findings.append(_finding(
          WARNING, 'pipeline gated by {} at {:.0%} of the device rate '
          '(step {}): the input path, not the chip, caps e2e '
          'throughput'.format(bottleneck, headroom, latest.get('step')),
          bottleneck=bottleneck, headroom_vs_device=headroom))
    elif bottleneck:
      # Structured detail on the healthy case too: automation gates
      # (bin/check_pipeline_doctor's untransferred fixture) judge
      # detail.bottleneck / detail.headroom_vs_device, not prose.
      findings.append(_finding(
          INFO, 'pipeline@{}: gating stage {} (headroom vs device '
          '{})'.format(latest.get('step'), bottleneck,
                       'n/a' if headroom is None
                       else '{:.0%}'.format(headroom)),
          bottleneck=bottleneck, headroom_vs_device=headroom))
  stall_indices = [i for i, r in enumerate(records)
                   if r.get('kind') == 'anomaly'
                   and r.get('anomaly') == 'pipeline_stall']
  if stall_indices:
    last_index = stall_indices[-1]
    last_stall = records[last_index]
    stage = (last_stall.get('detail') or {}).get('stage', 'unknown')
    # Recovery check: a LATER pipeline record not itself flagging a
    # stall means flow resumed — one historical hiccup must not hold
    # the automation gate at exit 2 for the rest of a days-long run.
    # (The same window's train/pipeline records are co-emitted with the
    # anomaly, so only a subsequent HEALTHY window counts.)
    recovered = any(
        r.get('kind') == 'pipeline'
        and 'pipeline_stall' not in (r.get('anomalies') or [])
        for r in records[last_index + 1:])
    findings.append(_finding(
        # A CURRENTLY stalled pipeline halts training: CRITICAL while
        # the run is live and unrecovered; historical context otherwise.
        WARNING if (run_ended or recovered) else CRITICAL,
        'pipeline stalled {} time(s), last at step {}{} (gating stage: '
        '{})'.format(len(stall_indices), last_stall.get('step'),
                     ' — recovered since' if recovered else '', stage),
        stage=stage, count=len(stall_indices), recovered=recovered))

  # Serving section (ISSUE 8): kind='serving' SLO reports from a
  # PolicyServer. A p99 over the SLO in the newest evidence, while the
  # server is still live, is the one condition a serving fleet pages on.
  serving_indices = [i for i, r in enumerate(records)
                     if r.get('kind') == 'serving']
  if serving_indices:
    latest = records[serving_indices[-1]]
    breach_indices = [i for i in serving_indices
                      if records[i].get('over_slo')
                      and (records[i].get('requests') or 0) > 0]
    if breach_indices:
      last_breach = records[breach_indices[-1]]
      # Recovery check (same shape as pipeline_stall): a LATER serving
      # window that handled traffic back under the SLO means the breach
      # passed — history, not a live page. A 'serving_stop' after the
      # breach means nobody is being served out-of-SLO right now either.
      recovered = any(
          records[i].get('kind') == 'serving'
          and not records[i].get('over_slo')
          and (records[i].get('requests') or 0) > 0
          for i in range(breach_indices[-1] + 1, len(records)))
      stopped = any(r.get('kind') == 'serving_stop'
                    for r in records[breach_indices[-1] + 1:])
      findings.append(_finding(
          WARNING if (run_ended or recovered or stopped) else CRITICAL,
          'serving p99 {:.1f} ms exceeded the {:g} ms SLO in {} '
          'window(s), last at {:.1f} req/s{}'.format(
              last_breach.get('p99_ms', 0.0),
              last_breach.get('slo_ms', 0.0), len(breach_indices),
              last_breach.get('requests_per_sec', 0.0),
              ' — recovered since' if recovered
              else (' — server stopped' if stopped else ' (live)')),
          p99_ms=last_breach.get('p99_ms'),
          slo_ms=last_breach.get('slo_ms'),
          count=len(breach_indices), recovered=recovered))
    rejected = latest.get('rejected_total') or 0
    if rejected > 0:
      findings.append(_finding(
          WARNING, 'admission control shed {:g} request(s) (queue depth '
          'reached max): demand exceeds this replica\'s '
          'capacity'.format(rejected), rejected_total=rejected))
    if not breach_indices:
      findings.append(_finding(
          INFO, 'serving healthy: {:.1f} req/s, p99 {:.1f} ms vs SLO '
          '{:g} ms, batch fill {:.0%}, params v{}'.format(
              latest.get('requests_per_sec', 0.0),
              latest.get('p99_ms', 0.0), latest.get('slo_ms', 0.0),
              latest.get('batch_fill', 0.0),
              latest.get('params_version', 0))))

  # Serving-fleet section (ISSUE 14): kind='serving_fleet'
  # (t2r.serving_fleet.v1) windows from a ServingFleet router — the
  # primary stream of a fleet-shaped serving dir (the router owns
  # stream 0; replicas 1..N federate underneath). Two page-worthy
  # conditions, each NAMING the replica: a replica breaching its SLO in
  # the newest evidence while the fleet is live, and a replica ejected
  # from rotation (heartbeat stale / dead) that has not returned.
  fleet_serving = [r for r in records
                   if r.get('kind') == 'serving_fleet']
  if fleet_serving:
    latest = fleet_serving[-1]
    # Per-replica SLO breaches across the fleet history.
    breaches_by_replica: Dict[str, List[int]] = {}
    for index, record in enumerate(records):
      if record.get('kind') != 'serving_fleet':
        continue
      for replica, entry in sorted((record.get('replicas') or {}).items()):
        if entry.get('over_slo') and (entry.get('requests') or 0) > 0:
          breaches_by_replica.setdefault(replica, []).append(index)
    for replica, indices in sorted(breaches_by_replica.items()):
      last_index = indices[-1]
      entry = (records[last_index].get('replicas') or {}).get(replica, {})
      # Recovery check (the serving-section rule, per replica): a LATER
      # fleet window where THIS replica handled traffic back under its
      # SLO means the breach passed — history, not a live page.
      recovered = any(
          r.get('kind') == 'serving_fleet'
          and not ((r.get('replicas') or {}).get(replica) or {})
              .get('over_slo')
          and (((r.get('replicas') or {}).get(replica) or {})
               .get('requests') or 0) > 0
          for r in records[last_index + 1:])
      findings.append(_finding(
          WARNING if (run_ended or recovered) else CRITICAL,
          'serving fleet: replica {} p99 {:.1f} ms exceeded its {:g} ms '
          'SLO in {} window(s){} — one replica out of envelope drags '
          'every request routed to it'.format(
              replica, entry.get('p99_ms') or 0.0,
              entry.get('slo_ms') or 0.0, len(indices),
              ' — recovered since' if recovered
              else (' (run ended)' if run_ended else ' (live)')),
          kind='fleet_replica_over_slo', replica=replica,
          p99_ms=entry.get('p99_ms'), slo_ms=entry.get('slo_ms'),
          count=len(indices), recovered=recovered))
    ejected_now = [str(replica) for replica in latest.get('ejected') or []]
    if ejected_now:
      findings.append(_finding(
          WARNING if run_ended else CRITICAL,
          'serving fleet: replica{} {} ejected from rotation (heartbeat '
          'stale or dead) and {} not returned — the fleet serves on {} '
          'of {} replicas'.format(
              's' if len(ejected_now) > 1 else '',
              ', '.join(ejected_now),
              'have' if len(ejected_now) > 1 else 'has',
              latest.get('healthy_count'), latest.get('replica_count')),
          kind='fleet_replica_ejected', replicas=ejected_now,
          healthy_count=latest.get('healthy_count'),
          replica_count=latest.get('replica_count')))
    elif (latest.get('ejections_total') or 0) > 0:
      findings.append(_finding(
          WARNING, 'serving fleet: {:g} ejection(s) occurred (every '
          'ejected replica has since returned to rotation); retried '
          'requests so far: {:g}'.format(
              latest.get('ejections_total') or 0,
              latest.get('retries_total') or 0),
          kind='fleet_ejections_recovered',
          ejections_total=latest.get('ejections_total')))
    rejected = latest.get('rejected_total') or 0
    if rejected > 0:
      findings.append(_finding(
          WARNING, 'serving fleet: router shed {:g} request(s) at the '
          'door (fleet-wide pending cap): demand exceeds the replica '
          'set — scale up'.format(rejected), kind='fleet_shed',
          rejected_total=rejected))
    if not breaches_by_replica and not ejected_now:
      findings.append(_finding(
          INFO, 'serving fleet healthy: {} replica(s) ({} healthy), '
          '{:.1f} actions/s aggregate, fleet p99 {:.1f} ms vs SLO '
          '{:g} ms, versions serving {}'.format(
              latest.get('replica_count'), latest.get('healthy_count'),
              latest.get('actions_per_sec', 0.0),
              latest.get('p99_ms', 0.0), latest.get('slo_ms', 0.0),
              latest.get('versions_serving')),
          kind='fleet_healthy',
          replica_count=latest.get('replica_count'),
          healthy_count=latest.get('healthy_count'),
          actions_per_sec=latest.get('actions_per_sec'),
          p99_ms=latest.get('p99_ms'), slo_ms=latest.get('slo_ms')))

  # Replay section (ISSUE 11): kind='replay' (t2r.replay.v1) windows
  # from a ReplayService. The one condition a replay fleet pages on: a
  # shard holding examples that stopped serving draws while the service
  # as a whole still samples — every learner batch is now biased away
  # from that shard's experience, silently. Two consecutive windows
  # must agree (occupancy > 0, shard samples == 0, service samples > 0)
  # so one small-window multinomial fluke cannot page.
  replay_records = [r for r in records if r.get('kind') == 'replay']
  if replay_records:
    latest = replay_records[-1]
    stalled_shards = []
    window_pair = replay_records[-2:]
    if len(window_pair) == 2 and all(
        (r.get('samples') or 0) > 0 for r in window_pair):
      for shard, entry in sorted((latest.get('shards') or {}).items()):
        stalled = all(
            ((r.get('shards') or {}).get(shard) or {}).get(
                'occupancy_examples', 0) > 0
            and ((r.get('shards') or {}).get(shard) or {}).get(
                'samples', 0) == 0
            for r in window_pair)
        if stalled:
          stalled_shards.append(shard)
    if stalled_shards:
      findings.append(_finding(
          WARNING if run_ended else CRITICAL,
          'replay shard{} {} stalled: holding examples but served zero '
          'draws across the last 2 windows while the service sampled '
          '{}/s — learner batches are biased away from {} '
          'experience'.format(
              's' if len(stalled_shards) > 1 else '',
              ', '.join(stalled_shards),
              latest.get('samples_per_sec', 0.0),
              'their' if len(stalled_shards) > 1 else 'its'),
          kind='replay_shard_stalled', shards=stalled_shards,
          samples_per_sec=latest.get('samples_per_sec')))
    corrupt_by_shard = {
        shard: entry.get('corrupt', 0)
        for shard, entry in sorted((latest.get('shards') or {}).items())
        if entry.get('corrupt', 0) > 0}
    if corrupt_by_shard:
      findings.append(_finding(
          WARNING, 'replay quarantined {:g} corrupt append(s) ({}): a '
          'writer is shipping damaged records'.format(
              sum(corrupt_by_shard.values()),
              ', '.join('shard {} x{:g}'.format(shard, count)
                        for shard, count in corrupt_by_shard.items())),
          kind='replay_corrupt_appends', by_shard=corrupt_by_shard))
    rejected = latest.get('rejected_total') or 0
    if rejected > 0:
      findings.append(_finding(
          WARNING, 'replay admission control shed {:g} sample '
          'request(s): learners are outrunning this replica'.format(
              rejected), rejected_total=rejected))
    if not stalled_shards:
      findings.append(_finding(
          INFO, 'replay healthy: {} examples resident ({:.1f} MB, '
          '{:.0f} B/ex packed), {:.1f} appends/s, {:.1f} samples/s '
          'across {} shards'.format(
              latest.get('occupancy_examples', 0),
              (latest.get('occupancy_bytes') or 0) / 1e6,
              latest.get('bytes_per_example', 0.0),
              latest.get('appends_per_sec', 0.0),
              latest.get('samples_per_sec', 0.0),
              len(latest.get('shards') or {}))))

  # RL section (ISSUE 12): kind='rl' (t2r.rl.v1) windows from the
  # actor<->learner loop. The page-worthy condition is ONE SIDE of the
  # closed loop dying while the other runs on: an actor that stopped
  # stepping starves the learner of fresh experience (it silently
  # overfits the resident buffer); a learner that stopped stepping
  # freezes the policy while collection burns compute. Two consecutive
  # windows must agree, the side must have STARTED in an earlier window
  # — a learner still waiting for its first replay batch is a boot
  # order, not a stall — and the side must not have FINISHED its
  # configured target (the records' actor_done/learner_done flags): a
  # learner that completed --learner_steps while the actor collects on
  # is a documented healthy mode, not a page.
  rl_records = [r for r in records if r.get('kind') == 'rl']
  if rl_records:
    latest = rl_records[-1]
    window_pair = rl_records[-2:]
    actor_started = any((r.get('actor_steps') or 0) > 0
                        for r in rl_records)
    learner_started = any((r.get('learner_steps') or 0) > 0
                          for r in rl_records)
    stalled_side = None
    if len(window_pair) == 2:
      if actor_started and all(
          (r.get('actor_steps') or 0) == 0
          and (r.get('learner_steps') or 0) > 0
          and not r.get('actor_done') for r in window_pair):
        stalled_side = 'actor'
      elif learner_started and all(
          (r.get('learner_steps') or 0) == 0
          and (r.get('actor_steps') or 0) > 0
          and not r.get('learner_done') for r in window_pair):
        stalled_side = 'learner'
    if stalled_side is not None:
      other = 'learner' if stalled_side == 'actor' else 'actor'
      findings.append(_finding(
          WARNING if run_ended else CRITICAL,
          'rl loop: the {} side stalled — zero {} steps across the last '
          '2 windows while the {} kept stepping ({})'.format(
              stalled_side, stalled_side, other,
              'fresh experience has stopped flowing; the learner is '
              'training on a frozen buffer' if stalled_side == 'actor'
              else 'the policy is frozen while collection burns '
              'compute'),
          kind='rl_{}_stalled'.format(stalled_side), side=stalled_side,
          actor_steps=latest.get('actor_steps'),
          learner_steps=latest.get('learner_steps')))
    cache = latest.get('act_jit_cache')
    if cache is not None and cache > 1.0:
      findings.append(_finding(
          WARNING, 'rl loop: acting path compiled {:g} executables — a '
          'signature-unstable input reached the jitted acting step '
          '(expected exactly 1; see rl/loop.py make_act_step)'.format(
              cache), kind='rl_act_recompile', act_jit_cache=cache))
    if stalled_side is None:
      spread = latest.get('scenario_success_spread')
      findings.append(_finding(
          INFO, 'rl loop@{}: {:.1f} ep/s ({:.0f} env steps/s), success '
          '{:.0%} cumulative, actor v{} of learner v{} ({} swaps{}){}'
          .format(
              latest.get('step'), latest.get('episodes_per_sec', 0.0),
              latest.get('env_steps_per_sec', 0.0),
              latest.get('success_rate_cumulative', 0.0),
              latest.get('actor_version', 0),
              latest.get('learner_version', 0),
              latest.get('swaps', 0),
              ', {} dropped'.format(latest['dropped_swaps'])
              if latest.get('dropped_swaps') else '',
              '' if spread is None else
              ', scenario spread {:.0%}'.format(spread))))

  # Compile section (ISSUE 13): kind='compile' records from the unified
  # CompiledArtifact store, plus fingerprint-drift anomalies. Drift —
  # the same artifact key (workload, shapes, chip, jax version, config)
  # compiling to a DIFFERENT post-optimization program — means the
  # persisted-executable contract is broken for that workload: page
  # while live, evidence after the run ends.
  compile_records = [r for r in records if r.get('kind') == 'compile']
  drift_records = [r for r in records
                   if r.get('kind') == 'anomaly'
                   and r.get('anomaly') == 'fingerprint_drift']
  if drift_records:
    # One finding PER drifted workload — a run where two workloads
    # drift must name both, or the operator investigates only the last.
    drift_by_workload: Dict[str, int] = {}
    for record in drift_records:
      workload = (record.get('detail') or {}).get('workload') or \
          'unknown'
      drift_by_workload[workload] = drift_by_workload.get(workload,
                                                          0) + 1
    for workload, count in sorted(drift_by_workload.items()):
      findings.append(_finding(
          WARNING if run_ended else CRITICAL,
          'compile: post-optimization fingerprint drifted for workload '
          '{!r} ({} event(s)) — the same artifact key (shapes/chip/jax/'
          'config unchanged) now compiles to a different program; the '
          'toolchain moved under a pinned version string, or lowering '
          'went nondeterministic'.format(workload, count),
          kind='fingerprint_drift', workload=workload, count=count))
  if compile_records:
    hits = sum(1 for r in compile_records if r.get('outcome') == 'hit')
    misses = len(compile_records) - hits
    compile_ms = sum(float(r.get('compile_ms') or 0.0)
                     for r in compile_records)
    workloads = sorted({str(r.get('workload'))
                        for r in compile_records})
    findings.append(_finding(
        INFO, 'compile: {} artifact load(s) across {} workload(s) — '
        '{} deserialized (zero-compile), {} compiled ({:.0f} ms '
        'compiling)'.format(
            len(compile_records), len(workloads), hits, misses,
            compile_ms),
        hits=hits, misses=misses, compile_ms_total=compile_ms,
        workloads=workloads))

  # Fleet federation pass, computed BEFORE the elastic section: the
  # elastic event ladder may live in ANOTHER host's stream (after a
  # coordinator re-election the new coordinator narrates the shrink),
  # so both the elastic verdicts and the fleet section judge the
  # merged view.
  try:
    # Single-host dirs skip the federation pass: fleet_summary would
    # re-read every rotated generation this function already parsed,
    # doubling doctor's I/O for nothing (the only fleet-relevant facts
    # of a one-host dir — recovery records — are in ``records``).
    fsum = None
    if len(host_files) > 1:
      fsum = fleet_lib.fleet_summary(model_dir, now=now,
                                     stale_secs=heartbeat_stale_secs)
  except Exception as e:  # noqa: BLE001 — one torn stream, not a crash
    fsum = None
    findings.append(_finding(
        WARNING, 'fleet summary failed: {}'.format(e)))

  # Elastic section (ISSUE 15): t2r.elastic.v1 membership events from
  # the coordinator-led elastic driver. Two verdicts: a shrink that
  # BEGAN but never completed its ladder (emergency_save ->
  # mesh_rebuild -> artifact_rebind -> resume) has the fleet wedged
  # mid-rebuild — CRITICAL while live, naming the stalled phase and the
  # narrating host; otherwise an INFO summary of the world's history.
  # The departed-host classification feeds the fleet section below: a
  # host named departed by a shrink event must not page host_dead.
  elastic_events = (fsum.get('elastic_events') if fsum is not None
                    else None) or [r for r in records
                                   if r.get('kind') == 'elastic']
  orderly_departed: Dict[int, Dict[str, object]] = {}
  lapse_departed: Dict[int, Dict[str, object]] = {}
  if elastic_events:
    from tensor2robot_tpu.elastic.membership import (
        EVENT_GROW,
        EVENT_JOIN,
        EVENT_REBUILD,
        EVENT_SHRINK,
        EVENT_SHRINK_BEGIN,
        EVENT_SHRINK_PHASE,
        SHRINK_PHASES,
    )

    for event in elastic_events:
      name = event.get('event')
      if name in (EVENT_SHRINK_BEGIN, EVENT_SHRINK):
        for host in event.get('departed') or []:
          bucket = (orderly_departed if event.get('orderly')
                    else lapse_departed)
          bucket[int(host)] = event
      elif name == EVENT_GROW:
        for host in event.get('joined') or []:
          orderly_departed.pop(int(host), None)
          lapse_departed.pop(int(host), None)
      elif name == EVENT_JOIN and event.get('host') is not None:
        orderly_departed.pop(int(event['host']), None)
        lapse_departed.pop(int(event['host']), None)
    begins = [e for e in elastic_events
              if e.get('event') == EVENT_SHRINK_BEGIN]
    completed_epochs = {int(e.get('epoch') or 0) for e in elastic_events
                       if e.get('event') == EVENT_SHRINK}
    # A begin with no completion at its OWN epoch is only "wedged" while
    # the world never moved past it: when the declaring coordinator
    # itself dies mid-ladder, its shrink_begin is orphaned (only the
    # coordinator narrates the ladder) and a SUCCESSOR completes the
    # resize at a later epoch — any completed shrink or grow beyond the
    # begin's epoch proves the fleet reconfigured past it.
    resolved_epochs = completed_epochs | {
        int(e.get('epoch') or 0) for e in elastic_events
        if e.get('event') == EVENT_GROW}
    stalled = [b for b in begins
               if int(b.get('epoch') or 0) not in completed_epochs
               and not any(epoch > int(b.get('epoch') or 0)
                           for epoch in resolved_epochs)]
    if stalled:
      begin = stalled[-1]
      epoch = int(begin.get('epoch') or 0)
      done_phases = [e.get('phase') for e in elastic_events
                     if e.get('event') == EVENT_SHRINK_PHASE
                     and int(e.get('epoch') or 0) == epoch]
      stalled_phase = next(
          (phase for phase in SHRINK_PHASES if phase not in done_phases),
          'resume')
      reporter = begin.get('host', begin.get('process_index'))
      findings.append(_finding(
          WARNING if run_ended else CRITICAL,
          'elastic shrink (epoch {}, world {} -> {}) stalled in the '
          '{} phase: host {} declared host(s) {} departed but the '
          'rebuild ladder never completed — the fleet is wedged '
          'mid-resize'.format(
              epoch, begin.get('world_before'), begin.get('world_after'),
              stalled_phase, reporter, begin.get('departed')),
          kind='elastic_rebuild_stalled', phase=stalled_phase,
          host=reporter, epoch=epoch,
          departed=begin.get('departed'),
          completed_phases=done_phases))
    else:
      worlds = [int(e.get('world_after') or 0) for e in elastic_events
                if e.get('event') in (EVENT_GROW, EVENT_SHRINK_BEGIN)]
      shrinks = [e for e in elastic_events
                 if e.get('event') == EVENT_SHRINK]
      grows = [e for e in elastic_events if e.get('event') == EVENT_GROW]
      rebuilds = [e for e in elastic_events
                  if e.get('event') == EVENT_REBUILD
                  and int(e.get('epoch') or 0) > 1]
      rebuild_compiles = sum(float(e.get('compiles_delta') or 0.0)
                             for e in rebuilds)
      findings.append(_finding(
          INFO, 'elastic: world size {} after {} shrink(s) / {} grow(s)'
          ' ({} orderly departure(s)); {} post-epoch-1 rebuild(s) cost '
          '{:g} XLA compile(s)'.format(
              worlds[-1] if worlds else 'n/a', len(shrinks), len(grows),
              sum(1 for e in shrinks if e.get('orderly')),
              len(rebuilds), rebuild_compiles),
          kind='elastic_summary',
          world_size=worlds[-1] if worlds else None,
          shrinks=len(shrinks), grows=len(grows),
          rebuild_compiles=rebuild_compiles))

  # Fleet section (ISSUE 9): federated per-host view. A host whose
  # heartbeat is stale while others advance, or a straggler the fleet
  # has not recovered from, halts/gates the whole mesh: CRITICAL while
  # the run is live. Everything is recomputed from the per-host files —
  # doctor must name the host without a live process anywhere.
  fleet_records = [r for r in records if r.get('kind') == 'fleet']
  if fsum is not None and (fsum['host_count'] > 1 or fsum['recoveries']):
    if fsum['host_count'] > 1:
      parts = ['fleet: {} hosts'.format(fsum['host_count'])]
      if fsum.get('step_time_skew'):
        parts.append('step-time skew {:.2f}x (gating host {})'.format(
            fsum['step_time_skew'], fsum['gating_host']))
      if fsum.get('fleet_min_goodput') is not None:
        parts.append('fleet-min goodput {:.0%}'.format(
            fsum['fleet_min_goodput']))
      findings.append(_finding(
          INFO, ', '.join(parts), host_count=fsum['host_count'],
          step_time_skew=fsum.get('step_time_skew'),
          gating_host=fsum.get('gating_host'),
          fleet_min_goodput=fsum.get('fleet_min_goodput')))
    for host in fsum['dead_hosts']:
      entry = fsum['hosts'].get(str(host), {})
      if int(host) in orderly_departed:
        # ISSUE 15: the host departed in an ORDERLY elastic shrink — a
        # t2r.elastic.v1 shrink event names it, the fleet reconfigured
        # around it on purpose, and its silence is the design, not a
        # death. INFO, citing the shrink event.
        event = orderly_departed[int(host)]
        findings.append(_finding(
            INFO, 'fleet: host {} departed in an orderly elastic '
            'shrink (epoch {}, world {} -> {}); its stale heartbeat is '
            'expected, not a page'.format(
                host, event.get('epoch'), event.get('world_before'),
                event.get('world_after')),
            kind='host_departed_orderly', host=host,
            epoch=event.get('epoch')))
        continue
      if int(host) in lapse_departed:
        # Preempted, but the elastic shrink already reconfigured the
        # fleet around it: the outage is history (the recovery record
        # carries it), not a live page — unless it never resumed, which
        # the stuck-rebuild CRITICAL above owns.
        event = lapse_departed[int(host)]
        findings.append(_finding(
            WARNING, 'fleet: host {} was preempted and the elastic '
            'shrink (epoch {}, world {} -> {}) already closed around '
            'it — evidence, not a live page'.format(
                host, event.get('epoch'), event.get('world_before'),
                event.get('world_after')),
            kind='host_departed_preempted', host=host,
            epoch=event.get('epoch')))
        continue
      # WARNING (not INFO) after run end — same downgrade rule as the
      # straggler verdict: a host that died during a now-ended run is
      # still evidence worth surfacing, just not a live page.
      findings.append(_finding(
          WARNING if run_ended else CRITICAL,
          'fleet: host {} ({}) heartbeat is {:.0f}s stale while other '
          'hosts advance — dead or partitioned{}'.format(
              host, entry.get('hostname'),
              entry.get('heartbeat_age_s') or 0.0,
              '' if not run_ended else ' (run already ended)'),
          kind='host_dead', host=host, hostname=entry.get('hostname'),
          heartbeat_age_s=entry.get('heartbeat_age_s')))
    straggler_indices = [i for i, r in enumerate(records)
                         if r.get('kind') == 'anomaly'
                         and r.get('anomaly') == watchdog_lib.STRAGGLER]
    if straggler_indices:
      last_index = straggler_indices[-1]
      last_straggler = records[last_index]
      host = (last_straggler.get('detail') or {}).get('host')
      # Recovery check (same shape as pipeline_stall): a LATER fleet
      # window without a straggler means the skew passed — history,
      # not a live page.
      recovered = any(
          r.get('kind') == 'fleet'
          and watchdog_lib.STRAGGLER not in (r.get('anomalies') or [])
          for r in records[last_index + 1:])
      findings.append(_finding(
          WARNING if (run_ended or recovered) else CRITICAL,
          'fleet: host {} straggled {} window(s), last at step {}{} '
          '({:.1f}x the fleet median)'.format(
              host, len(straggler_indices), last_straggler.get('step'),
              ' — recovered since' if recovered else '',
              (last_straggler.get('detail') or {}).get('ratio') or 0.0),
          kind='straggler', host=host, count=len(straggler_indices),
          recovered=recovered))
    elif fleet_records:
      latest = fleet_records[-1]
      findings.append(_finding(
          INFO, 'fleet@{}: no straggler; gating host {} at skew '
          '{}'.format(
              latest.get('step'), latest.get('gating_host'),
              'n/a' if latest.get('step_time_skew') is None
              else '{:.2f}x'.format(latest['step_time_skew']))))
    for warning in fsum.get('warnings') or []:
      findings.append(_finding(WARNING, 'fleet: ' + warning))
  recoveries = (fsum['recoveries'] if fsum is not None else
                [r for r in records if r.get('kind') == 'recovery'])
  for recovery in recoveries:
    worlds = ''
    if recovery.get('world_before') is not None:
      worlds = ', world {} -> {}'.format(recovery.get('world_before'),
                                         recovery.get('world_after'))
    findings.append(_finding(
        INFO, 'recovered from preemption at step {} in {:.1f}s '
        '(save {:.1f}s, down {:.1f}s, restore {:.1f}s, first step '
        '{:.1f}s{})'.format(
            recovery.get('preempted_step'),
            recovery.get('preemption_recovery_seconds') or 0.0,
            (recovery.get('phases') or {}).get('emergency_save_s', 0.0),
            (recovery.get('phases') or {}).get('downtime_s', 0.0),
            (recovery.get('phases') or {}).get('restore_s', 0.0),
            (recovery.get('phases') or {}).get('first_step_s', 0.0),
            worlds),
        kind='recovery',
        preemption_recovery_seconds=recovery.get(
            'preemption_recovery_seconds'),
        world_before=recovery.get('world_before'),
        world_after=recovery.get('world_after')))

  # Watchdog anomaly records written in-process.
  anomalies = [r for r in records if r.get('kind') == 'anomaly']
  if anomalies:
    by_kind: Dict[str, int] = {}
    for record in anomalies:
      by_kind[str(record.get('anomaly'))] = \
          by_kind.get(str(record.get('anomaly')), 0) + 1
    findings.append(_finding(
        WARNING, 'watchdog fired {} anomaly record(s): {}'.format(
            len(anomalies),
            ', '.join('{} x{}'.format(kind, count)
                      for kind, count in sorted(by_kind.items()))),
        counts=by_kind))

  # Newest forensics report: the attribution evidence.
  reports = forensics_lib.read_reports(model_dir)
  if reports:
    step, report = reports[-1]
    top_ops = report.get('top_ops') or []
    if top_ops:
      top = top_ops[0]
      findings.append(_finding(
          INFO, 'forensics@{} ({}): top op {} {:.2f} ms/step '
          '({:.0%} of attributed time)'.format(
              step, report.get('reason'), top.get('name'),
              top.get('ms_per_step', 0.0), top.get('fraction', 0.0)),
          report='{}/{}.json'.format(forensics_lib.FORENSICS_DIRNAME,
                                     step)))
    occupancy = report.get('device_occupancy') or {}
    if occupancy.get('extent_ms'):
      findings.append(_finding(
          INFO, 'forensics@{}: device line {:.0%} occupied over a '
          '{:.0f} ms window'.format(step, occupancy.get('occupancy', 0.0),
                                    occupancy.get('extent_ms', 0.0))))
    for warning in report.get('warnings') or []:
      findings.append(_finding(INFO, 'forensics@{}: {}'.format(
          step, warning)))

  # Roofline verdict: the newest t2r.roofline.v1 evidence — the latest
  # capture report's attribution, else the compact telemetry record the
  # trainer logs alongside it. Under the MFU floor with a memory-bound
  # family in the table, the verdict NAMES that family: it is the op
  # the kernel work (ROADMAP item 1) should fuse first, and its
  # headroom is the predicted win.
  roofline = None
  roofline_step = None
  if reports and reports[-1][1].get('roofline'):
    roofline_step = reports[-1][0]
    roofline = reports[-1][1]['roofline']
  else:
    roofline_records = [r for r in records if r.get('kind') == 'roofline']
    if roofline_records:
      roofline = roofline_records[-1]
      roofline_step = roofline.get('step')
  if roofline:
    mfu_value = roofline.get('mfu')
    gating = roofline.get('gating_memory_bound_family')
    headroom_ms = None
    for row in roofline.get('families') or []:
      if row.get('family') == gating:
        headroom_ms = row.get('headroom_ms')
        break
    if roofline.get('mode') == 'intensity-only':
      families = roofline.get('families') or []
      top_family = families[0].get('family') if families else None
      findings.append(_finding(
          INFO, 'roofline@{}: intensity-only mode — device kind {!r} has '
          'no peaks entry (CPU or unknown), so %-peak/MFU/headroom are '
          'withheld; program intensity {} flops/byte{}'.format(
              roofline_step, roofline.get('device_kind'),
              roofline.get('arithmetic_intensity'),
              ', top measured family {}'.format(top_family)
              if top_family else ''),
          kind='roofline', mode='intensity-only',
          arithmetic_intensity=roofline.get('arithmetic_intensity')))
    elif mfu_value is not None and mfu_value < _MFU_FLOOR:
      if gating:
        findings.append(_finding(
            WARNING if run_ended else CRITICAL,
            'roofline@{}: MFU {:.1%} is under the {:.0%} floor and the '
            'gating memory-bound family is {}{} — a fused kernel for it '
            'is the predicted win'.format(
                roofline_step, mfu_value, _MFU_FLOOR, gating,
                ' (headroom {:.2f} ms/step)'.format(headroom_ms)
                if headroom_ms is not None else ''),
            kind='roofline', mfu=mfu_value,
            gating_memory_bound_family=gating, headroom_ms=headroom_ms))
      else:
        findings.append(_finding(
            WARNING,
            'roofline@{}: MFU {:.1%} is under the {:.0%} floor but no '
            'memory-bound family stands out — compute-bound or '
            'unattributed; inspect the capture'.format(
                roofline_step, mfu_value, _MFU_FLOOR),
            kind='roofline', mfu=mfu_value))
    else:
      findings.append(_finding(
          INFO, 'roofline@{}: MFU {}, HBM bandwidth {}, '
          'bound profile healthy{}'.format(
              roofline_step,
              '{:.1%}'.format(mfu_value) if mfu_value is not None
              else 'n/a',
              '{:.1%}'.format(roofline['hbm_bw_util'])
              if roofline.get('hbm_bw_util') is not None else 'n/a',
              ' (watch {})'.format(gating) if gating else ''),
          kind='roofline', mfu=mfu_value,
          gating_memory_bound_family=gating))

  if not any(f['severity'] in (CRITICAL, WARNING) for f in findings):
    findings.append(_finding(
        OK, 'no anomalies in the available telemetry' if not records else
        'no anomalies: heartbeat fresh, goodput healthy, no recompiles, '
        'no watchdog events'))
  findings.sort(key=lambda f: _SEVERITY_RANK.get(str(f['severity']), 9))
  return findings


def format_findings(findings: List[Dict[str, object]]) -> str:
  tags = {CRITICAL: 'CRIT', WARNING: 'WARN', INFO: 'INFO', OK: ' OK '}
  return '\n'.join('{} {}'.format(
      tags.get(str(f['severity']), '????'), f['message'])
      for f in findings)
