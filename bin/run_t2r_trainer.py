#!/usr/bin/env python
"""Config-driven trainer entry point.

Parity target: /root/reference/bin/run_t2r_trainer.py:32-39. Usage:

    python bin/run_t2r_trainer.py \
        --gin_configs tensor2robot_tpu/research/pose_env/configs/train_pose_env.gin \
        --gin_bindings "train_eval_model.model_dir = '/tmp/pose_run'" \
        --gin_bindings "train_eval_model.max_train_steps = 100"
"""

import argparse
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main(argv=None):
  parser = argparse.ArgumentParser(description=__doc__)
  parser.add_argument('--gin_configs', action='append', default=[],
                      help='Path to a gin config file (repeatable).')
  parser.add_argument('--gin_bindings', action='append', default=[],
                      help="Individual binding, e.g. \"a.b = 1\" (repeatable).")
  parser.add_argument('--replay_endpoint', default=None,
                      help='Train from a t2r_replay service (host:port) '
                           'instead of the configured record files: the '
                           'learner samples packed megabatches at wire '
                           'rate (docs/replay.md).')
  parser.add_argument('--replay_batch_size', type=int, default=32,
                      help='Sampled megabatch size with --replay_endpoint.')
  parser.add_argument('--use_compiled_artifacts', action='store_true',
                      help='Cold-start the train step from the unified '
                           'CompiledArtifact store (docs/performance.md '
                           '"Cold start"): a warm start deserializes the '
                           'persisted executable and the first step '
                           'executes without an XLA compile.')
  parser.add_argument('--artifact_workload', default=None,
                      help='Store workload name with '
                           '--use_compiled_artifacts (default: derived '
                           'from the tuned_config string or model class).')
  args = parser.parse_args(argv)

  from tensor2robot_tpu import config

  config.register_framework_configurables()
  config.add_config_file_search_path(
      os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
  config.parse_config_files_and_bindings(args.gin_configs, args.gin_bindings)
  train_eval_model = config.get_configurable('train_eval_model')
  overrides = {}
  if args.replay_endpoint:
    from tensor2robot_tpu.replay import ReplayInputGenerator

    overrides['input_generator_train'] = ReplayInputGenerator(
        args.replay_endpoint, batch_size=args.replay_batch_size)
  if args.use_compiled_artifacts:
    overrides['use_compiled_artifacts'] = True
    if args.artifact_workload:
      overrides['artifact_workload'] = args.artifact_workload
  results = train_eval_model(**overrides)
  metrics = results.get('eval_metrics') if isinstance(results, dict) else None
  if metrics:
    print('final eval metrics:', metrics)
  return results


if __name__ == '__main__':
  from tensor2robot_tpu import runtime

  # Process-wide configuration belongs to the process entry, not to
  # main(argv), which tests call in-process.
  runtime.enable_compile_cache()
  main()
