"""The benchmark's harness: everything here is the yardstick, not the program.

``cells``     BENCHMARK.json, configuration, traffic and metric files by name
``records``   tf.Example/TFRecord writer and camera-like frames, from a seed
``timing``    process age, whole-step rates
``costs``     FLOPs and bytes of a step, counted from its jaxpr
``peaks``     one file of peaks per device kind
``trace``     profiler trace -> busy/idle, op families, exposed collectives
``reference`` the plain evaluations that decide ``correct``
``train_disk``  the driver of the one KIND of traffic there is so far
"""
