"""chipbench — the on-chip benchmark of paddle-tpu.

The yardstick lives here and nowhere else: traffic generation, the plain
references, the table of peaks, the cost functions, the reduction from a
profiler trace to metrics and the comparison that decides ``correct``.  From
the program it takes only the system under test (``paddle.jit.TrainStep``,
``paddle.io.DataLoader``, ``serving.ServingEngine``) and its counters.

``python3 -m chipbench.run --workload <cell> --seed <n> --seconds <s>
--trace <0|1>`` runs one cell; ``BENCHMARK.json`` at the root of the repo
names the cells, configurations and metrics, and every one of them is a file
of its own under this directory (see ``spec.py``).
"""
