"""On-chip benchmark of the served real-time index (see BENCHMARK.json).

One run measures one cell (a deployment from ``configs/`` under a traffic
mix from ``traffic/``) through ``repro.core.serve.ServeLoop`` and prints
one JSON result line; ``python3 chipbench/run.py --help`` lists the
arguments.  Everything that decides a number lives here, apart from the
program under test: the stream and query generators (:mod:`.streams`),
the numpy reference (:mod:`.reference`), the peaks table
(:mod:`.peaks`), the kernels' byte counts (:mod:`.roofline`), the trace
reduction (:mod:`.trace`) and the per-layer readers
(``layer_metrics/``).
"""
