"""On-chip benchmark of the serving engine: one cell per run.

``python3 bench/run.py --workload <name> --seed <n> --seconds <s>
--trace <0|1>`` runs one entry of ``BENCHMARK.json``'s ``workloads``.
Configurations, traffic mixes and per-layer metric readers are files of
their own under ``bench/configs``, ``bench/traffic`` and
``bench/metrics``, found by the names ``BENCHMARK.json`` gives them.
"""
