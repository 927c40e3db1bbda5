"""On-chip benchmark of the async RL loop: cells, configurations, traffic,
limits and per-layer metric readers, all found by name from
``BENCHMARK.json``.  Run a cell with ``python3 bench/run.py --workload
<name> --seed <n> --seconds <s> --trace <0|1>``."""
