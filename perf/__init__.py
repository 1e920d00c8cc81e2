"""The benchmark of ray_tpu: one cell, one run, one JSON line.

    python3 -m perf.run --workload <name> --seed <n> --seconds <s> --trace <0|1>

Everything the yardstick needs lives here (BENCHMARK.json ``paths``):
traffic and configuration files, the plain references, the FLOP
arithmetic, the table of peaks, the trace reduction and one reader per
per-layer metric. From ``ray_tpu`` it takes only the system under test
and its counters. See PERF.md.
"""
