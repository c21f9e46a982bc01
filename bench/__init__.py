"""On-chip serving benchmark.

``python3 -m bench.run --workload <cell> --seed <n> --seconds <s> --trace <0|1>``
runs one cell of ``BENCHMARK.json`` once and prints one JSON line.  Every
cell, configuration, traffic mix and metric is a file of its own under this
directory, found by the name ``BENCHMARK.json`` gives it (``bench/spec.py``).
"""
