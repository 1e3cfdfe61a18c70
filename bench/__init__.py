"""On-chip benchmark of the TSM2X repository (see ``BENCHMARK.json``).

``python bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>``
runs one cell on the chips of the machine it starts on. Everything that
belongs to one configuration, traffic mix or per-layer metric is a file of
its own, found by the name ``BENCHMARK.json`` gives it (``load.py``); the
modules here are shared by all of them and name none.
"""
