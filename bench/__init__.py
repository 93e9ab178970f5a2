"""The chip benchmark: one cell per run, driven by ``BENCHMARK.json``.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Everything that belongs to one configuration, traffic mix, cell or
per-layer metric is a file of its own under ``configs/``, ``traffic/``,
``cells/`` and ``metrics/``, found by its name.
"""
