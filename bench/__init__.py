"""The chip benchmark: one command runs one cell of ``BENCHMARK.json`` once.

    python bench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

Everything one configuration, traffic mix, cell, driver or per-layer metric
needs sits in a file of its own under this directory, found by name.
"""
