"""The benchmark of the serving path: cells, traffic, metrics and the plain
references, all found by the names in ``BENCHMARK.json``.  See ``run.py``."""
