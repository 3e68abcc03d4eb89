"""On-chip benchmark of the serving path: cells named in ``BENCHMARK.json``."""
