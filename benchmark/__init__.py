"""The benchmark: one cell of BENCHMARK.json per run of benchmark/run.py."""
