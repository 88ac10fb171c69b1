"""The benchmark: cells of BENCHMARK.json run by `python3 benchmark/run.py`.
See benchmark/README.md."""
