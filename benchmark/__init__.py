"""The repo's benchmark: cells of BENCHMARK.json, run by ``benchmark/run.py``.

Everything the yardstick needs lives here (traffic generation, weights from
the seed, the float32 reference, trace reduction, operation counts, peaks);
from the program it takes only the system under test.
"""
