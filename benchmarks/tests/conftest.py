"""Self-checks of the benchmark, run by hand (not part of tier-1):

    JAX_PLATFORMS=cpu python -m pytest benchmarks/tests -q
"""
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
for p in (ROOT, os.path.join(ROOT, "tools")):
    if p not in sys.path:
        sys.path.insert(0, p)
BENCH_REHEARSE = os.path.join(ROOT, "benchmarks", "tests", "bench_rehearse.json")
