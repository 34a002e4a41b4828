"""Where the benchmark lives, for its tests: `benchmark/` goes on sys.path
so that `benchlib`, `kernels`, `readers` and `run` import as the harness
imports them."""

import os
import sys

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
BENCH_DIR = os.path.join(REPO_ROOT, "benchmark")
FIXTURES = os.path.join(os.path.dirname(os.path.abspath(__file__)), "fixtures")
if BENCH_DIR not in sys.path:
    sys.path.insert(0, BENCH_DIR)
