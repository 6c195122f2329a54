"""benchmark/tests are run by hand and in the rehearsal
(`python3 -m pytest benchmark/tests -q`), not with the repo's tests/."""

import os
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH_DIR)
for _p in (ROOT, BENCH_DIR):
    if _p not in sys.path:
        sys.path.insert(0, _p)
