"""The benchmark's own tests (run from the repo root with
``python -m pytest bench/tests``; the repo's tier-1 suite does not collect
them).  They import the harness modules from ``bench/``."""
import os
import sys

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
for p in (BENCH, os.path.join(os.path.dirname(BENCH), "src")):
    if p not in sys.path:
        sys.path.insert(0, p)
