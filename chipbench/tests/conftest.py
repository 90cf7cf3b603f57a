"""Tests of the benchmark itself (outside the repo's tier-1 testpaths):

    python -m pytest chipbench/tests -q

from the root of a checkout.  They run on the CPU at small sizes."""
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
for p in (ROOT, os.path.join(ROOT, "src")):
    if p not in sys.path:
        sys.path.insert(0, p)
