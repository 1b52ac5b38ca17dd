"""Tests of the on-chip benchmark's harness, run on the CPU at tiny sizes.

The harness imports as `bench` from the root of the checkout."""
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
for p in (ROOT, os.path.join(ROOT, "src")):
    if p not in sys.path:
        sys.path.insert(0, p)
