"""Share of the traced training window in which no op ran on the device:
the trainer loop's host work (batch building, the per-step health sync)
that the device waits on. Layer: trainer loop (launch/train.py)."""
from bench.harness.trace import idle_share


def read(run):
    return None if run.trace is None else 100.0 * idle_share(run.trace)
