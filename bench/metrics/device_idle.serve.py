"""Share of the traced serving stretch in which no op ran on the device:
the synchronous engine's host work (scheduling, the logits copy, sampling)
and waiting for arrivals. Layer: serving engine (serve/engine.py,
serve/scheduler.py)."""
from bench.harness.trace import idle_share


def read(run):
    return None if run.trace is None else 100.0 * idle_share(run.trace)
