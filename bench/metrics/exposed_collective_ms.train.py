"""The part of `collective_ms.train` in which no other op runs on that
device: collective time the step waits on instead of hiding it behind
compute, ms a step, averaged over devices. Layer: sharding
(dist/sharding.py and the SPMD partitioner)."""
from bench.harness.collectives import collective_s


def read(run):
    if run.trace is None or not run.work.get("steps"):
        return None
    t = collective_s(run.trace, exposed=True)
    return None if t is None else 1e3 * t / run.work["steps"]
