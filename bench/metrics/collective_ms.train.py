"""Device time of the collective ops a training step runs over the mesh
(all-gathers, reduce-scatters, all-reduces, collective-permutes and their
async starts and dones), ms a step, averaged over devices. Layer: sharding
(dist/sharding.py and the SPMD partitioner that inserts them)."""
from bench.harness.collectives import collective_s


def read(run):
    if run.trace is None or not run.work.get("steps"):
        return None
    t = collective_s(run.trace)
    return None if t is None else 1e3 * t / run.work["steps"]
