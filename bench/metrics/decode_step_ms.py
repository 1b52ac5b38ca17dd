"""Device time of one pooled decode step: busy device time inside the
benchmark's spans around `ModelExecutor.decode`, per call. Layer: executor
(ModelExecutor.decode)."""
from bench.harness.trace import device_s_in


def read(run):
    if run.trace is None:
        return None
    t, n = device_s_in(run.trace, "bench.decode")
    return 1e3 * t / n if n and t > 0 else None
