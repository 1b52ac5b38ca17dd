"""Device time of one prompt chunk: busy device time inside the
benchmark's spans around `ModelExecutor.prefill_chunk`, per call. Layer:
executor (ModelExecutor.prefill_chunk)."""
from bench.harness.trace import device_s_in


def read(run):
    if run.trace is None:
        return None
    t, n = device_s_in(run.trace, "bench.prefill_chunk")
    return 1e3 * t / n if n and t > 0 else None
