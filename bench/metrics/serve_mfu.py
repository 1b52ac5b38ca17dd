"""Model flops of every token served in the traced stretch (prompt and
decoded) over the device's busy time at the bf16 peak: the whole served
step's share of the chip. Layer: executor (prefill and decode programs)."""
from bench.harness import work
from bench.harness.trace import busy_s


def read(run):
    if run.trace is None:
        return None
    busy = busy_s(run.trace)
    flops = work.served_flops(run.config, run.work.get("log", []))
    if busy <= 0 or flops <= 0:
        return None
    return 100.0 * flops / (busy * run.peaks["bf16_flops"])
