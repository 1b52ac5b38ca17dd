"""Model flops of the traced training steps over the busy time of every
chip of the mesh at the bf16 peak: the sharded step's share of the chips
(idle time is device_idle.train's). Flops as train_step_mfu counts them;
busy time averaged over the devices in the trace, times their number.
Layer: train step, sharded (train/train_step.py over the mesh)."""
from bench.harness import work
from bench.harness.trace import busy_s


def read(run):
    if run.trace is None or not run.trace.ops or not run.work.get("steps"):
        return None
    busy = busy_s(run.trace)
    if busy <= 0:
        return None
    w = run.work
    flops = w["steps"] * work.train_flops_per_step(run.config, w["batch"],
                                                   w["seq_len"])
    chips = len(run.trace.ops)
    return 100.0 * flops / (busy * chips * run.peaks["bf16_flops"])
