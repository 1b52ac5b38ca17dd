"""Model flops of the traced training steps over the device's busy time
at the bf16 peak: the train step's own share of the chip (idle time is
device_idle.train's). Flops are forward + backward of the dense model per
token, causal attention included, without rematerialisation. Layer: train
step (train/train_step.py)."""
from bench.harness import work
from bench.harness.trace import busy_s


def read(run):
    if run.trace is None:
        return None
    busy = busy_s(run.trace)
    if busy <= 0:
        return None
    w = run.work
    flops = w["steps"] * work.train_flops_per_step(run.config, w["batch"],
                                                   w["seq_len"])
    return 100.0 * flops / (busy * run.peaks["bf16_flops"])
