"""The fused QAT matmul kernels' share of their roofline over the traced
training steps: the least time the chip could take for the work those
matmuls require (forward, dX and dW of every linear, counted once: the
rematerialised forward is not required work), over the summed device time
of the kernels (forward, recomputed forward and backward) in the trace.
Each call is bound by compute or bandwidth on its own. Layer: kernels
(kernels/quant_matmul.py)."""
from bench.harness import work
from bench.harness.trace import kernel_s

KERNELS = (r"^%(?:jvp_jit_)?quant_matmul(?:_bwd|_dx|_dw)?(?:__)?\.\d+ = "
           r".*custom-call")


def read(run):
    if run.trace is None:
        return None
    t = kernel_s(run.trace, KERNELS)
    if t <= 0:
        return None
    w = run.work
    calls = work.qat_matmul_calls(run.config, w["batch"] * w["seq_len"])
    need = sum(work.roofline_seconds(f, b, run.peaks)[0] for f, b in calls)
    return 100.0 * w["steps"] * need / t
