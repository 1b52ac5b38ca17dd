"""The flash-decode attention kernel's share of its roofline over the
traced stretch: the least time for the attention the served tokens
require (valid cached positions only, int8 codes and f32 scales as
stored), over the kernel's summed device time, prompt chunks' calls
included. Layer: kernels (kernels/decode_attention.py)."""
from bench.harness import work
from bench.harness.trace import kernel_s

# today the kernel shows as a called computation whose body is the custom
# call: "%closed_call.6 = (f32[32,8,8,128]..., f32[..], f32[..]) custom-call("
KERNEL = r"^%closed_call[.\d]* = \(f32\[.*\) custom-call\("


def read(run):
    if run.trace is None:
        return None
    t = kernel_s(run.trace, KERNEL)
    calls = work.decode_attention_calls(run.config, run.work.get("log", []))
    if t <= 0 or not calls:
        return None
    need = sum(work.roofline_seconds(f, b, run.peaks)[0] for f, b in calls)
    return 100.0 * need / t
