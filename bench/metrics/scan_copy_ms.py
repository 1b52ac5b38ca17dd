"""Device time the layer scan adds around the blocks in a pooled decode
step: inside the program's `serve.decode` spans, self time of the layer
loop (`%while`) and of the ops in it whose HLO types carry the stacked
layer axis (slicing each layer's weights and KV slab out of the stacked
arrays, writing the new slab back), per span. The blocks' own ops see
only one layer's slices. Layer: executor, layer scan
(models/model.py:prefill_step)."""
from bench.harness import layers, program
from bench.harness.trace import inside, op_label


def read(run):
    if run.trace is None:
        return None
    spans = layers.windowed(run.trace, layers.SERVE_DECODE)
    if not spans:
        return None
    groups = program.arch_config(run.config).n_groups

    def scan_own(evs):
        loops = inside([e for e in evs if op_label(e) == "while"], spans)
        stacked = [e for e in evs if layers.has_dim(e, groups, leading=True)]
        return loops + inside(stacked, loops)
    t = layers.self_s(run.trace, scan_own)
    return 1e3 * t / len(spans) if t > 0 else None
