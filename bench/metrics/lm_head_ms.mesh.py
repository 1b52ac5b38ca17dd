"""Device time of the vocabulary-wide work of a training step over a mesh,
ms a step, averaged over devices: self time of every op whose HLO types
carry the padded vocabulary split over the mesh's model axis, the width
each device holds of the head's weight, its logits and their gradient.
That is the head's products forward and backward, the logits and their
loss, the collectives over them, and the passes over the vocabulary-wide
tables (the head's weight, and an untied embedding table split the same
way: quantizing, the optimizer, the sentinel's checks). `lm_head_ms` reads
the unsplit width, which no op on such a mesh shows. Layer: train step,
lm head (models/common.py:lm_head_apply and what the vocabulary drives)."""
from bench.harness import layers, program


def read(run):
    w = run.work
    mp = int(w.get("model_parallel") or 1)
    if run.trace is None or not w.get("steps") or mp < 2:
        return None
    vocab = program.arch_config(run.config).padded_vocab
    if vocab % mp:
        return None
    t = layers.self_s(run.trace, lambda evs: [
        e for e in evs if layers.has_dim(e, vocab // mp)])
    return 1e3 * t / w["steps"] if t > 0 else None
