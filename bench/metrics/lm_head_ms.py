"""Device time of the vocabulary-wide work of a training step: self time
of every op whose HLO types carry the padded vocabulary width (or the
flattened logits): the head's matmul kernels forward and backward, the
logits and their loss, and the passes over the tied embedding table that
is the head's weight (quantizing it for the head, the optimizer, the
sentinel's check), over the traced steps. Layer: train step, lm head
(models/common.py:lm_head_apply and what the vocabulary drives)."""
from bench.harness import layers, program


def read(run):
    w = run.work
    if run.trace is None or not w.get("steps"):
        return None
    vocab = program.arch_config(run.config).padded_vocab
    flat = w["batch"] * w["seq_len"] * vocab
    t = layers.self_s(run.trace, lambda evs: [
        e for e in evs if layers.has_dim(e, vocab) or layers.has_dim(e, flat)])
    return 1e3 * t / w["steps"] if t > 0 else None
