"""One general generator of inputs, driven by a traffic file's parameters.

Serving: an open loop of independent requests. The sizes, the gaps
between arrivals and their order are drawn once from the mix's own
`mix_seed`, so every run seed gets the same requests at the same times;
the run seed draws only the prompts' token ids. The work in a window is
then the same for every seed: which long requests are in flight when it
opens does not change with the seed. Gaps are exponential (Poisson
arrivals) and scaled so that they sum to the window: `rate_rps * seconds`
requests are due in it.

Training: the synthetic language-model stream that the trainer reads
(`data/synthetic.sample_batch`), copied here so that the plain reference is
fed the same rows without importing the program, and the synthetic top-K
distillation labels (`data/mckd_store.synthetic_kd_labels`).
"""
from __future__ import annotations

from dataclasses import dataclass

import numpy as np


@dataclass
class Request:
    rid: str
    due: float            # seconds after the window opens
    prompt: np.ndarray    # int32 token ids
    max_new: int


def _lognormal(rng, n: int, median: float, sigma: float, lo: int,
               hi: int) -> np.ndarray:
    x = median * np.exp(sigma * rng.standard_normal(n))
    return np.clip(np.round(x), lo, hi).astype(np.int64)


def open_loop(traffic: dict, seed: int, seconds: float,
              vocab: int) -> list:
    """The requests due in a window of `seconds`, in arrival order."""
    n = max(1, int(round(traffic["rate_rps"] * seconds)))
    base = np.random.default_rng(traffic["mix_seed"])
    p = traffic["prompt"]
    o = traffic["output"]
    plen = _lognormal(base, n, p["median"], p["sigma"], p["min"], p["max"])
    olen = _lognormal(base, n, o["median"], o["sigma"], o["min"], o["max"])
    gaps = base.exponential(1.0, n)
    gaps *= seconds / gaps.sum()
    due = np.concatenate([[0.0], np.cumsum(gaps)[:-1]])
    rng = np.random.default_rng(np.random.SeedSequence(seed))
    return [Request(rid=f"r{i}", due=float(due[i]),
                    prompt=rng.integers(0, vocab, int(plen[i]),
                                        dtype=np.int32),
                    max_new=int(olen[i]))
            for i in range(n)]


def lm_batch(data: dict, seed: int, step: int, batch: int, seq: int,
             vocab: int, host: int = 0) -> dict:
    """Rows of the affine-successor stream: t' = (a t + c) mod V, replaced
    by a uniform draw with probability p_noise; keyed on (seed, step,
    host)."""
    rng = np.random.default_rng(np.random.SeedSequence([seed, step, host]))
    toks = np.empty((batch, seq + 1), np.int64)
    toks[:, 0] = rng.integers(0, vocab, size=batch)
    noise = rng.random((batch, seq)) < data["p_noise"]
    rand = rng.integers(0, vocab, size=(batch, seq))
    for i in range(seq):
        nxt = (data["mult"] * toks[:, i] + data["add"]) % vocab
        toks[:, i + 1] = np.where(noise[:, i], rand[:, i], nxt)
    return {"tokens": toks[:, :-1].astype(np.int32),
            "labels": toks[:, 1:].astype(np.int32)}


def kd_labels(labels: np.ndarray, vocab: int, k: int, step: int,
              smooth: float = 0.1):
    """Top-K soft labels around the true next token: the truth with
    1 - smooth, K - 1 ids drawn from PRNGKey(step) sharing the rest."""
    import jax
    alt = jax.random.randint(jax.random.PRNGKey(step),
                             (*labels.shape, k - 1), 0, vocab)
    idx = np.concatenate([labels[..., None], np.asarray(alt)], axis=-1)
    p = np.concatenate([np.full((*labels.shape, 1), 1.0 - smooth),
                        np.full((*labels.shape, k - 1), smooth / (k - 1))],
                       axis=-1)
    return idx.astype(np.int32), p.astype(np.float32)
