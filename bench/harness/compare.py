"""How the program's readings are compared with the plain reference's."""
from __future__ import annotations

import statistics

import numpy as np


def loss_gap(prog: list, ref: list) -> float:
    """Largest relative gap between the per-step losses."""
    if len(prog) != len(ref) or not ref:
        return float("inf")
    return max(abs(p - r) / abs(r) for p, r in zip(prog, ref))


def moved(ref_grad: dict, floor: float = 1e-3) -> list:
    """Leaves whose reference gradient is more than `floor` times the
    median leaf's: the others (a key's bias under softmax, say) move under
    Adam by round-off alone and are left out of the change."""
    med = statistics.median(ref_grad.values())
    return sorted(k for k, v in ref_grad.items() if v > floor * med)


def _gaps(prog: dict, ref: dict, keys) -> dict:
    """Per leaf: |norm_prog - norm_ref| over the larger of the leaf's
    reference norm and the median leaf's."""
    med = statistics.median(ref[k] for k in keys)
    return {k: abs(prog[k] - ref[k]) / max(ref[k], med, 1e-30) for k in keys}


def leaf_gap(prog: dict, ref: dict, keys=None) -> tuple[float, str]:
    """The worst leaf's gap, and the leaf."""
    keys = sorted(ref) if keys is None else keys
    if not keys or any(k not in prog for k in keys):
        return float("inf"), "missing"
    gaps = _gaps(prog, ref, keys)
    at = max(gaps, key=gaps.get)
    return gaps[at], at


def median_leaf_gap(prog: dict, ref: dict, keys) -> float:
    """The median leaf's gap."""
    if not keys or any(k not in prog for k in keys):
        return float("inf")
    return float(statistics.median(_gaps(prog, ref, keys).values()))


def row_balance(prog: dict, rows: list) -> float:
    """How alike the rows count in the program's first gradient.

    The program's gradient is fitted as a weighted sum of the reference's
    gradients of each row alone (least squares over every element of every
    leaf). With the weights scaled to a mean of 1, the largest departure of
    a row's weight from 1: a step over every row reads near 0, one that
    leaves half of them out about 1, and a zero gradient infinity."""
    keys = sorted(rows[0])
    if any(k not in prog for k in keys):
        return float("inf")
    n = len(rows)
    gram, proj = np.zeros((n, n)), np.zeros(n)
    for k in keys:
        r = [np.ravel(x[k]).astype(np.float64) for x in rows]
        p = np.ravel(prog[k]).astype(np.float64)
        for i in range(n):
            proj[i] += r[i] @ p
            for j in range(i + 1):
                gram[i, j] += r[i] @ r[j]
                gram[j, i] = gram[i, j]
    a = np.linalg.solve(gram, proj)
    if not np.all(np.isfinite(a)) or a.mean() <= 0:
        return float("inf")
    return float(np.max(np.abs(a / a.mean() - 1.0)))
