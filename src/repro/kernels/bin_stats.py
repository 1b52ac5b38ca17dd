"""Pallas TPU kernel: fused per-bin statistics for OBR / oscillation telemetry.

For a weight tensor and its quantizer this computes, in ONE pass over the
weights, the per-bin (count, sum, sum-of-squares) histogram that Eq. 10's
within-bin variance and the Tab. 7/12/13 oscillation accounting need. A
CUDA implementation would scatter-atomic into shared memory; TPU has no
atomics, so each tile builds a one-hot (elements x bins) mask with
broadcasted_iota and contracts it on the MXU (bins = Q_N+Q_P+1 <= 256
columns), accumulating into a VMEM scratch across the grid.

Output: (3, n_bins) f32 = [count, sum, sumsq].
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.platform import resolve_interpret

DEFAULT_BLOCK = (512, 128)


def _bin_stats_kernel(w_ref, s_ref, o_ref, acc_ref, *, q_n, q_p, n_bins, n_steps):
    @pl.when(pl.program_id(0) == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    w = w_ref[...].astype(jnp.float32)
    s = jnp.maximum(s_ref[0, 0], 1e-9)
    codes = jnp.clip(jnp.round(w / s), -float(q_n), float(q_p)) + float(q_n)
    flat_w = w.reshape(-1, 1)                       # (E, 1)
    flat_c = codes.reshape(-1, 1)                   # (E, 1)
    bins = jax.lax.broadcasted_iota(jnp.float32, (1, n_bins), 1)
    onehot = (flat_c == bins).astype(jnp.float32)   # (E, n_bins)
    stacked = jnp.concatenate(
        [jnp.ones_like(flat_w), flat_w, flat_w * flat_w], axis=1)  # (E, 3)
    acc_ref[...] += jnp.dot(stacked.T, onehot,
                            preferred_element_type=jnp.float32)

    @pl.when(pl.program_id(0) == n_steps - 1)
    def _done():
        o_ref[...] = acc_ref[...]


@functools.partial(jax.jit, static_argnames=("q_n", "q_p", "block", "interpret"))
def bin_stats_2d(w, scale, *, q_n: int, q_p: int, block=DEFAULT_BLOCK,
                 interpret=None):
    """w: (M, N) with per-tensor scale () -> (3, n_bins) [count, sum, sumsq]."""
    m, n = w.shape
    n_bins = q_n + q_p + 1
    bm = min(block[0], m)
    grid = (pl.cdiv(m, bm),)
    s2 = jnp.reshape(jnp.asarray(scale, jnp.float32), (1, 1))
    return pl.pallas_call(
        functools.partial(_bin_stats_kernel, q_n=q_n, q_p=q_p, n_bins=n_bins,
                          n_steps=grid[0]),
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm, n), lambda i: (i, 0)),
            pl.BlockSpec((1, 1), lambda i: (0, 0)),
        ],
        out_specs=pl.BlockSpec((3, n_bins), lambda i: (0, 0)),
        out_shape=jax.ShapeDtypeStruct((3, n_bins), jnp.float32),
        scratch_shapes=[pltpu.VMEM((3, n_bins), jnp.float32)],
        name="bin_stats_2d",
        interpret=resolve_interpret(interpret),
    )(w, s2)
