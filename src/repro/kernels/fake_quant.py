"""Pallas TPU kernel: tiled LSQ fake-quantization (Eq. 5 forward).

The QAT hot path streams every weight and activation through
quantize->dequantize each step. This kernel tiles the tensor HBM->VMEM in
(block_m x block_n) blocks (128-aligned for the VPU lanes), applies
  y = s * clip(round((x - b)/s), -Q_N, Q_P) + b
in-register, and streams back — one HBM round trip, no intermediate
materialization (the pure-jnp composition writes x/s, the clip, and the
round as separate buffers unless XLA fuses perfectly).

Two scale layouts:
  * per-tensor: scale/offset are (1, 1) blocks broadcast to every tile.
  * per-row-group: scale is (M, 1) — callers put the group axis (heads,
    experts) on rows (ops.py handles the reshape), giving the paper's
    module-dependent granularity.

Validated on CPU with interpret=True against kernels/ref.py.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.platform import resolve_interpret

DEFAULT_BLOCK = (256, 512)


def _fq_kernel_scalar(x_ref, s_ref, b_ref, o_ref, *, q_n, q_p):
    x = x_ref[...].astype(jnp.float32)
    s = jnp.maximum(s_ref[0, 0], 1e-9)
    b = b_ref[0, 0]
    xs = (x - b) / s
    xq = jnp.clip(jnp.round(xs), -float(q_n), float(q_p))
    o_ref[...] = (xq * s + b).astype(o_ref.dtype)


def _fq_kernel_rows(x_ref, s_ref, o_ref, *, q_n, q_p):
    x = x_ref[...].astype(jnp.float32)
    s = jnp.maximum(s_ref[...].astype(jnp.float32), 1e-9)  # (bm, 1)
    xs = x / s
    xq = jnp.clip(jnp.round(xs), -float(q_n), float(q_p))
    o_ref[...] = (xq * s).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("q_n", "q_p", "block", "interpret"))
def fake_quant_2d(x, scale, offset=None, *, q_n: int, q_p: int,
                  block=DEFAULT_BLOCK, interpret=None):
    """Per-tensor fake-quant of a 2D array. scale/offset: () scalars."""
    m, n = x.shape
    bm = min(block[0], m)
    bn = min(block[1], n)
    grid = (pl.cdiv(m, bm), pl.cdiv(n, bn))
    s2 = jnp.reshape(jnp.asarray(scale, jnp.float32), (1, 1))
    b2 = jnp.reshape(jnp.asarray(0.0 if offset is None else offset, jnp.float32),
                     (1, 1))
    return pl.pallas_call(
        functools.partial(_fq_kernel_scalar, q_n=q_n, q_p=q_p),
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm, bn), lambda i, j: (i, j)),
            pl.BlockSpec((1, 1), lambda i, j: (0, 0)),
            pl.BlockSpec((1, 1), lambda i, j: (0, 0)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct((m, n), x.dtype),
        name="fake_quant_2d",
        interpret=resolve_interpret(interpret),
    )(x, s2, b2)


@functools.partial(jax.jit, static_argnames=("q_n", "q_p", "block", "interpret"))
def fake_quant_rows(x, row_scale, *, q_n: int, q_p: int,
                    block=DEFAULT_BLOCK, interpret=None):
    """Row-grouped fake-quant: x (M, N), row_scale (M, 1) — heads/experts on
    rows (MDQ granularity)."""
    m, n = x.shape
    bm = min(block[0], m)
    bn = min(block[1], n)
    grid = (pl.cdiv(m, bm), pl.cdiv(n, bn))
    return pl.pallas_call(
        functools.partial(_fq_kernel_rows, q_n=q_n, q_p=q_p),
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm, bn), lambda i, j: (i, j)),
            pl.BlockSpec((bm, 1), lambda i, j: (i, 0)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct((m, n), x.dtype),
        name="fake_quant_rows",
        interpret=resolve_interpret(interpret),
    )(x, row_scale.astype(jnp.float32))
