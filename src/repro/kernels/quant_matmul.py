"""Pallas TPU kernel: fused fake-quant matmul — the QAT compute hot spot.

Computes  out = q_a(X) @ q_w(W)  in one pass:
  * X (M, K) is quantized with a learnable per-tensor (scale, offset)
    (LSQ+ activation quantizer),
  * W (K, N) with grouped scales on EITHER side of the 2D reshape — (1, N)
    column scales (per-head qkv, per-channel) or (K, 1) row scales (per-head
    wo/xo whose head axis is contracted) — per-tensor scales broadcast; this
    is the paper's full module-dependent granularity (Sec. 4.3),
  * tiles are (bm, bk) x (bk, bn) with bk the MXU contraction tile; the
    f32 accumulator lives in the output VMEM block across the K grid
    dimension (revisited output pattern).

Fusing avoids writing the dequantized X and W back to HBM between the
quantizer and the matmul: 2x(W bytes + X bytes) of traffic saved per linear
per step versus the unfused composition.

Grid iteration order is (M, N, K) with K innermost so the output block is
revisited consecutively (legal accumulation pattern on TPU).

Batched-expert variants (`quant_matmul_batched` / `quant_matmul_bwd_batched`)
add a leading grid dimension over the expert axis: each expert's weight
(E, K, N), per-expert activation scale/offset (E, 1) and per-expert column
scales (E, N) are indexed by program_id(0), covering the MoE expert einsums
gecd,edf->gecf / gecf,efd->gecd without leaving the fused path.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.platform import resolve_interpret

DEFAULT_TILES = (128, 128, 512)  # (bm, bn, bk) — MXU-aligned

# VMEM ceiling for the combined backward's scratch accumulators (its dW row
# panel is (bk, Np) f32 — unbounded in N). ~16MB VMEM/core on current TPUs;
# 8MB leaves room for the double-buffered in/out blocks. Past this,
# quant_matmul_bwd[_batched] falls back to the split dx/dw kernels, whose
# scratches are tile-sized (see bwd_uses_combined).
BWD_SCRATCH_BUDGET_BYTES = 8 * 1024 * 1024


def _qmm_kernel(x_ref, w_ref, as_ref, ab_ref, ws_ref, o_ref, acc_ref, *,
                q_n_a, q_p_a, q_n_w, q_p_w, n_k):
    @pl.when(pl.program_id(2) == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    x = x_ref[...].astype(jnp.float32)
    a_s = jnp.maximum(as_ref[0, 0], 1e-9)
    a_b = ab_ref[0, 0]
    xq = jnp.clip(jnp.round((x - a_b) / a_s), -float(q_n_a), float(q_p_a))
    xd = xq * a_s + a_b

    w = w_ref[...].astype(jnp.float32)
    w_s = jnp.maximum(ws_ref[...].astype(jnp.float32), 1e-9)  # (1, bn)
    wq = jnp.clip(jnp.round(w / w_s), -float(q_n_w), float(q_p_w))
    wd = wq * w_s

    acc_ref[...] += jnp.dot(xd.astype(jnp.bfloat16), wd.astype(jnp.bfloat16),
                            preferred_element_type=jnp.float32)

    @pl.when(pl.program_id(2) == n_k - 1)
    def _done():
        o_ref[...] = acc_ref[...].astype(o_ref.dtype)


def _w_scale_spec(w_scale, bk, bn):
    """BlockSpec for a (1, N) column-scale or (K, 1) row-scale operand."""
    if w_scale.shape[0] == 1:   # column groups (broadcast over K rows)
        return pl.BlockSpec((1, bn), lambda i, j, kk: (0, j))
    assert w_scale.shape[1] == 1, w_scale.shape
    return pl.BlockSpec((bk, 1), lambda i, j, kk: (kk, 0))


@functools.partial(jax.jit, static_argnames=("q_n_a", "q_p_a", "q_n_w", "q_p_w",
                                             "tiles", "interpret", "out_dtype"))
def quant_matmul(x, w, a_scale, a_offset, w_scale, *,
                 q_n_a: int, q_p_a: int, q_n_w: int, q_p_w: int,
                 tiles=DEFAULT_TILES, interpret=None,
                 out_dtype=jnp.float32):
    """x: (M, K); w: (K, N); a_scale/a_offset: scalars; w_scale: (1, N)
    column groups or (K, 1) row groups (K-side per-head scales)."""
    m, k = x.shape
    k2, n = w.shape
    assert k == k2, (x.shape, w.shape)
    bm = min(tiles[0], m)
    bn = min(tiles[1], n)
    bk = min(tiles[2], k)
    grid = (pl.cdiv(m, bm), pl.cdiv(n, bn), pl.cdiv(k, bk))
    a_s = jnp.reshape(jnp.asarray(a_scale, jnp.float32), (1, 1))
    a_b = jnp.reshape(jnp.asarray(a_offset, jnp.float32), (1, 1))
    return pl.pallas_call(
        functools.partial(_qmm_kernel, q_n_a=q_n_a, q_p_a=q_p_a,
                          q_n_w=q_n_w, q_p_w=q_p_w, n_k=grid[2]),
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, j, kk: (i, kk)),
            pl.BlockSpec((bk, bn), lambda i, j, kk: (kk, j)),
            pl.BlockSpec((1, 1), lambda i, j, kk: (0, 0)),
            pl.BlockSpec((1, 1), lambda i, j, kk: (0, 0)),
            _w_scale_spec(w_scale, bk, bn),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, kk: (i, j)),
        out_shape=jax.ShapeDtypeStruct((m, n), out_dtype),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32)],
        name="quant_matmul",
        interpret=resolve_interpret(interpret),
    )(x, w, a_s, a_b, w_scale.astype(jnp.float32))


def _expert_operands(a_scale, a_offset, w_scale):
    """(E, 1) scale/offset -> (E, 1, 1) and (E, N) column scales ->
    (E, 1, N): a singleton sublane axis makes each per-expert block's last
    two dims equal the array's, as the TPU block-shape rule requires."""
    e = a_scale.shape[0]
    return (a_scale.astype(jnp.float32).reshape(e, 1, 1),
            a_offset.astype(jnp.float32).reshape(e, 1, 1),
            w_scale.astype(jnp.float32).reshape(e, 1, -1))


def _qmm_batched_kernel(x_ref, w_ref, as_ref, ab_ref, ws_ref, o_ref, acc_ref,
                        *, q_n_a, q_p_a, q_n_w, q_p_w, n_k):
    @pl.when(pl.program_id(3) == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    x = x_ref[0].astype(jnp.float32)
    a_s = jnp.maximum(as_ref[0, 0, 0], 1e-9)
    a_b = ab_ref[0, 0, 0]
    xq = jnp.clip(jnp.round((x - a_b) / a_s), -float(q_n_a), float(q_p_a))
    xd = xq * a_s + a_b

    w = w_ref[0].astype(jnp.float32)
    w_s = jnp.maximum(ws_ref[0].astype(jnp.float32), 1e-9)  # (1, bn)
    wq = jnp.clip(jnp.round(w / w_s), -float(q_n_w), float(q_p_w))
    wd = wq * w_s

    acc_ref[...] += jnp.dot(xd.astype(jnp.bfloat16), wd.astype(jnp.bfloat16),
                            preferred_element_type=jnp.float32)

    @pl.when(pl.program_id(3) == n_k - 1)
    def _done():
        o_ref[0] = acc_ref[...].astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("q_n_a", "q_p_a", "q_n_w", "q_p_w",
                                             "tiles", "interpret", "out_dtype"))
def quant_matmul_batched(x, w, a_scale, a_offset, w_scale, *,
                         q_n_a: int, q_p_a: int, q_n_w: int, q_p_w: int,
                         tiles=DEFAULT_TILES, interpret=None,
                         out_dtype=jnp.float32):
    """Batched-expert fused matmul: out[e] = q_a(x[e]) @ q_w(w[e]).

    x: (E, M, K); w: (E, K, N); a_scale/a_offset: (E, 1) per-expert scalars;
    w_scale: (E, N) per-expert column scales. The grid's leading dimension
    runs over experts; every per-expert operand is indexed by program_id(0).
    """
    e, m, k = x.shape
    e2, k2, n = w.shape
    assert (e, k) == (e2, k2), (x.shape, w.shape)
    bm = min(tiles[0], m)
    bn = min(tiles[1], n)
    bk = min(tiles[2], k)
    grid = (e, pl.cdiv(m, bm), pl.cdiv(n, bn), pl.cdiv(k, bk))
    return pl.pallas_call(
        functools.partial(_qmm_batched_kernel, q_n_a=q_n_a, q_p_a=q_p_a,
                          q_n_w=q_n_w, q_p_w=q_p_w, n_k=grid[3]),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, bm, bk), lambda ee, i, j, kk: (ee, i, kk)),
            pl.BlockSpec((1, bk, bn), lambda ee, i, j, kk: (ee, kk, j)),
            pl.BlockSpec((1, 1, 1), lambda ee, i, j, kk: (ee, 0, 0)),
            pl.BlockSpec((1, 1, 1), lambda ee, i, j, kk: (ee, 0, 0)),
            pl.BlockSpec((1, 1, bn), lambda ee, i, j, kk: (ee, 0, j)),
        ],
        out_specs=pl.BlockSpec((1, bm, bn), lambda ee, i, j, kk: (ee, i, j)),
        out_shape=jax.ShapeDtypeStruct((e, m, n), out_dtype),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32)],
        name="quant_matmul_batched",
        interpret=resolve_interpret(interpret),
    )(x, w, *_expert_operands(a_scale, a_offset, w_scale))


# ---------------------------------------------------------------------------
# Backward kernels (LSQ/LSQ+ Eq. 6-7, masks recomputed tile-wise in VMEM)
# ---------------------------------------------------------------------------
#
# The unfused composition materializes the dequantized X and W in HBM twice
# per linear (forward + saved-for-backward). These kernels redo the cheap
# quantize math on the tile already resident in VMEM, so the backward — like
# the forward — makes exactly one HBM round trip per operand:
#
#   dX      = (dY @ Wd^T) * 1[-Q_N <= (x-b)/s <= Q_P]            (Eq. 6)
#   d s_a   = sum dXq * (round(u) - u  inside | -Q_N / Q_P outside)   (Eq. 7)
#   d b_a   = sum dXq * (1 - mask)                               (LSQ+ offset)
#   dW      = (Xd^T @ dY) * 1[-Q_N <= w/s <= Q_P]
#   d s_w   = per-column sum dWq * (round(u_w) - u_w | -Q_N | Q_P)
#
# Cotangents are rounded through bf16 after the f32-accumulated dot so the
# fused path is bit-compatible with the unfused bf16 einsum's autodiff.
#
# d s_a and d b_a are sums over all of X. The TPU cannot store a scalar to
# VMEM, and a (1, 1) block revisited by every grid step would also depend on
# output-block residency. So each dX tile writes its column sums once, as a
# lane-dense (1, bk) row of an (M-tiles, 1, K) partials array, and the
# wrapper sums the partials.


def _qmm_dx_kernel(dy_ref, w_ref, ws_ref, x_ref, as_ref, ab_ref,
                   dx_ref, dsa_ref, dba_ref, acc_ref, *,
                   q_n_a, q_p_a, q_n_w, q_p_w, n_n, round_cot):
    j = pl.program_id(2)

    @pl.when(j == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    w = w_ref[...].astype(jnp.float32)
    w_s = jnp.maximum(ws_ref[...].astype(jnp.float32), 1e-9)
    wd = jnp.clip(jnp.round(w / w_s), -float(q_n_w), float(q_p_w)) * w_s
    wd = wd.astype(jnp.bfloat16)
    if round_cot:  # bf16-einsum caller: cotangent rounds like its autodiff
        dy = dy_ref[...].astype(jnp.bfloat16)
    else:          # f32-preferred einsum caller (lm_head): keep f32
        dy = dy_ref[...].astype(jnp.float32)
        wd = wd.astype(jnp.float32)
    acc_ref[...] += jax.lax.dot_general(
        dy, wd, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32)

    @pl.when(j == n_n - 1)
    def _done():
        # cotangents take the primal's dtype, so the unfused einsum's dX
        # always rounds through bf16 at the astype boundary — match it
        dxd = acc_ref[...].astype(jnp.bfloat16).astype(jnp.float32)
        x = x_ref[...].astype(jnp.float32)
        a_s = jnp.maximum(as_ref[0, 0], 1e-9)
        a_b = ab_ref[0, 0]
        u = (x - a_b) / a_s
        mf = jnp.logical_and(u >= -float(q_n_a),
                             u <= float(q_p_a)).astype(jnp.float32)
        q = jnp.clip(jnp.round(u), -float(q_n_a), float(q_p_a))
        dx_ref[...] = (dxd * mf).astype(dx_ref.dtype)
        dsa_ref[0] = jnp.sum(dxd * (q - mf * u), axis=0, keepdims=True)
        dba_ref[0] = jnp.sum(dxd * (1.0 - mf), axis=0, keepdims=True)


@functools.partial(jax.jit, static_argnames=("q_n_a", "q_p_a", "q_n_w", "q_p_w",
                                             "round_cot", "tiles", "interpret"))
def quant_matmul_dx(dy, x, w, a_scale, a_offset, w_scale, *,
                    q_n_a: int, q_p_a: int, q_n_w: int, q_p_w: int,
                    round_cot: bool = True,
                    tiles=DEFAULT_TILES, interpret=None):
    """Backward wrt x of quant_matmul: (dX, d a_scale_raw, d a_offset_raw).

    dy: (M, N); x: (M, K); w: (K, N); w_scale: (1, N) column groups or
    (K, 1) row groups (K-side per-head scales, dequant only). The
    scale/offset cotangents are the RAW range-indicator sums — the caller
    applies the module-wise gradient scale g (via core.quantizer.grad_scale,
    outside).
    """
    m, k = x.shape
    _, n = w.shape
    bm = min(tiles[0], m)
    bn = min(tiles[1], n)
    bk = min(tiles[2], k)
    grid = (pl.cdiv(m, bm), pl.cdiv(k, bk), pl.cdiv(n, bn))
    if w_scale.shape[0] == 1:
        ws_spec = pl.BlockSpec((1, bn), lambda i, kk, j: (0, j))
    else:
        assert w_scale.shape[1] == 1, w_scale.shape
        ws_spec = pl.BlockSpec((bk, 1), lambda i, kk, j: (kk, 0))
    a_s = jnp.reshape(jnp.asarray(a_scale, jnp.float32), (1, 1))
    a_b = jnp.reshape(jnp.asarray(a_offset, jnp.float32), (1, 1))
    dx, dsa, dba = pl.pallas_call(
        functools.partial(_qmm_dx_kernel, q_n_a=q_n_a, q_p_a=q_p_a,
                          q_n_w=q_n_w, q_p_w=q_p_w, n_n=grid[2],
                          round_cot=round_cot),
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm, bn), lambda i, kk, j: (i, j)),
            pl.BlockSpec((bk, bn), lambda i, kk, j: (kk, j)),
            ws_spec,
            pl.BlockSpec((bm, bk), lambda i, kk, j: (i, kk)),
            pl.BlockSpec((1, 1), lambda i, kk, j: (0, 0)),
            pl.BlockSpec((1, 1), lambda i, kk, j: (0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((bm, bk), lambda i, kk, j: (i, kk)),
            pl.BlockSpec((1, 1, bk), lambda i, kk, j: (i, 0, kk)),
            pl.BlockSpec((1, 1, bk), lambda i, kk, j: (i, 0, kk)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((m, k), jnp.float32),
            jax.ShapeDtypeStruct((grid[0], 1, k), jnp.float32),
            jax.ShapeDtypeStruct((grid[0], 1, k), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((bm, bk), jnp.float32)],
        name="quant_matmul_dx",
        interpret=resolve_interpret(interpret),
    )(dy, w, w_scale.astype(jnp.float32), x, a_s, a_b)
    return dx, jnp.sum(dsa), jnp.sum(dba)


def _qmm_dw_kernel(x_ref, dy_ref, as_ref, ab_ref, w_ref, ws_ref,
                   dw_ref, dws_ref, acc_ref, dws_acc, *,
                   q_n_a, q_p_a, q_n_w, q_p_w, n_m, n_j, round_cot, k_side):
    j, kk, i = pl.program_id(0), pl.program_id(1), pl.program_id(2)

    @pl.when(i == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    x = x_ref[...].astype(jnp.float32)
    a_s = jnp.maximum(as_ref[0, 0], 1e-9)
    a_b = ab_ref[0, 0]
    xq = jnp.clip(jnp.round((x - a_b) / a_s), -float(q_n_a), float(q_p_a))
    xd = (xq * a_s + a_b).astype(jnp.bfloat16)
    if round_cot:
        dy = dy_ref[...].astype(jnp.bfloat16)
    else:
        dy = dy_ref[...].astype(jnp.float32)
        xd = xd.astype(jnp.float32)
    acc_ref[...] += jax.lax.dot_general(
        xd, dy, (((0,), (0,)), ((), ())), preferred_element_type=jnp.float32)

    @pl.when(i == n_m - 1)
    def _done():
        dwd = acc_ref[...].astype(jnp.bfloat16).astype(jnp.float32)
        w = w_ref[...].astype(jnp.float32)
        w_s = jnp.maximum(ws_ref[...].astype(jnp.float32), 1e-9)
        u = w / w_s
        mf = jnp.logical_and(u >= -float(q_n_w),
                             u <= float(q_p_w)).astype(jnp.float32)
        q = jnp.clip(jnp.round(u), -float(q_n_w), float(q_p_w))
        dw_ref[...] = (dwd * mf).astype(dw_ref.dtype)
        if k_side:
            # block (kk, 0) is revisited across j NON-consecutively (j is
            # outermost here): accumulate in the persistent scratch and write
            # the output block once, on its final visit
            part = jnp.sum(dwd * (q - mf * u), axis=1, keepdims=True)
            ksl = pl.dslice(kk * w_ref.shape[0], w_ref.shape[0])

            @pl.when(j == 0)
            def _first():
                dws_acc[ksl, :] = part

            @pl.when(j > 0)
            def _rest():
                dws_acc[ksl, :] += part

            @pl.when(j == n_j - 1)
            def _emit():
                dws_ref[...] = dws_acc[ksl, :]
        else:
            # block (0, j) is resident for the whole j run (its index map
            # ignores kk and i): in-ref accumulation over kk is legal
            part = jnp.sum(dwd * (q - mf * u), axis=0, keepdims=True)

            @pl.when(kk == 0)
            def _first():
                dws_ref[...] = part

            @pl.when(kk > 0)
            def _rest():
                dws_ref[...] += part


@functools.partial(jax.jit, static_argnames=("q_n_a", "q_p_a", "q_n_w", "q_p_w",
                                             "round_cot", "tiles", "interpret"))
def quant_matmul_dw(dy, x, w, a_scale, a_offset, w_scale, *,
                    q_n_a: int, q_p_a: int, q_n_w: int, q_p_w: int,
                    round_cot: bool = True,
                    tiles=DEFAULT_TILES, interpret=None):
    """Backward wrt w of quant_matmul: (dW, d w_scale_raw).

    w_scale (1, N) column groups -> dws (1, N), the per-column cotangent
    summed over K in-kernel; w_scale (K, 1) row groups (K-side per-head) ->
    dws (K, 1), summed over N. Either way the caller reduces into the scale
    groups and applies the gradient scale.
    """
    m, k = x.shape
    _, n = w.shape
    bm = min(tiles[0], m)
    bn = min(tiles[1], n)
    bk = min(tiles[2], k)
    grid = (pl.cdiv(n, bn), pl.cdiv(k, bk), pl.cdiv(m, bm))
    k_side = w_scale.shape[0] != 1
    if k_side:
        assert w_scale.shape[1] == 1, w_scale.shape
        ws_spec = pl.BlockSpec((bk, 1), lambda j, kk, i: (kk, 0))
        dws_spec = pl.BlockSpec((bk, 1), lambda j, kk, i: (kk, 0))
        dws_shape = (k, 1)
        dws_scratch = pltpu.VMEM((grid[1] * bk, 1), jnp.float32)
    else:
        ws_spec = pl.BlockSpec((1, bn), lambda j, kk, i: (0, j))
        dws_spec = pl.BlockSpec((1, bn), lambda j, kk, i: (0, j))
        dws_shape = (1, n)
        dws_scratch = pltpu.VMEM((1, 1), jnp.float32)
    a_s = jnp.reshape(jnp.asarray(a_scale, jnp.float32), (1, 1))
    a_b = jnp.reshape(jnp.asarray(a_offset, jnp.float32), (1, 1))
    dw, dws = pl.pallas_call(
        functools.partial(_qmm_dw_kernel, q_n_a=q_n_a, q_p_a=q_p_a,
                          q_n_w=q_n_w, q_p_w=q_p_w, n_m=grid[2], n_j=grid[0],
                          round_cot=round_cot, k_side=k_side),
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm, bk), lambda j, kk, i: (i, kk)),
            pl.BlockSpec((bm, bn), lambda j, kk, i: (i, j)),
            pl.BlockSpec((1, 1), lambda j, kk, i: (0, 0)),
            pl.BlockSpec((1, 1), lambda j, kk, i: (0, 0)),
            pl.BlockSpec((bk, bn), lambda j, kk, i: (kk, j)),
            ws_spec,
        ],
        out_specs=[
            pl.BlockSpec((bk, bn), lambda j, kk, i: (kk, j)),
            dws_spec,
        ],
        out_shape=[
            jax.ShapeDtypeStruct((k, n), jnp.float32),
            jax.ShapeDtypeStruct(dws_shape, jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((bk, bn), jnp.float32), dws_scratch],
        name="quant_matmul_dw",
        interpret=resolve_interpret(interpret),
    )(x, dy, a_s, a_b, w, w_scale.astype(jnp.float32))
    return dw, dws


# ---------------------------------------------------------------------------
# Combined backward: dX, dW and all three scale reductions in ONE pallas_call
# ---------------------------------------------------------------------------
#
# The split quant_matmul_dx / quant_matmul_dw kernels each stage dY, X and W
# from HBM (dx reads dY+W per tile and X at finalization; dw reads X+dY per
# tile and W at finalization), so the backward pays two HBM round trips per
# operand. This kernel shares one staging of all three: grid (K, M, N) with
# N innermost; per step it dequantizes the X and W tiles once and feeds both
# accumulations —
#
#   dX(i,kk) += dY(i,j) @ Wd(kk,j)^T   accumulated over j in a (bm, bk)
#               scratch, finalized (Eq. 6 mask + Eq. 7 scale/offset sums)
#               at the last j;
#   dW(kk,j) += Xd(i,kk)^T @ dY(i,j)   accumulated over i in a (bk, Np)
#               scratch row panel, finalized at the last i with the
#               per-column (1, N) or per-row (K, 1) scale-gradient sums.
#
# The entry boundary therefore reads dY/X/W once and writes each output once
# — ~1.5x less modeled backward traffic than the two split kernels (see
# BENCH_kernels.json qat_bwd.combined_vs_split). The (bk, Np) panel bounds
# N by VMEM: past BWD_SCRATCH_BUDGET_BYTES the wrapper falls back to the
# split dx/dw kernels, whose scratches are tile-sized (lm_head-vocab N never
# tries to allocate the panel). Tiles stay the MXU defaults either way.
#
# Output-residency note: Pallas TPU keeps an output block in VMEM only
# across CONSECUTIVE grid steps that map to it. The (1, Np) column-scale
# cotangent is reduced over the OUTERMOST kk axis while its block index
# tracks the innermost j, so it is accumulated in a persistent VMEM scratch
# and each output block is written exactly once, on its final visit.
# (The (Kp, 1) row-scale cotangent's block index tracks kk itself, so it
# stays resident for the whole kk run and in-ref accumulation is legal.)


def _qmm_bwd_kernel(dy_ref, x_ref, w_ref, as_ref, ab_ref, ws_ref,
                    dx_ref, dsa_ref, dba_ref, dw_ref, dws_ref,
                    dx_acc, dw_acc, dws_acc, *,
                    q_n_a, q_p_a, q_n_w, q_p_w, n_k, n_i, n_j, round_cot,
                    k_side):
    kk, i, j = pl.program_id(0), pl.program_id(1), pl.program_id(2)
    bn = dy_ref.shape[-1]

    @pl.when(j == 0)
    def _init_dx():
        dx_acc[...] = jnp.zeros_like(dx_acc)

    # dequantize both operand tiles ONCE from the VMEM-resident data
    x = x_ref[...].astype(jnp.float32)
    a_s = jnp.maximum(as_ref[0, 0], 1e-9)
    a_b = ab_ref[0, 0]
    u_x = (x - a_b) / a_s
    xq = jnp.clip(jnp.round(u_x), -float(q_n_a), float(q_p_a))
    xd = (xq * a_s + a_b).astype(jnp.bfloat16)

    w = w_ref[...].astype(jnp.float32)
    w_s = jnp.maximum(ws_ref[...].astype(jnp.float32), 1e-9)
    u_w = w / w_s
    qw = jnp.clip(jnp.round(u_w), -float(q_n_w), float(q_p_w))
    wd = (qw * w_s).astype(jnp.bfloat16)

    if round_cot:  # bf16-einsum caller: cotangent rounds like its autodiff
        dy = dy_ref[...].astype(jnp.bfloat16)
    else:          # f32-preferred einsum caller (lm_head): keep f32
        dy = dy_ref[...].astype(jnp.float32)
        wd = wd.astype(jnp.float32)
        xd = xd.astype(jnp.float32)

    dx_acc[...] += jax.lax.dot_general(
        dy, wd, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32)
    part_dw = jax.lax.dot_general(
        xd, dy, (((0,), (0,)), ((), ())), preferred_element_type=jnp.float32)
    jsl = pl.dslice(j * bn, bn)

    @pl.when(i == 0)
    def _dw_first():
        dw_acc[:, jsl] = part_dw

    @pl.when(i > 0)
    def _dw_rest():
        dw_acc[:, jsl] += part_dw

    @pl.when(j == n_j - 1)
    def _fin_dx():
        # cotangents take the primal's dtype: the unfused einsum's dX always
        # rounds through bf16 at the astype boundary — match it
        dxd = dx_acc[...].astype(jnp.bfloat16).astype(jnp.float32)
        mf = jnp.logical_and(u_x >= -float(q_n_a),
                             u_x <= float(q_p_a)).astype(jnp.float32)
        dx_ref[...] = (dxd * mf).astype(dx_ref.dtype)
        dsa_ref[0] = jnp.sum(dxd * (xq - mf * u_x), axis=0, keepdims=True)
        dba_ref[0] = jnp.sum(dxd * (1.0 - mf), axis=0, keepdims=True)

    @pl.when(i == n_i - 1)
    def _fin_dw():
        dwd = dw_acc[:, jsl].astype(jnp.bfloat16).astype(jnp.float32)
        mfw = jnp.logical_and(u_w >= -float(q_n_w),
                              u_w <= float(q_p_w)).astype(jnp.float32)
        dw_ref[...] = (dwd * mfw).astype(dw_ref.dtype)
        if k_side:
            # block (kk, 0) is resident for the whole kk run (its index map
            # ignores i and j): in-ref accumulation over j is legal
            part = jnp.sum(dwd * (qw - mfw * u_w), axis=1, keepdims=True)

            @pl.when(j == 0)
            def _first():
                dws_ref[...] = part

            @pl.when(j > 0)
            def _rest():
                dws_ref[...] += part
        else:
            # block (0, j) is revisited across kk NON-consecutively (j is
            # innermost): accumulate in the persistent scratch and write the
            # output block once, on its final visit
            part = jnp.sum(dwd * (qw - mfw * u_w), axis=0, keepdims=True)

            @pl.when(kk == 0)
            def _first():
                dws_acc[:, jsl] = part

            @pl.when(kk > 0)
            def _rest():
                dws_acc[:, jsl] += part

            @pl.when(kk == n_k - 1)
            def _emit():
                dws_ref[...] = dws_acc[:, jsl]


def bwd_scratch_bytes(m, k, n, tiles=DEFAULT_TILES):
    """f32 scratch footprint of the combined backward: the (bm, bk) dX
    accumulator, the (bk, Np) dW row panel, and the (1, Np) dws scratch."""
    bm = min(tiles[0], m)
    bn = min(tiles[1], n)
    bk = min(tiles[2], k)
    n_pad = -(-n // bn) * bn
    return 4 * (bm * bk + bk * n_pad + n_pad)


def bwd_uses_combined(m, k, n, tiles=DEFAULT_TILES, scratch_budget=None):
    """Whether the combined backward's scratch fits the VMEM budget; past it
    quant_matmul_bwd[_batched] falls back to the split dx/dw kernels."""
    budget = (BWD_SCRATCH_BUDGET_BYTES if scratch_budget is None
              else scratch_budget)
    return bwd_scratch_bytes(m, k, n, tiles) <= budget


@functools.partial(jax.jit, static_argnames=("q_n_a", "q_p_a", "q_n_w", "q_p_w",
                                             "round_cot", "tiles", "interpret",
                                             "scratch_budget"))
def quant_matmul_bwd(dy, x, w, a_scale, a_offset, w_scale, *,
                     q_n_a: int, q_p_a: int, q_n_w: int, q_p_w: int,
                     round_cot: bool = True,
                     tiles=DEFAULT_TILES, interpret=None,
                     scratch_budget: int | None = None):
    """Combined backward of quant_matmul — one pallas_call, one HBM read of
    dY/X/W each: (dX, d a_scale_raw, d a_offset_raw, dW, d w_scale_raw).

    dy: (M, N); x: (M, K); w: (K, N); w_scale: (1, N) column groups or
    (K, 1) row groups. Scale cotangents are the RAW range-indicator sums —
    the caller applies the module-wise gradient scale g and the per-group
    reduction (via core.quantizer.grad_scale + a differentiable broadcast).
    All dims must be padded to tile multiples by the caller.

    When the (bk, Np) dW panel would exceed `scratch_budget` VMEM bytes
    (default BWD_SCRATCH_BUDGET_BYTES — lm_head-vocab or very wide d_ff N),
    dispatches to the split quant_matmul_dx / quant_matmul_dw kernels, whose
    scratches are tile-sized, and returns the identical cotangent tuple.
    """
    m, k = x.shape
    _, n = w.shape
    kw = dict(q_n_a=q_n_a, q_p_a=q_p_a, q_n_w=q_n_w, q_p_w=q_p_w,
              round_cot=round_cot, tiles=tiles, interpret=interpret)
    if not bwd_uses_combined(m, k, n, tiles, scratch_budget):
        dx, dsa, dba = quant_matmul_dx(dy, x, w, a_scale, a_offset, w_scale,
                                       **kw)
        dw, dws = quant_matmul_dw(dy, x, w, a_scale, a_offset, w_scale, **kw)
        return dx, dsa, dba, dw, dws
    bm = min(tiles[0], m)
    bn = min(tiles[1], n)
    bk = min(tiles[2], k)
    grid = (pl.cdiv(k, bk), pl.cdiv(m, bm), pl.cdiv(n, bn))
    n_pad = grid[2] * bn
    k_side = w_scale.shape[0] != 1
    a_s = jnp.reshape(jnp.asarray(a_scale, jnp.float32), (1, 1))
    a_b = jnp.reshape(jnp.asarray(a_offset, jnp.float32), (1, 1))
    if k_side:
        ws_spec = pl.BlockSpec((bk, 1), lambda kk, i, j: (kk, 0))
        dws_spec = pl.BlockSpec((bk, 1), lambda kk, i, j: (kk, 0))
        dws_shape = (k, 1)
    else:
        ws_spec = pl.BlockSpec((1, bn), lambda kk, i, j: (0, j))
        dws_spec = pl.BlockSpec((1, bn), lambda kk, i, j: (0, j))
        dws_shape = (1, n)
    dx, dsa, dba, dw, dws = pl.pallas_call(
        functools.partial(_qmm_bwd_kernel, q_n_a=q_n_a, q_p_a=q_p_a,
                          q_n_w=q_n_w, q_p_w=q_p_w, n_k=grid[0],
                          n_i=grid[1], n_j=grid[2],
                          round_cot=round_cot, k_side=k_side),
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm, bn), lambda kk, i, j: (i, j)),
            pl.BlockSpec((bm, bk), lambda kk, i, j: (i, kk)),
            pl.BlockSpec((bk, bn), lambda kk, i, j: (kk, j)),
            pl.BlockSpec((1, 1), lambda kk, i, j: (0, 0)),
            pl.BlockSpec((1, 1), lambda kk, i, j: (0, 0)),
            ws_spec,
        ],
        out_specs=[
            pl.BlockSpec((bm, bk), lambda kk, i, j: (i, kk)),
            pl.BlockSpec((1, 1, bk), lambda kk, i, j: (i, 0, kk)),
            pl.BlockSpec((1, 1, bk), lambda kk, i, j: (i, 0, kk)),
            pl.BlockSpec((bk, bn), lambda kk, i, j: (kk, j)),
            dws_spec,
        ],
        out_shape=[
            jax.ShapeDtypeStruct((m, k), jnp.float32),
            jax.ShapeDtypeStruct((grid[1], 1, k), jnp.float32),
            jax.ShapeDtypeStruct((grid[1], 1, k), jnp.float32),
            jax.ShapeDtypeStruct((k, n), jnp.float32),
            jax.ShapeDtypeStruct(dws_shape, jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((bm, bk), jnp.float32),
                        pltpu.VMEM((bk, n_pad), jnp.float32),
                        pltpu.VMEM((1, 1) if k_side else (1, n_pad),
                                   jnp.float32)],
        name="quant_matmul_bwd",
        interpret=resolve_interpret(interpret),
    )(dy, x, w, a_s, a_b, w_scale.astype(jnp.float32))
    return dx, jnp.sum(dsa), jnp.sum(dba), dw, dws


def _qmm_bwd_batched_kernel(dy_ref, x_ref, w_ref, as_ref, ab_ref, ws_ref,
                            dx_ref, dsa_ref, dba_ref, dw_ref, dws_ref,
                            dx_acc, dw_acc, dws_acc, *,
                            q_n_a, q_p_a, q_n_w, q_p_w, n_k, n_i, n_j,
                            round_cot):
    kk, i, j = pl.program_id(1), pl.program_id(2), pl.program_id(3)
    bn = dy_ref.shape[-1]

    @pl.when(j == 0)
    def _init_dx():
        dx_acc[...] = jnp.zeros_like(dx_acc)

    x = x_ref[0].astype(jnp.float32)
    a_s = jnp.maximum(as_ref[0, 0, 0], 1e-9)
    a_b = ab_ref[0, 0, 0]
    u_x = (x - a_b) / a_s
    xq = jnp.clip(jnp.round(u_x), -float(q_n_a), float(q_p_a))
    xd = (xq * a_s + a_b).astype(jnp.bfloat16)

    w = w_ref[0].astype(jnp.float32)
    w_s = jnp.maximum(ws_ref[0].astype(jnp.float32), 1e-9)  # (1, bn)
    u_w = w / w_s
    qw = jnp.clip(jnp.round(u_w), -float(q_n_w), float(q_p_w))
    wd = (qw * w_s).astype(jnp.bfloat16)

    if round_cot:
        dy = dy_ref[0].astype(jnp.bfloat16)
    else:
        dy = dy_ref[0].astype(jnp.float32)
        wd = wd.astype(jnp.float32)
        xd = xd.astype(jnp.float32)

    dx_acc[...] += jax.lax.dot_general(
        dy, wd, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32)
    part_dw = jax.lax.dot_general(
        xd, dy, (((0,), (0,)), ((), ())), preferred_element_type=jnp.float32)
    jsl = pl.dslice(j * bn, bn)

    @pl.when(i == 0)
    def _dw_first():
        dw_acc[:, jsl] = part_dw

    @pl.when(i > 0)
    def _dw_rest():
        dw_acc[:, jsl] += part_dw

    @pl.when(j == n_j - 1)
    def _fin_dx():
        dxd = dx_acc[...].astype(jnp.bfloat16).astype(jnp.float32)
        mf = jnp.logical_and(u_x >= -float(q_n_a),
                             u_x <= float(q_p_a)).astype(jnp.float32)
        dx_ref[0] = (dxd * mf).astype(dx_ref.dtype)
        dsa_ref[0, 0] = jnp.sum(dxd * (xq - mf * u_x), axis=0, keepdims=True)
        dba_ref[0, 0] = jnp.sum(dxd * (1.0 - mf), axis=0, keepdims=True)

    @pl.when(i == n_i - 1)
    def _fin_dw():
        dwd = dw_acc[:, jsl].astype(jnp.bfloat16).astype(jnp.float32)
        mfw = jnp.logical_and(u_w >= -float(q_n_w),
                              u_w <= float(q_p_w)).astype(jnp.float32)
        dw_ref[0] = (dwd * mfw).astype(dw_ref.dtype)
        # per-expert dws block (ee, j) is revisited across kk NON-consecutively
        # (j is innermost): accumulate in the persistent scratch (re-initialized
        # at kk == 0 of every expert) and write the output block on its final
        # visit only
        part = jnp.sum(dwd * (qw - mfw * u_w), axis=0, keepdims=True)

        @pl.when(kk == 0)
        def _first():
            dws_acc[:, jsl] = part

        @pl.when(kk > 0)
        def _rest():
            dws_acc[:, jsl] += part

        @pl.when(kk == n_k - 1)
        def _emit():
            dws_ref[0] = dws_acc[:, jsl]


@functools.partial(jax.jit, static_argnames=("q_n_a", "q_p_a", "q_n_w", "q_p_w",
                                             "round_cot", "tiles", "interpret",
                                             "scratch_budget"))
def quant_matmul_bwd_batched(dy, x, w, a_scale, a_offset, w_scale, *,
                             q_n_a: int, q_p_a: int, q_n_w: int, q_p_w: int,
                             round_cot: bool = True,
                             tiles=DEFAULT_TILES, interpret=None,
                             scratch_budget: int | None = None):
    """Per-expert combined backward of quant_matmul_batched.

    dy: (E, M, N); x: (E, M, K); w: (E, K, N); a_scale/a_offset: (E, 1);
    w_scale: (E, N). Returns (dX (E,M,K), dsa (E,1), dba (E,1), dW (E,K,N),
    dws (E,N)) with the scale cotangents raw (per-expert range-indicator
    sums); the leading grid dimension runs over experts.

    Shares the 2D kernel's VMEM scratch budget: when the (bk, Np) dW panel
    would not fit, each expert's cotangents come from the split dx/dw
    kernels instead (same values, tile-sized scratches).
    """
    e, m, k = x.shape
    _, _, n = w.shape
    if not bwd_uses_combined(m, k, n, tiles, scratch_budget):
        kw = dict(q_n_a=q_n_a, q_p_a=q_p_a, q_n_w=q_n_w, q_p_w=q_p_w,
                  round_cot=round_cot, tiles=tiles, interpret=interpret)
        outs = []
        for ee in range(e):
            dx_e, dsa_e, dba_e = quant_matmul_dx(
                dy[ee], x[ee], w[ee], a_scale[ee, 0], a_offset[ee, 0],
                w_scale[ee:ee + 1], **kw)
            dw_e, dws_e = quant_matmul_dw(
                dy[ee], x[ee], w[ee], a_scale[ee, 0], a_offset[ee, 0],
                w_scale[ee:ee + 1], **kw)
            outs.append((dx_e, dsa_e, dba_e, dw_e, dws_e[0]))
        dx, dsa, dba, dw, dws = (jnp.stack(t) for t in zip(*outs))
        return dx, dsa.reshape(e, 1), dba.reshape(e, 1), dw, dws
    bm = min(tiles[0], m)
    bn = min(tiles[1], n)
    bk = min(tiles[2], k)
    grid = (e, pl.cdiv(k, bk), pl.cdiv(m, bm), pl.cdiv(n, bn))
    n_pad = grid[3] * bn
    dx, dsa, dba, dw, dws = pl.pallas_call(
        functools.partial(_qmm_bwd_batched_kernel, q_n_a=q_n_a, q_p_a=q_p_a,
                          q_n_w=q_n_w, q_p_w=q_p_w, n_k=grid[1],
                          n_i=grid[2], n_j=grid[3],
                          round_cot=round_cot),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, bm, bn), lambda ee, kk, i, j: (ee, i, j)),
            pl.BlockSpec((1, bm, bk), lambda ee, kk, i, j: (ee, i, kk)),
            pl.BlockSpec((1, bk, bn), lambda ee, kk, i, j: (ee, kk, j)),
            pl.BlockSpec((1, 1, 1), lambda ee, kk, i, j: (ee, 0, 0)),
            pl.BlockSpec((1, 1, 1), lambda ee, kk, i, j: (ee, 0, 0)),
            pl.BlockSpec((1, 1, bn), lambda ee, kk, i, j: (ee, 0, j)),
        ],
        out_specs=[
            pl.BlockSpec((1, bm, bk), lambda ee, kk, i, j: (ee, i, kk)),
            pl.BlockSpec((1, 1, 1, bk), lambda ee, kk, i, j: (ee, i, 0, kk)),
            pl.BlockSpec((1, 1, 1, bk), lambda ee, kk, i, j: (ee, i, 0, kk)),
            pl.BlockSpec((1, bk, bn), lambda ee, kk, i, j: (ee, kk, j)),
            pl.BlockSpec((1, 1, bn), lambda ee, kk, i, j: (ee, 0, j)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((e, m, k), jnp.float32),
            jax.ShapeDtypeStruct((e, grid[2], 1, k), jnp.float32),
            jax.ShapeDtypeStruct((e, grid[2], 1, k), jnp.float32),
            jax.ShapeDtypeStruct((e, k, n), jnp.float32),
            jax.ShapeDtypeStruct((e, 1, n), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((bm, bk), jnp.float32),
                        pltpu.VMEM((bk, n_pad), jnp.float32),
                        pltpu.VMEM((1, n_pad), jnp.float32)],
        name="quant_matmul_bwd_batched",
        interpret=resolve_interpret(interpret),
    )(dy, x, w, *_expert_operands(a_scale, a_offset, w_scale))
    return (dx, jnp.sum(dsa, axis=(1, 2, 3))[:, None],
            jnp.sum(dba, axis=(1, 2, 3))[:, None], dw, dws[:, 0])


@functools.partial(jax.jit, static_argnames=("q_n_w", "q_p_w", "tiles",
                                             "interpret", "out_dtype"))
def int_matmul(x, w_codes, w_col_scale, *, q_n_w: int, q_p_w: int,
               tiles=DEFAULT_TILES, interpret=None,
               out_dtype=jnp.float32):
    """Serving variant: W already int8 codes; dequantize tile-wise in VMEM.

    HBM reads 1 byte/weight (vs 2-4 for fp); the MXU still sees bf16 tiles.
    """
    m, k = x.shape
    k2, n = w_codes.shape
    assert k == k2
    bm = min(tiles[0], m)
    bn = min(tiles[1], n)
    bk = min(tiles[2], k)
    grid = (pl.cdiv(m, bm), pl.cdiv(n, bn), pl.cdiv(k, bk))

    def kernel(x_ref, c_ref, ws_ref, o_ref, acc_ref):
        @pl.when(pl.program_id(2) == 0)
        def _init():
            acc_ref[...] = jnp.zeros_like(acc_ref)
        xd = x_ref[...].astype(jnp.bfloat16)
        wd = (c_ref[...].astype(jnp.float32)
              * jnp.maximum(ws_ref[...].astype(jnp.float32), 1e-9)).astype(jnp.bfloat16)
        acc_ref[...] += jnp.dot(xd, wd, preferred_element_type=jnp.float32)

        @pl.when(pl.program_id(2) == grid[2] - 1)
        def _done():
            o_ref[...] = acc_ref[...].astype(o_ref.dtype)

    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, j, kk: (i, kk)),
            pl.BlockSpec((bk, bn), lambda i, j, kk: (kk, j)),
            pl.BlockSpec((1, bn), lambda i, j, kk: (0, j)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, kk: (i, j)),
        out_shape=jax.ShapeDtypeStruct((m, n), out_dtype),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32)],
        name="int_matmul",
        interpret=resolve_interpret(interpret),
    )(x, w_codes, w_col_scale.astype(jnp.float32))


# int4 serving tiles are chosen from the shape (int4_tiles), not from
# DEFAULT_TILES: a decode step reads every weight once, so each grid step
# streams one large contiguous packed block.
INT4_BK_MAX = 4096           # contraction rows per block (multiple of 256)
INT4_BN_MAX = 2048           # output columns per block (multiple of 128)
INT4_BM_MAX = 256            # activation rows per block
INT4_VMEM_BUDGET = 16 << 20  # the default scoped VMEM limit of a v5e kernel


def _int4_sub(bkp: int) -> int:
    """Packed rows unpacked per dot inside a weight block: bounds the
    unpacked temporaries while the block itself stays large."""
    return 128 if bkp % 128 == 0 else bkp


def _deinterleave(g: int):
    """(g, g) 0/1 matrix: x @ P puts the even columns of each g-column group
    of x first, then the odd ones."""
    src = np.concatenate([np.arange(0, g, 2), np.arange(1, g, 2)])
    return jnp.asarray(np.eye(g, dtype=np.float32)[:, src], jnp.bfloat16)


def int4_vmem_bytes(bm: int, bn: int, bk: int) -> int:
    """Upper bound on int4_matmul's VMEM at tiles (bm, bn, bk): the
    double-buffered bf16 x block, packed weight block, scale row and f32
    output block, the f32 accumulator, and one unpacked sub-block."""
    return (2 * bm * bk * 2 + 2 * (bk // 2) * bn + 2 * 8 * bn * 4
            + 2 * bm * bn * 4 + bm * bn * 4 + 8 * _int4_sub(bk // 2) * bn)


def _divisor_tiles(d: int, unit: int, cap: int) -> list:
    """Multiples of `unit` up to `cap` that divide d, largest first."""
    return [t for t in range(cap - cap % unit, 0, -unit) if d % t == 0]


def int4_tiles(m: int, k: int, n: int) -> tuple:
    """(bm, bn, bk) for int4_matmul on an (m, k) x (k, n) product.

    bm covers all m rows up to 256 (rounded up to bf16's 16 sublanes), so
    a decode batch or a prefill chunk reads and unpacks each weight block
    once. bk and bn are the largest tiles that divide k and n exactly, so
    the packed weights are never padded (copied) on a call, shrunk (bn
    first) until int4_vmem_bytes fits INT4_VMEM_BUDGET. Shapes without
    such tiles (k not a multiple of 256, n not of 128) fall back to
    DEFAULT_TILES and the ops wrapper pads.
    """
    bks = _divisor_tiles(k, 256, INT4_BK_MAX)
    bns = _divisor_tiles(n, 128, INT4_BN_MAX)
    if not bks or not bns:
        bm, bn, bk = DEFAULT_TILES
        return bm, bn, min(bk, k)
    bm = min(-(-m // 16) * 16, INT4_BM_MAX)
    return next((bm, bn, bk) for bk in bks for bn in bns
                if int4_vmem_bytes(bm, bn, bk) <= INT4_VMEM_BUDGET)


@functools.partial(jax.jit, static_argnames=("tiles", "interpret", "out_dtype"))
def int4_matmul(x, w_packed, w_col_scale, *, tiles, interpret=None,
                out_dtype=jnp.float32):
    """Serving matmul over NIBBLE-PACKED int4 weight codes.

    w_packed: (K//2, N) int8, byte p holding code row 2p in the low nibble and
    row 2p+1 in the high nibble (two's complement, so any bits<=4 code fits).
    HBM reads 0.5 byte/weight — half of int_matmul, a quarter of bf16.

    No interleave: x @ W = x[:, 0::2] @ W_lo + x[:, 1::2] @ W_hi, where W_lo
    and W_hi are the sign-extended nibbles as they lie in the packed block,
    converted straight to bf16 (exact). The wrapper de-interleaves x's
    columns within each group of 2*sub by one 0/1 permutation matmul on the
    MXU (exact: one nonzero term per output; a lane-strided slice of x is
    several times slower on the TPU), so each sub-block's even and odd x
    columns are contiguous. The column scale multiplies the f32 accumulator
    once per output tile, after the last K step.

    `tiles` come from int4_tiles; the grid must tile x and w_packed exactly
    (ops.int_matmul pads where the fallback tiles need it).
    """
    m, k = x.shape
    kp, n = w_packed.shape
    assert k == 2 * kp, (x.shape, w_packed.shape)
    bm = min(tiles[0], m)
    bn = min(tiles[1], n)
    bk = min(tiles[2], k)
    assert bk % 2 == 0, bk
    bkp = bk // 2
    sub = _int4_sub(bkp)
    grid = (pl.cdiv(m, bm), pl.cdiv(n, bn), pl.cdiv(k, bk))

    def kernel(x_ref, c_ref, ws_ref, o_ref, acc_ref):
        @pl.when(pl.program_id(2) == 0)
        def _init():
            acc_ref[...] = jnp.zeros_like(acc_ref)
        acc = acc_ref[...]
        for r in range(0, bkp, sub):
            b32 = c_ref[r:r + sub, :].astype(jnp.int32)
            lo = ((b32 << 28) >> 28).astype(jnp.float32).astype(jnp.bfloat16)
            hi = (b32 >> 4).astype(jnp.float32).astype(jnp.bfloat16)
            acc += jnp.dot(x_ref[:, 2 * r:2 * r + sub], lo,
                           preferred_element_type=jnp.float32)
            acc += jnp.dot(x_ref[:, 2 * r + sub:2 * (r + sub)], hi,
                           preferred_element_type=jnp.float32)
        acc_ref[...] = acc

        @pl.when(pl.program_id(2) == grid[2] - 1)
        def _done():
            o_ref[...] = (acc_ref[...] * jnp.maximum(ws_ref[...], 1e-9)
                          ).astype(o_ref.dtype)

    g = 2 * sub
    xp = jnp.dot(x.astype(jnp.bfloat16).reshape(m * k // g, g),
                 _deinterleave(g), preferred_element_type=jnp.float32)
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, j, kk: (i, kk)),
            pl.BlockSpec((bkp, bn), lambda i, j, kk: (kk, j)),
            pl.BlockSpec((1, bn), lambda i, j, kk: (0, j)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, kk: (i, j)),
        out_shape=jax.ShapeDtypeStruct((m, n), out_dtype),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32)],
        name="int4_matmul",
        interpret=resolve_interpret(interpret),
    )(xp.astype(jnp.bfloat16).reshape(m, k), w_packed,
      w_col_scale.astype(jnp.float32))
