"""Jit'd dispatch wrappers over the Pallas kernels.

Handle arbitrary-rank tensors (reshape to 2D, pad to tile multiples, unpad),
QuantSpec plumbing, and the interpret flag (True on CPU; False on real TPU —
`platform.resolve_interpret` picks automatically).

`fused_qat_matmul` is the differentiable entry point: a jax.custom_vjp whose
forward AND backward are single Pallas kernels (one HBM round trip each —
the backward is ONE combined dX/dW kernel sharing a single staging of
dY/X/W, bounded by a VMEM scratch budget: shapes whose dW row panel would
not fit, e.g. lm_head-vocab N, dispatch to the split dx/dw kernels inside
quant_matmul_bwd), with the LSQ/LSQ+ gradients (Eq. 6-7) recomputed
tile-wise in VMEM. Weight scales ride as an N-side (N,) column vector or a K-side (K,)
row vector (`w_scale_axis`, per-head wo/xo); `fused_qat_matmul_batched`
covers the MoE (E, M, K) @ (E, K, N) expert matmul with per-expert scales.
The module-wise gradient scale g and per-group scale reductions are applied
OUTSIDE the vjp boundary (via core.quantizer.grad_scale and a differentiable
broadcast of the scale to vector form), exactly mirroring
core.quantizer.fake_quant's composition.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.core.quantizer import QuantSpec
from repro.kernels import bin_stats as _bs
from repro.kernels import fake_quant as _fq
from repro.kernels import quant_matmul as _qmm
from repro.kernels.platform import on_tpu, resolve_interpret  # noqa: F401 (on_tpu: dispatch in models/)


def _pad2d(x, bm, bn):
    m, n = x.shape
    pm = (-m) % bm
    pn = (-n) % bn
    if pm or pn:
        x = jnp.pad(x, ((0, pm), (0, pn)))
    return x, m, n


def fake_quant(x, scale, spec: QuantSpec, offset=None, *, interpret=None):
    """Per-tensor fake-quant of an arbitrary-rank tensor (scalar scale)."""
    shape = x.shape
    x2 = x.reshape(-1, shape[-1]) if x.ndim > 1 else x.reshape(1, -1)
    bm, bn = _fq.DEFAULT_BLOCK
    x2p, m, n = _pad2d(x2, bm, bn)
    out = _fq.fake_quant_2d(x2p, scale, offset, q_n=spec.q_n, q_p=spec.q_p,
                            interpret=interpret)
    return out[:m, :n].reshape(shape)


def fake_quant_grouped(x, group_scale, spec: QuantSpec, *, interpret=None):
    """Row-grouped fake-quant: x (G, ...) with scale (G,) — per-head/expert."""
    g = x.shape[0]
    x2 = x.reshape(g, -1)
    bm, bn = _fq.DEFAULT_BLOCK
    x2p, m, n = _pad2d(x2, bm, bn)
    sc = jnp.pad(group_scale.reshape(-1, 1), ((0, x2p.shape[0] - g), (0, 0)),
                 constant_values=1.0)
    out = _fq.fake_quant_rows(x2p, sc, q_n=spec.q_n, q_p=spec.q_p,
                              interpret=interpret)
    return out[:m, :n].reshape(x.shape)


def quant_matmul(x, w, a_scale, a_offset, w_scale, a_spec: QuantSpec,
                 w_spec: QuantSpec, *, interpret=None, out_dtype=jnp.float32):
    """Fused q(x) @ q(w). x (..., K), w (K, N); w_scale () or (N,)."""
    lead = x.shape[:-1]
    k = x.shape[-1]
    n = w.shape[-1]
    x2 = x.reshape(-1, k)
    bm, bn, bk = _qmm.DEFAULT_TILES
    x2p, m, _ = _pad2d(x2, bm, bk)
    wp, _, _ = _pad2d(w, bk, bn)
    ws = jnp.broadcast_to(jnp.asarray(w_scale, jnp.float32).reshape(1, -1),
                          (1, n))
    wsp = jnp.pad(ws, ((0, 0), (0, wp.shape[1] - n)), constant_values=1.0)
    out = _qmm.quant_matmul(
        x2p, wp, a_scale, a_offset, wsp,
        q_n_a=a_spec.q_n, q_p_a=a_spec.q_p, q_n_w=w_spec.q_n, q_p_w=w_spec.q_p,
        interpret=interpret, out_dtype=out_dtype)
    return out[:m, :n].reshape(*lead, n)


def int_matmul(x, w_codes, w_scale, w_spec: QuantSpec, *, packed: bool = False,
               interpret=None, out_dtype=jnp.float32):
    """Serving matmul over int-coded weights.

    packed=False: w_codes (K, N) int8 — 1 byte/weight HBM reads.
    packed=True:  w_codes (K//2, N) int8 nibble-packed int4 pairs (see
    core.quantizer.pack_int4) — 0.5 byte/weight, unpacked tile-wise in VMEM,
    with tiles chosen from the shape (quant_matmul.int4_tiles).
    """
    lead = x.shape[:-1]
    k = x.shape[-1]
    n = w_codes.shape[-1]
    x2 = x.reshape(-1, k)
    if packed:
        assert w_codes.shape[0] * 2 == k, (x.shape, w_codes.shape)
        tiles = _qmm.int4_tiles(x2.shape[0], k, n)
        bm, bn, bk = tiles
        x2p, m, _ = _pad2d(x2, bm, bk)
        pad_rows = (x2p.shape[1] - k) // 2
        pn = (-n) % bn
        wp = jnp.pad(w_codes, ((0, pad_rows), (0, pn)))  # none at int4_tiles
        ws = jnp.broadcast_to(jnp.asarray(w_scale, jnp.float32).reshape(1, -1),
                              (1, n))
        wsp = jnp.pad(ws, ((0, 0), (0, pn)), constant_values=1.0)
        out = _qmm.int4_matmul(x2p, wp, wsp, tiles=tiles, interpret=interpret,
                               out_dtype=out_dtype)
        return out[:m, :n].reshape(*lead, n)
    bm, bn, bk = _qmm.DEFAULT_TILES
    x2p, m, _ = _pad2d(x2, bm, bk)
    wp, _, _ = _pad2d(w_codes, bk, bn)
    ws = jnp.broadcast_to(jnp.asarray(w_scale, jnp.float32).reshape(1, -1), (1, n))
    wsp = jnp.pad(ws, ((0, 0), (0, wp.shape[1] - n)), constant_values=1.0)
    out = _qmm.int_matmul(x2p, wp, wsp, q_n_w=w_spec.q_n, q_p_w=w_spec.q_p,
                          interpret=interpret, out_dtype=out_dtype)
    return out[:m, :n].reshape(*lead, n)


# ---------------------------------------------------------------------------
# Fused QAT matmul with custom_vjp (the training hot path)
# ---------------------------------------------------------------------------

def _pad_w_scale(ws_vec, k_side: bool, k, n, kp, np_):
    """(N,) -> padded (1, Np) column scale, or (K,) -> padded (Kp, 1) rows."""
    if k_side:
        ws = jnp.reshape(ws_vec, (k, 1)).astype(jnp.float32)
        return jnp.pad(ws, ((0, kp - k), (0, 0)), constant_values=1.0)
    ws = jnp.reshape(ws_vec, (1, n)).astype(jnp.float32)
    return jnp.pad(ws, ((0, 0), (0, np_ - n)), constant_values=1.0)


def _qmm2d_forward(static, x2, w2, a_scale, a_offset, ws_vec):
    q_n_a, q_p_a, q_n_w, q_p_w, interpret, out_dtype, _round_cot, k_side = static
    m, k = x2.shape
    n = w2.shape[1]
    bm, bn, bk = _qmm.DEFAULT_TILES
    x2p, _, _ = _pad2d(x2, bm, bk)
    wp, _, _ = _pad2d(w2, bk, bn)
    wsp = _pad_w_scale(ws_vec, k_side, k, n, wp.shape[0], wp.shape[1])
    out = _qmm.quant_matmul(x2p, wp, a_scale, a_offset, wsp,
                            q_n_a=q_n_a, q_p_a=q_p_a, q_n_w=q_n_w, q_p_w=q_p_w,
                            interpret=interpret, out_dtype=out_dtype)
    return out[:m, :n]


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def _fused_qmm2d(static, x2, w2, a_scale, a_offset, ws_vec):
    return _qmm2d_forward(static, x2, w2, a_scale, a_offset, ws_vec)


def _fused_qmm2d_fwd(static, x2, w2, a_scale, a_offset, ws_vec):
    y = _qmm2d_forward(static, x2, w2, a_scale, a_offset, ws_vec)
    return y, (x2, w2, a_scale, a_offset, ws_vec)


def _fused_qmm2d_bwd(static, res, dy):
    q_n_a, q_p_a, q_n_w, q_p_w, interpret, _out_dtype, round_cot, k_side = static
    x2, w2, a_scale, a_offset, ws_vec = res
    m, k = x2.shape
    n = w2.shape[1]
    bm, bn, bk = _qmm.DEFAULT_TILES
    # dy rows pad to the same ceil(m/bm)*bm as x, cols to ceil(n/bn)*bn as w
    dyp, _, _ = _pad2d(dy.astype(jnp.float32), bm, bn)
    xp, _, _ = _pad2d(x2, bm, bk)
    wp, _, _ = _pad2d(w2, bk, bn)
    wsp = _pad_w_scale(ws_vec, k_side, k, n, wp.shape[0], wp.shape[1])
    dx, dsa, dba, dw, dws = _qmm.quant_matmul_bwd(
        dyp, xp, wp, a_scale, a_offset, wsp,
        q_n_a=q_n_a, q_p_a=q_p_a, q_n_w=q_n_w, q_p_w=q_p_w,
        round_cot=round_cot, interpret=interpret)
    dws_vec = dws[:k, 0] if k_side else dws[0, :n]
    return (dx[:m, :k].astype(x2.dtype),
            dw[:k, :n].astype(w2.dtype),
            dsa.astype(jnp.result_type(a_scale)).reshape(jnp.shape(a_scale)),
            dba.astype(jnp.result_type(a_offset)).reshape(jnp.shape(a_offset)),
            dws_vec.astype(jnp.result_type(ws_vec)))


_fused_qmm2d.defvjp(_fused_qmm2d_fwd, _fused_qmm2d_bwd)


def fused_qat_matmul(x, w2, a_scale, a_offset, ws_vec,
                     a_spec: QuantSpec, w_spec: QuantSpec, *,
                     interpret=None, out_dtype=jnp.float32,
                     cotangent_rounding: bool = True,
                     w_scale_axis: str = "n"):
    """Differentiable fused q(x) @ q(w) — forward and backward each one
    Pallas kernel (single HBM round trip), LSQ/LSQ+ gradients for all five
    inputs.

    x: (..., K); w2: (K, N); a_scale/a_offset: 0-d (pre-grad_scale'd by the
    caller); ws_vec: the weight scale expanded per column (N,) when
    w_scale_axis="n", or per contracted row (K,) when w_scale_axis="k"
    (K-side per-head scales). Either way it is pre-grad_scale'd and expanded
    from its group shape by a differentiable broadcast, so group-sum and g
    factors ride on autodiff outside this boundary.
    """
    assert w_scale_axis in ("n", "k"), w_scale_axis
    interpret = resolve_interpret(interpret)
    lead = x.shape[:-1]
    x2 = x.reshape(-1, x.shape[-1])
    static = (a_spec.q_n, a_spec.q_p, w_spec.q_n, w_spec.q_p,
              bool(interpret), out_dtype, bool(cotangent_rounding),
              w_scale_axis == "k")
    y2 = _fused_qmm2d(static, x2, w2, a_scale, a_offset, ws_vec)
    return y2.reshape(*lead, w2.shape[-1])


# ---------------------------------------------------------------------------
# Batched-expert fused QAT matmul (MoE expert einsums)
# ---------------------------------------------------------------------------

def _pad3d(x, b1, b2):
    _, m, n = x.shape
    pm = (-m) % b1
    pn = (-n) % b2
    if pm or pn:
        x = jnp.pad(x, ((0, 0), (0, pm), (0, pn)))
    return x


def _qmm3d_forward(static, x3, w3, a_scale, a_offset, ws_en):
    q_n_a, q_p_a, q_n_w, q_p_w, interpret, out_dtype, _round_cot = static
    e, m, k = x3.shape
    n = w3.shape[-1]
    bm, bn, bk = _qmm.DEFAULT_TILES
    xp = _pad3d(x3, bm, bk)
    wp = _pad3d(w3, bk, bn)
    wsp = jnp.pad(ws_en.astype(jnp.float32),
                  ((0, 0), (0, wp.shape[-1] - n)), constant_values=1.0)
    out = _qmm.quant_matmul_batched(
        xp, wp, a_scale.reshape(e, 1), a_offset.reshape(e, 1), wsp,
        q_n_a=q_n_a, q_p_a=q_p_a, q_n_w=q_n_w, q_p_w=q_p_w,
        interpret=interpret, out_dtype=out_dtype)
    return out[:, :m, :n]


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def _fused_qmm3d(static, x3, w3, a_scale, a_offset, ws_en):
    return _qmm3d_forward(static, x3, w3, a_scale, a_offset, ws_en)


def _fused_qmm3d_fwd(static, x3, w3, a_scale, a_offset, ws_en):
    y = _qmm3d_forward(static, x3, w3, a_scale, a_offset, ws_en)
    return y, (x3, w3, a_scale, a_offset, ws_en)


def _fused_qmm3d_bwd(static, res, dy):
    q_n_a, q_p_a, q_n_w, q_p_w, interpret, _out_dtype, round_cot = static
    x3, w3, a_scale, a_offset, ws_en = res
    e, m, k = x3.shape
    n = w3.shape[-1]
    bm, bn, bk = _qmm.DEFAULT_TILES
    dyp = _pad3d(dy.astype(jnp.float32), bm, bn)
    xp = _pad3d(x3, bm, bk)
    wp = _pad3d(w3, bk, bn)
    wsp = jnp.pad(ws_en.astype(jnp.float32),
                  ((0, 0), (0, wp.shape[-1] - n)), constant_values=1.0)
    dx, dsa, dba, dw, dws = _qmm.quant_matmul_bwd_batched(
        dyp, xp, wp, a_scale.reshape(e, 1), a_offset.reshape(e, 1), wsp,
        q_n_a=q_n_a, q_p_a=q_p_a, q_n_w=q_n_w, q_p_w=q_p_w,
        round_cot=round_cot, interpret=interpret)
    return (dx[:, :m, :k].astype(x3.dtype),
            dw[:, :k, :n].astype(w3.dtype),
            dsa.astype(jnp.result_type(a_scale)).reshape(jnp.shape(a_scale)),
            dba.astype(jnp.result_type(a_offset)).reshape(jnp.shape(a_offset)),
            dws[:, :n].astype(jnp.result_type(ws_en)))


_fused_qmm3d.defvjp(_fused_qmm3d_fwd, _fused_qmm3d_bwd)


def fused_qat_matmul_batched(x3, w3, a_scale, a_offset, ws_en,
                             a_spec: QuantSpec, w_spec: QuantSpec, *,
                             interpret=None, out_dtype=jnp.float32,
                             cotangent_rounding: bool = True):
    """Per-expert differentiable fused matmul: y[e] = q_a(x[e]) @ q_w(w[e]).

    x3: (E, M, K); w3: (E, K, N); a_scale/a_offset: (E,) per-expert scalars
    (broadcast from the shared module scalar by the caller, so the cotangent
    sums back through autodiff); ws_en: (E, N) per-expert column scales
    (pre-grad_scale'd, expanded from the (E, 1, 1) group shape by a
    differentiable broadcast). Forward and backward are each ONE Pallas
    kernel whose grid leads with the expert axis.
    """
    interpret = resolve_interpret(interpret)
    static = (a_spec.q_n, a_spec.q_p, w_spec.q_n, w_spec.q_p,
              bool(interpret), out_dtype, bool(cotangent_rounding))
    return _fused_qmm3d(static, x3, w3, a_scale, a_offset, ws_en)


def bin_stats(w, scale, spec: QuantSpec, *, interpret=None):
    """(count, sum, sumsq) per bin for a per-tensor-scaled weight tensor."""
    w2 = w.reshape(-1, w.shape[-1]) if w.ndim > 1 else w.reshape(1, -1)
    # rows must tile evenly; pad rows with values far outside the clip range
    # is wrong (they'd land in edge bins) — instead pad with the scale value
    # itself and subtract the padded rows' contribution analytically: padded
    # elements quantize to code round(1.0) = 1 -> bin q_n+1. Simpler: pad to
    # the row-block multiple with zeros and subtract the zero-bin overcount.
    bm, _ = _bs.DEFAULT_BLOCK
    m, n = w2.shape
    pm = (-m) % min(bm, m) if m else 0
    if pm:
        w2 = jnp.pad(w2, ((0, pm), (0, 0)))
    out = _bs.bin_stats_2d(w2, scale, q_n=spec.q_n, q_p=spec.q_p,
                           interpret=interpret)
    if pm:
        # zeros quantize to code 0 -> bin index q_n; remove their count
        out = out.at[0, spec.q_n].add(-float(pm * n))
    return out
