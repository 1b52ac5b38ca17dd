"""Fused flash-decode attention over the (possibly quantized) pooled KV cache.

The serving hot loop (`ServeEngine` -> `model.block_decode` ->
`attention.attend_chunk`/`attend_decode`) used to dequantize the ENTIRE
pooled cache (all slots x max_len, idle rows included) from int8/int4 codes
to f32/bf16 in HBM every step, then `repeat_kv` both K and V another
`q_per_kv`x before a dense softmax over all max_len positions. This kernel
removes that whole traffic class:

  * KV codes are read directly from the pool and dequantized per KV-tile in
    VMEM with the per-(slot, token, head) `k_scale`/`v_scale` rows; int4
    codes arrive nibble-packed two-per-byte along head_dim (the serving
    weight path's `codes4` interleave, see quantizer.pack_int4) and are
    unpacked tile-wise like kernels/quant_matmul.int4_matmul.
  * The pos >= 0 / pos <= q_pos / ring-window validity masks are computed
    in-kernel from the pool's `pos` rows, so idle (pos = -1) slots and
    ring-layer windows never cost an HBM read of a dequantized copy.
  * GQA blocks each kv head's `q_per_kv` query heads (x the chunk's C query
    tokens) into one (G, D) tile against that head's KV — no head-repeated
    K/V is ever materialized.
  * Online softmax: running max `m`, running sum `l`, and the f32
    accumulator live in VMEM scratch across KV tiles; no (B, H, C, T) score
    tensor exists anywhere.

The call returns the UNNORMALIZED triple (acc, m, l) — flash-decode partial
reductions — so `attend_chunk` can merge the in-chunk (not yet cached) keys
with one more online-softmax step in plain jnp; `attend_decode` just
normalizes (out = acc / l).

Masking matches the jnp fallback bit-for-bit in spirit: masked scores are
set to the finite NEG_INF, so a fully-masked row (idle serving slot)
degrades to the same uniform-weights junk the fallback's softmax produces
instead of NaN.

Grid/residency notes: grid = (batch, kv_tiles) with the KV-tile axis
innermost; each output block is indexed by batch only, so its revisits are
consecutive — but the kernel still accumulates in persistent VMEM scratch
and writes each output exactly once on the final tile, the pattern that is
legal regardless of output-block residency. Lane alignment pads head_dim to
128 and the KV tile to >= 8 sublanes. The int32 positions ride as
(B, 1, T) and (B, G, 1) arrays so their last two block dims are (1, bt) and
(G, 1): each equals the array's dim or is (8, 128)-tiled, as the TPU
lowering requires (tests/test_tpu_compile.py compiles every KV layout for a
v5e chip).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.platform import resolve_interpret

NEG_INF = -2.0e9  # matches models/attention.py: finite, exp() underflows to 0
LANE = 128
DEFAULT_KV_TILE = 128


def _round_up(x: int, m: int) -> int:
    return (x + m - 1) // m * m


def _unpack_nibbles(packed: jax.Array) -> jax.Array:
    """(..., P) int8 bytes -> (..., 2P) int4 codes, DE-interleaved: the low
    nibbles (codes 0, 2, 4, ...) then the high ones (1, 3, 5, ...).
    quantizer.pack_int4 stores byte p = code 2p low, code 2p+1 high, two's
    complement; shift-based sign extension as in int4_matmul. Re-interleaving
    along lanes inside the kernel does not compile for the TPU, so the
    wrapper permutes q's head_dim the same way and un-permutes the output."""
    p32 = packed.astype(jnp.int32)
    lo = (p32 << 28) >> 28
    hi = (p32 << 24) >> 28
    return jnp.concatenate([lo, hi], axis=-1)


def _deinterleave(x: jax.Array) -> jax.Array:
    """(..., 2P) -> (..., 2P) with even entries first, then odd."""
    return jnp.concatenate([x[..., 0::2], x[..., 1::2]], axis=-1)


def _reinterleave(x: jax.Array) -> jax.Array:
    """Inverse of _deinterleave."""
    half = x.shape[-1] // 2
    st = jnp.stack([x[..., :half], x[..., half:]], axis=-1)
    return st.reshape(x.shape)


def _flash_decode_kernel(*refs, quantized: bool, packed: bool, window: int,
                         softcap: float, n_tiles: int, compute_dtype):
    if quantized:
        (q_ref, k_ref, v_ref, ks_ref, vs_ref, pos_ref, qpos_ref,
         acc_out, m_out, l_out, m_scr, l_scr, acc_scr) = refs
    else:
        (q_ref, k_ref, v_ref, pos_ref, qpos_ref,
         acc_out, m_out, l_out, m_scr, l_scr, acc_scr) = refs
    t = pl.program_id(1)

    @pl.when(t == 0)
    def _init():
        m_scr[...] = jnp.full(m_scr.shape, NEG_INF, jnp.float32)
        l_scr[...] = jnp.zeros(l_scr.shape, jnp.float32)
        acc_scr[...] = jnp.zeros(acc_scr.shape, jnp.float32)

    q = q_ref[0]                     # (Hkv, G, D), already pre-scaled
    kv_pos = pos_ref[0]              # (1, bt) int32
    q_pos = qpos_ref[0]              # (G, 1) int32

    if quantized:
        kc, vc = k_ref[0], v_ref[0]  # (bt, Hkv, D or D/2) int codes
        if packed:
            kc, vc = _unpack_nibbles(kc), _unpack_nibbles(vc)
        ks = ks_ref[0]               # (bt, Hkv) f32
        vs = vs_ref[0]
        k = (kc.astype(jnp.float32) * ks[..., None]).astype(compute_dtype)
        v = (vc.astype(jnp.float32) * vs[..., None]).astype(compute_dtype)
    else:
        k = k_ref[0].astype(compute_dtype)  # (bt, Hkv, D)
        v = v_ref[0].astype(compute_dtype)

    kt = jnp.swapaxes(k, 0, 1)       # (Hkv, bt, D)
    vt = jnp.swapaxes(v, 0, 1)
    # batched over kv heads; contraction over head_dim -> (Hkv, G, bt)
    s = jax.lax.dot_general(q, kt, (((2,), (2,)), ((0,), (0,))),
                            preferred_element_type=jnp.float32)
    s = s.astype(jnp.float32)
    if softcap > 0.0:
        s = softcap * jnp.tanh(s / softcap)
    valid = (kv_pos >= 0) & (kv_pos <= q_pos)          # (G, bt)
    if window > 0:
        valid &= kv_pos > (q_pos - window)
    s = jnp.where(valid[None, :, :], s, NEG_INF)  # (Hkv, G, bt)

    m_prev = m_scr[...]              # (Hkv, G)
    m_cur = jnp.maximum(m_prev, jnp.max(s, axis=-1))
    alpha = jnp.exp(m_prev - m_cur)
    p = jnp.exp(s - m_cur[..., None])
    l_scr[...] = l_scr[...] * alpha + jnp.sum(p, axis=-1)
    pv = jax.lax.dot_general(p.astype(compute_dtype), vt,
                             (((2,), (1,)), ((0,), (0,))),
                             preferred_element_type=jnp.float32)
    acc_scr[...] = acc_scr[...] * alpha[..., None] + pv.astype(jnp.float32)
    m_scr[...] = m_cur

    @pl.when(t == n_tiles - 1)
    def _done():
        acc_out[0] = acc_scr[...]
        m_out[0] = m_scr[...]
        l_out[0] = l_scr[...]


def pooled_decode_attention(q, k_store, v_store, k_scale, v_scale, kv_pos,
                            q_pos, *, q_per_kv: int, window: int,
                            softcap: float, kv_tile: int = DEFAULT_KV_TILE,
                            interpret=None):
    """Flash-decode over the pooled cache; returns partial reductions.

    q:        (B, C, H, D) queries (C = 1 for decode, the chunk width for
              chunked prefill). Scaled by D**-0.5 here, like the fallback.
    k_store:  (B, T, Hkv, D) fp values, or int8 code bytes with the last
              axis D (int8 / odd-head_dim int4) or D/2 (nibble-packed int4).
    k_scale:  (B, T, Hkv, 1) f32 per-(slot, token, head) scales, or None
              for the fp cache. v_store/v_scale mirror k.
    kv_pos:   (B, T) int32 absolute positions, -1 = idle/unwritten row.
    q_pos:    (B, C) int32 query positions, -1 = padding query.

    Returns (acc, m, l): acc (B, C, H, D) f32 UNNORMALIZED output, m / l
    (B, C, H) f32 running max / sum. out = acc / l; to merge extra keys,
    continue the online softmax with (m, l, acc).
    """
    interpret = resolve_interpret(interpret)
    b, c, h, d = q.shape
    assert h % q_per_kv == 0, (h, q_per_kv)
    hkv = h // q_per_kv
    g = c * q_per_kv
    t = k_store.shape[1]
    quantized = k_scale is not None
    packed = quantized and (k_store.shape[-1] * 2 == d)
    assert packed or k_store.shape[-1] == d, (k_store.shape, d)
    compute_dtype = q.dtype

    # pre-scale in f32 exactly like the jnp fallback, then regroup queries
    # as (B, Hkv, G, D) with G = (chunk token, q-head-in-group) rows
    qs = (q.astype(jnp.float32) * d ** -0.5).astype(q.dtype)
    q5 = qs.reshape(b, c, hkv, q_per_kv, d).transpose(0, 2, 1, 3, 4)
    q5 = q5.reshape(b, hkv, g, d)
    qp = jnp.repeat(q_pos.astype(jnp.int32), q_per_kv, axis=1)  # (B, G)

    # lane/sublane padding (zeros score 0; pos = -1 rows/queries are masked)
    dp = _round_up(d, LANE)
    gp = _round_up(g, 8)
    bt = min(kv_tile, _round_up(t, 8))
    tp = _round_up(t, bt)
    n_tiles = tp // bt
    dsp = dp // 2 if packed else dp

    q5 = jnp.pad(q5, ((0, 0), (0, 0), (0, gp - g), (0, dp - d)))
    if packed:  # match the kernel's de-interleaved unpack order
        q5 = _deinterleave(q5)
    qp = jnp.pad(qp, ((0, 0), (0, gp - g)), constant_values=-1)
    ds = k_store.shape[-1]
    k_store = jnp.pad(k_store, ((0, 0), (0, tp - t), (0, 0), (0, dsp - ds)))
    v_store = jnp.pad(v_store, ((0, 0), (0, tp - t), (0, 0), (0, dsp - ds)))
    kv_pos = jnp.pad(kv_pos.astype(jnp.int32), ((0, 0), (0, tp - t)),
                     constant_values=-1)

    kern = functools.partial(_flash_decode_kernel, quantized=quantized,
                             packed=packed, window=window, softcap=softcap,
                             n_tiles=n_tiles, compute_dtype=compute_dtype)
    in_specs = [
        pl.BlockSpec((1, hkv, gp, dp), lambda bb, tt: (bb, 0, 0, 0)),
        pl.BlockSpec((1, bt, hkv, dsp), lambda bb, tt: (bb, tt, 0, 0)),
        pl.BlockSpec((1, bt, hkv, dsp), lambda bb, tt: (bb, tt, 0, 0)),
    ]
    args = [q5, k_store, v_store]
    if quantized:
        in_specs += [pl.BlockSpec((1, bt, hkv), lambda bb, tt: (bb, tt, 0)),
                     pl.BlockSpec((1, bt, hkv), lambda bb, tt: (bb, tt, 0))]
        args += [jnp.pad(k_scale[..., 0].astype(jnp.float32),
                         ((0, 0), (0, tp - t), (0, 0))),
                 jnp.pad(v_scale[..., 0].astype(jnp.float32),
                         ((0, 0), (0, tp - t), (0, 0)))]
    # positions carry a singleton axis so the last two block dims are
    # (1, bt) / (gp, 1): each either equals the array's dim or is tiled
    # (bt % 128, gp % 8) — the TPU block-shape rule. q_pos arrives as a
    # column so the mask broadcasts without an in-kernel transpose.
    in_specs += [pl.BlockSpec((1, 1, bt), lambda bb, tt: (bb, 0, tt)),
                 pl.BlockSpec((1, gp, 1), lambda bb, tt: (bb, 0, 0))]
    args += [kv_pos[:, None, :], qp[:, :, None]]

    acc, m, l = pl.pallas_call(
        kern,
        grid=(b, n_tiles),
        in_specs=in_specs,
        out_specs=[
            pl.BlockSpec((1, hkv, gp, dp), lambda bb, tt: (bb, 0, 0, 0)),
            pl.BlockSpec((1, hkv, gp), lambda bb, tt: (bb, 0, 0)),
            pl.BlockSpec((1, hkv, gp), lambda bb, tt: (bb, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b, hkv, gp, dp), jnp.float32),
            jax.ShapeDtypeStruct((b, hkv, gp), jnp.float32),
            jax.ShapeDtypeStruct((b, hkv, gp), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((hkv, gp), jnp.float32),
                        pltpu.VMEM((hkv, gp), jnp.float32),
                        pltpu.VMEM((hkv, gp, dp), jnp.float32)],
        interpret=interpret,
    )(*args)

    # slice padding away and restore (B, C, H, ...) layout
    if packed:
        acc = _reinterleave(acc)
    acc = acc[:, :, :g, :d].reshape(b, hkv, c, q_per_kv, d)
    acc = acc.transpose(0, 2, 1, 3, 4).reshape(b, c, h, d)
    m = m[:, :, :g].reshape(b, hkv, c, q_per_kv).transpose(0, 2, 1, 3)
    l = l[:, :, :g].reshape(b, hkv, c, q_per_kv).transpose(0, 2, 1, 3)
    return acc, m.reshape(b, c, h), l.reshape(b, c, h)
