"""Fused Pallas quant-matmul (custom_vjp) vs the pure-jnp qlinear composition.

The fused path must match the unfused composition bit-for-bit-modulo-
accumulation-order: forward within 1e-5 and all five gradients (x, w,
a_scale, a_offset, w_scale) within 1e-4, for per-tensor AND per-column-group
scales, at non-tile-multiple shapes (padding edges). All kernels run in
interpret mode (QuantConfig.fused_matmul="on" forces the dispatch on CPU).
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from numpy.testing import assert_allclose

from repro.core.policy import QuantConfig
from repro.core.quantizer import QuantSpec, pack_int4, unpack_int4
from repro.kernels import ops, ref
from repro.models import common as C

Q_OFF = QuantConfig(w_bits=4, a_bits=4, mode="mdq", fused_matmul="off")
Q_ON = Q_OFF.replace(fused_matmul="on")


def _close(a, b, tol):
    assert_allclose(np.asarray(a, np.float32), np.asarray(b, np.float32),
                    rtol=0, atol=tol)


def _grad_parity(p, x, name, eq, q_off, q_on, tol=1e-4):
    def loss(p, x, qcfg):
        y = C.qlinear(p, x, name, qcfg, eq)
        # cosine weighting makes every gradient structurally non-trivial
        wgt = jnp.cos(jnp.arange(y.size, dtype=jnp.float32) * 0.1)
        return jnp.sum(y.astype(jnp.float32).reshape(-1) * wgt)

    (g_off, gx_off) = jax.grad(loss, argnums=(0, 1))(p, x, q_off)
    (g_on, gx_on) = jax.grad(loss, argnums=(0, 1))(p, x, q_on)
    _close(gx_off.astype(jnp.float32), gx_on.astype(jnp.float32), tol)
    for k in g_off:
        scale = max(float(jnp.max(jnp.abs(g_off[k]))), 1.0)
        _close(g_off[k] / scale, g_on[k] / scale, tol)


@pytest.mark.parametrize("mkn", [(16, 32, 24), (37, 130, 90), (5, 700, 130)])
@pytest.mark.parametrize("bits", [4, 8])
def test_ffn_linear_parity(key, rng, mkn, bits):
    """2D contraction, per-tensor scales, padding edges."""
    m, k, n = mkn
    q_off = QuantConfig(w_bits=bits, a_bits=bits, mode="mdq",
                        fused_matmul="off")
    q_on = q_off.replace(fused_matmul="on")
    p = C.linear_init(key, "w_in", q_off, (k, n), std=0.1)
    x = jnp.asarray(rng.standard_normal((2, m, k)), jnp.bfloat16)
    y_off = C.qlinear(p, x, "w_in", q_off, "bsd,df->bsf")
    y_on = C.qlinear(p, x, "w_in", q_on, "bsd,df->bsf")
    _close(y_off, y_on, 1e-5)
    _grad_parity(p, x, "w_in", "bsd,df->bsf", q_off, q_on)


def test_qkv_per_head_parity(key, rng):
    """Reshaped-head projection: per-COLUMN-GROUP (per-head) w_scale."""
    p = C.linear_init(key, "wq", Q_OFF, (40, 6, 24), std=0.1,
                      group_axes=(1,), bias_shape=(6, 24))
    assert p["w_scale"].shape == (1, 6, 1)
    x = jnp.asarray(rng.standard_normal((2, 7, 40)), jnp.bfloat16)
    y_off = C.qlinear(p, x, "wq", Q_OFF, "bsd,dhk->bshk")
    y_on = C.qlinear(p, x, "wq", Q_ON, "bsd,dhk->bshk")
    assert y_on.shape == (2, 7, 6, 24)
    _close(y_off, y_on, 1e-5)
    _grad_parity(p, x, "wq", "bsd,dhk->bshk", Q_OFF, Q_ON)


def test_wo_per_tensor_parity(key, rng):
    """Output projection (two contracted leading axes), per-tensor scale."""
    q_off = QuantConfig(w_bits=4, a_bits=4, mode="lsq", fused_matmul="off")
    q_on = q_off.replace(fused_matmul="on")
    p = C.linear_init(key, "wo", q_off, (6, 24, 40), std=0.1)
    x = jnp.asarray(rng.standard_normal((2, 7, 6, 24)), jnp.bfloat16)
    y_off = C.qlinear(p, x, "wo", q_off, "bshk,hkd->bsd")
    y_on = C.qlinear(p, x, "wo", q_on, "bshk,hkd->bsd")
    _close(y_off, y_on, 1e-5)
    _grad_parity(p, x, "wo", "bshk,hkd->bsd", q_off, q_on)


@pytest.mark.parametrize("name", ["wo", "xo"])
def test_wo_per_head_parity(key, rng, name):
    """K-side per-HEAD scale (MDQ output projections): groups live on the
    contracted axes, dequantized per K-tile with the Eq. 6-7 scale gradient
    group-summed along K."""
    p = C.linear_init(key, name, Q_OFF, (6, 24, 40), std=0.1, group_axes=(0,))
    assert p["w_scale"].shape == (6, 1, 1)
    p["a_scale"] = jnp.asarray(0.3)
    p["a_offset"] = jnp.asarray(0.02)
    x = jnp.asarray(rng.standard_normal((2, 7, 6, 24)), jnp.bfloat16)
    y_off = C.qlinear(p, x, name, Q_OFF, "bshk,hkd->bsd")
    y_on = C.qlinear(p, x, name, Q_ON, "bshk,hkd->bsd")
    _close(y_off, y_on, 1e-5)
    _grad_parity(p, x, name, "bshk,hkd->bsd", Q_OFF, Q_ON)


def test_mixed_side_scale_falls_back(key, rng):
    """A scale with groups on BOTH sides of the 2D reshape (no policy emits
    one) must take the unfused composition: both configs bit-identical."""
    from repro.core.quantizer import init_scale
    from repro.core.policy import weight_spec
    p = C.linear_init(key, "wo", Q_OFF, (6, 24, 40), std=0.1, group_axes=(0,))
    p["w_scale"] = init_scale(p["w"], weight_spec(Q_OFF, "attn_o"), (0, 2))
    assert p["w_scale"].shape == (6, 1, 40)
    x = jnp.asarray(rng.standard_normal((2, 7, 6, 24)), jnp.bfloat16)
    y_off = C.qlinear(p, x, "wo", Q_OFF, "bshk,hkd->bsd")
    y_on = C.qlinear(p, x, "wo", Q_ON, "bshk,hkd->bsd")
    assert bool(jnp.all(y_off == y_on))


# ---------------------------------------------------------------------------
# MoE batched expert einsums (per-expert scales)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("name,eq,shape,xshape", [
    ("moe_in", "gecd,edf->gecf", (3, 32, 40), (2, 3, 6, 32)),
    ("moe_out", "gecf,efd->gecd", (3, 40, 32), (2, 3, 6, 40)),
])
@pytest.mark.parametrize("mode", ["mdq", "lsq"])
def test_moe_expert_parity(key, rng, name, eq, shape, xshape, mode):
    """Batched expert matmul: per-EXPERT scales (mdq) and per-tensor (lsq)
    both ride the expert-grid kernel; five-gradient parity vs unfused."""
    q_off = QuantConfig(w_bits=4, a_bits=4, mode=mode, fused_matmul="off")
    q_on = q_off.replace(fused_matmul="on")
    p = C.linear_init(key, name, q_off, shape, std=0.1, group_axes=(0,))
    assert p["w_scale"].shape == ((3, 1, 1) if mode == "mdq" else ())
    p["a_scale"] = jnp.asarray(0.3)
    p["a_offset"] = jnp.asarray(0.02)
    x = jnp.asarray(rng.standard_normal(xshape), jnp.bfloat16)
    y_off = C.qlinear(p, x, name, q_off, eq)
    y_on = C.qlinear(p, x, name, q_on, eq)
    assert y_on.shape == y_off.shape
    _close(y_off, y_on, 1e-5)
    _grad_parity(p, x, name, eq, q_off, q_on)


def test_lm_head_parity(key, rng):
    p = C.lm_head_init(key, Q_OFF, 48, 160)
    x = jnp.asarray(rng.standard_normal((2, 5, 48)), jnp.bfloat16)
    lg_off = C.lm_head_apply(p, x, Q_OFF, 150, 160)
    lg_on = C.lm_head_apply(p, x, Q_ON, 150, 160)
    assert lg_off.dtype == lg_on.dtype == jnp.float32
    _close(lg_off, lg_on, 1e-4)

    def loss(p, x, qcfg):
        lg = C.lm_head_apply(p, x, qcfg, 150, 160)
        return jnp.sum(jnp.tanh(lg * 0.05))

    g_off = jax.grad(loss)(p, x, Q_OFF)
    g_on = jax.grad(loss)(p, x, Q_ON)
    for k in g_off:
        scale = max(float(jnp.max(jnp.abs(g_off[k]))), 1.0)
        _close(g_off[k] / scale, g_on[k] / scale, 1e-4)


def test_tied_lm_head_parity(key, rng):
    """Tied-embedding head: the transposed latent embedding rides the fused
    path as an N-side per-tensor weight; shared-w_scale gradient included."""
    emb = C.embed_init(key, Q_OFF, 160, 48)
    p = C.tied_head_act_init(Q_OFF)
    p["a_scale"] = jnp.asarray(0.4)
    p["a_offset"] = jnp.asarray(0.01)
    x = jnp.asarray(rng.standard_normal((2, 5, 48)), jnp.bfloat16)
    lg_off = C.lm_head_apply(p, x, Q_OFF, 150, 160, tied_embed=emb)
    lg_on = C.lm_head_apply(p, x, Q_ON, 150, 160, tied_embed=emb)
    assert lg_off.dtype == lg_on.dtype == jnp.float32
    _close(lg_off, lg_on, 1e-5)

    def loss(p, emb, x, qcfg):
        lg = C.lm_head_apply(p, x, qcfg, 150, 160, tied_embed=emb)
        return jnp.sum(jnp.tanh(lg * 0.05))

    gp_off, ge_off = jax.grad(loss, argnums=(0, 1))(p, emb, x, Q_OFF)
    gp_on, ge_on = jax.grad(loss, argnums=(0, 1))(p, emb, x, Q_ON)
    for g_off, g_on in [(gp_off, gp_on), (ge_off, ge_on)]:
        for k in g_off:
            scale = max(float(jnp.max(jnp.abs(g_off[k]))), 1.0)
            _close(g_off[k] / scale, g_on[k] / scale, 1e-4)


def test_tied_head_grad_scale_ref_matches_untied(key, rng):
    """Regression: the tied head's module-wise g factor (Sec. 4.4.1) must
    come from the LATENT f32 embedding, not the rounded bf16-cast dequant —
    its activation-scale gradient must equal an untied head holding the
    transposed embedding with the same scales."""
    emb = C.embed_init(key, Q_OFF, 160, 48)
    pt = C.tied_head_act_init(Q_OFF)
    pt["a_scale"] = jnp.asarray(0.4)
    pt["a_offset"] = jnp.asarray(0.01)
    pu = {"w": emb["w"].T, "w_scale": emb["w_scale"],
          "a_scale": pt["a_scale"], "a_offset": pt["a_offset"]}
    x = jnp.asarray(rng.standard_normal((2, 5, 48)), jnp.bfloat16)

    def loss_t(pt):
        lg = C.lm_head_apply(pt, x, Q_OFF, 150, 160, tied_embed=emb)
        return jnp.sum(jnp.tanh(lg * 0.05))

    def loss_u(pu):
        lg = C.lm_head_apply(pu, x, Q_OFF, 150, 160)
        return jnp.sum(jnp.tanh(lg * 0.05))

    gt = jax.grad(loss_t)(pt)
    gu = jax.grad(loss_u)(pu)
    for k in ("a_scale", "a_offset"):
        scale = max(float(jnp.max(jnp.abs(gu[k]))), 1e-12)
        _close(gt[k] / scale, gu[k] / scale, 1e-5)


def test_no_offset_activation_parity(key, rng):
    """Signed (offset-free) activation spec routes through the same kernel."""
    q_off = QuantConfig(w_bits=4, a_bits=8, mode="mdq", fused_matmul="off",
                        edge_bits=8)
    q_on = q_off.replace(fused_matmul="on")
    p = C.linear_init(key, "w_in", q_off, (40, 24), std=0.1)
    if "a_offset" in p:
        del p["a_offset"]  # exercise the b=0 path explicitly
    x = jnp.asarray(rng.standard_normal((3, 40)), jnp.bfloat16)
    y_off = C.qlinear(p, x[:, None], "w_in", q_off, "bsd,df->bsf")
    y_on = C.qlinear(p, x[:, None], "w_in", q_on, "bsd,df->bsf")
    _close(y_off, y_on, 1e-5)


# ---------------------------------------------------------------------------
# combined-backward VMEM budget: the (bk, Np) dW panel is unbounded in N, so
# oversized shapes (lm_head vocab, wide d_ff) must dispatch to the split
# dx/dw kernels — same cotangents, tile-sized scratches.
# ---------------------------------------------------------------------------

from repro.kernels import quant_matmul as qmm


def _bwd_operands(rng, m, k, n, k_side):
    dy = jnp.asarray(rng.standard_normal((m, n)), jnp.float32)
    x = jnp.asarray(rng.standard_normal((m, k)), jnp.float32)
    w = jnp.asarray(rng.standard_normal((k, n)) * 0.05, jnp.float32)
    sh = (k, 1) if k_side else (1, n)
    ws = jnp.asarray(np.abs(rng.standard_normal(sh)) * 0.02 + 0.01,
                     jnp.float32)
    return dy, x, w, jnp.asarray(0.2), jnp.asarray(0.05), ws


def _close_normed(a, b, tol=1e-5):
    a = np.asarray(a, np.float64)
    b = np.asarray(b, np.float64)
    scale = max(np.max(np.abs(a)), 1.0)
    assert_allclose(a / scale, b / scale, rtol=0, atol=tol)


@pytest.mark.parametrize("k_side", [False, True])
@pytest.mark.parametrize("round_cot", [True, False])
def test_bwd_split_fallback_matches_combined(rng, k_side, round_cot):
    """scratch_budget=0 forces the split dx/dw path; all five cotangents
    must match the combined kernel (multi-block in every grid axis)."""
    args = _bwd_operands(rng, 256, 1024, 256, k_side)
    kw = dict(q_n_a=8, q_p_a=7, q_n_w=8, q_p_w=7, round_cot=round_cot,
              interpret=True)
    combined = qmm.quant_matmul_bwd(*args, **kw)
    split = qmm.quant_matmul_bwd(*args, scratch_budget=0, **kw)
    assert split[3].shape == args[2].shape
    assert split[4].shape == ((1024, 1) if k_side else (1, 256))
    for a, b in zip(combined, split):
        _close_normed(a, b)


def test_bwd_batched_split_fallback_matches_combined(rng):
    e, m, k, n = 3, 128, 512, 128
    dy = jnp.asarray(rng.standard_normal((e, m, n)), jnp.float32)
    x = jnp.asarray(rng.standard_normal((e, m, k)), jnp.float32)
    w = jnp.asarray(rng.standard_normal((e, k, n)) * 0.05, jnp.float32)
    a_s = jnp.asarray(np.abs(rng.standard_normal((e, 1))) * 0.1 + 0.1,
                      jnp.float32)
    a_b = jnp.asarray(rng.standard_normal((e, 1)) * 0.01, jnp.float32)
    ws = jnp.asarray(np.abs(rng.standard_normal((e, n))) * 0.02 + 0.01,
                     jnp.float32)
    kw = dict(q_n_a=8, q_p_a=7, q_n_w=8, q_p_w=7, interpret=True)
    combined = qmm.quant_matmul_bwd_batched(dy, x, w, a_s, a_b, ws, **kw)
    split = qmm.quant_matmul_bwd_batched(dy, x, w, a_s, a_b, ws,
                                         scratch_budget=0, **kw)
    for a, b in zip(combined, split):
        assert a.shape == b.shape
        _close_normed(a, b)


def test_bwd_budget_routing():
    """The dispatch boundary itself: QAT hot-path shapes stay on the combined
    kernel; vocab-sized N (tied/untied lm_head) must NOT try to allocate the
    (bk, Np) panel on real TPU."""
    assert qmm.bwd_uses_combined(256, 1024, 512)
    assert not qmm.bwd_uses_combined(256, 512, 50304)      # lm_head vocab
    assert not qmm.bwd_uses_combined(256, 1024, 8192)      # very wide d_ff
    assert not qmm.bwd_uses_combined(256, 1024, 512, scratch_budget=0)
    assert qmm.bwd_scratch_bytes(256, 1024, 512) < qmm.BWD_SCRATCH_BUDGET_BYTES


def test_huge_n_backward_runs_without_panel(rng):
    """A vocab-sized N goes down the budget fallback end-to-end (the combined
    kernel would allocate a (512, Np) f32 panel — ~100MB at real vocab)."""
    m, k, n = 128, 512, qmm.DEFAULT_TILES[1] * 40  # Np panel > 8MB budget
    assert not qmm.bwd_uses_combined(m, k, n)
    args = _bwd_operands(rng, m, k, n, k_side=False)
    dx, dsa, dba, dw, dws = qmm.quant_matmul_bwd(
        *args, q_n_a=8, q_p_a=7, q_n_w=8, q_p_w=7, interpret=True)
    assert dx.shape == (m, k) and dw.shape == (k, n) and dws.shape == (1, n)
    assert np.isfinite(np.asarray(dsa)) and np.isfinite(np.asarray(dws)).all()


# ---------------------------------------------------------------------------
# int4 packing + serving
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("shape,axis", [((80, 56), 0), ((8, 10, 16), 1),
                                        ((6, 4, 12), 0), ((64,), 0)])
def test_pack_int4_roundtrip(rng, shape, axis):
    codes = jnp.asarray(rng.integers(-8, 8, shape), jnp.int8)
    assert (unpack_int4(pack_int4(codes, axis), axis) == codes).all()


def test_pack_int4_odd_axis_raises():
    with pytest.raises(ValueError):
        pack_int4(jnp.zeros((5, 4), jnp.int8), 0)


_GRANITE_KN = [(4096, 1024), (4096, 14336), (14336, 4096)]  # k/v, gate/up, down
_INT4_CASES = ([(33, 80, 56, "normal"), (5, 130, 300, "normal")]
               + [(m, k, n, "normal") for k, n in _GRANITE_KN for m in (1, 32, 256)]
               + [(32, 4096, 1024, "clamp")])


@pytest.mark.parametrize(
    "mkn", _INT4_CASES,
    ids=["mkn0", "mkn1"] + [f"granite-{k}x{n}-m{m}" for m, k, n, _ in
                            _INT4_CASES[2:-1]] + ["scale-clamp"])
def test_packed_int4_matmul_matches_int8(rng, mkn):
    """Packed int4 serving matmul vs the int8 reference, at odd shapes (the
    fallback tiles) and at granite-8b's shapes (the streaming tiles).

    The reference rounds each dequantized weight code*scale to bf16; the
    kernel keeps the codes exact and scales the f32 sum. So the kernel must
    match the exact product of the bf16 inputs closely, and differ from the
    reference by no more than bf16's unit roundoff (2^-8) of |x| @ |w|.
    """
    m, k, n, scale = mkn
    wspec = QuantSpec(bits=4)
    x = jnp.asarray(rng.standard_normal((m, k)), jnp.float32)
    codes = jnp.asarray(rng.integers(-8, 8, (k, n)), jnp.int8)
    if scale == "clamp":   # at, above and below the 1e-9 floor, and zero
        ws = jnp.asarray(rng.choice([0.0, 2.5e-10, 1e-9, 4e-9], n), jnp.float32)
    else:
        ws = jnp.asarray(np.abs(rng.standard_normal(n)) * 0.05 + 0.01,
                         jnp.float32)
    want = ref.int_matmul(x, codes, ws.reshape(1, -1), q_n_w=8, q_p_w=7)
    got = ops.int_matmul(x, pack_int4(codes, 0), ws, wspec, packed=True,
                         interpret=True)
    xb = x.astype(jnp.bfloat16).astype(jnp.float32)
    w = codes.astype(jnp.float32) * jnp.maximum(ws, 1e-9)
    hi = jax.lax.Precision.HIGHEST
    exact = jnp.dot(xb, w, precision=hi)
    absprod = jnp.dot(jnp.abs(xb), jnp.abs(w), precision=hi)
    assert got.shape == (m, n)
    assert (jnp.abs(got - exact) <= 2.0 ** -12 * absprod).all()
    assert (jnp.abs(got - want) <= (2.0 ** -8 + 2.0 ** -12) * absprod).all()


@pytest.mark.parametrize("arch", ["granite-8b", "qwen1.5-0.5b"])
@pytest.mark.parametrize("m", [32, 256])
def test_int4_tiles_divide_published_linears(arch, m):
    """Every packed linear (k, n) of the model gets tiles that divide it, so
    no call pads (copies) the weights, within the kernel's VMEM budget;
    the QAT kernels' DEFAULT_TILES stay as they were."""
    from repro.configs.registry import get_config
    from repro.kernels import quant_matmul as qmm
    cfg = get_config(arch)
    d, f = cfg.d_model, cfg.d_ff
    q, kv = cfg.n_heads * cfg.head_dim_, cfg.n_kv_heads * cfg.head_dim_
    for k, n in [(d, q), (d, kv), (q, d), (d, f), (f, d)]:
        bm, bn, bk = qmm.int4_tiles(m, k, n)
        assert k % bk == 0 and n % bn == 0, (k, n, bk, bn)
        assert bm == m  # one row block: each weight block is read once
        assert qmm.int4_vmem_bytes(bm, bn, bk) <= qmm.INT4_VMEM_BUDGET
    assert qmm.DEFAULT_TILES == (128, 128, 512)


def test_convert_to_serving_packs_low_bits(key):
    qcfg = QuantConfig(w_bits=4, a_bits=4, mode="mdq")
    params = {"w_in": C.linear_init(key, "w_in", qcfg, (48, 64), std=0.1),
              "wq": C.linear_init(key, "wq", qcfg, (48, 4, 16), std=0.1,
                                  group_axes=(1,)),
              "lm_head": C.lm_head_init(key, qcfg, 48, 160)}
    sp = C.convert_to_serving(params, qcfg)
    assert "codes4" in sp["w_in"] and sp["w_in"]["codes4"].shape == (24, 64)
    assert "codes4" in sp["wq"] and sp["wq"]["codes4"].shape == (24, 4, 16)
    assert "codes" in sp["lm_head"]  # edge layers pinned to 8 bits: unpacked
    # at 8 bits nothing packs
    q8 = QuantConfig(w_bits=8, a_bits=8, mode="mdq")
    sp8 = C.convert_to_serving({"w_in": C.linear_init(key, "w_in", q8,
                                                      (48, 64), std=0.1)}, q8)
    assert "codes" in sp8["w_in"]


@pytest.mark.parametrize("name,shape,eq,kw", [
    ("w_in", (48, 64), "bsd,df->bsf", {}),
    ("wq", (48, 4, 16), "bsd,dhk->bshk", {"group_axes": (1,)}),
])
def test_serving_fused_matches_fallback(key, rng, name, shape, eq, kw):
    """Packed-int4 Pallas serving path vs dequantize+einsum fallback."""
    qcfg = QuantConfig(w_bits=4, a_bits=32, mode="mdq")
    sp = C.convert_to_serving(
        {name: C.linear_init(key, name, qcfg.replace(a_bits=4), shape,
                             std=0.1, **kw)}, qcfg)
    assert "codes4" in sp[name]
    x = jnp.asarray(rng.standard_normal((2, 5, 48)), jnp.bfloat16)
    y_fb = C.qlinear(sp[name], x, name, qcfg.replace(fused_matmul="off"), eq)
    y_fu = C.qlinear(sp[name], x, name, qcfg.replace(fused_matmul="on"), eq)
    _close(y_fb, y_fu, 1e-2)  # double-rounding of scale*code differs in bf16


# ---------------------------------------------------------------------------
# end-to-end: full model forward/backward with the fused dispatch on
# ---------------------------------------------------------------------------

def test_model_forward_parity_fused(key):
    from repro.configs.registry import get_config, reduced_config
    from repro.models import model as M
    cfg = reduced_config(get_config("granite-8b")).replace(n_layers=2)
    qcfg = QuantConfig(w_bits=4, a_bits=4, mode="mdq")
    params = M.init_params(key, cfg, qcfg)
    tokens = jax.random.randint(jax.random.PRNGKey(1), (2, 8), 0,
                                cfg.vocab_size)
    lg_off, _ = M.forward(params, {"tokens": tokens}, cfg,
                          qcfg.replace(fused_matmul="off"))
    lg_on, _ = M.forward(params, {"tokens": tokens}, cfg,
                         qcfg.replace(fused_matmul="on"))
    # The math is identical modulo f32 accumulation order inside the tiles;
    # deep in the network a last-bit bf16 difference can land an activation
    # on the other side of a quantizer round() boundary and flip isolated
    # codes (scan-vs-unrolled recompilation of the SAME unfused math shows
    # the identical effect), so assert functional parity: the overwhelming
    # majority of logits bit-equal, distributions and predictions unchanged.
    d = np.abs(np.asarray(lg_on) - np.asarray(lg_off))
    assert np.isfinite(np.asarray(lg_on)).all()
    assert np.quantile(d, 0.9) < 1e-3, np.quantile(d, 0.9)
    assert d.mean() < 0.05, d.mean()
    p_on = jax.nn.softmax(lg_on[..., :cfg.vocab_size], -1)
    p_off = jax.nn.softmax(lg_off[..., :cfg.vocab_size], -1)
    assert float(jnp.max(jnp.abs(p_on - p_off))) < 0.02
    assert bool(jnp.all(jnp.argmax(lg_on, -1) == jnp.argmax(lg_off, -1)))


def test_moe_model_forward_parity_fused(key):
    """MoE backbone end-to-end: the batched expert kernels (per-expert
    scales) compose with the rest of the fused dispatch."""
    from repro.configs.registry import get_config, reduced_config
    from repro.models import model as M
    cfg = reduced_config(get_config("granite-moe-1b-a400m")).replace(n_layers=2)
    qcfg = QuantConfig(w_bits=4, a_bits=4, mode="mdq")
    params = M.init_params(key, cfg, qcfg)
    tokens = jax.random.randint(jax.random.PRNGKey(1), (2, 8), 0,
                                cfg.vocab_size)
    lg_off, _ = M.forward(params, {"tokens": tokens}, cfg,
                          qcfg.replace(fused_matmul="off"))
    lg_on, _ = M.forward(params, {"tokens": tokens}, cfg,
                         qcfg.replace(fused_matmul="on"))
    d = np.abs(np.asarray(lg_on) - np.asarray(lg_off))
    assert np.isfinite(np.asarray(lg_on)).all()
    # same functional-parity bar as the dense model test above (router stays
    # f32/unfused in both configs, so expert assignment is identical)
    assert np.quantile(d, 0.9) < 1e-3, np.quantile(d, 0.9)
    assert d.mean() < 0.05, d.mean()
    p_on = jax.nn.softmax(lg_on[..., :cfg.vocab_size], -1)
    p_off = jax.nn.softmax(lg_off[..., :cfg.vocab_size], -1)
    assert float(jnp.max(jnp.abs(p_on - p_off))) < 0.02
