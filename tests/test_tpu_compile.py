"""Compile the main-path Pallas kernels for a TPU v5e chip, without the chip.

Interpret-mode tests run the kernel bodies on the CPU but never meet the
TPU's lowering rules (block-shape tiling, scalar stores to VMEM, scoped VMEM
limits). Here each kernel is compiled with `interpret=False` for a v5e chip
that libtpu describes but that is not attached, at the published widths of
qwen1.5-0.5b (d_model 1024, 16 heads of 64, d_ff 2816, vocab 151936), and
the compiled program must hold the kernel (`tpu_custom_call`). One whole
sharded train step, Granite-8B's `qat-granite-8b-2x2` cell, compiles over
the four described chips and must fit their memory. Nothing runs, so this
says nothing about results or times.

The topology is described inside a module fixture, never at import: only
one process may hold libtpu, and under pytest-xdist every worker imports
this file while only the one that runs it loads the library.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core.quantizer import QuantSpec
from repro.kernels import ops
from repro.kernels import quant_matmul as qmm
from repro.kernels.decode_attention import pooled_decode_attention

D_MODEL, HEADS, HEAD_DIM, D_FF, VOCAB = 1024, 16, 64, 2816, 151936
TOKENS = 2048          # one training batch: 4 x 512
SLOTS, MAX_LEN = 4, 2048
SLOTS_DECODE, CHUNK = 32, 256  # a pooled decode step; a prefill chunk
A4 = QuantSpec(bits=4, signed=False, offset=True)
W4 = QuantSpec(bits=4, signed=True)
A8 = QuantSpec(bits=8, signed=False, offset=True)
W8 = QuantSpec(bits=8, signed=True)


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any failure means "cannot"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module", autouse=True)
def no_persistent_cache():
    """A compile for a described chip is written to the persistent cache but
    cannot be read back without the chip; keep the cache out of it."""
    from jax.experimental.compilation_cache import compilation_cache as cc
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    cc.reset_cache()


def _compile(fn, *shapes):
    text = jax.jit(fn).lower(*shapes).compile().as_text()
    assert text.count("tpu_custom_call") >= 1, "no Pallas kernel compiled"
    return text


@pytest.mark.parametrize("kv", ["fp", "int8", "int4"])
def test_decode_attention_compiles(one_chip, kv):
    s = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)
    q = s((SLOTS, 1, HEADS, HEAD_DIM), jnp.bfloat16)
    pos, qpos = s((SLOTS, MAX_LEN), jnp.int32), s((SLOTS, 1), jnp.int32)
    kw = dict(q_per_kv=1, window=0, softcap=0.0, interpret=False)
    if kv == "fp":
        k = s((SLOTS, MAX_LEN, HEADS, HEAD_DIM), jnp.bfloat16)
        _compile(lambda q, k, v, p, qp: pooled_decode_attention(
            q, k, v, None, None, p, qp, **kw), q, k, k, pos, qpos)
        return
    width = HEAD_DIM if kv == "int8" else HEAD_DIM // 2  # int4: packed
    k = s((SLOTS, MAX_LEN, HEADS, width), jnp.int8)
    ks = s((SLOTS, MAX_LEN, HEADS, 1), jnp.float32)
    _compile(lambda q, k, v, ks, vs, p, qp: pooled_decode_attention(
        q, k, v, ks, vs, p, qp, **kw), q, k, k, ks, ks, pos, qpos)


def _qat_case(one_chip, k, n, side, aspec, wspec, *, grad, round_cot=True):
    s = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)
    args = (s((TOKENS, k), jnp.bfloat16), s((k, n), jnp.float32),
            s((), jnp.float32), s((), jnp.float32),
            s((n if side == "n" else k,), jnp.float32))

    def fwd(x, w, sa, ba, wv):
        return ops.fused_qat_matmul(x, w, sa, ba, wv, aspec, wspec,
                                    interpret=False, w_scale_axis=side,
                                    cotangent_rounding=round_cot)

    fn = fwd
    if grad:
        fn = jax.grad(lambda *a: jnp.sum(fwd(*a) ** 2), argnums=(0, 1, 2, 3, 4))
    return _compile(fn, *args)


@pytest.mark.parametrize("grad", [False, True], ids=["fwd", "bwd"])
@pytest.mark.parametrize("case", ["ffn_in", "wo"])
def test_fused_qat_matmul_compiles(one_chip, case, grad):
    """N-side ffn in (1024 -> 2816, per-tensor scale) and K-side per-head
    wo (16 heads x 64 -> 1024, per-head row scales)."""
    k, n, side = {"ffn_in": (D_MODEL, D_FF, "n"),
                  "wo": (HEADS * HEAD_DIM, D_MODEL, "k")}[case]
    assert qmm.bwd_uses_combined(TOKENS, k, n)
    text = _qat_case(one_chip, k, n, side, A4, W4, grad=grad)
    assert text.count("tpu_custom_call") >= (2 if grad else 1)


def test_lm_head_backward_split_compiles(one_chip):
    """The tied vocab-wide head: its dW panel exceeds the scratch budget,
    so the backward takes the split dx/dw kernels."""
    assert not qmm.bwd_uses_combined(TOKENS, D_MODEL, VOCAB)
    text = _qat_case(one_chip, D_MODEL, VOCAB, "n", A8, W8, grad=True,
                     round_cot=False)
    assert text.count("tpu_custom_call") >= 3  # fwd + dx + dw


def test_combined_backward_at_scratch_budget_compiles(one_chip):
    """The widest N the combined backward accepts: its (bk, N) dW panel
    plus the double-buffered tiles must fit v5e's default scoped VMEM."""
    n = D_FF
    while qmm.bwd_uses_combined(TOKENS, D_MODEL, n + 128):
        n += 128
    assert n > D_FF
    _qat_case(one_chip, D_MODEL, n, "n", A4, W4, grad=True)


def test_batched_expert_backward_compiles(one_chip):
    """MoE expert matmul (granite-moe-1b widths: 1024 -> 512 per expert)."""
    s = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)
    e, m = 8, 256

    def loss(x, w, sa, ba, ws):
        y = ops.fused_qat_matmul_batched(x, w, sa, ba, ws, A4, W4,
                                         interpret=False)
        return jnp.sum(y ** 2)

    _compile(jax.grad(loss, argnums=(0, 1, 2, 3, 4)),
             s((e, m, D_MODEL), jnp.bfloat16), s((e, D_MODEL, 512), jnp.float32),
             s((e,), jnp.float32), s((e,), jnp.float32),
             s((e, 512), jnp.float32))


@pytest.mark.parametrize("packed,tokens", [(False, TOKENS), (True, TOKENS),
                                           (True, SLOTS_DECODE), (True, CHUNK)],
                         ids=["int8", "int4", "int4-decode", "int4-prefill"])
def test_int_matmul_compiles(one_chip, packed, tokens):
    """int4 at a pooled decode step and a prefill chunk compiles the tiles
    int4_tiles picks for them, with the packed weights passed unpadded."""
    s = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)
    rows = D_MODEL // 2 if packed else D_MODEL
    spec = W4 if packed else W8
    text = _compile(lambda x, c, sc: ops.int_matmul(x, c, sc, spec,
                                                    packed=packed,
                                                    interpret=False),
                    s((tokens, D_MODEL), jnp.bfloat16),
                    s((rows, D_FF), jnp.int8), s((D_FF,), jnp.float32))
    if packed:
        assert " pad(" not in text


def test_granite_sharded_train_step_fits_a_2x2_host(topo):
    """The QAT step of the `qat-granite-8b-2x2` cell (Granite-8B widths at
    the configuration's depth, its batch and length, the (data, model) mesh
    and rules `run_training` builds) compiles over the four described chips
    and leaves each at least 0.5 GB of v5e's 15.75 (14.62 GB when written)."""
    import json

    from repro.configs.registry import get_config
    from repro.core.policy import get_preset
    from repro.dist import sharding as shard
    from repro.launch.mesh import kernels_for_mesh, make_host_mesh
    from repro.train.sentinel import SentinelConfig
    from repro.train.state import TrainConfig, init_state
    from repro.train.train_step import make_train_step
    from jax.sharding import NamedSharding, PartitionSpec as P

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    c = json.load(open(os.path.join(root, "bench", "configs",
                                    "granite-8b-qat-w4a4.json")))
    tr = json.load(open(os.path.join(root, "bench", "traffic",
                                     "qat_b4_s1024_2x2.json")))
    cfg = get_config(c["arch"]).replace(n_layers=c["num_hidden_layers"])
    assert (cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.d_ff,
            cfg.vocab_size, cfg.tie_embeddings) == (
        c["hidden_size"], c["num_attention_heads"],
        c["num_key_value_heads"], c["intermediate_size"], c["vocab_size"],
        c["tie_word_embeddings"])
    qcfg = get_preset(c["quant"]["preset"])
    tcfg = TrainConfig(total_steps=c["train"]["total_steps"], kd="mckd",
                       kd_topk=c["train"]["kd_topk"],
                       sentinel=SentinelConfig())
    mesh = make_host_mesh(model=tr["model_parallel"], devices=topo.devices)
    like = jax.eval_shape(lambda k: init_state(k, cfg, qcfg, tcfg),
                          jax.random.PRNGKey(0))
    sh = shard.named_tree(shard.state_pspecs(like, mesh, qcfg), mesh)
    state = jax.tree.map(lambda x, s: jax.ShapeDtypeStruct(
        x.shape, x.dtype, sharding=s), like, sh)
    rep = NamedSharding(mesh, P())
    b, s, k = tr["batch"], tr["seq_len"], c["train"]["kd_topk"]
    batch = {"tokens": jax.ShapeDtypeStruct((b, s), jnp.int32, sharding=rep),
             "labels": jax.ShapeDtypeStruct((b, s), jnp.int32, sharding=rep),
             "kd_idx": jax.ShapeDtypeStruct((b, s, k), jnp.int32,
                                            sharding=rep),
             "kd_p": jax.ShapeDtypeStruct((b, s, k), jnp.float32,
                                          sharding=rep)}
    constrain, logits_constrain = shard.make_constrains(mesh)
    step = jax.jit(make_train_step(cfg, kernels_for_mesh(qcfg, mesh), tcfg,
                                   constrain=constrain,
                                   logits_constrain=logits_constrain),
                   in_shardings=(sh, None), out_shardings=(sh, None),
                   donate_argnums=0)
    compiled = step.lower(state, batch).compile()
    assert "tpu_custom_call" not in compiled.as_text()   # jnp path on a mesh
    ma = compiled.memory_analysis()
    per_device = (ma.argument_size_in_bytes + ma.output_size_in_bytes
                  - ma.alias_size_in_bytes + ma.temp_size_in_bytes)
    assert per_device <= 15.25e9, per_device
