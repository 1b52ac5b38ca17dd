"""Sharding rules validated on abstract meshes (no devices needed)."""
import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import AbstractMesh, PartitionSpec as P

from repro.configs.registry import get_config, reduced_config
from repro.core.policy import QuantConfig
from repro.dist import sharding as shard
from repro.models import model as M
from repro.train.state import TrainConfig, init_state

MESH = AbstractMesh((16, 16), ("data", "model"))
QCFG = QuantConfig(w_bits=4, a_bits=4, mode="mdq")


def test_weight_rules_head_sharding():
    # qwen110b wq stacked: (80, 8192, 64, 128) -> heads on model, d on data
    spec = shard.weight_pspec("wq", (80, 8192, 64, 128), MESH)
    assert spec == P(None, "data", "model", None)
    # kv heads 8 don't divide 16 -> replicate model axis, keep FSDP
    spec = shard.weight_pspec("wk", (80, 8192, 8, 128), MESH)
    assert spec == P(None, "data", None, None)
    # ffn col/row parallel
    assert shard.weight_pspec("w_in", (80, 8192, 49152), MESH) == P(None, "data", "model")
    assert shard.weight_pspec("w_out", (80, 49152, 8192), MESH) == P(None, "model", "data")


def test_moe_expert_vs_tp():
    # granite-moe: 32 experts % 16 == 0 -> EP
    assert shard.weight_pspec("moe_in", (24, 32, 1024, 512), MESH) == \
        P(None, "model", "data", None)
    # mixtral: 8 experts -> fallback TP on d_ff
    assert shard.weight_pspec("moe_in", (32, 8, 4096, 14336), MESH) == \
        P(None, None, "data", "model")


def test_small_head_fallback_replicates():
    # gemma2 8 heads on model=16 -> no model sharding; FSDP on d
    spec = shard.weight_pspec("wq", (13, 2304, 8, 256), MESH)
    assert spec == P(None, "data", None, None)


def test_embed_lm_head():
    assert shard.weight_pspec("embed", (152064, 8192), MESH, fsdp=False) == \
        P("model", None)
    assert shard.weight_pspec("lm_head", (8192, 152064), MESH) == P("data", "model")


def test_param_pspecs_tree(key):
    cfg = get_config("qwen1.5-0.5b").replace(n_layers=2)
    params = jax.eval_shape(lambda k: M.init_params(k, cfg, QCFG), key)
    specs = shard.param_pspecs(params, MESH)
    g = specs["groups"][0]
    assert g["wq"]["w"] == P(None, "data", "model", None)
    # per-head scale (G,1,H,1) shards with heads
    assert g["wq"]["w_scale"] == P(None, None, "model", None)
    # per-tensor act scale replicated
    assert g["wq"]["a_scale"] == P()
    # embed: vocab-shard only (no FSDP d-axis — multi-pod gather pathology,
    # EXPERIMENTS.md Perf-2)
    assert specs["embed"]["w"] == P("model", None)


def test_state_pspecs_mirror(key):
    cfg = reduced_config(get_config("granite-8b")).replace(n_layers=2)
    qc = QCFG.replace(track_oscillation=True)
    state = jax.eval_shape(
        lambda k: init_state(k, cfg, qc, TrainConfig()), key)
    specs = shard.state_pspecs(state, MESH, qc)
    assert jax.tree.structure(specs["mu"]) == jax.tree.structure(specs["params"])
    assert specs["step"] == P()
    assert len(specs["osc"]) == len(state["osc"])


def test_batch_pspecs_divisibility():
    batch = {"tokens": jax.ShapeDtypeStruct((256, 128), jnp.int32),
             "one": jax.ShapeDtypeStruct((1, 128), jnp.int32)}
    specs = shard.batch_pspecs(batch, MESH)
    assert specs["tokens"] == P(("data",), None)
    assert specs["one"] == P(None, None)  # batch=1 can't shard over 16


def test_cache_pspecs_seq_sharding(key):
    cfg = reduced_config(get_config("granite-8b"))
    cache = jax.eval_shape(lambda: M.init_cache(cfg, QCFG, 32, 64))
    specs = shard.cache_pspecs(cache, MESH)
    kv = specs["groups"][0]["kv"]
    # stacked: (G, B, T, Hkv, D) -> batch axis 1 on data, seq axis 2 on model
    assert kv.k == P(None, ("data",), "model", None, None)
    assert kv.pos == P(None, ("data",), "model")
