"""Composable decoder assembly for every assigned architecture.

The layer stack is cfg.pattern repeated cyclically: a lax.scan covers the
full pattern periods (params vmap-stacked along a leading `n_groups` axis,
so HLO size and activation residency are depth-independent) and an unrolled
tail covers n_layers % period. Per-layer KV/recurrent caches follow the same
layout.

Entry points:
  init_params(key, cfg, qcfg)
  forward(params, batch, cfg, qcfg, ...)            -> logits [, cache]
  init_cache(cfg, qcfg, batch, cache_len)           -> decode cache pytree
  decode_step(params, cache, batch, cfg, qcfg, ...) -> (logits, cache)
  prefill_step(params, cache, batch, cfg, qcfg, ..) -> (logits, cache)  [C>=1]
  cache_slot_insert / cache_slot_reset              -> serving slot pool ops
  quant_leaves(params, qcfg)                        -> [(w, scale, spec)]
"""
from __future__ import annotations

import functools
from typing import Callable, Optional

import jax
import jax.numpy as jnp

from repro import tracing
from repro.configs.base import ArchConfig, BlockDef
from repro.core.policy import QuantConfig, weight_spec
from repro.models import attention as attn
from repro.models import moe as moe_mod
from repro.models import recurrent as rec
from repro.models.common import (NAME2KIND, apply_norm, embed_init,
                                 embed_lookup, linear_init, lm_head_apply,
                                 lm_head_init, norm_init, qlinear,
                                 tied_head_act_init)

Constrain = Callable[[jax.Array], jax.Array]
_IDENT: Constrain = lambda x: x


def _cdtype(cfg: ArchConfig):
    return jnp.bfloat16 if cfg.dtype == "bfloat16" else jnp.float32


# ===========================================================================
# Block init
# ===========================================================================

def _attn_init(key, cfg: ArchConfig, qcfg: QuantConfig, cross: bool) -> dict:
    d, h, hkv, hd = cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.head_dim_
    ks = jax.random.split(key, 8)
    pre = "x" if cross else "w"
    bias = (cfg.qkv_bias and not cross)
    p = {
        f"{pre}q": linear_init(ks[0], f"{pre}q", qcfg, (d, h, hd), std=d ** -0.5,
                               group_axes=(1,), bias_shape=(h, hd) if bias else None),
        f"{pre}k": linear_init(ks[1], f"{pre}k", qcfg, (d, hkv, hd), std=d ** -0.5,
                               group_axes=(1,), bias_shape=(hkv, hd) if bias else None),
        f"{pre}v": linear_init(ks[2], f"{pre}v", qcfg, (d, hkv, hd), std=d ** -0.5,
                               group_axes=(1,), bias_shape=(hkv, hd) if bias else None),
        f"{pre}o": linear_init(ks[3], f"{pre}o", qcfg, (h, hd, d),
                               std=(h * hd) ** -0.5, group_axes=(0,)),
    }
    if cross:
        p["xgate"] = jnp.zeros((), jnp.float32)
        p["ln_x"] = norm_init(d, cfg.norm)
    return p


def _ffn_init(key, cfg: ArchConfig, qcfg: QuantConfig) -> dict:
    d, f = cfg.d_model, cfg.d_ff
    ks = jax.random.split(key, 3)
    p = {"w_in": linear_init(ks[0], "w_in", qcfg, (d, f), std=d ** -0.5),
         "w_out": linear_init(ks[1], "w_out", qcfg, (f, d), std=f ** -0.5)}
    if cfg.ffn_gated:
        p["w_gate"] = linear_init(ks[2], "w_gate", qcfg, (d, f), std=d ** -0.5)
    return p


def block_init(key, cfg: ArchConfig, qcfg: QuantConfig, bd: BlockDef) -> dict:
    ks = jax.random.split(key, 4)
    if bd.attn == "mlstm":
        p = rec.mlstm_init(ks[0], cfg, qcfg)
    elif bd.attn == "slstm":
        p = rec.slstm_init(ks[0], cfg, qcfg)
    elif bd.attn == "rglru":
        p = {"rg": rec.rglru_init(ks[0], cfg, qcfg), "ln1": norm_init(cfg.d_model, cfg.norm)}
    else:
        p = {"ln1": norm_init(cfg.d_model, cfg.norm)}
        p.update(_attn_init(ks[0], cfg, qcfg, cross=False))
        if cfg.sandwich_norm:
            p["ln1_post"] = norm_init(cfg.d_model, cfg.norm)
    if bd.cross_attn:
        p.update(_attn_init(ks[1], cfg, qcfg, cross=True))
    if bd.ffn == "dense":
        p["ln2"] = norm_init(cfg.d_model, cfg.norm)
        p.update(_ffn_init(ks[2], cfg, qcfg))
        if cfg.sandwich_norm:
            p["ln2_post"] = norm_init(cfg.d_model, cfg.norm)
    elif bd.ffn == "moe":
        p["ln2"] = norm_init(cfg.d_model, cfg.norm)
        p["moe"] = moe_mod.moe_init(ks[2], cfg, qcfg)
    return p


def init_params(key, cfg: ArchConfig, qcfg: QuantConfig) -> dict:
    cfg.validate()
    keys = jax.random.split(key, 8)
    params: dict = {"embed": embed_init(keys[0], qcfg, cfg.padded_vocab, cfg.d_model),
                    "final_norm": norm_init(cfg.d_model, cfg.norm)}
    if cfg.pos == "learned":
        params["pos_embed"] = (jax.random.normal(keys[1], (cfg.max_seq, cfg.d_model),
                                                 jnp.float32) * 0.02)
    if cfg.tie_embeddings:
        params["lm_head"] = tied_head_act_init(qcfg)
    else:
        params["lm_head"] = lm_head_init(keys[2], qcfg, cfg.d_model, cfg.padded_vocab)

    # scan groups: per pattern position, params stacked over n_groups
    if cfg.n_groups > 0:
        def make_group(gkey):
            gks = jax.random.split(gkey, cfg.period)
            return tuple(block_init(gks[i], cfg, qcfg, cfg.pattern[i])
                         for i in range(cfg.period))
        gkeys = jax.random.split(keys[3], cfg.n_groups)
        params["groups"] = jax.vmap(make_group)(gkeys)
    # unrolled tail (n_layers % period), pattern positions 0..n_tail-1
    if cfg.n_tail:
        tkeys = jax.random.split(keys[4], cfg.n_tail)
        params["tail"] = tuple(block_init(tkeys[i], cfg, qcfg, cfg.pattern[i])
                               for i in range(cfg.n_tail))
    return params


# ===========================================================================
# Block apply — training / prefill
# ===========================================================================

def _attn_sublayer(p, x, cfg: ArchConfig, qcfg: QuantConfig, bd: BlockDef,
                   positions, cdtype, collect: bool, constrain: Constrain):
    xn = apply_norm(p["ln1"], x, cfg.norm)
    q = qlinear(p["wq"], xn, "wq", qcfg, "bsd,dhk->bshk", cdtype)
    k = qlinear(p["wk"], xn, "wk", qcfg, "bsd,dhk->bshk", cdtype)
    v = qlinear(p["wv"], xn, "wv", qcfg, "bsd,dhk->bshk", cdtype)
    if cfg.pos == "rope":
        q = attn.rope_apply(q, positions, cfg.rope_theta)
        k = attn.rope_apply(k, positions, cfg.rope_theta)
    # k/v stay un-repeated: GQA runs as a grouped einsum inside attend_*
    window = cfg.window if bd.attn == "local" else 0
    if window and cfg.causal and x.shape[1] > window:
        o = attn.attend_local_chunked(q, k, v, window=window,
                                      softcap=cfg.attn_softcap,
                                      q_per_kv=cfg.q_per_kv)
    else:
        o = attn.attend_full(q, k, v, causal=cfg.causal, window=window,
                             softcap=cfg.attn_softcap, q_positions=positions,
                             k_positions=positions, q_per_kv=cfg.q_per_kv)
    out = qlinear(p["wo"], o, "wo", qcfg, "bshk,hkd->bsd", cdtype)
    if cfg.sandwich_norm:
        out = apply_norm(p["ln1_post"], out, cfg.norm)
    cache = None
    if collect:
        eff = min(cfg.window, x.shape[1]) if bd.attn == "local" else x.shape[1]
        cache = attn.cache_from_prefill(k, v, positions, qcfg, eff,
                                        ring=(bd.attn == "local"),
                                        window=cfg.window)
    return constrain(x + out), cache


def _cross_sublayer(p, x, frontend_kv, cfg, qcfg, cdtype, constrain):
    xn = apply_norm(p["ln_x"], x, cfg.norm)
    q = qlinear(p["xq"], xn, "xq", qcfg, "bsd,dhk->bshk", cdtype)
    k, v = frontend_kv  # precomputed per-block? no: shared projections below
    o = attn.attend_full(q, k, v, causal=False, window=0, softcap=0.0,
                         q_positions=jnp.arange(x.shape[1]),
                         k_positions=jnp.arange(k.shape[1]),
                         q_per_kv=cfg.q_per_kv)
    out = qlinear(p["xo"], o, "xo", qcfg, "bshk,hkd->bsd", cdtype)
    return constrain(x + jnp.tanh(p["xgate"]).astype(cdtype) * out)


def cross_kv(p, embeds, cfg, qcfg, cdtype):
    k = qlinear(p["xk"], embeds, "xk", qcfg, "bsd,dhk->bshk", cdtype)
    v = qlinear(p["xv"], embeds, "xv", qcfg, "bsd,dhk->bshk", cdtype)
    return k, v


def _ffn_sublayer(p, x, cfg, qcfg, cdtype, constrain):
    xn = apply_norm(p["ln2"], x, cfg.norm)
    if cfg.ffn_gated:
        g = qlinear(p["w_gate"], xn, "w_gate", qcfg, "bsd,df->bsf", cdtype)
        u = qlinear(p["w_in"], xn, "w_in", qcfg, "bsd,df->bsf", cdtype)
        h = (jax.nn.silu(g) if cfg.act == "silu" else jax.nn.gelu(g)) * u
    else:
        u = qlinear(p["w_in"], xn, "w_in", qcfg, "bsd,df->bsf", cdtype)
        h = jax.nn.silu(u) if cfg.act == "silu" else jax.nn.gelu(u)
    out = qlinear(p["w_out"], h, "w_out", qcfg, "bsf,fd->bsd", cdtype)
    if cfg.sandwich_norm:
        out = apply_norm(p["ln2_post"], out, cfg.norm)
    return constrain(x + out)


def block_apply(p: dict, x: jax.Array, bd: BlockDef, cfg: ArchConfig,
                qcfg: QuantConfig, positions: jax.Array,
                frontend_embeds: Optional[jax.Array], cdtype,
                collect: bool, constrain: Constrain):
    """Returns (x, (layer_cache, aux))."""
    from repro.core.sdam import sdam as _sdam
    cache: dict = {}
    aux = {"lb_loss": jnp.zeros((), jnp.float32),
           "drop_frac": jnp.zeros((), jnp.float32)}
    if bd.attn == "mlstm":
        x, st = rec.mlstm_block(p, x, cfg, qcfg, cdtype, collect=collect)
        if collect:
            cache["mlstm"] = st
        x = constrain(x)
    elif bd.attn == "slstm":
        x, st = rec.slstm_block(p, x, cfg, qcfg, cdtype, collect=collect)
        if collect:
            cache["slstm"] = st
        x = constrain(x)
    elif bd.attn == "rglru":
        x, st = rec.rglru_block(p["rg"], x, cfg, qcfg, cdtype, collect=collect)
        if collect:
            cache["rglru"] = st
        x = constrain(x)
    else:
        x, kvc = _attn_sublayer(p, x, cfg, qcfg, bd, positions, cdtype,
                                collect, constrain)
        if collect:
            cache["kv"] = kvc
    if bd.cross_attn:
        fkv = cross_kv(p, frontend_embeds, cfg, qcfg, cdtype)
        x = _cross_sublayer(p, x, fkv, cfg, qcfg, cdtype, constrain)
        if collect:
            cache["xkv"] = fkv
    if bd.ffn == "dense":
        x = _ffn_sublayer(p, x, cfg, qcfg, cdtype, constrain)
    elif bd.ffn == "moe":
        xn = apply_norm(p["ln2"], x, cfg.norm)
        y, maux = moe_mod.moe_ffn(p["moe"], xn, cfg, qcfg, cdtype)
        aux = {k: aux[k] + maux.get(k, 0.0) for k in aux}
        x = constrain(x + y)
    # per-block activation SDAM telemetry (Tab. 2/6 metric); scalar so it
    # rides through lax.scan as an aux output
    aux["sdam_sum"] = _sdam(x).astype(jnp.float32)
    return x, (cache if collect else None, aux)




# ===========================================================================
# Forward (train / prefill)
# ===========================================================================

@functools.partial(jax.jit, static_argnames=("cfg", "qcfg", "collect_cache",
                                             "remat"))
def forward_jit(params, batch, cfg, qcfg, collect_cache=False, remat=False):
    return forward(params, batch, cfg, qcfg, collect_cache=collect_cache,
                   remat=remat)


def forward(params: dict, batch: dict, cfg: ArchConfig, qcfg: QuantConfig, *,
            collect_cache: bool = False, remat: bool = False,
            constrain: Constrain = _IDENT, logits_constrain: Constrain = _IDENT):
    """Full-sequence forward. batch: tokens (B,S) [+ frontend_embeds].

    Returns logits (B, S, padded_vocab) f32, plus (cache, aux) when
    collect_cache else aux only.
    """
    cdtype = _cdtype(cfg)
    tokens = batch["tokens"]
    fe = batch.get("frontend_embeds")
    if cfg.frontend == "vision_patches" and not any(b.cross_attn for b in cfg.pattern):
        x = fe.astype(cdtype)  # encoder over patches (paper's ViT stand-in)
        cross_embeds = None
    else:
        x = embed_lookup(params["embed"], tokens, qcfg, cdtype)
        x = x * jnp.asarray(cfg.d_model ** 0.5, cdtype)
        if cfg.frontend == "audio_frames" and fe is not None:
            x = x + fe.astype(cdtype)
        cross_embeds = fe if cfg.frontend == "vision_patches" else None
    s = x.shape[1]
    positions = jnp.arange(s)
    if cfg.pos == "learned":
        x = x + jax.lax.dynamic_slice_in_dim(
            params["pos_embed"], 0, s, axis=0).astype(cdtype)[None]
    x = constrain(x)

    def apply_one(p, x, bd):
        return block_apply(p, x, bd, cfg, qcfg, positions, cross_embeds,
                           cdtype, collect_cache, constrain)

    caches = {"groups": (), "tail": ()}
    aux_sum = {"lb_loss": jnp.zeros((), jnp.float32),
               "drop_frac": jnp.zeros((), jnp.float32),
               "sdam_sum": jnp.zeros((), jnp.float32)}

    if cfg.n_groups > 0:
        def group_fn(x, gp):
            ys = []
            auxs = []
            for i in range(cfg.period):
                fn = apply_one
                if remat:
                    fn = jax.checkpoint(apply_one, static_argnums=(2,),
                                        prevent_cse=False)
                x, (c, a) = fn(gp[i], x, cfg.pattern[i])
                ys.append(c)
                auxs.append(a)
            asum = jax.tree.map(lambda *v: sum(v), *auxs)
            return x, (tuple(ys), asum)

        x, (gcaches, gaux) = jax.lax.scan(group_fn, x, params["groups"])
        caches["groups"] = gcaches
        aux_sum = jax.tree.map(lambda t, g: t + jnp.sum(g), aux_sum, gaux)

    for i in range(cfg.n_tail):
        fn = apply_one
        if remat:
            fn = jax.checkpoint(apply_one, static_argnums=(2,), prevent_cse=False)
        x, (c, a) = fn(params["tail"][i], x, cfg.pattern[i])
        caches["tail"] = caches["tail"] + (c,)
        aux_sum = jax.tree.map(lambda t, v: t + v, aux_sum, a)

    x = apply_norm(params["final_norm"], x, cfg.norm)
    with jax.named_scope(tracing.LM_HEAD):
        logits = lm_head_apply(
            params["lm_head"], x, qcfg, cfg.vocab_size, cfg.padded_vocab,
            final_softcap=cfg.final_softcap,
            tied_embed=params["embed"] if cfg.tie_embeddings else None)
    logits = logits_constrain(logits)
    aux_sum["act_sdam"] = aux_sum.pop("sdam_sum") / max(cfg.n_layers, 1)
    if collect_cache:
        return logits, (caches, aux_sum)
    return logits, aux_sum


# ===========================================================================
# Decode
# ===========================================================================

def _layer_cache_init(cfg: ArchConfig, qcfg: QuantConfig, bd: BlockDef,
                      batch: int, cache_len: int, cdtype) -> dict:
    c: dict = {}
    if bd.attn in ("global", "local"):
        eff = min(cfg.window, cache_len) if bd.attn == "local" else cache_len
        c["kv"] = attn.init_kv_cache(qcfg, batch, eff, cfg.n_kv_heads,
                                     cfg.head_dim_, cdtype)
    elif bd.attn == "mlstm":
        c["mlstm"] = rec.mlstm_fresh_state(cfg, batch)
    elif bd.attn == "slstm":
        c["slstm"] = rec.slstm_state_init(batch, cfg.n_heads,
                                          cfg.d_model // cfg.n_heads)
    elif bd.attn == "rglru":
        c["rglru"] = rec.rglru_state_init(batch, cfg.lru_width or cfg.d_model,
                                          cfg.conv_kernel)
    if bd.cross_attn:
        hkv, hd = cfg.n_kv_heads, cfg.head_dim_
        z = jnp.zeros((batch, cfg.n_frontend_tokens, hkv, hd), cdtype)
        c["xkv"] = (z, z)
    return c


def init_cache(cfg: ArchConfig, qcfg: QuantConfig, batch: int,
               cache_len: int) -> dict:
    """Fresh decode cache (pre-prefill). Mirrors the params group/tail layout."""
    cdtype = _cdtype(cfg)
    cache: dict = {"groups": (), "tail": ()}
    if cfg.n_groups > 0:
        def one_group(_):
            return tuple(_layer_cache_init(cfg, qcfg, cfg.pattern[i], batch,
                                           cache_len, cdtype)
                         for i in range(cfg.period))
        cache["groups"] = jax.vmap(one_group)(jnp.arange(cfg.n_groups))
    if cfg.n_tail:
        cache["tail"] = tuple(
            _layer_cache_init(cfg, qcfg, cfg.pattern[i], batch, cache_len, cdtype)
            for i in range(cfg.n_tail))
    return cache


def block_decode(p: dict, x: jax.Array, bd: BlockDef, cfg: ArchConfig,
                 qcfg: QuantConfig, cache: dict, pos: jax.Array,
                 frontend_embeds, cdtype, constrain: Constrain):
    """Chunk step against the cache. x: (B,C,d); pos: (B,C) (C=1: decode).

    Returns (x, new_cache). pos entries of -1 mark padding (partial prefill
    chunks / inactive serving slots): their K/V never reach the cache and
    they attend to nothing. Recurrent blocks consume every chunk token
    unconditionally, so padded chunks are only valid for attention blocks
    (the serving engine enforces this).
    """
    new_cache = dict(cache)
    if bd.attn == "mlstm":
        x, st = rec.mlstm_block(p, x, cfg, qcfg, cdtype, state=cache["mlstm"])
        new_cache["mlstm"] = st
    elif bd.attn == "slstm":
        x, st = rec.slstm_block(p, x, cfg, qcfg, cdtype, state=cache["slstm"])
        new_cache["slstm"] = st
    elif bd.attn == "rglru":
        x, st = rec.rglru_block(p["rg"], x, cfg, qcfg, cdtype, state=cache["rglru"])
        new_cache["rglru"] = st
    else:
        xn = apply_norm(p["ln1"], x, cfg.norm)
        q = qlinear(p["wq"], xn, "wq", qcfg, "bsd,dhk->bshk", cdtype)
        k = qlinear(p["wk"], xn, "wk", qcfg, "bsd,dhk->bshk", cdtype)
        v = qlinear(p["wv"], xn, "wv", qcfg, "bsd,dhk->bshk", cdtype)
        if cfg.pos == "rope":
            q = attn.rope_apply(q, pos, cfg.rope_theta)
            k = attn.rope_apply(k, pos, cfg.rope_theta)
        o = attn.attend_chunk(q, k, v, cache["kv"], qcfg,
                              q_per_kv=cfg.q_per_kv, pos=pos,
                              window=cfg.window if bd.attn == "local" else 0,
                              softcap=cfg.attn_softcap)
        new_cache["kv"] = attn.cache_append_chunk(
            cache["kv"], k, v, pos, qcfg, ring=(bd.attn == "local"),
            window=cfg.window)
        out = qlinear(p["wo"], o, "wo", qcfg, "bshk,hkd->bsd", cdtype)
        if cfg.sandwich_norm:
            out = apply_norm(p["ln1_post"], out, cfg.norm)
        x = constrain(x + out)
    if bd.cross_attn:
        x = _cross_sublayer(p, x, cache["xkv"], cfg, qcfg, cdtype, constrain)
    if bd.ffn == "dense":
        x = _ffn_sublayer(p, x, cfg, qcfg, cdtype, constrain)
    elif bd.ffn == "moe":
        xn = apply_norm(p["ln2"], x, cfg.norm)
        y, _ = moe_mod.moe_ffn(p["moe"], xn, cfg, qcfg, cdtype)
        x = constrain(x + y)
    return x, new_cache


def prefill_step(params: dict, cache: dict, batch: dict, cfg: ArchConfig,
                 qcfg: QuantConfig, *, constrain: Constrain = _IDENT,
                 logits_constrain: Constrain = _IDENT):
    """Multi-token step against the cache (chunked prefill / decode).

    batch: tokens (B,C) int32, pos (B,C) int32 [+ frontend_embeds]. pos=-1
    marks padding tokens (see block_decode). Returns (logits (B,C,V),
    new_cache). C=1 with pos (B,1) is exactly the classic decode step;
    C=prompt_len against a fresh cache is a full prefill whose [:, -1]
    logits seed generation.
    """
    cdtype = _cdtype(cfg)
    tokens, pos = batch["tokens"], batch["pos"]
    fe = batch.get("frontend_embeds")
    x = embed_lookup(params["embed"], tokens, qcfg, cdtype)
    x = x * jnp.asarray(cfg.d_model ** 0.5, cdtype)
    if cfg.frontend == "audio_frames" and fe is not None:
        x = x + fe.astype(cdtype)
    if cfg.pos == "learned":
        x = x + jnp.take(params["pos_embed"], jnp.maximum(pos, 0),
                         axis=0).astype(cdtype)
    x = constrain(x)

    new_cache = {"groups": (), "tail": ()}
    if cfg.n_groups > 0:
        def group_fn(x, scanned):
            gp, gc = scanned
            ncs = []
            for i in range(cfg.period):
                x, nc = block_decode(gp[i], x, cfg.pattern[i], cfg, qcfg,
                                     gc[i], pos, fe, cdtype, constrain)
                ncs.append(nc)
            return x, tuple(ncs)
        with jax.named_scope(tracing.LAYER_SCAN):
            x, gcache = jax.lax.scan(group_fn, x,
                                     (params["groups"], cache["groups"]))
        new_cache["groups"] = gcache
    for i in range(cfg.n_tail):
        x, nc = block_decode(params["tail"][i], x, cfg.pattern[i], cfg, qcfg,
                             cache["tail"][i], pos, fe, cdtype, constrain)
        new_cache["tail"] = new_cache["tail"] + (nc,)

    x = apply_norm(params["final_norm"], x, cfg.norm)
    with jax.named_scope(tracing.LM_HEAD):
        logits = lm_head_apply(
            params["lm_head"], x, qcfg, cfg.vocab_size, cfg.padded_vocab,
            final_softcap=cfg.final_softcap,
            tied_embed=params["embed"] if cfg.tie_embeddings else None)
    return logits_constrain(logits), new_cache


def decode_step(params: dict, cache: dict, batch: dict, cfg: ArchConfig,
                qcfg: QuantConfig, *, constrain: Constrain = _IDENT,
                logits_constrain: Constrain = _IDENT):
    """serve_step: one new token per sequence against the cache.

    batch: tokens (B,1) int32, pos (B,) int32 [+ frontend_embeds].
    Returns (logits (B,1,V), new_cache). Thin C=1 wrapper of prefill_step.
    """
    b2 = dict(batch)
    if b2["pos"].ndim == 1:
        b2["pos"] = b2["pos"][:, None]
    return prefill_step(params, cache, b2, cfg, qcfg, constrain=constrain,
                        logits_constrain=logits_constrain)


# ===========================================================================
# Serving slot pool (continuous batching): per-slot cache insert / reset
# ===========================================================================

def cache_slot_insert(pool: dict, row: dict, slot) -> dict:
    """Write batch row 0 of `row` (a batch-1 cache tree) into batch row
    `slot` of `pool`. Both trees come from init_cache (same cfg/qcfg and
    cache length); "groups" leaves carry a leading stacked scan axis, so
    their batch axis is axis 1. `slot` may be a traced int32 — the op jits
    to a per-row dynamic-update-slice.
    """
    def ins_g(p, s):
        return p.at[:, slot].set(s[:, 0].astype(p.dtype))

    def ins_t(p, s):
        return p.at[slot].set(s[0].astype(p.dtype))

    return {"groups": jax.tree.map(ins_g, pool["groups"], row["groups"]),
            "tail": jax.tree.map(ins_t, pool["tail"], row["tail"])}


def cache_slot_reset(pool: dict, template: dict, slot) -> dict:
    """Recycle one slot: restore its cache row to the freshly-initialized
    state (KV pos rows back to -1 — attend_* masks them — and recurrent
    states back to their init values, which are not all zero: sLSTM's m
    starts at -1e9). `template` is a batch-1 init_cache(...) tree kept
    around by the caller; stale K/V codes are left in place, masked by pos.
    """
    return cache_slot_insert(pool, template, slot)


# ===========================================================================
# Quantized-leaf walker (OBR / oscillation / telemetry)
# ===========================================================================

def quant_leaves_named(params: dict, qcfg: QuantConfig):
    """Yield (name, w, w_scale, spec) for every quantized weight (stacked
    scan copies included; deterministic walk order)."""
    out = []

    def walk(node):
        if isinstance(node, dict):
            # SORTED keys == jax pytree canonical order, so the walk order is
            # identical before and after any flatten/unflatten roundtrip
            # (oscillation state tuples zip against this order).
            for name in sorted(node.keys()):
                child = node[name]
                if (isinstance(child, dict) and "w" in child
                        and "w_scale" in child and name in NAME2KIND):
                    spec = weight_spec(qcfg, NAME2KIND[name])
                    if spec is not None:
                        w, sc = child["w"], child["w_scale"]
                        # vmap-stacked per-tensor scales are (G,); pad
                        # trailing singleton dims so they broadcast over the
                        # stacked weight (G, ...).
                        if sc.ndim not in (0, w.ndim):
                            shp = tuple(sc.shape) + (1,) * (w.ndim - sc.ndim)
                            if isinstance(sc, jax.ShapeDtypeStruct):
                                sc = jax.ShapeDtypeStruct(shp, sc.dtype)
                            else:
                                sc = sc.reshape(shp)
                        out.append((name, w, sc, spec))
                else:
                    walk(child)
        elif isinstance(node, (tuple, list)):
            for child in node:
                walk(child)

    walk(params)
    return out


def quant_leaves(params: dict, qcfg: QuantConfig):
    """(w, w_scale, spec) triples — see quant_leaves_named."""
    return [(w, s, spec) for _, w, s, spec in quant_leaves_named(params, qcfg)]
