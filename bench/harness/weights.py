"""Weights made from the run seed, in the benchmark's flat layout.

The program under test and the plain reference are both given these
values; neither makes its own. Names: "embed.w", "final_norm.g",
"lm_head.<leaf>", and "layers.<linear>.<leaf>" with a leading layer axis.

QAT: latent float32 weights with the spread of a standard initialisation
(1/sqrt(fan_in); 0.02 for the embedding), LSQ scales from them
(2 mean|w| / sqrt(Q_P) per scale group), input quantizers calibrated to
[-3, 3] (offset -3, scale 6 / Q_P), small random q/k/v biases and norm gains
near 1.

Serving: integer codes of a normal weight (LSQ-like: codes of standard
deviation ~1.66 at 4 bits, ~20 at 8 bits, clipped to the code range) and
scales that give the dequantized weights a standard deviation of 0.02; per-
head scales vary by up to 25% between heads. One layer is made at a time
(`serve_layer`), so neither side ever holds the whole model unpacked.
"""
from __future__ import annotations

import zlib

import numpy as np
import jax
import jax.numpy as jnp

LINEARS = ("wq", "wk", "wv", "wo", "w_gate", "w_in", "w_out")


def seed_key(seed: int):
    """A JAX key from any whole-number seed (PRNGKey alone keeps only the
    low 32 bits)."""
    hi, lo = np.random.SeedSequence(int(seed)).generate_state(2)
    return jax.random.fold_in(jax.random.PRNGKey(int(lo)), int(hi))


def _name_key(key, name: str):
    return jax.random.fold_in(key, zlib.crc32(name.encode()))


def _fan_in(name: str, shape: tuple) -> int:
    """Contracted size of a stacked linear weight (layer axis first)."""
    lin = name.split(".")[-2]
    if lin in ("wq", "wk", "wv"):    # (L, d, heads, hd)
        return int(shape[-3])
    if lin == "wo":                  # (L, heads, hd, d)
        return int(shape[-3] * shape[-2])
    return int(shape[-2])            # (L, d_in, d_out)


def _lsq_scale(w, scale_shape, qp: int):
    if len(scale_shape) == 0:
        m = jnp.mean(jnp.abs(w))
    elif len(scale_shape) == w.ndim:
        axes = tuple(i for i, n in enumerate(scale_shape) if n == 1)
        m = jnp.mean(jnp.abs(w), axis=axes, keepdims=True)
    else:  # one per-tensor scale per layer, stacked: (L,)
        m = jnp.mean(jnp.abs(w), axis=tuple(range(1, w.ndim)))
    return jnp.maximum(2.0 * m / jnp.sqrt(float(qp)), 1e-9).reshape(scale_shape)


def qat_params(shapes: dict, q: dict, key) -> dict:
    """Fill every leaf of `shapes` ({name: shape}) from `key`. Run under jit:
    the whole tree comes from one call."""
    out = {}
    for name in sorted(shapes):
        if name.endswith(".w"):
            shape = shapes[name]
            std = 0.02 if name == "embed.w" else _fan_in(name, shape) ** -0.5
            out[name] = jax.random.normal(_name_key(key, name), shape) * std
    for name in sorted(shapes):
        shape, k = shapes[name], _name_key(key, name)
        leaf = name.split(".")[-1]
        edge = name.startswith("embed.") or name.startswith("lm_head.")
        if leaf == "w":
            continue
        if leaf == "w_scale":
            bits = q["edge_bits"] if edge else q["w_bits"]
            w = out[name[:-len("w_scale")] + "w"]
            out[name] = _lsq_scale(w, shape, 2 ** (bits - 1) - 1)
        elif leaf == "a_scale":
            bits = q["edge_bits"] if edge else q["a_bits"]
            out[name] = jnp.full(shape, 6.0 / (2 ** bits - 1), jnp.float32)
        elif leaf == "a_offset":
            out[name] = jnp.full(shape, -3.0, jnp.float32)
        elif leaf == "b":
            out[name] = jax.random.normal(k, shape) * 0.02
        elif leaf == "g":
            out[name] = 1.0 + 0.1 * jax.random.normal(k, shape)
        else:
            raise KeyError(f"no rule to make {name}")
    return out


# ------------------------------------------------------------------ serving

def serve_shapes(c: dict) -> dict:
    """Unpacked code shapes and scale shapes of one layer."""
    d, h, kv = c["hidden_size"], c["num_attention_heads"], c["num_key_value_heads"]
    hd = c.get("head_dim") or d // h
    f = c["intermediate_size"]
    return {"wq": ((d, h, hd), (1, h, 1)), "wk": ((d, kv, hd), (1, kv, 1)),
            "wv": ((d, kv, hd), (1, kv, 1)), "wo": ((h, hd, d), (h, 1, 1)),
            "w_gate": ((d, f), ()), "w_in": ((d, f), ()), "w_out": ((f, d), ())}


def _codes(key, shape, bits: int):
    std = 1.66 if bits <= 4 else 20.0
    qn, qp = 2 ** (bits - 1), 2 ** (bits - 1) - 1
    z = jax.random.normal(key, shape) * std
    return jnp.clip(jnp.round(z), -qn, qp).astype(jnp.int8), 0.02 / std


def serve_layer(key, layer, c: dict) -> dict:
    """Codes (int8, unpacked), scales (f32) and norm gains of one layer;
    `layer` may be traced."""
    bits = c["quant"]["w_bits"]
    lk = jax.random.fold_in(key, layer)
    out = {}
    for name, (shape, sshape) in serve_shapes(c).items():
        codes, base = _codes(_name_key(lk, name + ".codes"), shape, bits)
        jitter = jax.random.uniform(_name_key(lk, name + ".scale"), sshape,
                                    minval=-0.25, maxval=0.25)
        out[f"{name}.codes"] = codes
        out[f"{name}.w_scale"] = (base * (1.0 + jitter)).astype(jnp.float32)
    for g in ("ln1.g", "ln2.g"):
        out[g] = (1.0 + 0.1 * jax.random.normal(
            _name_key(lk, g), (c["hidden_size"],))).astype(jnp.bfloat16)
    return out


def serve_edges(key, c: dict) -> dict:
    """Embedding and output head (8-bit codes, per-tensor scales) and the
    final norm gain."""
    bits = c["quant"]["edge_bits"]
    d, v = c["hidden_size"], c["vocab_size"]
    ek = jax.random.fold_in(key, 1 << 20)
    emb, es = _codes(_name_key(ek, "embed"), (v, d), bits)
    head, hs = _codes(_name_key(ek, "lm_head"), (d, v), bits)
    return {"embed.codes": emb, "embed.w_scale": jnp.float32(es),
            "lm_head.codes": head, "lm_head.w_scale": jnp.float32(hs),
            "final_norm.g": (1.0 + 0.1 * jax.random.normal(
                _name_key(ek, "final_norm"), (d,))).astype(jnp.bfloat16)}
