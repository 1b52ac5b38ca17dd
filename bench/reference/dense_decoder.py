"""Plain reference of a dense decoder of the Llama family (Qwen1.5, Granite).

Written from the published description, in straightforward jax.numpy, and
independent of the program under test: pre-norm blocks of RMSNorm, grouped-
query causal attention with rotary positions (rotate-half form), and a
SiLU-gated feed-forward; an optional bias on q/k/v and an optional output
head tied to the embedding. Two departures follow the program, and each
configuration lists them under `assumed`: the embedding rows are scaled by
sqrt(hidden_size) (Gemma's convention, not Qwen1.5's or Granite's), and
RMSNorm's epsilon is 1e-6.

Quantization follows the configuration file:
  * QAT (training): LSQ+ fake quantization (Quantization Variation, Eq. 5-7)
    of every linear's weights (4-bit signed; per-head scales on the q/k/v/o
    projections, per-tensor elsewhere; the embedding and the tied head 8-bit)
    and inputs (4-bit unsigned with a learned offset; 8-bit into the head),
    with the paper's module-wise scale-gradient factor
    g = 1 / sqrt(Q_P * ||w||_1) per scale group. Rounding is
    straight-through. The loss is the sparse top-K distillation loss (Eq. 9),
    the optimizer AdamW with global-norm clipping.
  * Serving: weights given as integer codes and scales, inputs unquantized,
    and keys and values rounded per (token, kv head) to int8 with a
    symmetric scale amax / 127, as an int8 KV cache stores them.

`prec` selects the arithmetic: "f32", every product at the highest
precision (the reference); or "fp8" (one precision below the bfloat16
that the configurations compute in: the serving control), where every
value that the configuration holds in bfloat16 (the residual stream,
norm outputs, the operands and results of every product, attention
probabilities) is rounded to float8_e4m3. Rounding passes the gradient
through unrounded. In training, `master` "bf16" is the control of the
float32 master weights that the configuration states: the parameters
held in bfloat16.

Parameters come in the benchmark's flat layout: "embed.w", "embed.w_scale",
"final_norm.g", "lm_head.a_scale", ..., and "layers.<linear>.<leaf>" stacked
over layers on a leading axis.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

F32 = jnp.float32
EPS_SCALE = 1e-9
NORM_EPS = 1e-6


def _fp8(x):
    """x rounded to float8_e4m3; the gradient passes through unrounded (a
    cotangent rounded to e4m3 would flush to zero: fp8 training scales it
    first)."""
    x = x.astype(F32)
    return x + jax.lax.stop_gradient(
        x.astype(jnp.float8_e4m3fn).astype(F32) - x)


def _lo(x, prec: str):
    """A value the configuration holds in bfloat16, at the control's
    precision: rounded to e4m3 for "fp8", exact for "f32"."""
    return _fp8(x) if prec == "fp8" else x


def _mm(eq: str, a, b, prec: str, f32_out: bool = False):
    y = jnp.einsum(eq, _lo(a, prec).astype(F32), _lo(b, prec).astype(F32),
                   precision=jax.lax.Precision.HIGHEST)
    return y if f32_out else _lo(y, prec)


def _rms(x, g):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + NORM_EPS) * g


def _rope(x, pos, theta):
    """x (B, S, heads, hd); rotate-half rotary embedding."""
    half = x.shape[-1] // 2
    freq = theta ** (-jnp.arange(half, dtype=F32) / half)
    ang = pos[..., None].astype(F32) * freq
    cos, sin = jnp.cos(ang)[..., None, :], jnp.sin(ang)[..., None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _attend(q, k, v, kv_heads, lo=lambda t: t):
    """Causal grouped-query attention; q (B,S,H,hd), k/v (B,S,KV,hd); `lo`
    rounds the probabilities as the configuration stores them."""
    b, s, h, hd = q.shape
    g = h // kv_heads
    q5 = q.reshape(b, s, kv_heads, g, hd)
    sc = jnp.einsum("bqhgd,bthd->bhgqt", q5, k,
                    precision=jax.lax.Precision.HIGHEST) / jnp.sqrt(F32(hd))
    causal = jnp.tril(jnp.ones((s, s), bool))
    sc = jnp.where(causal, sc, -jnp.inf)
    p = lo(jax.nn.softmax(sc, axis=-1))
    o = jnp.einsum("bhgqt,bthd->bqhgd", p, v,
                   precision=jax.lax.Precision.HIGHEST)
    return o.reshape(b, s, h, hd)


# --------------------------------------------------------------- QAT pieces

def _levels(bits: int, signed: bool):
    return (2 ** (bits - 1), 2 ** (bits - 1) - 1) if signed else (0, 2 ** bits - 1)


def _grad_scaled(x, g):
    """Value of x; gradient multiplied by g."""
    g = jax.lax.stop_gradient(g)
    return x * g + jax.lax.stop_gradient(x - x * g)


def _l1_factor(w, scale_shape, qp):
    """g = 1 / sqrt(Q_P * ||w||_1) over each scale group of w."""
    w = jax.lax.stop_gradient(w)
    if len(scale_shape) == 0:
        l1 = jnp.sum(jnp.abs(w))
    else:
        axes = tuple(i for i, n in enumerate(scale_shape) if n == 1)
        l1 = jnp.sum(jnp.abs(w), axis=axes, keepdims=True)
    return 1.0 / jnp.sqrt(qp * jnp.maximum(l1, EPS_SCALE))


def fake_quant(x, scale, bits: int, signed: bool, g, offset=None):
    """LSQ+ quantize-dequantize with straight-through rounding."""
    qn, qp = _levels(bits, signed)
    s = jnp.maximum(_grad_scaled(scale, g), EPS_SCALE)
    b = _grad_scaled(offset, g) if offset is not None else 0.0
    xs = jnp.clip((x - b) / s, -qn, qp)
    xr = xs + jax.lax.stop_gradient(jnp.round(xs) - xs)
    return xr * s + b


def _qat_linear(p: dict, name: str, x, eq: str, q: dict, prec: str):
    w = p[f"{name}.w"]
    ws = p[f"{name}.w_scale"]
    wq = fake_quant(w, ws, q["w_bits"], True,
                    _l1_factor(w, ws.shape, _levels(q["w_bits"], True)[1]))
    qa = _levels(q["a_bits"], False)[1]
    xq = fake_quant(x, p[f"{name}.a_scale"], q["a_bits"], False,
                    _l1_factor(w, (), qa), offset=p[f"{name}.a_offset"])
    y = _mm(eq, xq, wq, prec)
    if f"{name}.b" in p:
        y = y + p[f"{name}.b"]
    return y


def _block(x, p, pos, c, lin, prec):
    kv = c["num_key_value_heads"]
    lo = lambda t: _lo(t, prec)
    h = lo(_rms(x, p["ln1.g"]))
    qh = lo(_rope(lin(p, "wq", h, "bsd,dhk->bshk"), pos, c["rope_theta"]))
    kh = lin(p, "wk", h, "bsd,dhk->bshk")
    vh = lin(p, "wv", h, "bsd,dhk->bshk")
    kh = lo(_rope(kh, pos, c["rope_theta"]))
    kh, vh = c["_kv_round"](kh), c["_kv_round"](vh)
    x = lo(x + lin(p, "wo", lo(_attend(qh, kh, vh, kv, lo)),
                   "bshk,hkd->bsd"))
    h = lo(_rms(x, p["ln2.g"]))
    m = lo(jax.nn.silu(lin(p, "w_gate", h, "bsd,df->bsf"))
           * lin(p, "w_in", h, "bsd,df->bsf"))
    return lo(x + lin(p, "w_out", m, "bsf,fd->bsd"))


def _layers(params: dict) -> dict:
    return {k[len("layers."):]: v for k, v in params.items()
            if k.startswith("layers.")}


def qat_loss(params: dict, batch: dict, c: dict, prec: str):
    """Sparse top-K distillation loss of the fake-quantized model."""
    q = c["quant"]
    cc = dict(c, _kv_round=lambda t: t)
    lin = lambda p, n, x, eq: _qat_linear(p, n, x, eq, q, prec)
    ew, es = params["embed.w"], params["embed.w_scale"]
    eb = q["edge_bits"]
    embed_q = fake_quant(ew, es, eb, True,
                         _l1_factor(ew, es.shape, _levels(eb, True)[1]))
    tokens = batch["tokens"]
    x = _lo(embed_q[tokens] * jnp.sqrt(F32(c["hidden_size"])), prec)
    pos = jnp.broadcast_to(jnp.arange(tokens.shape[1]), tokens.shape)
    body = jax.checkpoint(lambda x, p: (_block(x, p, pos, cc, lin, prec),
                                        None))
    x, _ = jax.lax.scan(body, x, _layers(params))
    x = _lo(_rms(x, params["final_norm.g"]), prec)
    xq = fake_quant(x, params["lm_head.a_scale"], eb, False,
                    _l1_factor(ew, (), _levels(eb, False)[1]),
                    offset=params["lm_head.a_offset"])
    head = embed_q[:c["vocab_size"]]

    @jax.checkpoint
    def row_loss(args):   # one row at a time: its logits are (S, V)
        xr, idx, p = args
        logits = _mm("sd,vd->sv", xr, head, prec, f32_out=True)
        logp = jax.nn.log_softmax(logits, axis=-1)
        picked = jnp.take_along_axis(logp, idx, axis=-1)
        return -jnp.mean(jnp.sum(p * picked, axis=-1))

    return jnp.mean(jax.lax.map(row_loss, (xq, batch["kd_idx"],
                                           batch["kd_p"])))


def _lr(step, t: dict):
    warm = t["lr_peak"] * step / max(t["warmup_steps"], 1)
    frac = jnp.clip((step - t["warmup_steps"])
                    / max(t["total_steps"] - t["warmup_steps"], 1), 0.0, 1.0)
    cos = t["min_lr"] + 0.5 * (t["lr_peak"] - t["min_lr"]) \
        * (1.0 + jnp.cos(jnp.pi * frac))
    return jnp.where(step < t["warmup_steps"], warm, cos)


def _bf16(x):
    return x.astype(jnp.bfloat16).astype(F32)


def adamw_step(params, grads, mu, nu, step, t: dict, master: str = "f32"):
    """One AdamW update with global-norm clipping; weights (".w") decay,
    quantizer scales are kept at or above 1e-6. With `master` "bf16" the
    updated parameters are held in bfloat16. Also returns the norm of each
    gradient leaf as the update used it (after clipping)."""
    gn = jnp.sqrt(sum(jnp.sum(g * g) for g in grads.values()))
    clip = jnp.minimum(1.0, t["clip_norm"] / jnp.maximum(gn, 1e-9))
    step = jnp.asarray(step, F32)
    lr = _lr(step, t)
    b1, b2 = t["b1"], t["b2"]
    new_p, new_m, new_v, used = {}, {}, {}, {}
    for k, p in params.items():
        g = grads[k] * clip
        used[k] = jnp.sqrt(jnp.sum(g * g))
        m = b1 * mu[k] + (1 - b1) * g
        v = b2 * nu[k] + (1 - b2) * g * g
        mh = m / (1 - b1 ** (step + 1))
        vh = v / (1 - b2 ** (step + 1))
        decay = t["weight_decay"] if k.endswith(".w") else 0.0
        p = p - lr * (mh / (jnp.sqrt(vh) + t["eps"]) + decay * p)
        if k.endswith(".w_scale") or k.endswith(".a_scale"):
            p = jnp.maximum(p, t["scale_floor"])
        new_p[k] = _bf16(p) if master == "bf16" else p
        new_m[k], new_v[k] = m, v
    return new_p, new_m, new_v, used


def train_readings(make_params, batches: list, c: dict, prec: str = "f32",
                   master: str = "f32") -> dict:
    """Follow the first len(batches) training steps from the parameters
    `make_params()` returns (called twice: at the start, and at the end to
    measure the change, so that only one copy is held while stepping).
    `master` "bf16" holds the parameters in bfloat16 (the control of a
    float32 master copy).

    The first step's gradient is taken one row at a time, and the step uses
    their mean (the loss is the mean over rows). Returns, with arrays on
    the host:
      losses  the loss of each step
      grad    per leaf, the norm of the first gradient as the update used
              it (after clipping)
      change  per leaf, the norm of the parameters' change over the steps
      first   the first gradient as the update used it
      rows    the first gradient of each row alone, before clipping"""
    t = c["train"]
    grad_fn = jax.jit(jax.value_and_grad(
        lambda p, b: qat_loss(p, b, c, prec)))
    clip_of = jax.jit(lambda g: jnp.minimum(1.0, t["clip_norm"] / jnp.maximum(
        jnp.sqrt(sum(jnp.sum(x * x) for x in g.values())), 1e-9)))
    add = jax.jit(lambda a, b: jax.tree.map(jnp.add, a, b),
                  donate_argnums=0)
    step_fn = jax.jit(lambda p, g, m, v, i: adamw_step(p, g, m, v, i, t,
                                                       master),
                      donate_argnums=(0, 2, 3))
    p = {k: v.astype(F32) for k, v in make_params().items()}
    if master == "bf16":
        p = {k: _bf16(v) for k, v in p.items()}
    mu = {k: jnp.zeros_like(v) for k, v in p.items()}
    nu = {k: jnp.zeros_like(v) for k, v in p.items()}
    out = {"losses": [], "rows": []}
    for i, b in enumerate(batches):
        if i == 0:
            n = b["tokens"].shape[0]
            loss, g = 0.0, None
            for r in range(n):
                lr, gr = grad_fn(p, {k: v[r:r + 1] for k, v in b.items()})
                out["rows"].append({k: np.asarray(v, np.float32)
                                    for k, v in gr.items()})
                loss = loss + float(lr) / n
                g = gr if g is None else add(g, gr)
            g = jax.jit(lambda g: jax.tree.map(lambda x: x / n, g),
                        donate_argnums=0)(g)
            k = np.float32(clip_of(g))
            out["first"] = {name: np.asarray(v, np.float32) * k
                            for name, v in g.items()}
        else:
            loss, g = grad_fn(p, b)
        p, mu, nu, used = step_fn(p, g, mu, nu, jnp.int32(i))
        del g
        out["losses"].append(float(loss))
        if i == 0:
            out["grad"] = {k: float(v) for k, v in used.items()}
    del mu, nu
    p0 = make_params()
    out["change"] = {k: float(jnp.sqrt(jnp.sum(jnp.square(
        p[k] - p0[k].astype(F32))))) for k in p}
    return out


# ----------------------------------------------------------- serving pieces

def kv_int8(t):
    """Symmetric int8 round trip per (token, kv head)."""
    s = jnp.maximum(jnp.max(jnp.abs(t), -1, keepdims=True) / 127.0, 1e-9)
    return jnp.clip(jnp.round(t / s), -128, 127) * s


def _serve_linear(p, name, x, eq, prec):
    y = _mm(eq, x, p[f"{name}.w"], prec)
    if f"{name}.b" in p:
        y = y + p[f"{name}.b"]
    return y


def make_serve_block(c: dict, prec: str):
    """jit(x, layer_params, pos) -> x: one block of the served model, with
    dequantized float32 weights."""
    cc = dict(c, _kv_round=kv_int8 if c["serve"]["kv_bits"] == 8 else
              (lambda t: t))
    lin = lambda p, n, x, eq: _serve_linear(p, n, x, eq, prec)
    return jax.jit(lambda x, p, pos: _block(x, p, pos, cc, lin, prec))


def make_serve_head(c: dict, prec: str):
    """jit(x, final_g, head_w (d, V)) -> logits."""
    return jax.jit(lambda x, g, w: _mm("bsd,dv->bsv", _lo(_rms(x, g), prec),
                                       w, prec, f32_out=True))


def serve_embed(codes, scale, tokens, c: dict, prec: str):
    """Rows of the dequantized embedding, scaled by sqrt(hidden)."""
    x = codes.astype(F32)[tokens] * scale * jnp.sqrt(F32(c["hidden_size"]))
    return _lo(x, prec)
