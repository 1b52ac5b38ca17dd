"""The work an algorithm requires, counted from shapes.

Everything here counts what the mathematics needs, not what an
implementation happens to do: no padding (the real vocabulary, the valid KV
positions), no recomputation (a rematerialised forward counts once), and
bytes at the widths the data is stored in. So the count is the same
whatever implements the kernel, and a share of a roofline or of a peak
built on it cannot pass 100% unless the time leaves out part of the work.

Shapes come from a dense decoder configuration file (HF key names).
"""
from __future__ import annotations

BF16, F32, INT8 = 2, 4, 1


def dims(c: dict) -> dict:
    d = c["hidden_size"]
    h = c["num_attention_heads"]
    hd = c.get("head_dim") or d // h
    return {"L": c["num_hidden_layers"], "d": d, "h": h,
            "kv": c["num_key_value_heads"], "hd": hd,
            "f": c["intermediate_size"], "V": c["vocab_size"]}


def linears(c: dict) -> list:
    """(name, K, N) of every matmul a token goes through, per layer, and
    the output head once (name 'lm_head')."""
    m = dims(c)
    d, qd, kvd, f = m["d"], m["h"] * m["hd"], m["kv"] * m["hd"], m["f"]
    per_layer = [("wq", d, qd), ("wk", d, kvd), ("wv", d, kvd),
                 ("wo", qd, d), ("w_gate", d, f), ("w_in", d, f),
                 ("w_out", f, d)]
    return [(n, k, nn) for _ in range(m["L"]) for n, k, nn in per_layer] + [
        ("lm_head", d, m["V"])]


def matmul_params(c: dict) -> int:
    return sum(k * n for _, k, n in linears(c))


def forward_flops_per_token(c: dict, ctx: float) -> float:
    """Model flops of one token's forward pass that attends to `ctx`
    positions (itself included): 2 per matmul weight, plus QK^T and PV."""
    m = dims(c)
    attn = 2 * 2 * m["L"] * m["h"] * m["hd"] * ctx
    return 2.0 * matmul_params(c) + attn


def head_flops(c: dict) -> float:
    m = dims(c)
    return 2.0 * m["d"] * m["V"]


def served_flops(c: dict, log: list) -> float:
    """Model flops of a log of executor calls: ("decode", pos per slot, -1
    idle) and ("prefill", start, n). A decoded token pays the whole forward;
    a prompt token pays it without the output head (only the prompt's last
    token needs logits, which this leaves out: the count errs low)."""
    total = 0.0
    for call in log:
        if call[0] == "decode":
            total += sum(forward_flops_per_token(c, p + 1)
                         for p in call[1] if p >= 0)
        else:
            _, start, n = call
            total += sum(forward_flops_per_token(c, start + j + 1)
                         - head_flops(c) for j in range(n))
    return total


def train_flops_per_step(c: dict, batch: int, seq: int) -> float:
    """Forward + backward (x3) model flops of one training step over causal
    sequences: a token at position i attends to i + 1 positions."""
    mean_ctx = (seq + 1) / 2.0
    return 3.0 * batch * seq * forward_flops_per_token(c, mean_ctx)


def qat_matmul_calls(c: dict, tokens: int) -> list:
    """(flops, bytes) of each fused QAT matmul of one training step over
    `tokens` rows, forward and backward together: forward 2MKN, backward
    dX and dW 4MKN. Bytes: activations and cotangents bf16, latent weights
    and their gradient f32; forward reads x, w and writes y; backward reads
    dy, x, w and writes dx, dw."""
    calls = []
    mm = float(tokens)
    for _, k, n in linears(c):
        flops = 6.0 * mm * k * n
        fwd = BF16 * mm * k + F32 * k * n + BF16 * mm * n
        bwd = BF16 * mm * n + BF16 * mm * k + F32 * k * n \
            + BF16 * mm * k + F32 * k * n
        calls.append((flops, fwd + bwd))
    return calls


def decode_attention_work(c: dict, q_tokens: int, cached: int,
                          kv_bytes: float = INT8) -> tuple[float, float]:
    """(flops, bytes) of one layer's attention of `q_tokens` queries over
    `cached` valid cache positions (one slot). KV codes at `kv_bytes` each
    with an f32 scale per (position, kv head) for K and for V; queries bf16
    in, f32 accumulator out."""
    m = dims(c)
    flops = 2.0 * 2.0 * m["h"] * m["hd"] * q_tokens * cached
    kv = cached * m["kv"] * (2 * m["hd"] * kv_bytes + 2 * F32)
    io = q_tokens * m["h"] * m["hd"] * (BF16 + F32)
    return flops, kv + io


def decode_attention_calls(c: dict, log: list) -> list:
    """(flops, bytes) of every attention kernel call (one per layer) in a
    log of executor calls; a decoded token attends to the `pos` positions
    cached before it, a prompt chunk to the `start` cached before it (the
    chunk's own keys are merged outside the kernel)."""
    L = dims(c)["L"]
    calls = []
    for call in log:
        if call[0] == "decode":
            fl = by = 0.0
            for p in call[1]:
                if p >= 0:
                    f, b = decode_attention_work(c, 1, int(p))
                    fl, by = fl + f, by + b
        else:
            fl, by = decode_attention_work(c, call[2], call[1])
        calls.extend([(fl, by)] * L)
    return calls


def roofline_seconds(flops: float, byts: float, peaks: dict) -> tuple:
    """Least time the chip could take, and which peak bounds it."""
    t_c = flops / peaks["bf16_flops"]
    t_m = byts / peaks["hbm_bytes_s"]
    return (t_c, "compute") if t_c >= t_m else (t_m, "bandwidth")
