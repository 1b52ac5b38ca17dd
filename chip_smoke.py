#!/usr/bin/env python3
"""Bring-up check: the QAT trainer and the serving engine on a TPU.

    python3 chip_smoke.py             # one chip: serving, then training
    python3 chip_smoke.py --chips 4   # four chips: sharded training only

Both phases run qwen1.5-0.5b at its published width (24 layers, d_model
1024, vocab 151936) with random weights from a seed, through the entry
points a user calls: `launch.serve.run_serving` (ServeEngine over
ModelExecutor) and `launch.train.run_training`.

One chip:
  * serving: w4a4 weights (nibble-packed int4 codes through int4_matmul),
    int8 KV through the flash-decode kernel; 8 requests of a few hundred
    prompt tokens, 32 new tokens each, 4 slots, max_len 2048. Every request
    must finish by length with no sentinel fault, and request 0's prefill
    logits on the fused path must match the jnp path (fused off).
  * training: w4a4 MDQ, mckd KD, sentinel on, a few steps at 4 x 512
    tokens. The loss must be finite, no update skipped and no rollback, and
    the first-step loss must match the fused-off step.
  * both compiled steps must contain Pallas kernels (`tpu_custom_call`).

Four chips: the same QAT steps on a (data=2, model=2) mesh against the
one-chip run (losses within 5%), and the train state must be spread over
all four devices.

Lines before the last are information; times include compilation. The last
line is one JSON object naming the device. Any failed check exits non-zero.
The script refuses to run without a TPU and never falls back to the CPU.
"""
from __future__ import annotations

import argparse
import gc
import json
import os
import sys
import tempfile
import time

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(HERE, "src"))

ARCH = "qwen1.5-0.5b"
N_REQUESTS, PROMPT_LEN_RANGE, NEW_TOKENS = 8, (192, 384), 32
SLOTS, MAX_LEN, CHUNK = 4, 2048, 64
TRAIN_BATCH, TRAIN_SEQ, TRAIN_STEPS = 4, 512, 3
# fused vs jnp paths: the same int codes / weights, different accumulation
# order and bf16 rounding points
# bf16 activations round at ~4e-3 relative per op; through 24 layers the
# two paths' roundings drift apart by a few 1e-2 at most
LOGIT_TOL = 5e-2    # ||fused - jnp|| / ||jnp|| over the real vocab
LOSS_TOL = 1e-2     # relative first-step loss difference
SHARDED_TOL = 0.05  # sharded vs one-chip loss, per step (relative)


def die(msg: str) -> None:
    print(f"chip_smoke: {msg}", file=sys.stderr, flush=True)
    sys.exit(1)


def info(msg: str) -> None:
    print(msg, flush=True)


def kernel_count(compiled) -> int:
    return compiled.as_text().count("tpu_custom_call")


def serve_phase(cfg, device, *, n_requests=N_REQUESTS,
                prompt_len_range=PROMPT_LEN_RANGE, new_tokens=NEW_TOKENS,
                slots=SLOTS, max_len=MAX_LEN, chunk=CHUNK) -> list:
    """Serve through the engine on one device; returns failed checks."""
    import numpy as np
    import jax.numpy as jnp

    from repro.core.policy import get_preset
    from repro.launch.serve import run_serving
    from repro.serve import ModelExecutor

    fails = []
    qcfg = get_preset("w4a4").replace(kv_cache_bits=8, a_bits=32)
    rng = np.random.default_rng(0)
    prompts = [rng.integers(0, cfg.vocab_size, size=int(n), dtype=np.int32)
               for n in rng.integers(*prompt_len_range, size=n_requests)]
    t0 = time.monotonic()
    engine, _, summary = run_serving(
        cfg, qcfg, prompts, new_tokens=new_tokens, n_slots=slots,
        max_len=max_len, chunk=chunk, devices=[device])
    info(f"[info] serve: {n_requests} requests, prompts "
         f"{min(map(len, prompts))}-{max(map(len, prompts))} tokens, "
         f"{new_tokens} new, {slots} slots, max_len {max_len}, chunk {chunk}: "
         f"{time.monotonic() - t0:.1f}s incl. compile; decode "
         f"{summary['throughput']['decode_tok_s']:.1f} tok/s, prefill "
         f"{summary['throughput']['prefill_tok_s']:.1f} tok/s")
    reasons = {rid: r.finish_reason for rid, r in engine.results.items()}
    info(f"serve: finish reasons {sorted(set(reasons.values()))} over "
         f"{len(reasons)} requests; faults {summary['faults']}")
    if len(reasons) != n_requests or set(reasons.values()) != {"length"}:
        fails.append(f"serve: not every request finished by length: {reasons}")
    if any(len(r.tokens) != new_tokens for r in engine.results.values()):
        fails.append("serve: a request emitted the wrong number of tokens")
    if any(summary["faults"].values()):
        fails.append(f"serve: sentinel faults {summary['faults']}")

    ex = engine.executor
    for name, step, cache, tok_shape, pos_shape in (
            ("decode", ex._decode, ex.pool, (slots, 1), (slots,)),
            ("prefill", ex._prefill, ex.scratch, (1, chunk), (1, chunk))):
        n = kernel_count(step.lower(
            ex.params, cache, jnp.zeros(tok_shape, jnp.int32),
            jnp.full(pos_shape, -1, jnp.int32)).compile())
        info(f"serve: {name} step tpu_custom_call count {n}")
        if n == 0:
            fails.append(f"serve: no Pallas kernel in the compiled {name} step")

    # request 0's prefill logits: fused path vs the plain jnp path
    off = qcfg.replace(fused_matmul="off", fused_attention="off")
    logits = []
    for ex_q in (ex, ModelExecutor(ex.params, cfg, off, n_slots=1,
                                   max_len=max_len, chunk=chunk)):
        ex_q.scratch_reset()
        for c0 in range(0, len(prompts[0]), chunk):
            last = ex_q.prefill_chunk(prompts[0][c0:c0 + chunk], c0)
        # padded vocab columns hold the -1e9 mask: compare real ones only
        logits.append(np.asarray(last[:cfg.vocab_size], np.float64))
    fused, ref = logits
    err = float(np.linalg.norm(fused - ref) / np.linalg.norm(ref))
    info(f"serve: request 0 prefill logits, fused vs jnp: "
         f"||diff||/||ref|| = {err:.3e} (tolerance {LOGIT_TOL:g}); "
         f"max|diff| {np.max(np.abs(fused - ref)):.4g}, max|ref| "
         f"{np.max(np.abs(ref)):.4g}; argmax {int(fused.argmax())} vs "
         f"{int(ref.argmax())}")
    if not (np.all(np.isfinite(fused)) and err <= LOGIT_TOL):
        fails.append(f"serve: fused prefill logits off by {err:.3e}")
    return fails


def _train(cfg, qcfg, *, devices, steps, batch, seq, model_parallel=1,
           on_step=None):
    from repro.data.synthetic import DataConfig
    from repro.launch.train import run_training
    from repro.optim.adamw import AdamWConfig
    from repro.train.sentinel import SentinelConfig
    from repro.train.state import TrainConfig

    tcfg = TrainConfig(total_steps=steps, warmup_steps=2, kd="mckd",
                       kd_topk=16, adamw=AdamWConfig(lr_peak=3e-3),
                       sentinel=SentinelConfig())
    with tempfile.TemporaryDirectory() as ckpt_dir:
        t0 = time.monotonic()
        report = run_training(cfg, qcfg, tcfg, DataConfig(seed=0),
                              steps=steps, batch_size=batch, seq_len=seq,
                              ckpt_dir=ckpt_dir, save_every=10 ** 9,
                              log_every=0, model_parallel=model_parallel,
                              on_step=on_step, devices=devices)
    return report, time.monotonic() - t0


def _batch_like(cfg, batch, seq):
    """The step's batch, as run_training builds it for step 0."""
    from repro.data.mckd_store import synthetic_kd_labels
    from repro.data.synthetic import DataConfig, sample_batch
    b = sample_batch(cfg, DataConfig(seed=0), 0, batch, seq)
    b["kd_idx"], b["kd_p"] = synthetic_kd_labels(b["labels"], cfg.vocab_size,
                                                 16, seed=0)
    return b


def _shapes_of(tree):
    import jax
    return jax.tree.map(lambda a: jax.ShapeDtypeStruct(
        a.shape, a.dtype, sharding=a.sharding), tree)


def _check_report(tag, report, fails):
    import numpy as np
    losses = report.losses
    info(f"{tag}: losses {losses}; skipped {report.skipped}, "
         f"rollbacks {report.rollbacks}")
    if not (losses and np.all(np.isfinite(losses))):
        fails.append(f"{tag}: non-finite loss {losses}")
    if report.skipped or report.rollbacks:
        fails.append(f"{tag}: sentinel skipped {report.skipped} updates, "
                     f"rolled back {report.rollbacks} times")


def train_phase(cfg, device, *, batch=TRAIN_BATCH, seq=TRAIN_SEQ,
                steps=TRAIN_STEPS) -> list:
    """QAT steps on one device; returns failed checks."""
    from repro.core.policy import get_preset

    fails = []
    qcfg = get_preset("w4a4")
    seen = {}

    def grab_state(i, state):
        if i == 0:
            seen["state"] = _shapes_of(state)

    report, dt = _train(cfg, qcfg, devices=[device], steps=steps,
                        batch=batch, seq=seq, on_step=grab_state)
    peak = (device.memory_stats() or {}).get("peak_bytes_in_use")
    info(f"[info] train: batch {batch} x seq {seq}, {steps} steps: "
         f"{dt:.1f}s incl. compile; peak_bytes_in_use {peak}")
    _check_report("train", report, fails)
    step = report.step_fn.lower(seen.pop("state"),
                                _batch_like(cfg, batch, seq)).compile()
    n_step = kernel_count(step)
    info(f"train: train step tpu_custom_call count {n_step}")
    if n_step == 0:
        fails.append("train: no Pallas kernel in the compiled train step")
    fused = report.losses[0]
    del report, step
    gc.collect()

    ref, _ = _train(cfg, qcfg.replace(fused_matmul="off"), devices=[device],
                    steps=1, batch=batch, seq=seq)
    rel = abs(fused - ref.losses[0]) / abs(ref.losses[0])
    info(f"train: first-step loss fused {fused:.6f} vs jnp "
         f"{ref.losses[0]:.6f}: relative diff {rel:.3e} "
         f"(tolerance {LOSS_TOL:g})")
    if not rel <= LOSS_TOL:
        fails.append(f"train: fused first-step loss off by {rel:.3e}")
    return fails


def sharded_phase(cfg, devices, *, batch=TRAIN_BATCH, seq=TRAIN_SEQ,
                  steps=TRAIN_STEPS) -> list:
    """QAT steps on a (data=2, model=2) mesh of four devices against the
    same steps on one device; returns failed checks."""
    import jax

    from repro.core.policy import get_preset

    fails = []
    qcfg = get_preset("w4a4")
    in_use = {}

    def grab_memory(i, state):
        if i == 0:  # only the sharded state is live on the devices here
            for d in devices:
                in_use[d.id] = (d.memory_stats() or {}).get("bytes_in_use", 0)
            shards = dict.fromkeys(in_use, 0)
            for x in jax.tree.leaves(state):
                for sh in x.addressable_shards:
                    shards[sh.device.id] += sh.data.nbytes
            in_use["shards"] = shards
            in_use["state"] = sum(x.nbytes for x in jax.tree.leaves(state))

    sharded, dt = _train(cfg, qcfg, devices=devices, steps=steps, batch=batch,
                         seq=seq, model_parallel=2, on_step=grab_memory)
    info(f"[info] sharded train: (data=2, model=2) mesh, batch {batch} x seq "
         f"{seq}, {steps} steps: {dt:.1f}s incl. compile")
    _check_report("sharded train", sharded, fails)
    total, shards = in_use.pop("state"), in_use.pop("shards")
    info(f"sharded train: at step 0, bytes_in_use per device {in_use}; "
         f"train-state shard bytes per device {shards}; "
         f"train state {total} bytes in all")
    # an even spread puts total/4 on each device; all on device 0 puts 0
    # on the other three
    low = [d for d, b in in_use.items() if b < 0.5 * total / len(devices)]
    if low:
        fails.append(f"sharded train: devices {low} hold less than half "
                     f"their share of the state: {in_use}")
    gc.collect()

    single, dt = _train(cfg, qcfg, devices=devices[:1], steps=steps,
                        batch=batch, seq=seq)
    info(f"[info] one-chip train: {steps} steps: {dt:.1f}s incl. compile")
    _check_report("one-chip train", single, fails)
    rel = [abs(a - b) / abs(b) for a, b in zip(sharded.losses, single.losses)]
    info(f"sharded vs one-chip losses: relative diff per step {rel} "
         f"(tolerance {SHARDED_TOL:g})")
    if len(rel) != steps or max(rel) > SHARDED_TOL:
        fails.append(f"sharded train: losses differ from one chip by {rel}")
    return fails


def watch_compiles() -> dict:
    """Tally XLA compiles (or persistent-cache retrievals) and cache hits."""
    import jax

    tally = {"programs": 0, "seconds": 0.0, "cache_hits": 0}

    def on_duration(event, duration, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            tally["programs"] += 1
            tally["seconds"] += duration

    def on_event(event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            tally["cache_hits"] += 1

    jax.monitoring.register_event_duration_secs_listener(on_duration)
    jax.monitoring.register_event_listener(on_event)
    return tally


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    args = ap.parse_args(argv)
    try:
        from repro.launch.compile_cache import setup_compile_cache
    except ImportError as e:
        die(f"cannot import the repro package from {HERE}/src: {e}")
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        die(f"needs a TPU; JAX found {devices[0].platform} "
            f"({devices[0].device_kind}). No CPU fallback.")
    if len(devices) < args.chips:
        die(f"--chips {args.chips} needs {args.chips} devices, "
            f"found {len(devices)}")
    cache = setup_compile_cache()
    compiles = watch_compiles()
    info(f"[info] {len(devices)} x {devices[0].device_kind}; "
         f"compile cache {cache}")

    from repro.configs.registry import get_config
    cfg = get_config(ARCH)
    t0 = time.monotonic()
    if args.chips == 1:
        fails = serve_phase(cfg, devices[0])
        gc.collect()
        fails += train_phase(cfg, devices[0])
    else:
        fails = sharded_phase(cfg, devices[:4])
    info(f"[info] total {time.monotonic() - t0:.1f}s; {compiles['programs']} "
         f"programs compiled or fetched in {compiles['seconds']:.1f}s, "
         f"{compiles['cache_hits']} persistent-cache hits")
    if fails:
        for f in fails:
            print(f"FAIL {f}", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": devices[0].platform, "kind": devices[0].device_kind,
        "count": len(devices)}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
