"""Serving launcher: continuous-batching engine over int-coded weights.

    PYTHONPATH=src python -m repro.launch.serve --arch granite-8b --smoke \
        --batch 4 --new-tokens 8

Thin CLI over repro.serve.ServeEngine: params converted to serving int codes
(nibble-packed at <=4 bits, embedding included) and sharded with the
production rules; one pooled (optionally int8/int4) KV cache multiplexes all
requests through slot recycling. `--smoke` reports prefill and decode
tokens/sec SEPARATELY (a single number conflates prompt chunks with
generated tokens).

The serving sentinel is armed: non-finite logits rows fault only their
request, a persistent executor failure rebuilds from params and replays
in-flight work (the `executor_factory` closure below), and SIGTERM/SIGINT
(PreemptionGuard) triggers a graceful drain bounded by `--drain-timeout` —
in-flight requests finish or are cut with partial results, never lost.

`run_serving` is that wiring as a function: the CLI and `chip_smoke.py`
both call it.

`greedy_generate` is the engine-free batched loop: ONE chunked-prefill step
over the whole prompt, then new_tokens - 1 single-token decode steps — the
serving engine's per-request outputs match it exactly (the parity contract
tests/test_serve_engine.py pins).
"""
from __future__ import annotations

import argparse

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.registry import ARCH_IDS, get_config, reduced_config
from repro.core.policy import get_preset
from repro.data.synthetic import DataConfig, sample_batch
from repro.dist import sharding as shard
from repro.launch.compile_cache import setup_compile_cache
from repro.launch.mesh import kernels_for_mesh, make_host_mesh
from repro.models import model as M
from repro.models.common import convert_to_serving
from repro.serve import (FaultPolicy, ModelExecutor, SamplingParams,
                         Scheduler, ServeEngine)
from repro.train.fault_tolerance import PreemptionGuard


def greedy_generate(step, params, cache, prompts, new_tokens: int):
    """Greedy batched generation via the chunked prefill path.

    `step(params, cache, {"tokens": (B,C), "pos": (B,C)})` is a jitted
    prefill_step. The prompt runs as ONE batched call (not prompt_len
    single-token steps — the legacy loop survives only as a parity reference
    in tests/test_serve_loop.py), then `new_tokens - 1` C=1 decode calls.
    The first generated token is the argmax of the prefill's last-position
    logits and the final decode's argmax is emitted, not discarded.
    Returns (tokens (batch, new_tokens), cache).
    """
    batch, prompt_len = prompts.shape
    assert prompt_len >= 1 or new_tokens <= 0, (
        "greedy_generate needs at least one prompt token to seed generation "
        f"(got prompt_len={prompt_len}, new_tokens={new_tokens})")
    if new_tokens <= 0:
        return jnp.zeros((batch, 0), jnp.int32), cache
    pos = jnp.broadcast_to(jnp.arange(prompt_len, dtype=jnp.int32)[None],
                           (batch, prompt_len))
    logits, cache = step(params, cache, {"tokens": prompts, "pos": pos})
    tok = jnp.argmax(logits[:, -1], -1)[:, None].astype(jnp.int32)
    outs = []
    for i in range(new_tokens):
        outs.append(tok)
        if i + 1 < new_tokens:
            pos = jnp.full((batch, 1), prompt_len + i, jnp.int32)
            logits, cache = step(params, cache, {"tokens": tok, "pos": pos})
            tok = jnp.argmax(logits[:, -1], -1)[:, None].astype(jnp.int32)
    return jnp.concatenate(outs, 1), cache


def run_serving(cfg, qcfg, prompts, *, new_tokens: int, n_slots: int,
                max_len: int, chunk: int, model_parallel: int = 1,
                drain_timeout: float = 30.0, devices=None):
    """Serve `prompts` (a list of int token arrays) through `ServeEngine`
    over `ModelExecutor`: random serving weights from seed 0, sharded over a
    (data, model) mesh of `devices` (default: all), one pooled KV cache of
    `n_slots` x `max_len`. Every request asks for `new_tokens` greedy
    tokens. Returns (engine, mesh, summary); `engine.results` holds each
    request's GenResult under rid "req-<i>"."""
    mesh = make_host_mesh(model=model_parallel, devices=devices)
    qcfg = kernels_for_mesh(qcfg, mesh)
    key = jax.random.PRNGKey(0)
    params = convert_to_serving(M.init_params(key, cfg, qcfg), qcfg)
    p_sh = shard.named_tree(shard.param_pspecs(params, mesh), mesh)
    params = jax.device_put(params, p_sh)

    # the pool's slot axis stays unsharded (per-slot dynamic-slice inserts);
    # the KV sequence axis still shards over the model axis
    def shard_caches(cache):
        specs = shard.cache_pspecs(cache, mesh, shard_batch=False)
        return jax.device_put(cache, shard.named_tree(specs, mesh))

    def make_executor():
        # sentinel rebuild path: params/cfg stay valid, only the executor
        # (jit closures + caches) is rebuilt; in-flight work is replayed
        return ModelExecutor(params, cfg, qcfg, n_slots=n_slots,
                             max_len=max_len, chunk=chunk,
                             shard_caches=shard_caches)

    engine = ServeEngine(
        make_executor(), Scheduler(max_len=max_len, max_queue=len(prompts)),
        executor_factory=make_executor, guard=PreemptionGuard(),
        faults=FaultPolicy(drain_timeout_s=drain_timeout))
    for i, prompt in enumerate(prompts):
        ok, reason = engine.submit(prompt,
                                   SamplingParams(max_new_tokens=new_tokens),
                                   rid=f"req-{i}")
        assert ok, reason
    return engine, mesh, engine.run_until_idle()


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="granite-8b", choices=ARCH_IDS)
    ap.add_argument("--quant", default="w8a8")
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--batch", type=int, default=4,
                    help="number of requests submitted")
    ap.add_argument("--slots", type=int, default=0,
                    help="KV pool slots (0 = min(batch, 4))")
    ap.add_argument("--prompt-len", type=int, default=16, dest="prompt_len")
    ap.add_argument("--new-tokens", type=int, default=8, dest="new_tokens")
    ap.add_argument("--chunk", type=int, default=8,
                    help="prefill chunk width (tokens per prefill step)")
    ap.add_argument("--kv-bits", type=int, default=8, dest="kv_bits")
    ap.add_argument("--model-parallel", type=int, default=1, dest="mp")
    ap.add_argument("--drain-timeout", type=float, default=30.0,
                    dest="drain_timeout",
                    help="graceful-drain budget (s) on SIGTERM/preemption")
    args = ap.parse_args()
    setup_compile_cache()

    cfg = get_config(args.arch)
    if args.smoke:
        cfg = reduced_config(cfg)
    qcfg = get_preset(args.quant).replace(kv_cache_bits=args.kv_bits,
                                          a_bits=32)
    n_slots = args.slots or min(args.batch, 4)
    prompts = np.asarray(sample_batch(cfg, DataConfig(), 0, args.batch,
                                      args.prompt_len)["tokens"])
    engine, mesh, summary = run_serving(
        cfg, qcfg, list(prompts), new_tokens=args.new_tokens,
        n_slots=n_slots, max_len=args.prompt_len + args.new_tokens,
        chunk=args.chunk, model_parallel=args.mp,
        drain_timeout=args.drain_timeout)

    tp = summary["throughput"]
    print(f"arch={cfg.name} mesh={dict(mesh.shape)} int{args.kv_bits}-KV "
          f"slots={n_slots} requests={args.batch}: "
          f"prefill {tp['prefill_tok_s']:.0f} tok/s, "
          f"decode {tp['decode_tok_s']:.0f} tok/s "
          f"(occupancy {summary['occupancy']['mean']:.2f})")
    faults = summary["faults"]
    if any(faults.values()):
        print("faults:", {k: v for k, v in faults.items() if v})
    print("sample:", engine.results["req-0"].tokens)


if __name__ == "__main__":
    main()
