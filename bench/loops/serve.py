"""Open-loop serving: `ServeEngine` over `ModelExecutor`, the program's
continuous-batching server.

Set-up makes the served weights on the device from the run seed in one
jitted call (integer codes packed as the program stores them, through
`harness.weights`), builds the executor and the engine, and warms every
program the window uses (prefill chunk, slot insert, pooled decode, slot
reset) by serving two requests through the engine itself.

The window is an open loop: requests from `harness.traffic.open_loop` are
submitted when due, whatever the engine is doing, and the loop steps the
engine in between. Every request is timed from its due time, so a long
step delays the requests behind it and that shows. After `--seconds` no
more are due; the loop then steps on until every request due in the window
has its first token, at most `first_token_wait_s` past the close (one that
gets none fails). Requests still decoding then are left unfinished.

  ttft_p90_ms     p90 over all requests due, due time to first token
  itl_p95_ms      p95 over every gap between two tokens of a request
                  whose later token came inside the window
  serve_out_tok_s tokens emitted inside the window over its length

With `--trace 1`, `trace_s` seconds from `trace_at` into the window run
under the profiler, with host spans around each engine step and each
executor call. After the window the engine is dropped, peak memory read, and
the plain reference scores a sample of the finished requests (drawn from
the seed, the longest among them): the widest gap by which a served token's
logit lies below the reference's best at its position.
"""
from __future__ import annotations

import gc
import json
import sys
import time

import numpy as np

from bench.harness import program, stats, traffic, weights
from bench.harness.device import describe
from bench.harness.run import Outcome
from bench.harness.trace import Capture


class SpanExecutor:
    """Forwards to a ModelExecutor; wraps each call in a host span and, while
    `log` is a list, records what each call did (for the work counts)."""

    def __init__(self, inner):
        self.inner = inner
        self.log = None

    def __getattr__(self, name):
        return getattr(self.inner, name)

    def _call(self, op, *args):
        import jax
        with jax.profiler.TraceAnnotation(f"bench.{op}"):
            out = getattr(self.inner, op)(*args)
        return out

    def scratch_reset(self):
        return self._call("scratch_reset")

    def prefill_chunk(self, tokens, start_pos):
        if self.log is not None:
            self.log.append(("prefill", int(start_pos), int(len(tokens))))
        return self._call("prefill_chunk", tokens, start_pos)

    def commit_prefill(self, slot):
        return self._call("commit_prefill", slot)

    def decode(self, tokens, pos):
        if self.log is not None:
            self.log.append(("decode", np.asarray(pos).copy()))
        return self._call("decode", tokens, pos)

    def reset_slot(self, slot):
        return self._call("reset_slot", slot)


def _configs(c: dict):
    from repro.core.policy import get_preset
    cfg = program.arch_config(c)
    q = c["quant"]
    qcfg = get_preset(q["preset"]).replace(kv_cache_bits=c["serve"]["kv_bits"],
                                           a_bits=32)
    if (qcfg.w_bits, qcfg.edge_bits) != (q["w_bits"], q["edge_bits"]):
        raise ValueError(f"preset {q['preset']} is not the stated {q}")
    return cfg, qcfg


def make_params(c: dict, cfg, qcfg, seed: int):
    """The served parameter tree, made on the device in one jitted call."""
    import jax
    import jax.numpy as jnp
    from repro.core.quantizer import pack_int4
    from repro.models import model as M
    from repro.models.common import convert_to_serving, pack_axis_of
    like = jax.eval_shape(lambda k: convert_to_serving(
        M.init_params(k, cfg, qcfg), qcfg), jax.random.PRNGKey(0))
    shapes = {n: x.shape for n, x in program.to_flat(like).items()}

    def layer(key, i):
        w = weights.serve_layer(key, i, c)
        out = {}
        for lin in weights.LINEARS:
            codes = w[f"{lin}.codes"]
            if f"layers.{lin}.codes4" in shapes:
                out[f"layers.{lin}.codes4"] = pack_int4(
                    codes, pack_axis_of(lin) % codes.ndim)
            else:
                out[f"layers.{lin}.codes"] = codes
            out[f"layers.{lin}.w_scale"] = w[f"{lin}.w_scale"].reshape(
                shapes[f"layers.{lin}.w_scale"][1:])
        for g in ("ln1.g", "ln2.g"):
            out[f"layers.{g}"] = w[g]
        return out

    @jax.jit   # the key is an argument: one program serves every seed
    def build(key):
        flat = jax.lax.map(lambda i: layer(key, i),
                           jnp.arange(c["num_hidden_layers"]))
        flat.update(weights.serve_edges(key, c))
        return program.from_flat(flat, like)

    return build(weights.seed_key(seed))


def _warm(engine, vocab: int, chunk: int):
    """Serve two requests through the engine: every program compiles."""
    from repro.serve import SamplingParams
    rng = np.random.default_rng(0)
    for i in range(2):
        engine.submit(rng.integers(0, vocab, chunk + 7, dtype=np.int32),
                      SamplingParams(max_new_tokens=3), rid=f"warm-{i}")
    engine.run_until_idle()


def arrivals(tr: dict, seed: int, seconds: float, vocab: int) -> tuple:
    """(ramp, window): the requests due before the window opens (the load
    it opens on, part of set-up) and those due in it. Each comes from its
    own mix seed, so every run seed gets the same window work."""
    ramp = traffic.open_loop(dict(tr, mix_seed=tr["mix_seed"] + 1), seed,
                             tr["ramp_s"], vocab)
    for r in ramp:
        r.rid, r.due = "ramp-" + r.rid, r.due - tr["ramp_s"]
    return ramp, traffic.open_loop(tr, seed, seconds, vocab)


def serve_window(ctx, engine, execu, ramp, reqs) -> dict:
    """Drive the open loop from the start of the ramp until every request
    due in the window has its first token; returns each request's token
    times and the window's clock marks (and the trace, when traced)."""
    import jax
    from repro.serve import SamplingParams
    tr = ctx.cell.traffic
    times: dict = {r.rid: [] for r in ramp + reqs}
    orig = engine.metrics.on_token

    def on_token(rid, now):
        orig(rid, now)
        times[rid].append(now)
    engine.metrics.on_token = on_token
    out: dict = {"times": times}
    cap = None
    t0 = out["t0"] = time.monotonic() + tr["ramp_s"]
    out["close"] = t0 + ctx.seconds
    trace_on = t0 + float(tr["trace_at"])
    trace_off = trace_on + float(tr["trace_s"])
    give_up = out["close"] + tr["first_token_wait_s"]
    todo = ramp + reqs
    nxt, waiting = 0, {r.rid for r in reqs}
    while True:
        now = time.monotonic()
        if ctx.trace and cap is None and now >= trace_on:
            execu.log = []
            cap = Capture().__enter__()
        if cap is not None and execu.log is not None and now >= trace_off:
            cap.__exit__(None, None, None)
            out["trace"], out["log"], execu.log = cap.trace, execu.log, None
        while nxt < len(todo) and t0 + todo[nxt].due <= now:
            r = todo[nxt]
            ok, why = engine.submit(
                r.prompt, SamplingParams(max_new_tokens=r.max_new), rid=r.rid)
            if not ok:
                print(f"bench: {r.rid} refused: {why}", file=sys.stderr)
            nxt += 1
        waiting = {rid for rid in waiting if not times[rid]}
        tracing = cap is not None and execu.log is not None
        if nxt == len(todo) and not waiting and not tracing:
            break
        if now > give_up:
            break
        if engine.has_work:
            with jax.profiler.TraceAnnotation("bench.engine_step"):
                engine.step()
        else:
            with jax.profiler.TraceAnnotation("bench.wait"):
                due = t0 + todo[nxt].due if nxt < len(todo) else now + 0.002
                time.sleep(max(0.0, min(due - now, 0.002)))
    if cap is not None and execu.log is not None:
        cap.__exit__(None, None, None)
        out["trace"], out["log"], execu.log = cap.trace, execu.log, None
    out["end"] = time.monotonic()
    return out


def latency(reqs, results: dict, w: dict, seconds: float) -> dict:
    """End-to-end metrics of the requests due in the window. A request
    fails if it never got a first token or ended in a fault."""
    t0, close = w["t0"], w["close"]
    ttft, itl, in_window, failed = [], [], 0, 0
    for r in reqs:
        ts = w["times"][r.rid]
        res = results.get(r.rid)
        bad = not ts or (res is not None and res.finish_reason != "length")
        failed += bad
        ttft.append((ts[0] - (t0 + r.due)) * 1e3 if ts and not bad
                    else float("inf"))
    for rid, ts in w["times"].items():
        for a, b in zip(ts, ts[1:]):
            if t0 < b <= close:
                itl.append((b - a) * 1e3)
        in_window += sum(1 for t in ts if t0 < t <= close)
    return {"ttft_p90_ms": stats.nearest_rank(ttft, 90),
            "itl_p95_ms": stats.nearest_rank(itl, 95) if itl else None,
            "serve_out_tok_s": in_window / seconds,
            "failed": failed}


def sample_finished(reqs, results: dict, seed: int, tr: dict) -> list:
    """Finished requests to score: the one with most served tokens, then
    others drawn from the seed, until `check_tokens` served tokens or
    `check_rows` requests."""
    done = [r for r in reqs if r.rid in results
            and results[r.rid].finish_reason == "length"]
    if not done:
        return []
    done.sort(key=lambda r: -len(results[r.rid].tokens))
    pick, rest = [done[0]], done[1:]
    rng = np.random.default_rng(np.random.SeedSequence([seed, 7]))
    rng.shuffle(rest)
    total = len(results[done[0].rid].tokens)
    for r in rest:
        if total >= tr["check_tokens"] or len(pick) >= tr["check_rows"]:
            break
        pick.append(r)
        total += len(results[r.rid].tokens)
    return [(r.prompt, list(results[r.rid].tokens)) for r in pick]


def reference_logits(c: dict, seed: int, seqs: list, prec: str = "f32"):
    """Logits of the plain reference over prompt + served tokens, one row
    per sequence (padded to max_len), at the positions that produced each
    served token: [(n_served, V) array]."""
    import jax
    import jax.numpy as jnp
    from bench.harness.spec import load_module
    ref = load_module("reference", c["reference"])
    key = weights.seed_key(seed)
    T = c["serve"]["max_len"]
    toks = np.zeros((len(seqs), T), np.int32)
    for i, (p, s) in enumerate(seqs):
        row = np.concatenate([p, np.asarray(s[:-1], np.int32)])
        toks[i, :len(row)] = row
    edges = weights.serve_edges(key, c)
    x = jax.jit(lambda codes, scale, t: ref.serve_embed(codes, scale, t, c,
                                                         prec))(
        edges["embed.codes"], edges["embed.w_scale"], jnp.asarray(toks))
    pos = jnp.broadcast_to(jnp.arange(T), toks.shape)

    @jax.jit
    def deq(key, i):
        w = weights.serve_layer(key, i, c)
        out = {f"{n}.w": w[f"{n}.codes"].astype(jnp.float32)
               * w[f"{n}.w_scale"] for n in weights.LINEARS}
        out.update({g: w[g].astype(jnp.float32) for g in ("ln1.g", "ln2.g")})
        return out

    block = ref.make_serve_block(c, prec)
    for i in range(c["num_hidden_layers"]):
        x = block(x, deq(key, jnp.int32(i)), pos)
    head = ref.make_serve_head(c, prec)
    w = edges["lm_head.codes"].astype(jnp.float32) * edges["lm_head.w_scale"]
    logits = head(x, edges["final_norm.g"].astype(jnp.float32), w)
    out = []
    for i, (p, s) in enumerate(seqs):
        lo = len(p) - 1
        out.append(np.asarray(logits[i, lo:lo + len(s)], np.float64))
    return out


def served_gap(ref_logits: list, seqs: list) -> float:
    """Widest gap by which a served token's reference logit lies below the
    reference's best at its position."""
    gap = 0.0
    for lg, (_, s) in zip(ref_logits, seqs):
        picked = lg[np.arange(len(s)), np.asarray(s)]
        gap = max(gap, float(np.max(lg.max(-1) - picked)))
    return gap


def control_gap(ref_logits: list, ctl_logits: list) -> float:
    """The same gap for the tokens the control puts first."""
    gap = 0.0
    for lg, cl in zip(ref_logits, ctl_logits):
        top = cl.argmax(-1)
        gap = max(gap, float(np.max(lg.max(-1) - lg[np.arange(len(top)), top])))
    return gap


def build_engine(c: dict, seed: int, max_queue: int):
    """The served weights from the seed, the executor (host spans around
    its calls) and the engine over it, with every program warmed."""
    from repro.serve import (FaultPolicy, MetricsCollector, ModelExecutor,
                             Scheduler, ServeEngine)
    s = c["serve"]
    cfg, qcfg = _configs(c)
    execu = SpanExecutor(ModelExecutor(make_params(c, cfg, qcfg, seed), cfg,
                                       qcfg, n_slots=s["slots"],
                                       max_len=s["max_len"],
                                       chunk=s["chunk"]))
    engine = ServeEngine(execu, Scheduler(max_len=s["max_len"],
                                          max_queue=max_queue),
                         metrics=MetricsCollector(), faults=FaultPolicy())
    _warm(engine, c["vocab_size"], s["chunk"])
    engine.metrics = MetricsCollector()
    return engine, execu


def run(ctx, broken=None) -> Outcome:
    """`broken(engine)` may plant a fault in the built engine (tests)."""
    c, tr = ctx.cell.config, ctx.cell.traffic
    ramp, reqs = arrivals(tr, ctx.seed, ctx.seconds, c["vocab_size"])
    engine, execu = build_engine(c, ctx.seed, len(ramp + reqs) + 8)
    if broken is not None:
        broken(engine)
    w = serve_window(ctx, engine, execu, ramp, reqs)
    results = dict(engine.results)
    lat = latency(reqs, results, w, ctx.seconds)
    seqs = sample_finished(ramp + reqs, results, ctx.seed, tr)
    del engine, execu
    gc.collect()
    dev = describe(ctx.devs)

    gap = (served_gap(reference_logits(c, ctx.seed, seqs), seqs)
           if seqs else float("inf"))
    print(f"bench: {len(reqs)} requests due in the window, "
          f"{lat['failed']} failed; {len(results)} finished; scored "
          f"{len(seqs)} of them ({sum(len(x[1]) for x in seqs)} served "
          f"tokens): widest gap {gap}", file=sys.stderr)
    checks = {"served_logit_gap": {"value": gap,
                                   "limit": c["limits"]["served_logit_gap"]}}
    e2e = {"ttft_p90_ms": lat["ttft_p90_ms"], "itl_p95_ms": lat["itl_p95_ms"],
           "serve_out_tok_s": lat["serve_out_tok_s"],
           "setup_s": w["t0"] - ctx.t_process}
    return Outcome(attempted=len(reqs), failed=lat["failed"], end_to_end=e2e,
                   checks=checks, device=dev, trace=w.get("trace"),
                   work={"log": w.get("log", []), "seqs": seqs})


def sweep(ctx, rates: list) -> list:
    """The knee: one engine serves ramp + window at each rate in turn
    (in-flight work cancelled between rates); per rate, the queue depth
    when the window opens and when it closes, and the latencies."""
    import dataclasses
    from repro.serve import MetricsCollector
    c = ctx.cell.config
    engine, execu = build_engine(c, ctx.seed, 100000)
    out = []
    for rate in rates:
        tr = dict(ctx.cell.traffic, rate_rps=rate)
        cell = dataclasses.replace(ctx.cell, traffic=tr)
        rctx = dataclasses.replace(ctx, cell=cell, trace=False)
        ramp, reqs = arrivals(tr, ctx.seed, ctx.seconds, c["vocab_size"])
        for r in ramp + reqs:
            r.rid = f"{rate}-{r.rid}"
        depth = {}
        orig_step = engine.step

        def step(_orig=orig_step):
            now = time.monotonic()
            if "open" not in depth and now >= w0[0]:
                depth["open"] = len(engine.scheduler)
            if "close" not in depth and now >= w0[0] + ctx.seconds:
                depth["close"] = len(engine.scheduler)
            return _orig()
        w0 = [time.monotonic() + tr["ramp_s"]]
        engine.step = step
        w = serve_window(rctx, engine, execu, ramp, reqs)
        engine.step = orig_step
        lat = latency(reqs, dict(engine.results), w, ctx.seconds)
        ttft = sorted((w["times"][r.rid][0] - w["t0"] - r.due) * 1e3
                      for r in reqs if w["times"][r.rid])
        rec = {"rate_rps": rate, "requests": len(reqs),
               "queue_at_open": depth.get("open"),
               "queue_at_close": depth.get("close"),
               "queue_at_end": len(engine.scheduler),
               "slots_busy_at_end": len(engine.slots),
               "ttft_p50_ms": ttft[len(ttft) // 2] if ttft else None, **lat}
        print(json.dumps(rec), flush=True)
        out.append(rec)
        for r in ramp + reqs:
            engine.cancel(r.rid)
        engine.metrics = MetricsCollector()
    return out


def calibrate(ctx, control: bool) -> dict:
    """The program's gap on this seed, and with `control` the gap of the
    tokens that the reference in fp8 puts first, on the same sequences."""
    out = run(ctx)
    rec = {"seed": ctx.seed, "program": out.checks["served_logit_gap"]["value"],
           "attempted": out.attempted, "failed": out.failed,
           "e2e": out.end_to_end}
    seqs = out.work["seqs"]
    if control and seqs:
        c = ctx.cell.config
        ref = reference_logits(c, ctx.seed, seqs)
        rec["control"] = control_gap(ref, reference_logits(c, ctx.seed, seqs,
                                                           "fp8"))
    return rec
