"""Closed-loop training: the trainer's own entry point, `run_training`.

One call of `run_training` is the whole run. Its `on_step` hook (called on
the host before each step) does the benchmark's part:

  * before step 0, the parameters of the state that `run_training` built
    are replaced by weights made from the run seed (`harness.weights`), the
    same values the plain reference starts from;
  * after steps 1, 2 and 3 it reads what the reference is compared on:
    each step's loss (the sentinel keeps it: the first healthy loss, then an
    exponential average with a known momentum), the first gradient as the
    optimizer got it (its first moment after one step, over 1 - beta1,
    copied to the host whole), and the parameters' change after three
    steps;
  * after `setup_steps` steps (every shape compiled) it opens the window,
    and once `--seconds` have passed it waits for the device and closes it
    by raising `WindowClosed`, which ends `run_training`.

Each run gets a fresh checkpoint directory under the temporary directory,
removes it at exit, and saves nothing into it (`save_every` beyond any
step the window reaches), so no run restores or leaves state behind.
Updates the sentinel skips count as failed steps; if the sentinel aborts
the job (a rollback with no checkpoint), the window ends there, every step
in it counts as failed, and the check `sentinel_abort` makes the run not
correct.

With `--trace 1` the window is `trace_steps` steps under the profiler.
After the window the train state is dropped, peak memory read, and the
reference follows the first three steps from the same weights and rows.
"""
from __future__ import annotations

import gc
import shutil
import sys
import tempfile
import time

import numpy as np

from bench.harness import compare, program, traffic, weights
from bench.harness.device import describe
from bench.harness.run import Outcome
from bench.harness.trace import Capture

N_READ = 3   # steps the reference follows


class WindowClosed(Exception):
    """Raised from the step hook to end `run_training` when the window
    closes."""


def _configs(c: dict, seed: int):
    from repro.core.policy import get_preset
    from repro.data.synthetic import DataConfig
    from repro.optim.adamw import AdamWConfig
    from repro.train.sentinel import SentinelConfig
    from repro.train.state import TrainConfig
    t = c["train"]
    cfg = program.arch_config(c)
    qcfg = get_preset(c["quant"]["preset"])
    q = c["quant"]
    if (qcfg.w_bits, qcfg.a_bits, qcfg.edge_bits) != (
            q["w_bits"], q["a_bits"], q["edge_bits"]):
        raise ValueError(f"preset {q['preset']} is not the stated {q}")
    tcfg = TrainConfig(
        total_steps=t["total_steps"], warmup_steps=t["warmup_steps"],
        kd=t["kd"], kd_topk=t["kd_topk"], lr_schedule="cosine",
        adamw=AdamWConfig(lr_peak=t["lr_peak"], b1=t["b1"], b2=t["b2"],
                          eps=t["eps"], weight_decay=t["weight_decay"],
                          clip_norm=t["clip_norm"]),
        sentinel=SentinelConfig() if t["sentinel"] else None)
    d = c["data"]
    dcfg = DataConfig(seed=seed, mult=d["mult"], add=d["add"],
                      p_noise=d["p_noise"])
    return cfg, qcfg, tcfg, dcfg


def _ref_batches(c: dict, tr: dict, seed: int) -> list:
    import jax.numpy as jnp
    out = []
    for i in range(N_READ):
        b = traffic.lm_batch(c["data"], seed, i, tr["batch"], tr["seq_len"],
                             c["vocab_size"])
        idx, p = traffic.kd_labels(b["labels"], c["vocab_size"],
                                   c["train"]["kd_topk"], i)
        out.append({"tokens": jnp.asarray(b["tokens"]),
                    "kd_idx": jnp.asarray(idx), "kd_p": jnp.asarray(p)})
    return out


def reference(c: dict, tr: dict, seed: int, prec: str = "f32",
              master: str = "f32", half_batch: bool = False) -> dict:
    """The plain reference's readings over the first steps, from the run
    seed (`train_readings`). `half_batch` plants a fault: the loss is the
    mean over the first half of the rows only."""
    import jax
    from bench.harness.spec import load_module
    ref = load_module("reference", c["reference"])
    shapes = c["_shapes"]
    make = jax.jit(lambda k: weights.qat_params(shapes, c["quant"], k))
    key = weights.seed_key(seed)
    batches = _ref_batches(c, tr, seed)
    if half_batch:
        batches = [{k: v[: v.shape[0] // 2] for k, v in b.items()}
                   for b in batches]
    return ref.train_readings(lambda: make(key), batches, c, prec, master)


def calibrate(ctx, control: bool) -> dict:
    """Readings for setting limits: the program's numbers on this seed
    and, with `control`, those of the reference put in the program's place
    with its float32 master weights held in bfloat16 (the control), with
    its bfloat16 arithmetic in fp8, and with half of the batch left out,
    each against the float32 reference."""
    out = run(ctx)
    rec = {"seed": ctx.seed, "program": out.work["readings"],
           "losses": out.work["losses"], "checks": out.checks}
    if control:
        c = dict(ctx.cell.config, _shapes=out.work["shapes"])
        tr = ctx.cell.traffic
        ref = reference(c, tr, ctx.seed)
        for name, kw in (("control", {"master": "bf16"}),
                         ("fp8_compute", {"prec": "fp8"}),
                         ("half_batch", {"half_batch": True})):
            rec[name] = numbers(reference(c, tr, ctx.seed, **kw), ref)
    return rec


def _is_weight(name: str) -> bool:
    return name.rsplit(".", 1)[-1] in ("w", "b", "g")


def numbers(prog: dict, ref: dict) -> dict:
    """The numbers compared, from the readings of the program and of the
    reference (`train_readings`' keys; the program has no "rows").

    Weights, biases and norm gains are read by their worst leaf. Quantizer
    scales and offsets get gradients that sum a rounding residual over every
    element they scale, so one bin flip moves them far; they are read by
    their median leaf (`qgrad_gap`, `qchange_gap`). `row_balance` asks how
    much of each row's own first gradient (the reference's, row by row) the
    program's first gradient holds: every row should count alike, and a
    step that leaves rows out reads about 1. The gradient's direction is not
    compared element by element: with 4-bit inputs one rounding difference
    flips a bin, and the flips multiply from layer to layer, so any two
    precisions differ there alike (PERF.md)."""
    rg = ref["grad"]
    keep = compare.moved(rg)
    w = [k for k in sorted(rg) if _is_weight(k)]
    q = [k for k in sorted(rg) if not _is_weight(k)]
    return {
        "loss_gap": compare.loss_gap(prog["losses"], ref["losses"]),
        "grad_gap": compare.leaf_gap(prog["grad"], rg, w)[0],
        "change_gap": compare.leaf_gap(prog["change"], ref["change"],
                                       [k for k in keep if _is_weight(k)])[0],
        "row_balance": compare.row_balance(prog["first"], ref["rows"]),
        "qgrad_gap": compare.median_leaf_gap(prog["grad"], rg, q),
        "qchange_gap": compare.median_leaf_gap(
            prog["change"], ref["change"],
            [k for k in keep if not _is_weight(k)]),
    }


def run(ctx) -> Outcome:
    import jax
    import jax.numpy as jnp
    from repro.launch.train import run_training
    from repro.train import checkpoint as ckpt
    from repro.train.fault_tolerance import CheckpointManager
    from repro.train.sentinel import SentinelAbort
    from repro.train.state import init_state

    c, tr = dict(ctx.cell.config), ctx.cell.traffic
    cfg, qcfg, tcfg, dcfg = _configs(c, ctx.seed)
    like = jax.eval_shape(lambda k: init_state(k, cfg, qcfg, tcfg),
                          jax.random.PRNGKey(0))["params"]
    shapes = {n: tuple(x.shape) for n, x in program.to_flat(like).items()}
    c["_shapes"] = shapes
    key = weights.seed_key(ctx.seed)
    make = jax.jit(lambda k: program.from_flat(
        weights.qat_params(shapes, c["quant"], k), like))
    norms = jax.jit(lambda t: {n: jnp.sqrt(jnp.sum(jnp.square(
        x.astype(jnp.float32)))) for n, x in program.to_flat(t).items()})
    diff_norms = jax.jit(lambda a, b: norms(jax.tree.map(
        lambda x, y: x.astype(jnp.float32) - y.astype(jnp.float32), a, b)))
    sent = tcfg.sentinel
    m = np.float32(sent.loss_momentum) if sent else None
    b1 = np.float32(1.0) - np.float32(c["train"]["b1"])
    warm, n_trace = int(tr["setup_steps"]), int(tr["trace_steps"])
    rec: dict = {"ema": [], "skipped": []}

    def on_step(i, state):
        rec["i_last"] = i
        if i == 0:
            state = dict(state, params=make(key))
            return state
        if 1 <= i <= N_READ and sent is not None:
            rec["ema"].append(float(state["sent"].loss_ema))
            rec["skipped"].append(int(state["sent"].skipped))
        if i == 1:
            rec["grad"] = {k: float(v) / float(b1)
                           for k, v in norms(state["mu"]).items()}
            rec["first"] = {k: np.asarray(v, np.float32) / b1 for k, v in
                            program.to_flat(state["mu"]).items()}
        if i == N_READ:
            rec["change"] = {k: float(v) for k, v in
                             diff_norms(state["params"], make(key)).items()}
        if i == warm:
            jax.block_until_ready(state)
            rec["skip0"] = int(state["sent"].skipped) if sent else 0
            rec["t_open"] = time.monotonic()
            rec["i_open"] = i
            if ctx.trace:
                rec["cap"] = Capture().__enter__()
        elif i > warm:
            done = (i - warm >= n_trace if ctx.trace else
                    time.monotonic() - rec["t_open"] >= ctx.seconds)
            if done:
                jax.block_until_ready(state)
                rec["t_close"] = time.monotonic()
                rec["i_close"] = i
                rec["skip1"] = int(state["sent"].skipped) if sent else 0
                raise WindowClosed
        return None

    ckdir = tempfile.mkdtemp(prefix="bench-ckpt-")
    mgr = CheckpointManager(ckdir, save_every=10 ** 12, async_io=False,
                            expect_fingerprint=ckpt.fingerprint(cfg, qcfg))
    try:
        run_training(cfg, qcfg, tcfg, dcfg, steps=10 ** 12,
                     batch_size=tr["batch"], seq_len=tr["seq_len"],
                     ckpt_dir=ckdir, log_every=0, on_step=on_step, mgr=mgr,
                     seed=ctx.seed % (1 << 31), devices=ctx.devs)
        raise RuntimeError("run_training returned before the window closed")
    except WindowClosed:
        pass
    except SentinelAbort as e:
        # the job died: the steps it ran count, every one of them failed
        jax.effects_barrier()
        print(f"bench: the sentinel aborted the run at step "
              f"{rec.get('i_last')}: {e}", file=sys.stderr)
        if "t_open" not in rec:
            raise
        # the step after the last hook ran and ended in the abort
        rec.update(t_close=time.monotonic(), i_close=rec["i_last"] + 1,
                   skip1=None)
    finally:
        if "cap" in rec:
            rec["cap"].__exit__(None, None, None)
        mgr.guard.restore_handlers()
        shutil.rmtree(ckdir, ignore_errors=True)
    gc.collect()
    dev = describe(ctx.devs)
    print(f"bench: device bytes in use after the window "
          f"{(ctx.devs[0].memory_stats() or {}).get('bytes_in_use')}",
          file=sys.stderr)

    steps = rec["i_close"] - rec["i_open"]
    window = rec["t_close"] - rec["t_open"]
    tokens = steps * tr["batch"] * tr["seq_len"]
    e2e = {"train_tok_s": tokens / window,
           "setup_s": rec["t_open"] - ctx.t_process}

    # the program's per-step losses from the sentinel's average
    ema = rec["ema"]
    prog_loss = [ema[0]]
    for a, b in zip(ema, ema[1:]):
        prog_loss.append(float((np.float32(b) - (np.float32(1) - m)
                                * np.float32(a)) / m))
    prog = {"losses": prog_loss, "grad": rec["grad"],
            "change": rec["change"], "first": rec.pop("first")}
    got = numbers(prog, reference(c, tr, ctx.seed))
    checks = {k: {"value": got[k], "limit": v}
              for k, v in c["limits"].items()}
    checks["skipped_first_steps"] = {"value": float(rec["skipped"][-1]),
                                     "limit": 0}
    checks["sentinel_abort"] = {"value": float(rec["skip1"] is None),
                                "limit": 0}
    print(f"bench: losses {prog_loss}; readings {got}", file=sys.stderr)
    work = {"steps": steps, "tokens": tokens, "batch": tr["batch"],
            "seq_len": tr["seq_len"], "readings": got, "losses": prog_loss,
            "shapes": shapes}
    failed = steps if rec["skip1"] is None else rec["skip1"] - rec["skip0"]
    return Outcome(attempted=steps, failed=failed,
                   end_to_end=e2e, checks=checks, device=dev,
                   trace=rec["cap"].trace if ctx.trace else None, work=work)
