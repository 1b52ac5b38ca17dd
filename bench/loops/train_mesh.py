"""Closed-loop training over a mesh: `run_training` on the cell's chips with
the traffic's `model_parallel`, the (data, model) mesh and its production
FSDP + TP rules.

The window, the hook's readings and the checks are `train.py`'s, whose
configs, batches and exception it imports. What differs is where the
readings are reduced, because at a few billion parameters one gradient is
12 GB:

  * The seed's weights are made on the chips with the shardings of the
    state `run_training` built; before each reading the hook waits for the
    step, so no reading's buffers meet a step's temporaries.
  * Every reading is reduced on the devices: per-leaf norms of the first
    gradient (after step 1) and of the change after three steps, which is
    computed against weights made anew inside the same program.
  * The program's first gradient for `row_balance` comes from a second,
    one-step `run_training` after the window, from the same weights and
    rows as the first step of the first: the step needs nearly all of a
    chip (14.6 of 15.75 GB at 12 Granite layers; compile for a described
    v5e:2x2), so the gradient (3.2 GB a chip) cannot be held through the
    window. It stays on the chips, sharded as the program left it, until
    the reference has projected its rows' gradients onto it.
  * The reference (`Reference` of the configuration's reference file) runs
    on the same chips once the program's state is dropped: the rows' Gram
    matrix and projections first, while the program's gradient is held,
    then the three AdamW steps.

The host's memory is printed to standard error at each phase (`_host_mem`):
the peak resident set, and the resident set now split into anonymous,
file-backed and shared pages.
"""
from __future__ import annotations

import gc
import resource
import shutil
import sys
import tempfile
import time

import numpy as np

from bench.harness import compare, program, weights
from bench.harness.device import describe
from bench.harness.run import Outcome
from bench.harness.spec import load_module
from bench.harness.trace import Capture

T = load_module("loops", "train")
N_READ = T.N_READ
# (name, the reference's arguments, half of each batch's rows left out)
CONTROLS = (("control", {"master": "bf16"}, False),
            ("fp8_compute", {"prec": "fp8"}, False), ("half_batch", {}, True))


def _host_peak_bytes() -> int:
    return resource.getrusage(resource.RUSAGE_SELF).ru_maxrss * 1024


def _host_mem(phase: str, log: list) -> None:
    """Append this process's host memory at `phase` to `log` and print it:
    the peak resident bytes, and from /proc/self/status (where there is
    one) the resident bytes now, anonymous, file-backed and shared."""
    rec = {"phase": phase, "peak": _host_peak_bytes()}
    try:
        with open("/proc/self/status") as f:
            for line in f:
                key, _, val = line.partition(":")
                if key in ("VmRSS", "RssAnon", "RssFile", "RssShmem"):
                    rec[key] = int(val.split()[0]) * 1024
    except OSError:
        pass
    log.append(rec)
    print(f"bench: host memory {rec}", file=sys.stderr, flush=True)


def _half(n: int) -> list:
    """Row weights that leave out the second half of n rows."""
    return [1.0] * (n // 2) + [0.0] * (n - n // 2)


class _Run:
    """The program of one cell and seed: its configs, the seed's weights
    and the readings the hooks take."""

    def __init__(self, ctx):
        import jax
        import jax.numpy as jnp
        from repro.train.state import init_state
        self.ctx = ctx
        self.c, self.tr = dict(ctx.cell.config), ctx.cell.traffic
        self.cfg, self.qcfg, self.tcfg, self.dcfg = T._configs(self.c,
                                                               ctx.seed)
        like = jax.eval_shape(
            lambda k: init_state(k, self.cfg, self.qcfg, self.tcfg),
            jax.random.PRNGKey(0))["params"]
        self.like = like
        self.shapes = {n: tuple(x.shape)
                       for n, x in program.to_flat(like).items()}
        self.key = weights.seed_key(ctx.seed)
        self.ref_mod = load_module("reference", self.c["reference"])
        no_fold = self.no_fold = self.ref_mod.NO_FOLD
        q = self.c["quant"]
        self.flat = lambda k: weights.qat_params(self.shapes, q, k)
        self.tree = lambda k: program.from_flat(self.flat(k), like)
        norms = lambda t: {n: jnp.sqrt(jnp.sum(jnp.square(
            x.astype(jnp.float32)))) for n, x in program.to_flat(t).items()}
        self.norms = jax.jit(norms)
        self.change = jax.jit(lambda p, k: norms(jax.tree.map(
            lambda x, y: x.astype(jnp.float32) - y.astype(jnp.float32),
            p, self.tree(k))), compiler_options=no_fold)
        b1 = np.float32(1.0) - np.float32(self.c["train"]["b1"])
        self.b1 = b1
        self.first_of = jax.jit(lambda mu: {
            n: x / b1 for n, x in program.to_flat(mu).items()})

    def weights_like(self, params):
        """The seed's weights, placed as `params` are."""
        import jax
        sh = jax.tree.map(lambda x: x.sharding, params)
        return jax.jit(self.tree, out_shardings=sh,
                       compiler_options=self.no_fold)(self.key)

    def train(self, on_step):
        """One `run_training` on the cell's chips until `on_step` raises
        `WindowClosed`, in a fresh checkpoint directory that saves
        nothing."""
        from repro.launch.train import run_training
        from repro.train import checkpoint as ckpt
        from repro.train.fault_tolerance import CheckpointManager
        ckdir = tempfile.mkdtemp(prefix="bench-ckpt-")
        mgr = CheckpointManager(ckdir, save_every=10 ** 12, async_io=False,
                                expect_fingerprint=ckpt.fingerprint(
                                    self.cfg, self.qcfg))
        try:
            run_training(self.cfg, self.qcfg, self.tcfg, self.dcfg,
                         steps=10 ** 12, batch_size=self.tr["batch"],
                         seq_len=self.tr["seq_len"], ckpt_dir=ckdir,
                         model_parallel=int(self.tr["model_parallel"]),
                         log_every=0, on_step=on_step, mgr=mgr,
                         seed=self.ctx.seed % (1 << 31),
                         devices=self.ctx.devs)
            raise RuntimeError("run_training returned before the window "
                               "closed")
        except T.WindowClosed:
            pass
        finally:
            mgr.guard.restore_handlers()
            shutil.rmtree(ckdir, ignore_errors=True)

    def first_gradient(self) -> dict:
        """The program's first gradient as the optimizer got it (its first
        moment after one step, over 1 - beta1), flat, on the chips."""
        import jax
        out = {}

        def on_step(i, state):
            if i == 0:
                return dict(state, params=self.weights_like(state["params"]))
            out["first"] = jax.block_until_ready(self.first_of(state["mu"]))
            raise T.WindowClosed
        self.train(on_step)
        gc.collect()
        return out.pop("first")

    def reference(self, devs):
        ref = self.ref_mod
        return ref, ref.Reference(lambda: self.flat(self.key), self.c, devs)


def numbers(ref_mod, prog: dict, ref: dict) -> dict:
    """`train.numbers` with `row_balance` from the reference's Gram matrix
    of its rows' gradients and their projections onto the program's first
    gradient (`prog["proj"]`)."""
    rg = ref["grad"]
    keep = compare.moved(rg)
    w = [k for k in sorted(rg) if T._is_weight(k)]
    q = [k for k in sorted(rg) if not T._is_weight(k)]
    return {
        "loss_gap": compare.loss_gap(prog["losses"], ref["losses"]),
        "grad_gap": compare.leaf_gap(prog["grad"], rg, w)[0],
        "change_gap": compare.leaf_gap(prog["change"], ref["change"],
                                       [k for k in keep if T._is_weight(k)])[0],
        "row_balance": ref_mod.row_balance(ref["gram"], prog["proj"]),
        "qgrad_gap": compare.median_leaf_gap(prog["grad"], rg, q),
        "qchange_gap": compare.median_leaf_gap(
            prog["change"], ref["change"],
            [k for k in keep if not T._is_weight(k)]),
    }


def controls(ref_mod, ref, batches: list, f32: dict = None) -> dict:
    """The numbers of the reference with its float32 master weights held in
    bfloat16 (the control), with its bfloat16 arithmetic in fp8, and with
    half of each batch's rows left out, each in the program's place against
    the float32 reference (the rows' projections onto each one's own first
    gradient). `f32`: the float32 readings with their "gram", when a run
    has them already."""
    gram = None if f32 is None else f32["gram"]
    got = {}
    for name, kw, half in CONTROLS:
        w = _half(int(batches[0]["tokens"].shape[0])) if half else None
        first = ref.first_gradient(batches[0], row_w=w, **kw)
        gram, proj = ref.row_gram(batches[0], first, gram=gram)
        got[name] = dict(ref.step_readings(batches, row_w=w, **kw),
                         proj=proj)
    if f32 is None:
        f32 = dict(ref.step_readings(batches), gram=gram)
    out = {name: numbers(ref_mod, g, f32) for name, g in got.items()}
    for name, v in out.items():
        print(f"bench: {name} {v}", file=sys.stderr, flush=True)
    return out


def calibrate(ctx, control: bool) -> dict:
    """Readings for setting limits: the program's numbers on this seed and,
    with `control`, the controls' (`controls`)."""
    out = run(ctx, keep_reference=control)
    rec = {"seed": ctx.seed, "program": out.work["readings"],
           "losses": out.work["losses"], "checks": out.checks,
           "train_tok_s": out.end_to_end["train_tok_s"],
           "host_peak_bytes": out.work["host_peak_bytes"],
           "host_memory": out.work["host_memory"]}
    if control:
        ref_mod, ref, f32, batches = out.work.pop("reference")
        rec.update(controls(ref_mod, ref, batches, f32))
        _host_mem("controls", rec["host_memory"])
    return rec


def run(ctx, keep_reference: bool = False) -> Outcome:
    import jax
    from repro.train.sentinel import SentinelAbort

    mem: list = []
    _host_mem("start", mem)
    r = _Run(ctx)
    tr, sent = r.tr, r.tcfg.sentinel
    m = np.float32(sent.loss_momentum) if sent else None
    warm, n_trace = int(tr["setup_steps"]), int(tr["trace_steps"])
    rec: dict = {"ema": [], "skipped": []}

    def on_step(i, state):
        rec["i_last"] = i
        if i == 0:
            _host_mem("program state", mem)
            return dict(state, params=r.weights_like(state["params"]))
        if 1 <= i <= N_READ:
            jax.block_until_ready(state)
            if sent is not None:
                rec["ema"].append(float(state["sent"].loss_ema))
                rec["skipped"].append(int(state["sent"].skipped))
        if i == 1:
            rec["grad"] = {k: float(v) / float(r.b1) for k, v in
                           jax.device_get(r.norms(state["mu"])).items()}
        if i == N_READ:
            rec["change"] = {k: float(v) for k, v in jax.device_get(
                r.change(state["params"], r.key)).items()}
        if i == warm:
            jax.block_until_ready(state)
            _host_mem("window open", mem)
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
                raise T.WindowClosed
        return None

    try:
        r.train(on_step)
    except SentinelAbort as e:
        # the job died: the steps it ran count, every one of them failed
        jax.effects_barrier()
        print(f"bench: the sentinel aborted the run at step "
              f"{rec.get('i_last')}: {e}", file=sys.stderr)
        if "t_open" not in rec:
            raise
        rec.update(t_close=time.monotonic(), i_close=rec["i_last"] + 1,
                   skip1=None)
    finally:
        if "cap" in rec:
            rec["cap"].__exit__(None, None, None)
    gc.collect()
    _host_mem("window closed", mem)
    dev = describe(ctx.devs)

    steps = rec["i_close"] - rec["i_open"]
    window = rec["t_close"] - rec["t_open"]
    tokens = steps * tr["batch"] * tr["seq_len"]
    e2e = {"train_tok_s": tokens / window,
           "setup_s": rec["t_open"] - ctx.t_process}

    ema = rec["ema"]
    prog_loss = [ema[0]]
    for a, b in zip(ema, ema[1:]):
        prog_loss.append(float((np.float32(b) - (np.float32(1) - m)
                                * np.float32(a)) / m))

    first = r.first_gradient()
    print(f"bench: device bytes in use with the program's first gradient "
          f"{(ctx.devs[0].memory_stats() or {}).get('bytes_in_use')}",
          file=sys.stderr)
    _host_mem("program's first gradient", mem)
    ref_mod, ref = r.reference(ctx.devs)
    batches = T._ref_batches(r.c, tr, ctx.seed)
    gram, proj = ref.row_gram(batches[0], first)   # empties `first`
    _host_mem("reference rows", mem)
    f32 = dict(ref.step_readings(batches), gram=gram)
    _host_mem("reference steps", mem)
    prog = {"losses": prog_loss, "grad": rec["grad"],
            "change": rec["change"], "proj": proj}
    got = numbers(ref_mod, prog, f32)
    checks = {k: {"value": got[k], "limit": v}
              for k, v in r.c["limits"].items()}
    checks["skipped_first_steps"] = {"value": float(rec["skipped"][-1]),
                                     "limit": 0}
    checks["sentinel_abort"] = {"value": float(rec["skip1"] is None),
                                "limit": 0}
    host_peak = _host_peak_bytes()
    print(f"bench: losses {prog_loss}; readings {got}", file=sys.stderr)
    print(f"bench: host peak resident memory {host_peak} bytes",
          file=sys.stderr, flush=True)
    work = {"steps": steps, "tokens": tokens, "batch": tr["batch"],
            "seq_len": tr["seq_len"], "readings": got, "losses": prog_loss,
            "shapes": r.shapes, "host_peak_bytes": host_peak,
            "host_memory": mem, "model_parallel": int(tr["model_parallel"])}
    if keep_reference:
        work["reference"] = (ref_mod, ref, f32, batches)
    failed = steps if rec["skip1"] is None else rec["skip1"] - rec["skip0"]
    return Outcome(attempted=steps, failed=failed,
                   end_to_end=e2e, checks=checks, device=dev,
                   trace=rec["cap"].trace if ctx.trace else None, work=work)
