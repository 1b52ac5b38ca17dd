"""Production training launcher.

    PYTHONPATH=src python -m repro.launch.train --arch granite-8b --smoke \
        --quant w4a4 --steps 100 --ckpt /tmp/run1

Builds a mesh over the available devices (data x model), shards the train
state with the production rules (FSDP + TP + per-head scale sharding), and
runs the QAT loop with MCKD labels, async checkpointing, preemption
handling, straggler telemetry, and the run sentinel (train/sentinel.py):
in-step health checks skip poisoned updates, and after `k_consecutive`
fatal steps the loop rolls back to the newest CRC-verified checkpoint with
an LR backoff (bounded retries, then SentinelAbort). `--no-sentinel`
disables all of it so benchmarks can measure the sentinel's overhead.

The loop itself lives in `run_training()` so the fault-injection suite
(tests/test_sentinel_faults.py) and `chip_smoke.py` can drive it in-process
(the suite with deterministic injectors, repro/testing/faultinject.py). On a
TPU slice the same entrypoint runs unmodified (jax.distributed.initialize is
attempted when the JAX_COORDINATOR_ADDRESS env var is present); on a CPU
use --smoke for reduced configs.

`--tpu-flags` turns on latency-hiding collective overlap. These are libtpu
flags: they go in LIBTPU_INIT_ARGS, which libtpu parses once when the
backend starts (in XLA_FLAGS they abort the process as unknown flags).
"""
from __future__ import annotations

import argparse
import dataclasses
import os
import time
from typing import Callable, Optional

import jax

from repro import tracing
from repro.configs.registry import ARCH_IDS, get_config, reduced_config
from repro.core.policy import get_preset
from repro.data.mckd_store import synthetic_kd_labels
from repro.data.synthetic import DataConfig, sample_batch
from repro.dist import sharding as shard
from repro.launch.compile_cache import setup_compile_cache
from repro.launch.mesh import kernels_for_mesh, make_host_mesh
from repro.optim.adamw import AdamWConfig
from repro.train import checkpoint as ckpt
from repro.train.fault_tolerance import CheckpointManager
from repro.train.sentinel import SentinelConfig, SentinelRunner, describe
from repro.train.state import TrainConfig, init_state
from repro.train.train_step import make_train_step

TPU_PERF_FLAGS = ("--xla_enable_async_all_gather=true "
                  "--xla_enable_async_collective_permute=true "
                  "--xla_tpu_enable_async_collective_fusion=true")


@dataclasses.dataclass
class RunReport:
    """What happened during a `run_training` invocation (tests assert on
    this; the CLI prints it)."""

    final_step: int           # last loop index that completed
    final_loss: float
    steps_run: int            # step_fn invocations (includes replayed steps)
    rollbacks: int            # sentinel rollback-recoveries performed
    skipped: int              # updates skipped as fatal (sentinel counter)
    lr_scale: float           # final sentinel LR backoff multiplier
    preempted: bool           # SIGTERM/SIGINT clean exit taken
    straggler_flags: int
    losses: list = dataclasses.field(default_factory=list)  # per step run
    step_fn: Optional[Callable] = None  # the jitted train step


def run_training(cfg, qcfg, tcfg: TrainConfig, dcfg: DataConfig, *,
                 steps: int, batch_size: int = 16, seq_len: int = 64,
                 ckpt_dir: str, save_every: int = 100, model_parallel: int = 1,
                 log_every: int = 10,
                 extra_loss: Optional[Callable] = None,
                 on_step: Optional[Callable] = None,
                 mgr: Optional[CheckpointManager] = None,
                 seed: int = 0, devices=None) -> RunReport:
    """The QAT training loop: restore -> step -> health -> save, with
    sentinel rollback recovery. `tcfg.sentinel` (SentinelConfig | None)
    controls the health checks; None runs the bare loop.

    extra_loss(params, step): jit-side extra loss term (fault injection /
        regularizers), forwarded to `make_train_step`.
    on_step(i, state) -> state | None: host-side hook before each step
        (fault injectors poison state here; None keeps the state).
    mgr: pass a preconfigured CheckpointManager (tests use async_io=False
        for determinism); by default one is built over `ckpt_dir` with a
        (arch, quant) config fingerprint stamped into every manifest.
    devices: the devices the (data, model) mesh spans (default: all).
    """
    mesh = make_host_mesh(model=model_parallel, devices=devices)
    run_qcfg = kernels_for_mesh(qcfg, mesh)
    key = jax.random.PRNGKey(seed)
    constrain, logits_constrain = shard.make_constrains(mesh)
    like = jax.eval_shape(lambda k: init_state(k, cfg, qcfg, tcfg), key)
    state_sh = shard.named_tree(shard.state_pspecs(like, mesh, qcfg), mesh)

    if mgr is None:
        mgr = CheckpointManager(ckpt_dir, save_every=save_every,
                                expect_fingerprint=ckpt.fingerprint(cfg, qcfg))
    state, start = mgr.restore_or_init(
        lambda: jax.jit(lambda k: init_state(k, cfg, qcfg, tcfg),
                        out_shardings=state_sh)(key),
        like, shardings=state_sh)
    if start:
        print(f"restored from step {start} (elastic reshard onto "
              f"{mesh.size} devices)")

    step_fn = jax.jit(make_train_step(cfg, run_qcfg, tcfg,
                                      constrain=constrain,
                                      logits_constrain=logits_constrain,
                                      extra_loss=extra_loss),
                      in_shardings=(state_sh, None),
                      out_shardings=(state_sh, None), donate_argnums=0)
    runner = (SentinelRunner(tcfg.sentinel, mgr, like, state_sh)
              if tcfg.sentinel is not None else None)

    host = jax.process_index()
    t_log, n_log = time.monotonic(), 0   # host clock and steps since last log
    m: dict = {}
    losses: list = []
    steps_run = 0
    preempted = False
    # A checkpoint labelled s is taken AFTER loop index s completed, so a
    # restore/rollback at label s resumes at s + 1 (the data stream is
    # (step, host)-keyed, so the replay is identical).
    i = start if start == 0 else start + 1
    # which mesh and which matmul path the step ran: a trace says so itself
    step_args = dict(data=mesh.shape["data"], model=mesh.shape["model"],
                     fused_matmul=run_qcfg.fused_matmul)
    while i < steps:
        with jax.profiler.StepTraceAnnotation(tracing.TRAIN_STEP, step_num=i,
                                              **step_args):
            if on_step is not None:
                with tracing.span(tracing.TRAIN_HOOK):
                    injected = on_step(i, state)
                if injected is not None:
                    state = injected
            with tracing.span(tracing.TRAIN_INPUT):
                batch = sample_batch(cfg, dcfg, i, batch_size, seq_len,
                                     host_index=host)
                if tcfg.kd == "mckd":
                    idx, p = synthetic_kd_labels(batch["labels"],
                                                 cfg.vocab_size,
                                                 tcfg.kd_topk, seed=i)
                    batch.update(kd_idx=idx, kd_p=p)
            with tracing.span(tracing.TRAIN_DISPATCH):
                state, m = step_fn(state, batch)
            losses.append(m["loss"])  # device scalar: no host sync here
            steps_run += 1
            n_log += 1
            if runner is not None:
                with tracing.span(tracing.TRAIN_SYNC):
                    health = int(m["health"])
                if health:
                    with tracing.span(tracing.TRAIN_SYNC):
                        skipped = int(m["sentinel_skipped"])
                    print(f"step {i:5d} health={describe(health)} "
                          f"(skipped={skipped})", flush=True)
                if runner.observe(health):
                    state, i = runner.rollback(state)
                    print(f"sentinel: {runner.scfg.k_consecutive} "
                          f"consecutive fatal steps -> rolled back to step "
                          f"{i - 1}, lr_scale="
                          f"{float(state['sent'].lr_scale):.3g} (retry "
                          f"{runner.retries}/{runner.scfg.max_retries})",
                          flush=True)
                    continue
            with tracing.span(tracing.TRAIN_SAVE):
                slow = mgr.straggler.tick()
                mgr.maybe_save(state, i)
                stop = mgr.should_stop()
            if log_every and i % log_every == 0:
                with tracing.span(tracing.TRAIN_SYNC):
                    loss, lr = map(float, jax.device_get((m["loss"], m["lr"])))
                now = time.monotonic()
                print(f"step {i:5d} loss={loss:.4f} lr={lr:.2e} "
                      f"{(now - t_log) / n_log:.2f}s/step"
                      f"{' STRAGGLER' if slow else ''}", flush=True)
                t_log, n_log = now, 0
            if stop:
                print("preemption: final forced checkpoint + clean exit")
                mgr.maybe_save(state, i, force=True)
                preempted = True
                break
            i += 1
    mgr.finalize()
    mgr.guard.restore_handlers()
    return RunReport(
        final_step=i if preempted else i - 1,
        final_loss=float(m["loss"]) if m else float("nan"),
        steps_run=steps_run,
        rollbacks=runner.rollbacks if runner is not None else 0,
        skipped=int(m.get("sentinel_skipped", 0)) if m else 0,
        lr_scale=float(m.get("lr_scale", 1.0)) if m else 1.0,
        preempted=preempted,
        straggler_flags=mgr.straggler.flags,
        losses=[float(v) for v in losses],
        step_fn=step_fn)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="granite-8b", choices=ARCH_IDS)
    ap.add_argument("--quant", default="w4a4")
    ap.add_argument("--smoke", action="store_true",
                    help="reduced config (CPU-sized)")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=16)
    ap.add_argument("--seq", type=int, default=64)
    ap.add_argument("--grad-accum", type=int, default=1, dest="grad_accum")
    ap.add_argument("--model-parallel", type=int, default=1, dest="mp")
    ap.add_argument("--lr", type=float, default=3e-3)
    ap.add_argument("--kd", default="mckd", choices=("none", "mckd"))
    ap.add_argument("--compress-grads", action="store_true", dest="compress")
    ap.add_argument("--ckpt", default="")
    ap.add_argument("--save-every", type=int, default=100, dest="save_every")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--tpu-flags", action="store_true", dest="tpu_flags")
    ap.add_argument("--no-sentinel", action="store_true", dest="no_sentinel",
                    help="disable in-step health checks + rollback recovery "
                         "(overhead benchmarking escape hatch)")
    args = ap.parse_args()

    if args.tpu_flags:  # before anything starts the backend
        os.environ["LIBTPU_INIT_ARGS"] = (
            os.environ.get("LIBTPU_INIT_ARGS", "") + " " + TPU_PERF_FLAGS)
    if "JAX_COORDINATOR_ADDRESS" in os.environ:  # multi-host slice
        jax.distributed.initialize()
    setup_compile_cache()

    cfg = get_config(args.arch)
    if args.smoke:
        cfg = reduced_config(cfg)
    qcfg = get_preset(args.quant)
    tcfg = TrainConfig(total_steps=args.steps,
                       warmup_steps=max(args.steps // 20, 2),
                       grad_accum=args.grad_accum, kd=args.kd, kd_topk=16,
                       compress_grads=args.compress,
                       adamw=AdamWConfig(lr_peak=args.lr),
                       sentinel=None if args.no_sentinel else SentinelConfig())
    dcfg = DataConfig(seed=args.seed)
    print(f"arch={cfg.name} quant={args.quant} kd={args.kd} "
          f"accum={args.grad_accum} "
          f"sentinel={'off' if args.no_sentinel else 'on'}")

    report = run_training(
        cfg, qcfg, tcfg, dcfg, steps=args.steps, batch_size=args.batch,
        seq_len=args.seq, ckpt_dir=args.ckpt or f"/tmp/ckpt-{cfg.name}",
        save_every=args.save_every, model_parallel=args.mp, seed=args.seed)
    print(f"done. final_step={report.final_step} "
          f"loss={report.final_loss:.4f} rollbacks={report.rollbacks} "
          f"skipped={report.skipped} preempted={report.preempted}")


if __name__ == "__main__":
    main()
