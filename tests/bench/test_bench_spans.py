"""The program's own spans as the benchmark's trace loader reads them: the
trainer loop's under `train.step`, the serving engine's under `serve.step`,
on the CPU at a tiny size under `jax.profiler`."""
import math
import types

import jax
import numpy as np

from bench.harness.trace import Capture, inside
from repro.configs.registry import get_config, reduced_config
from repro.core.policy import QuantConfig
from repro.data.synthetic import DataConfig
from repro.train.fault_tolerance import CheckpointManager
from repro.train.sentinel import SentinelConfig
from repro.train.state import TrainConfig

CFG = reduced_config(get_config("qwen1.5-0.5b")).replace(n_layers=1)
QCFG = QuantConfig(w_bits=4, a_bits=4, mode="mdq")


def _train(tmp_path, steps, **kw):
    from repro.launch.train import run_training
    tcfg = TrainConfig(total_steps=10, warmup_steps=2,
                       sentinel=SentinelConfig())
    mgr = CheckpointManager(str(tmp_path), save_every=100, async_io=False)
    return run_training(CFG, QCFG, tcfg, DataConfig(), steps=steps,
                        batch_size=2, seq_len=16, ckpt_dir=str(tmp_path),
                        mgr=mgr, **kw)


def test_each_train_step_holds_one_input_dispatch_and_sync(tmp_path):
    with Capture() as cap:
        _train(tmp_path, 3, log_every=0)
    tr = cap.trace
    steps = tr.spans("train.step")
    assert sorted(s.stats["step_num"] for s in steps) == [0, 1, 2]
    for name in ("train.input", "train.dispatch", "train.sync",
                 "train.save"):
        spans = tr.spans(name)
        assert len(spans) == 3, name
        for st in steps:
            assert len(inside(spans, [st])) == 1, (name, st.stats)


def test_log_line_times_the_steps_since_the_last_line(tmp_path, capsys,
                                                      monkeypatch):
    """Step 0 pays the compile; the lines after it time only their own
    steps instead of averaging the compile in from the loop's start."""
    import repro.launch.train as T
    ticks = [0.0, 100.0, 101.0, 102.5]
    monkeypatch.setattr(T, "time", types.SimpleNamespace(
        monotonic=lambda: ticks.pop(0)))
    _train(tmp_path, 3, log_every=1)
    per_step = [float(w[:-len("s/step")]) for w in capsys.readouterr().out.split()
                if w.endswith("s/step")]
    assert per_step == [100.0, 1.0, 1.5]
    assert ticks == []


def test_engine_spans_nest_in_the_step_and_carry_the_request(tmp_path):
    from repro.models import model as M
    from repro.serve import (ModelExecutor, SamplingParams, Scheduler,
                             ServeEngine)
    cfg = reduced_config(get_config("granite-8b")).replace(n_layers=1)
    qcfg = QuantConfig(w_bits=8, a_bits=32, mode="mdq", kv_cache_bits=8)
    params = M.init_params(jax.random.PRNGKey(0), cfg, qcfg)
    ex = ModelExecutor(params, cfg, qcfg, n_slots=2, max_len=32, chunk=8)
    eng = ServeEngine(ex, Scheduler(max_len=32))
    rng = np.random.default_rng(0)
    prompts = {"a": 5, "b": 11, "c": 3}
    for rid, n in prompts.items():
        eng.submit(rng.integers(1, 200, n), SamplingParams(max_new_tokens=3),
                   rid=rid)
    with Capture() as cap:
        eng.run_until_idle()
    tr = cap.trace
    steps = tr.spans("serve.step")
    assert steps and all(set(s.stats) == {"queue", "active", "pending"}
                         for s in steps)
    prefill, decode = tr.spans("serve.prefill"), tr.spans("serve.decode")
    syncs = tr.spans("serve.sync")
    for name in ("serve.schedule", "serve.prefill", "serve.decode",
                 "serve.sample", "serve.finish", "serve.sync"):
        spans = tr.spans(name)
        assert spans and len(inside(spans, steps)) == len(spans), name
    # one prefill span a prompt chunk, each with its request and position
    assert sorted((s.stats["rid"], s.stats["start"]) for s in prefill) == \
        sorted((rid, c * 8) for rid, n in prompts.items()
               for c in range(math.ceil(n / 8)))
    assert sorted(s.stats["rid"] for s in tr.spans("serve.finish")) == \
        sorted(prompts)
    assert all(s.stats["active"] >= 1 for s in decode)
    # the logits cross to the host once a prompt chunk and once a decode
    assert len(inside(syncs, prefill)) == len(prefill)
    assert len(inside(syncs, decode)) == len(decode)
    assert len(syncs) == len(prefill) + len(decode)
    assert {r.finish_reason for r in eng.results.values()} == {"length"}
