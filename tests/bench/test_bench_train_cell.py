"""The training loop end to end on the CPU at a tiny size, with the
chip check skipped: sound runs come out correct and leave nothing behind;
a broken step or the control comes out not correct.

The program runs here in float32 (the cell states bfloat16), so that its
readings sit near rounding and the tiny limits below can be tight; on the
chip the cell's own limits come from the readings in PERF.md."""
import json
import os
import tempfile
import time

import jax
import pytest

from bench.harness import program
from bench.harness import spec as S
from bench.harness.run import Context, checks_pass

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
TINY = dict(num_hidden_layers=2, hidden_size=64, num_attention_heads=4,
            num_key_value_heads=4, intermediate_size=128, vocab_size=512)
# float32 program vs reference at this size and seed reads 3e-6 (loss),
# 2e-4 (gradient), 1e-4 (change), 2e-4 (row balance), 1e-3 (quantizer
# gradient), 4e-4 (quantizer change); half a batch reads 1 (row balance),
# the bfloat16-master control over 12 (change)
LIMITS = {"loss_gap": 2e-4, "grad_gap": 5e-3, "change_gap": 2e-3,
          "row_balance": 1e-2, "qgrad_gap": 2e-2, "qchange_gap": 2e-2}


@pytest.fixture(scope="module")
def cell():
    c = json.load(open(os.path.join(
        ROOT, "bench", "configs", "qwen1.5-0.5b-qat-w4a4.json")))
    c.update(TINY, limits=LIMITS)
    tr = {"loop": "train", "batch": 4, "seq_len": 16, "setup_steps": 4,
          "trace_steps": 2}
    return S.Cell(name="tiny", chips=1, config=c, config_entry={},
                  traffic_name="tiny", traffic=tr, end_to_end=[],
                  per_layer=[])


@pytest.fixture(scope="module")
def loop(tmp_path_factory):
    """The loop, with a compilation cache of this module's own: its runs
    compile the same programs."""
    from jax.experimental.compilation_cache import compilation_cache as cc
    keys = ("jax_compilation_cache_dir",
            "jax_persistent_cache_min_compile_time_secs",
            "jax_enable_compilation_cache")
    was = {k: getattr(jax.config, k) for k in keys}
    jax.config.update("jax_compilation_cache_dir",
                      str(tmp_path_factory.mktemp("jax_cache")))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_enable_compilation_cache", True)
    cc.reset_cache()
    yield S.load_module("loops", "train")
    for k, v in was.items():
        jax.config.update(k, v)
    cc.reset_cache()


@pytest.fixture(autouse=True)
def float32_program(monkeypatch):
    real = program.arch_config
    monkeypatch.setattr(program, "arch_config",
                        lambda c: real(c).replace(dtype="float32"))


def run(loop, cell, seed=3):
    return loop.run(Context(cell, jax.devices()[:1], seed, 0.0, False,
                              time.monotonic()))


@pytest.fixture(scope="module")
def pair(loop, cell, tmp_path_factory):
    """Two sound runs of one seed in a row, with their temporary
    directory."""
    tmp = tmp_path_factory.mktemp("tmpdir")
    was = tempfile.tempdir
    tempfile.tempdir = str(tmp)
    real = program.arch_config
    program.arch_config = lambda c: real(c).replace(dtype="float32")
    try:
        return run(loop, cell), run(loop, cell), tmp
    finally:
        tempfile.tempdir = was
        program.arch_config = real


def test_runs_in_a_row_are_correct_and_leave_no_checkpoint(pair):
    a, b, tmp = pair
    assert checks_pass(a.checks) and checks_pass(b.checks), (a.checks,
                                                             b.checks)
    assert a.attempted >= 1 and a.failed == 0
    # a restored checkpoint would start the second run elsewhere
    assert a.work["losses"] == b.work["losses"]
    assert list(tmp.iterdir()) == []


def _broken(monkeypatch, wrap):
    import repro.launch.train as LT
    real = LT.make_train_step
    monkeypatch.setattr(LT, "make_train_step",
                        lambda *a, **k: wrap(real(*a, **k)))


def test_a_step_that_returns_its_state_unchanged_is_caught(
        loop, cell, monkeypatch):
    def wrap(step):
        def broken(state, batch):
            _, m = step(state, batch)
            return state, m
        return broken
    _broken(monkeypatch, wrap)
    out = run(loop, cell)
    assert not checks_pass(out.checks)
    assert out.checks["change_gap"]["value"] > 0.5


def test_half_the_batch_left_out_is_caught(loop, cell, monkeypatch):
    def wrap(step):
        return lambda state, batch: step(state, {
            k: v[: v.shape[0] // 2] for k, v in batch.items()})
    _broken(monkeypatch, wrap)
    out = run(loop, cell)
    assert not checks_pass(out.checks)
    assert out.checks["row_balance"]["value"] > 0.5


def test_a_job_the_sentinel_aborts_is_not_correct(loop, cell, monkeypatch):
    """A fatal streak at the window's first step rolls back with no
    checkpoint: the job dies, and the run says so."""
    from repro.launch import train as LT
    calls = []

    def observe(self, health):
        calls.append(health)
        return len(calls) > cell.traffic["setup_steps"]
    monkeypatch.setattr(LT.SentinelRunner, "observe", observe)
    out = run(loop, cell)
    assert out.checks["sentinel_abort"]["value"] == 1
    assert not checks_pass(out.checks)
    assert out.attempted == out.failed == 1


def test_the_control_is_caught(loop, cell, pair):
    c = dict(cell.config, _shapes=pair[0].work["shapes"])
    ref = loop.reference(c, cell.traffic, 3)
    got = loop.numbers(loop.reference(c, cell.traffic, 3, master="bf16"),
                       ref)
    assert any(got[k] > v for k, v in LIMITS.items()), got
