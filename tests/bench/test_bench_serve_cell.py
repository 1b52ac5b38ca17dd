"""The serving loop end to end on the CPU at a tiny size, with the chip
check skipped: a sound run comes out correct; a token altered where it is
produced, a decode step that leaves the cache unchanged, or the control
comes out not correct."""
import json
import os
import time

import jax
import numpy as np
import pytest

from bench.harness import spec as S
from bench.harness.run import Context, checks_pass

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
# the bfloat16 program reads a widest gap under 1e-3 at this size; the
# control and the faults read over 1e-2
LIMIT = 5e-3


@pytest.fixture(scope="module")
def cell():
    c = json.load(open(os.path.join(
        ROOT, "bench", "configs", "granite-8b-serve-w4-kv8.json")))
    c.update(num_hidden_layers=2, hidden_size=128, num_attention_heads=4,
             num_key_value_heads=2, intermediate_size=256, vocab_size=512,
             limits={"served_logit_gap": LIMIT})
    c["serve"] = dict(c["serve"], slots=4, max_len=128, chunk=16)
    tr = json.load(open(os.path.join(ROOT, "bench", "traffic",
                                     "chat_poisson.json")))
    tr.update(rate_rps=4, ramp_s=1, first_token_wait_s=30, trace_at=0.5,
              trace_s=0.5, check_tokens=40, check_rows=3,
              prompt={"median": 20, "sigma": 0.5, "min": 8, "max": 60},
              output={"median": 12, "sigma": 0.5, "min": 4, "max": 40})
    return S.Cell(name="tiny", chips=1, config=c, config_entry={},
                  traffic_name="tiny", traffic=tr, end_to_end=[],
                  per_layer=[])


@pytest.fixture(scope="module")
def loop():
    return S.load_module("loops", "serve")


def run(loop, cell, broken=None, trace=False):
    return loop.run(Context(cell, jax.devices()[:1], 5, 3.0, trace,
                              time.monotonic()), broken=broken)


@pytest.fixture(scope="module")
def sound(loop, cell):
    return run(loop, cell, trace=True)


def test_a_sound_run_is_correct(sound):
    assert checks_pass(sound.checks), sound.checks
    assert sound.attempted == 12 and sound.failed == 0
    e = sound.end_to_end
    assert e["ttft_p90_ms"] > 0 and e["itl_p95_ms"] > 0
    assert e["serve_out_tok_s"] > 0 and e["setup_s"] > 0
    kinds = {call[0] for call in sound.work["log"]}
    assert kinds <= {"decode", "prefill"} and "decode" in kinds


def test_an_altered_token_is_caught(loop, cell):
    import repro.serve.engine as E
    real = E.sample_token

    def broken(engine):
        E.sample_token = lambda row, sp, i: (
            (real(row, sp, i) + 1) % row.shape[-1] if i == 2
            else real(row, sp, i))
    try:
        out = run(loop, cell, broken)
    finally:
        E.sample_token = real
    assert not checks_pass(out.checks)


def test_a_decode_that_leaves_the_cache_unchanged_is_caught(loop, cell):
    def broken(engine):
        inner = engine.executor.inner
        real = inner.decode

        def decode(tokens, pos):
            pool = inner.pool
            out = real(tokens, pos)
            inner.pool = pool
            return out
        inner.decode = decode
    assert not checks_pass(run(loop, cell, broken).checks)


def test_the_control_is_caught(loop, cell, sound):
    seqs = sound.work["seqs"]
    ref = loop.reference_logits(cell.config, 5, seqs)
    assert loop.served_gap(ref, seqs) <= LIMIT
    ctl = loop.reference_logits(cell.config, 5, seqs, "fp8")
    assert loop.control_gap(ref, ctl) > LIMIT
    assert np.isfinite(loop.control_gap(ref, ctl))
