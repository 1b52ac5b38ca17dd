"""The traffic generator: deterministic per seed, the same requests at the
same times for every seed, and a faithful copy of the trainer's synthetic
stream."""
import numpy as np
import pytest

from bench.harness import traffic

MIX = {"rate_rps": 3.0, "mix_seed": 5,
       "prompt": {"median": 256, "sigma": 0.8, "min": 64, "max": 1024},
       "output": {"median": 256, "sigma": 0.8, "min": 32, "max": 768}}


def test_open_loop_is_deterministic_per_seed():
    a = traffic.open_loop(MIX, 2 ** 33 + 1, 40.0, 49152)
    b = traffic.open_loop(MIX, 2 ** 33 + 1, 40.0, 49152)
    assert [(r.due, r.max_new) for r in a] == [(r.due, r.max_new) for r in b]
    assert all(np.array_equal(x.prompt, y.prompt) for x, y in zip(a, b))


def test_every_seed_gets_the_same_work():
    a = traffic.open_loop(MIX, 1, 40.0, 49152)
    b = traffic.open_loop(MIX, 2 ** 33 + 2, 40.0, 49152)
    assert len(a) == len(b) == 120
    assert [(r.due, len(r.prompt), r.max_new) for r in a] == \
        [(r.due, len(r.prompt), r.max_new) for r in b]
    assert not any(np.array_equal(x.prompt, y.prompt) for x, y in zip(a, b))
    assert a[0].due == 0.0 and max(r.due for r in a) < 40.0
    for r in a:
        assert 64 <= len(r.prompt) <= 1024 and 32 <= r.max_new <= 768
        assert r.prompt.dtype == np.int32 and r.prompt.max() < 49152
    assert np.median([r.max_new for r in a]) == pytest.approx(256, rel=0.25)
    assert len({r.max_new for r in a}) > 20


def test_lm_batch_is_the_trainers_stream():
    from repro.configs.registry import get_config, reduced_config
    from repro.data.synthetic import DataConfig, sample_batch
    cfg = reduced_config(get_config("qwen1.5-0.5b"))
    data = {"mult": 31, "add": 17, "p_noise": 0.1}
    for seed, step in ((7, 0), (2 ** 33 + 5, 3)):
        ours = traffic.lm_batch(data, seed, step, 2, 32, cfg.vocab_size)
        theirs = sample_batch(cfg, DataConfig(seed=seed), step, 2, 32)
        for k in ("tokens", "labels"):
            assert np.array_equal(ours[k], np.asarray(theirs[k]))


def test_kd_labels_are_the_trainers_labels():
    from repro.data.mckd_store import synthetic_kd_labels
    labels = np.arange(12, dtype=np.int32).reshape(2, 6)
    idx, p = traffic.kd_labels(labels, 500, 16, step=4)
    ti, tp = synthetic_kd_labels(labels, 500, 16, seed=4)
    assert np.array_equal(idx, np.asarray(ti))
    assert np.allclose(p, np.asarray(tp)) and np.allclose(p.sum(-1), 1.0)
