"""The benchmark's counts of work against hand counts at the published
sizes of qwen1.5-0.5b and granite-8b."""
import json
import os

import pytest

from bench.harness import work
from bench.harness.peaks import PEAKS, peaks_for

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def cfg(name):
    with open(os.path.join(ROOT, "bench", "configs", name + ".json")) as f:
        return json.load(f)


QWEN = cfg("qwen1.5-0.5b-qat-w4a4")
GRANITE = cfg("granite-8b-serve-w4-kv8")


def test_matmul_params_by_hand():
    # qwen: q, k, v, o of 1024 x 1024, three FFN mats of 1024 x 2816 per
    # layer, 24 layers, and the tied head 1024 x 151936
    assert work.matmul_params(QWEN) == 24 * (4 * 1024 * 1024
                                             + 3 * 1024 * 2816) \
        + 1024 * 151936 == 463_863_808
    # granite: q, o 4096 x 4096; k, v 4096 x 1024; FFN 3 x 4096 x 14336
    assert work.matmul_params(GRANITE) == 36 * 218_103_808 \
        + 4096 * 49152 == 8_053_063_680


def test_forward_and_train_flops_by_hand():
    per_ctx = 4 * 24 * 16 * 64        # QK^T and PV, every layer and head
    assert work.forward_flops_per_token(QWEN, 100) == \
        2 * 463_863_808 + per_ctx * 100
    step = work.train_flops_per_step(QWEN, 2, 1024)
    assert step == pytest.approx(3 * 2048 * (2 * 463_863_808
                                             + per_ctx * 512.5))
    assert 2.9e9 < step / 2048 < 3.0e9   # ~2.93 GFLOP per trained token


def test_qat_matmul_calls_by_hand():
    calls = work.qat_matmul_calls(QWEN, 2048)
    assert len(calls) == 7 * 24 + 1
    flops, byts = calls[0]              # wq: K = N = 1024, M = 2048
    m, k, n = 2048, 1024, 1024
    assert flops == 6 * m * k * n
    fwd = 2 * m * k + 4 * k * n + 2 * m * n
    bwd = 2 * m * n + 2 * m * k + 4 * k * n + 2 * m * k + 4 * k * n
    assert byts == fwd + bwd == 33_554_432
    head = calls[-1]
    assert head[0] == 6 * 2048 * 1024 * 151936


def test_decode_attention_work_by_hand():
    flops, byts = work.decode_attention_work(GRANITE, 1, 1000)
    assert flops == 2 * 2 * 32 * 128 * 1000
    # int8 K and V codes, an f32 scale each per (position, kv head);
    # a bf16 query in and an f32 accumulator out
    assert byts == 1000 * 8 * (2 * 128 + 2 * 4) + 32 * 128 * (2 + 4)
    log = [("decode", [999, -1, 9]), ("prefill", 256, 100)]
    calls = work.decode_attention_calls(GRANITE, log)
    assert len(calls) == 2 * 36
    assert calls[0][0] == 4 * 32 * 128 * (999 + 9)
    assert calls[36][0] == 4 * 32 * 128 * 100 * 256


def test_served_flops_by_hand():
    dec = work.served_flops(GRANITE, [("decode", [4, -1])])
    assert dec == work.forward_flops_per_token(GRANITE, 5)
    head = 2 * 4096 * 49152
    total = work.served_flops(GRANITE, [("decode", [4, -1]),
                                        ("prefill", 0, 2)])
    assert total - dec == pytest.approx(
        work.forward_flops_per_token(GRANITE, 1)
        + work.forward_flops_per_token(GRANITE, 2) - 2 * head)


def test_roofline_picks_the_binding_peak():
    p = peaks_for("TPU v5 lite")
    t, bound = work.roofline_seconds(197e12, 1.0, p)
    assert bound == "compute" and t == pytest.approx(1.0)
    t, bound = work.roofline_seconds(1.0, 819e9, p)
    assert bound == "bandwidth" and t == pytest.approx(1.0)


def test_peaks_table_has_sources_and_refuses_unknown_kinds():
    assert all(v["source"] for v in PEAKS.values())
    with pytest.raises(KeyError):
        peaks_for("TPU v99")
