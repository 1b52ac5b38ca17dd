"""The readers of the mesh's collectives, of the sharded step's share of
the chips and of its vocabulary-wide work, on hand-built device traces."""
import pytest

from bench.harness import trace as T
from bench.harness.collectives import collective_s, is_collective
from bench.harness.run import Outcome
from bench.harness.spec import load_module

AR = "%all-reduce.94 = f32[]{:T(128)} all-reduce(f32[] %abs_reduce_fusion.7)"
AG_START = ("%async-collective-start.1 = (bf16[2048,4,128], bf16[4096,4,128]) "
            "fusion(bf16[2048,4,128] %fusion.884), kind=kCustom")
AG_DONE = ("%async-collective-done.1 = bf16[4096,4,128] fusion(bf16[2048,4,"
           "128] %get-tuple-element.6749)")
CP_START = ("%collective-permute-start.1 = (bf16[32,7168], bf16[32,7168]) "
            "collective-permute-start(bf16[32,7168] %slice.195)")
CP_DONE = ("%collective-permute-done.1 = bf16[32,7168] collective-permute-"
           "done((bf16[32,7168], bf16[32,7168]) %collective-permute-start.1)")
FUSION = "%fusion.12 = bf16[4096] fusion(bf16[4096] %all-reduce.94)"
LOOP = "%while.3 = (s32[], f32[8]) while((s32[], f32[8]) %tuple.5)"


def op(s, e, hlo):
    return T.Event(hlo, s, e, {})


def trace(*devices, window=(0, 1000)):
    ops = {f"/device:TPU:{i}": list(evs) for i, evs in enumerate(devices)}
    return T.Trace(ops=ops, host=[T.Event(T.WINDOW, *window)], window=window)


def read(metric, tr, steps=2, config=None, **work):
    run = Outcome(attempted=1, failed=0, end_to_end={}, checks={}, device={},
                  trace=tr, work={"steps": steps, "batch": 4,
                                  "seq_len": 1024, **work},
                  config=config or {},
                  peaks={"bf16_flops": 197e12})
    return load_module("metrics", metric).read(run)


def test_collectives_are_known_by_name_or_opcode():
    for hlo in (AR, AG_START, AG_DONE, CP_START, CP_DONE,
                "%reduce-scatter.3 = f32[8] reduce-scatter(f32[32] %x)",
                "%all-gather-start.2 = (f32[8], f32[32]) all-gather-start("
                "f32[8] %x)", "%ar.1 = f32[8] all-reduce(f32[8] %x)"):
        assert is_collective(op(0, 1, hlo)), hlo
    for hlo in (FUSION, LOOP, "%copy-done = u32[2] copy-done(%copy-start)"):
        assert not is_collective(op(0, 1, hlo)), hlo


@pytest.fixture
def mesh_trace():
    """Two devices. Device 0: a sync all-reduce (100), an async all-gather
    from 200 to 500 over two compute ops (300-350, 360-400), and a loop
    that holds a collective-permute pair (600-700) and nothing else: the
    loop's own event hides nothing. Device 1: one all-reduce of 50."""
    dev0 = [op(0, 100, AR),
            op(200, 220, AG_START), op(300, 350, FUSION),
            op(360, 400, FUSION), op(480, 500, AG_DONE),
            op(590, 720, LOOP), op(600, 610, CP_START),
            op(690, 700, CP_DONE)]
    dev1 = [op(0, 50, AR), op(60, 900, FUSION)]
    return trace(dev0, dev1)


def test_collective_time_runs_from_start_to_done(mesh_trace):
    # device 0: 100 + 300 + 100 = 500 ns; device 1: 50 ns
    assert collective_s(mesh_trace) == pytest.approx(275e-9)
    assert read("collective_ms.train", mesh_trace) == pytest.approx(
        275e-6 / 2)


def test_exposed_time_leaves_out_what_other_ops_hide(mesh_trace):
    # device 0: the all-reduce 100, the all-gather 300 - 90 hidden, the
    # permute 100 (the loop hides nothing); device 1: 50
    assert collective_s(mesh_trace, exposed=True) == pytest.approx(
        (100 + 210 + 100 + 50) / 2 * 1e-9)
    assert read("exposed_collective_ms.train", mesh_trace) == \
        pytest.approx(230e-6 / 2)


def test_collectives_are_clipped_to_the_window():
    tr = trace([op(900, 920, AG_START), op(1100, 1200, AG_DONE)])
    assert collective_s(tr) == pytest.approx(100e-9)


def test_no_collectives_or_no_trace_read_none():
    tr = trace([op(0, 100, FUSION)])
    for m in ("collective_ms.train", "exposed_collective_ms.train"):
        assert read(m, tr) is None
        assert load_module("metrics", m).read(Outcome(
            1, 0, {}, {}, {}, trace=None, work={"steps": 2})) is None


def test_mesh_mfu_divides_by_every_chip():
    from bench.harness import work
    c = {"arch": "granite-8b", "num_hidden_layers": 2, "hidden_size": 4096,
         "num_attention_heads": 32, "num_key_value_heads": 8,
         "intermediate_size": 14336, "vocab_size": 49152}
    busy = [op(0, 500, FUSION)]
    tr = trace(busy, busy, busy, busy)
    flops = 2 * work.train_flops_per_step(c, 4, 1024)
    want = 100.0 * flops / (500e-9 * 4 * 197e12)
    assert read("train_mesh_mfu", tr, config=c) == pytest.approx(want)
    # the one-chip reader over the same trace reads four times as much
    assert read("train_step_mfu", tr, config=c) == pytest.approx(4 * want)


GRANITE = {"arch": "granite-8b", "num_hidden_layers": 2, "hidden_size": 4096,
           "num_attention_heads": 32, "num_key_value_heads": 8,
           "intermediate_size": 14336, "vocab_size": 49152}
HEAD = ("%convolution_bitcast_fusion.3 = bf16[2,1024,24576] fusion(bf16[2,"
        "1024,2048] %p.1, bf16[2048,24576] %p.2), kind=kOutput")
LOSS = ("%exponential_reduce_fusion.1 = f32[2,1024] fusion(f32[2,1024,24576] "
        "%convolution_bitcast_fusion.3), kind=kLoop")
WHOLE = "%fusion.7 = f32[4096,49152] fusion(f32[4096,49152] %p.3)"
MLP = "%fusion.8 = bf16[2,1024,7168] fusion(bf16[2,1024,2048] %p.4)"


def test_mesh_head_reads_the_vocabulary_split_over_the_model_axis():
    """Ops carrying the padded vocabulary over the model axis count, once
    each (self time); the unsplit width and other widths do not."""
    evs = [op(0, 100, HEAD), op(100, 160, LOSS), op(160, 400, WHOLE),
           op(400, 700, MLP)]
    tr = trace(evs, evs)
    got = read("lm_head_ms.mesh", tr, config=GRANITE, model_parallel=2)
    assert got == pytest.approx(1e3 * 160e-9 / 2)
    # the one-device reader matches only the unsplit width
    assert read("lm_head_ms", tr, config=GRANITE) == pytest.approx(
        1e3 * 240e-9 / 2)


@pytest.mark.parametrize("work", [{}, {"model_parallel": 1}])
def test_mesh_head_reads_none_without_a_model_axis(work):
    evs = [op(0, 100, HEAD)]
    assert read("lm_head_ms.mesh", trace(evs), config=GRANITE, **work) is None
    assert read("lm_head_ms.mesh", None, config=GRANITE,
                model_parallel=2) is None
