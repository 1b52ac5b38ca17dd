"""The readers of the program's spans and of the device ops its layers own,
on hand-built traces: each finds what is there, and reads None where the
trace holds none of it (a program without spans)."""
import pytest

from bench.harness import layers
from bench.harness import trace as T
from bench.harness.run import Outcome
from bench.harness.spec import load_module


def ev(name, s, e, **stats):
    return T.Event(name, s, e, stats)


def op(s, e, hlo):
    """A device op: its HLO text and times."""
    return T.Event(hlo, s, e, {})


QWEN = {"arch": "qwen1.5-0.5b", "vocab_size": 151936}   # padded to 152064
GRANITE = {"arch": "granite-8b", "num_hidden_layers": 36}


def outcome(tr, config=None, **work):
    return Outcome(attempted=1, failed=0, end_to_end={}, checks={}, device={},
                   trace=tr, work=work, config=config or {})


def read(metric, run):
    return load_module("metrics", metric).read(run)


@pytest.fixture
def train_trace():
    host = [ev(T.WINDOW, 0, 1000),
            # the window opens inside a step whose span was not recorded
            ev("train.input", 20, 80), ev("train.dispatch", 80, 95),
            ev("train.sync", 95, 99),
            ev("train.step", 100, 500, step_num=1),
            ev("train.hook", 100, 120), ev("train.input", 120, 220),
            ev("train.dispatch", 220, 240), ev("train.sync", 240, 400),
            ev("train.save", 400, 420),
            ev("train.step", 500, 900, step_num=2),
            ev("train.input", 520, 620), ev("train.dispatch", 620, 640),
            ev("train.sync", 640, 850), ev("train.sync", 860, 880),
            # the hook that closes the window: no step dispatched
            ev("train.step", 900, 1000, step_num=3), ev("train.hook", 900, 990)]
    ops = [op(30, 60, "%copy.1 = s32[2,16]{1,0} copy(s32[2,16]{1,0} %args_0_.1)"),
           op(150, 200, "%fusion.2 = f32[4] fusion(f32[4] %p)"),
           op(250, 300, "%quant_matmul.144 = f32[32,152064]{1,0} custom-call("
                        "bf16[32,64]{1,0} %b, f32[64,152064]{1,0} %w)"),
           op(300, 350, "%quant_matmul_dx.1 = (f32[32,64]{1,0}) custom-call("
                        "f32[32,152064]{1,0} %g, f32[64,152064]{1,0} %w)"),
           op(350, 450, "%while.3 = (s32[], f32[32,64]) while(...)"),
           op(360, 400, "%quant_matmul.7 = f32[32,64]{1,0} custom-call("
                        "bf16[32,64]{1,0} %x, f32[64,64]{1,0} %w)"),
           op(450, 460, "%fusion.3 = f32[4866048]{0} fusion(f32[] %c)"),
           op(560, 620, "%copy.2 = s32[2,16]{1,0} copy(s32[2,16]{1,0} %a)"),
           op(650, 700, "%subtract_select_fusion = f32[152064,64]{0,1} "
                        "fusion(f32[152064,64]{0,1} %p, f32[] %lr)"),
           op(700, 710, "%fusion.4 = f32[1520640]{0} fusion(f32[] %c)"),
           op(1100, 1200, "%quant_matmul.144 = f32[32,152064]{1,0} "
                          "custom-call(bf16[32,64]{1,0} %b)")]
    return T.Trace(ops={"/device:TPU:0": ops}, host=host, window=(0, 1000))


@pytest.fixture
def serve_trace():
    host = [ev(T.WINDOW, 0, 1000),
            ev("serve.step", 0, 300, queue=0, active=1, pending=1),
            ev("serve.schedule", 2, 8),
            ev("serve.prefill", 10, 100, rid="a", start=0),
            ev("serve.sync", 80, 100),
            ev("serve.decode", 100, 250, active=1), ev("serve.sync", 230, 250),
            ev("serve.step", 300, 600, queue=0, active=2, pending=0),
            ev("serve.decode", 310, 560, active=2), ev("serve.sync", 540, 560),
            ev("serve.step", 950, 1100, queue=0, active=2, pending=0)]
    ops = [op(20, 60, "%dynamic-slice_bitcast_fusion.1 = s8[1,256,8,128] "
                      "fusion(s8[36,1,256,8,128] %gte, s32[] %i)"),  # a chunk
           op(110, 130, "%dynamic-slice_bitcast_fusion.2 = s8[4,256,8,128] "
                        "fusion(s8[36,4,256,8,128] %gte, s32[] %i)"),
           op(110, 220, "%while.2 = (s32[], bf16[4,1,64], s8[36,4,256,8,128]) "
                        "while(...)"),               # the loop: self 20
           op(130, 180, "%int4_matmul.36 = f32[128,64] custom-call("
                        "bf16[128,64] %x, s8[32,64] %w)"),
           op(180, 200, "%bitcast_dynamic-update-slice_fusion.6 = "
                        "s8[36,4,256,8,128] fusion(s8[36,4,256,8,128] %gte, "
                        "s32[] %i, s8[4,256,8,128] %new)"),
           op(220, 230, "%int_matmul.1 = f32[128,512] custom-call(...)"),
           op(235, 245, "%dynamic-update-slice.9 = s32[36,4,256] "
                        "dynamic-update-slice(s32[36,4,256] %c)"),  # no loop
           op(320, 350, "%while.2 = (s32[], bf16[4,1,64], s8[36,4,256,8,128]) "
                        "while(...)"),
           op(325, 335, "%constant_dynamic-slice_fusion.24 = s8[1,32,8,128] "
                        "fusion(s8[36,32,8,128] %gte, s32[] %i)"),
           op(335, 345, "%closed_call.6 = (f32[4,8,8,128]) custom-call("
                        "bf16[4,8,8,128] %q, s8[4,256,8,128] %k)")]
    return T.Trace(ops={"/device:TPU:0": ops}, host=host, window=(0, 1000))


def test_dims_are_read_from_the_types():
    e = op(0, 1, "%fusion.1 = s8[4,256] fusion(s8[36,4,256] %g, s32[] %i)")
    assert layers.has_dim(e, 36) and layers.has_dim(e, 256)
    assert layers.has_dim(e, 36, leading=True)
    assert not layers.has_dim(e, 256, leading=True)
    assert not layers.has_dim(e, 25) and not layers.has_dim(e, 3)
    assert not layers.has_dim(op(0, 1, "%fusion.36 = f32[] fusion()"), 36)


def test_train_input_idle_ms(train_trace):
    # idle inside the three input spans: 30 + 50 + 40 ns
    got = read("train_input_idle_ms", outcome(train_trace))
    assert got == pytest.approx(1e3 * 120e-9 / 3)


def test_host_syncs_train_counts_syncs_of_dispatching_steps(train_trace):
    # steps 1 and 2 dispatched; step 2 also read its loss for a log line
    assert read("host_syncs.train", outcome(train_trace)) == 1.5


def test_lm_head_ms_sums_the_vocabulary_wide_ops(train_trace):
    # the head's forward and dX kernels, the optimizer's pass over the
    # table and the flattened logits (32 x 152064): 50 + 50 + 50 + 10 ns
    # over 2 steps; the blocks' kernel, an op of another width and the op
    # past the window do not count
    got = read("lm_head_ms", outcome(train_trace, QWEN, steps=2, batch=2,
                                     seq_len=16))
    assert got == pytest.approx(1e3 * 160e-9 / 2)


def test_scan_copy_ms_reads_the_loop_around_the_blocks(serve_trace):
    # decode 1: slice 20 + the loop's own 20 + update 20; decode 2: slice
    # 10 + the loop's own 10; the blocks' kernels, the head, a stacked
    # update outside the loop and the prompt chunk do not count
    got = read("scan_copy_ms", outcome(serve_trace, GRANITE))
    assert got == pytest.approx(1e3 * 80e-9 / 2)


def test_host_syncs_serve(serve_trace):
    # two whole steps in the window, three syncs in them
    assert read("host_syncs.serve", outcome(serve_trace)) == 1.5


@pytest.mark.parametrize("metric", ["train_input_idle_ms", "host_syncs.train",
                                    "lm_head_ms", "scan_copy_ms",
                                    "host_syncs.serve"])
def test_none_without_the_programs_spans(metric):
    bare = T.Trace(ops={"/device:TPU:0": [
        ev("%fusion.1 = f32[8] fusion(...)", 10, 20)]},
        host=[ev(T.WINDOW, 0, 100), ev("bench.decode", 5, 30),
              ev("bench.engine_step", 0, 40)], window=(0, 100))
    for cfg in (QWEN, GRANITE):
        work = dict(steps=2, batch=2, seq_len=16)
        assert read(metric, outcome(bare, cfg, **work)) is None
        assert read(metric, outcome(None, cfg, **work)) is None
