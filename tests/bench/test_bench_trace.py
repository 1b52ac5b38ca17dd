"""The reduction from a profiler trace to busy time, idle share, kernel
time and idle gaps, on a hand-built trace."""
import pytest

from bench.harness import trace as T


def ev(name, s, e, **stats):
    return T.Event(name, s, e, stats)


@pytest.fixture
def tr():
    ops = {"/device:TPU:0": [
        ev("%fusion.1 = bf16[8] fusion(...)", 100, 300),
        ev("%quant_matmul.7 = f32[8] custom-call(...)", 300, 400),
        ev("%quant_matmul_bwd.9 = (f32[8]) custom-call(...)", 600, 700),
        ev("%copy.3 = f32[8] copy(...)", 1200, 1300),   # after the window
    ]}
    host = [ev(T.WINDOW, 0, 1000), ev("bench.decode", 90, 420),
            ev("bench.engine_step", 50, 800), ev("PjitFunction(step)", 560, 580)]
    return T.Trace(ops=ops, host=host, window=(0, 1000))


def test_union_merges_overlaps_and_clips():
    assert T.union([(0, 10), (5, 20), (30, 40)], 0, 100) == 30
    assert T.union([(0, 10), (5, 20), (30, 40)], 8, 35) == 17
    assert T.union([], 0, 10) == 0


def test_busy_and_idle_share(tr):
    # ops cover 100-400 and 600-700 inside the window, 1000-1300 is out
    assert T.busy_s(tr) == pytest.approx(400e-9)
    assert T.idle_share(tr) == pytest.approx(0.6)
    assert tr.window_s == pytest.approx(1e-6)


def test_kernel_sums_match_by_kernel_name(tr):
    assert T.kernel_s(tr, r"^%quant_matmul(_bwd)?\.") == pytest.approx(200e-9)
    assert T.kernel_s(tr, r"^%quant_matmul\.") == pytest.approx(100e-9)
    assert T.kernel_s(tr, "nothing") == 0.0


def test_self_time_leaves_out_nested_ops():
    outer = ev("%while.1 = (...) while(...)", 0, 100)
    inner = [ev("%fusion.2 = f32[1] fusion()", 10, 30),
             ev("%quant_matmul.3 = f32[1] custom-call()", 40, 90)]
    own = {T.op_label(e): t for e, t in T.self_times([outer] + inner)}
    assert own == {"while": 30, "fusion": 20, "quant_matmul": 50}


def test_device_time_inside_spans(tr):
    t, n = T.device_s_in(tr, "bench.decode")
    assert n == 1 and t == pytest.approx(300e-9)   # 100-400


def test_breakdown_labels_gaps_by_innermost_host_span(tr):
    b = T.breakdown(tr)
    ops = dict(b["device_ops"])
    assert ops["quant_matmul"] == pytest.approx(100e-9)
    assert ops["fusion"] == pytest.approx(200e-9)
    gaps = dict(b["idle_gaps"])
    # gaps 0-100 and 400-600 have their middles in engine_step (50-800);
    # 700-1000 in no host span
    assert gaps["bench.engine_step"] == pytest.approx(300e-9)
    assert gaps["host (no span)"] == pytest.approx(300e-9)
    assert len(b["device_ops"]) <= 10 and len(b["idle_gaps"]) <= 10
