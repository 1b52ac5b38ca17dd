"""Capture a short profiler trace and reduce it to intervals.

The reduction is the benchmark's own, so every PR computes the same numbers
the same way:

  * device busy time: the union of the intervals in which an operation ran
    on a device (the "XLA Ops" line of each device plane), averaged over
    the chips used;
  * idle share: 1 - busy / window, where the window is the benchmark's
    `bench.window` host span;
  * per-kernel time: the summed device durations of the operations whose
    HLO text (the event's name on TPU, "%quant_matmul.12 = f32[...]
    custom-call(...)") matches a pattern;
  * what the host was doing in each idle gap: the innermost host event
    (a `bench.*` span, or JAX's own dispatch events) that covers the gap.

All times are nanoseconds on the profiler's clock; host and device events
share it.
"""
from __future__ import annotations

import glob
import os
import re
import shutil
import tempfile
from dataclasses import dataclass, field

WINDOW = "bench.window"


@dataclass
class Event:
    name: str
    start: float
    end: float
    stats: dict = field(default_factory=dict)

    @property
    def dur(self) -> float:
        return self.end - self.start


@dataclass
class Trace:
    ops: dict            # device plane name -> [Event] (XLA ops)
    host: list           # [Event] on host threads
    window: tuple        # (start, end) of the bench.window span

    @property
    def window_s(self) -> float:
        return (self.window[1] - self.window[0]) * 1e-9

    def spans(self, name: str) -> list:
        return [e for e in self.host if e.name == name]


class Capture:
    """`with Capture() as cap:` traces the block; `cap.trace` after exit.
    The trace lives in a temporary directory that is removed once read."""

    def __enter__(self):
        import jax
        self.dir = tempfile.mkdtemp(prefix="bench-trace-")
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(self.dir, profiler_options=opts)
        self.ann = jax.profiler.TraceAnnotation(WINDOW)
        self.ann.__enter__()
        return self

    def __exit__(self, *exc):
        import jax
        self.ann.__exit__(None, None, None)
        jax.profiler.stop_trace()
        try:
            self.trace = load(self.dir) if exc[0] is None else None
        finally:
            shutil.rmtree(self.dir, ignore_errors=True)
        return False


def _events(line) -> list:
    return [Event(e.name, e.start_ns, e.start_ns + e.duration_ns,
                  {k: v for k, v in e.stats}) for e in line.events]


def load(path: str) -> Trace:
    from jax.profiler import ProfileData
    files = glob.glob(os.path.join(path, "**", "*.xplane.pb"), recursive=True)
    if not files:
        raise RuntimeError(f"no trace written under {path}")
    pd = ProfileData.from_file(files[0])
    ops, host = {}, []
    for plane in pd.planes:
        if plane.name.startswith("/device:") and "TPU" in plane.name:
            for line in plane.lines:
                if line.name == "XLA Ops":
                    ops[plane.name] = _events(line)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                host.extend(_events(line))
    win = [e for e in host if e.name == WINDOW]
    if not win:
        raise RuntimeError("the trace holds no bench.window span")
    return Trace(ops=ops, host=host,
                 window=(win[0].start, win[0].end))


def union(intervals, lo: float, hi: float) -> float:
    """Length of the union of (start, end) intervals clipped to [lo, hi]."""
    total, cur_s, cur_e = 0.0, None, None
    for s, e in sorted((max(s, lo), min(e, hi)) for s, e in intervals):
        if e <= s:
            continue
        if cur_e is None or s > cur_e:
            if cur_e is not None:
                total += cur_e - cur_s
            cur_s, cur_e = s, e
        else:
            cur_e = max(cur_e, e)
    if cur_e is not None:
        total += cur_e - cur_s
    return total


def busy_s(tr: Trace) -> float:
    """Seconds in the window in which some op ran, averaged over devices."""
    lo, hi = tr.window
    per = [union([(e.start, e.end) for e in evs], lo, hi)
           for evs in tr.ops.values()]
    return (sum(per) / len(per)) * 1e-9 if per else 0.0


def idle_share(tr: Trace) -> float | None:
    b, w = busy_s(tr), tr.window_s
    return None if w <= 0 or b <= 0 else 1.0 - b / w


def op_label(e: Event) -> str:
    """A stable name for an op: its HLO instruction name without the
    number ("%quant_matmul_bwd.77 = ..." -> "quant_matmul_bwd")."""
    m = re.match(r"%?([A-Za-z_][\w\-]*?)(?:\.\d+)*(?:\s*=|$)", e.name)
    return m.group(1) if m else e.name


def kernel_s(tr: Trace, pattern: str) -> float:
    """Summed device seconds of ops whose HLO text matches `pattern`,
    inside the window, averaged over devices."""
    rx = re.compile(pattern)
    lo, hi = tr.window
    per = [sum(e.dur for e in evs if lo <= e.start and e.end <= hi
               and rx.search(e.name)) for evs in tr.ops.values()]
    return (sum(per) / len(per)) * 1e-9 if per else 0.0


def self_times(evs: list) -> list:
    """(event, self time): ops nest on the ops line (a loop holds its
    body's ops); self time leaves out what nested ops cover."""
    out, stack = [], []
    for e in sorted(evs, key=lambda e: (e.start, -e.end)):
        while stack and stack[-1][0].end <= e.start:
            out.append(tuple(stack.pop()))
        if stack:
            stack[-1][1] -= e.dur
        stack.append([e, e.dur])
    out.extend(tuple(x) for x in reversed(stack))
    return out


def inside(events: list, spans: list) -> list:
    """Events that lie wholly within one of `spans`."""
    sp = sorted((s.start, s.end) for s in spans)
    out, j = [], 0
    for e in sorted(events, key=lambda e: e.start):
        while j < len(sp) and sp[j][1] < e.start:
            j += 1
        if j < len(sp) and sp[j][0] <= e.start and e.end <= sp[j][1]:
            out.append(e)
    return out


def device_s_in(tr: Trace, span_name: str) -> tuple[float, int]:
    """(busy device seconds within the named host spans, number of spans),
    averaged over devices."""
    spans = tr.spans(span_name)
    per = []
    for evs in tr.ops.values():
        ins = inside(evs, spans)
        per.append(union([(e.start, e.end) for e in ins], *tr.window))
    return ((sum(per) / len(per)) * 1e-9 if per else 0.0), len(spans)


def _host_label(mid: float, host: list) -> str:
    best = None
    for e in host:
        if e.name == WINDOW or not (e.start <= mid <= e.end):
            continue
        if best is None or e.dur < best.dur:
            best = e
    return best.name if best is not None else "host (no span)"


def breakdown(tr: Trace, top: int = 10) -> dict:
    """The device ops that took most time, and the longest idle gaps by
    what the host was doing in them (first device only)."""
    if not tr.ops:
        return {"device_ops": [], "idle_gaps": []}
    evs = tr.ops[sorted(tr.ops)[0]]
    lo, hi = tr.window
    per_op: dict = {}
    for e, own in self_times([e for e in evs if lo <= e.start
                              and e.end <= hi]):
        k = op_label(e)
        per_op[k] = per_op.get(k, 0.0) + max(own, 0.0)
    ops = sorted(per_op.items(), key=lambda kv: -kv[1])[:top]
    gaps, cur = [], lo
    for s, e in sorted((e.start, e.end) for e in evs):
        if s > cur:
            gaps.append((cur, min(s, hi)))
        cur = max(cur, e)
        if cur >= hi:
            break
    if cur < hi:
        gaps.append((cur, hi))
    labelled: dict = {}
    for s, e in gaps:
        if e <= s:
            continue
        k = _host_label((s + e) / 2, tr.host)
        labelled[k] = labelled.get(k, 0.0) + (e - s)
    idle = sorted(labelled.items(), key=lambda kv: -kv[1])[:top]
    return {"device_ops": [[k, v * 1e-9] for k, v in ops],
            "idle_gaps": [[k, v * 1e-9] for k, v in idle]}
