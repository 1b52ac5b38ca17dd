"""What each layer of the program holds in a trace: its host spans, and the
device ops it owns.

The program's spans are host events named as in `src/repro/tracing.py`.
They are written out here, not imported: a program without them gives a
trace in which these readers find nothing, and they return None.

A device op is known here only by its HLO text and its times: the events of
the TPU's XLA Ops line carry no name stack (their stats are
`device_offset_ps`, `device_duration_ps` and `Time Scale Multiplier`), so
the program's named scopes are not visible to the readers. An op's layer is
read from the array types its HLO text shows instead: the vocabulary width
for the head, the stacked layer axis for the layer scan.
"""
from __future__ import annotations

import re

from bench.harness.trace import Trace, self_times, union

TRAIN_STEP = "train.step"
TRAIN_INPUT = "train.input"
TRAIN_DISPATCH = "train.dispatch"
TRAIN_SYNC = "train.sync"
SERVE_STEP = "serve.step"
SERVE_DECODE = "serve.decode"
SERVE_SYNC = "serve.sync"


def windowed(tr: Trace, name: str) -> list:
    """The spans named `name` that lie wholly inside the window."""
    lo, hi = tr.window
    return [s for s in tr.spans(name) if lo <= s.start and s.end <= hi]


def idle_s_in(tr: Trace, spans: list) -> float:
    """Seconds inside `spans` (clipped to the window) in which no op ran,
    averaged over devices."""
    lo, hi = tr.window
    cut = [(max(s.start, lo), min(s.end, hi)) for s in spans]
    cut = [(s, e) for s, e in cut if e > s]
    per = []
    for evs in tr.ops.values():
        busy = [(o.start, o.end) for o in evs]
        per.append(sum((e - s) - union(busy, s, e) for s, e in cut))
    return (sum(per) / len(per)) * 1e-9 if per else 0.0


def has_dim(e, n: int, leading: bool = False) -> bool:
    """Whether the HLO text of op `e` shows an array type with a dimension
    of size `n` (its leading one, with `leading`)."""
    pre = r"\w\[" if leading else r"\w\[(?:\d+,)*"
    return re.search(pre + str(int(n)) + r"[,\]]", e.name) is not None


def self_s(tr: Trace, select) -> float:
    """Summed self time (nested ops left out) of the ops that
    `select(ops)` picks from a device's ops in the window; averaged over
    devices."""
    lo, hi = tr.window
    per = []
    for evs in tr.ops.values():
        evs = [e for e in evs if lo <= e.start and e.end <= hi]
        kept = {id(e) for e in select(evs)}
        per.append(sum(max(t, 0.0) for e, t in self_times(evs)
                       if id(e) in kept))
    return (sum(per) / len(per)) * 1e-9 if per else 0.0

