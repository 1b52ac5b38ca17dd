"""Collective operations in a device trace, and the part of their time that
no other operation hides.

An op is a collective when its HLO instruction is one (all-gather,
all-reduce, reduce-scatter, collective-permute, all-to-all) or is XLA's
async collective fusion (`async-collective-*`), by the instruction's name
or by its opcode in the HLO text. A synchronous collective lasts as long as
its op. An asynchronous one is a `-start` op and a `-done` op of the same
instruction number, and lasts from the start's beginning to the done's end:
the transfer is in flight in between, while other ops may run. The TPU runs
one op at a time, so an async collective is hidden where other ops run
inside it, and exposed where none does (its start, and the done's wait).

Times are per device, clipped to the window and averaged over devices, like
every other device time here.
"""
from __future__ import annotations

import re

from bench.harness.trace import Trace

_KINDS = r"(?:all-gather|all-reduce|reduce-scatter|collective-permute|all-to-all)"
_NAME = re.compile(r"^%?(async-collective|" + _KINDS + r")(-start|-done)?"
                   r"((?:\.\d+)*)(?:\s*=|$)")
_OPCODE = re.compile(r"\s" + _KINDS + r"(?:-start|-done)?\(")


def _kind(e):
    """(name, "start" | "done" | "", number) of a collective op, or None."""
    m = _NAME.match(e.name)
    if m:
        return m.group(1), (m.group(2) or "")[1:], m.group(3)
    if _OPCODE.search(e.name):
        return "op", "", e.name.split("=")[0].strip()
    return None


def is_collective(e) -> bool:
    return _kind(e) is not None


def spans(evs: list) -> list:
    """(start, end) of each collective in one device's ops: a synchronous
    op's own, and an async one's from its start op to its done op."""
    out, open_ = [], {}
    for e in sorted(evs, key=lambda e: e.start):
        k = _kind(e)
        if k is None:
            continue
        name, phase, num = k
        if phase == "start":
            open_.setdefault((name, num), []).append(e.start)
        elif phase == "done" and open_.get((name, num)):
            out.append((open_[(name, num)].pop(0), e.end))
        else:   # synchronous, or a done whose start lies before the trace
            out.append((e.start, e.end))
    return out


def _leaves(evs: list) -> list:
    """Ops that hold no other op (a loop's event spans its body's ops)."""
    out, stack = [], []
    for e in sorted(evs, key=lambda e: (e.start, -e.end)):
        while stack and stack[-1][0].end <= e.start:
            ev, parent = stack.pop()
            if not parent:
                out.append(ev)
        if stack:
            stack[-1][1] = True
        stack.append([e, False])
    out.extend(ev for ev, parent in stack if not parent)
    return out


def _merged(intervals) -> list:
    out = []
    for s, e in sorted(intervals):
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def _overlap(a: list, b: list) -> float:
    """Length of the intersection of two sorted lists of disjoint
    intervals."""
    i = j = 0
    total = 0.0
    while i < len(a) and j < len(b):
        total += max(0.0, min(a[i][1], b[j][1]) - max(a[i][0], b[j][0]))
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return total


def collective_s(tr: Trace, exposed: bool = False) -> float | None:
    """Seconds in the window in which a collective was under way on a
    device, averaged over devices; with `exposed`, only those in which no
    other op ran on that device. None where no device ran a collective."""
    lo, hi = tr.window
    clip = lambda s, e: (max(s, lo), min(e, hi))
    per, found = [], False
    for evs in tr.ops.values():
        coll = _merged(clip(s, e) for s, e in spans(evs))
        found = found or bool(coll)
        t = sum(e - s for s, e in coll)
        if exposed:
            other = _merged(clip(e.start, e.end) for e in _leaves(evs)
                            if not is_collective(e))
            t -= _overlap(coll, other)
        per.append(t)
    return (sum(per) / len(per)) * 1e-9 if found else None
