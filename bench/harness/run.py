"""One run of one cell: the loop's window, then its readings.

A loop (`bench/loops/<name>.py`, named by the traffic file) exposes
`run(ctx) -> Outcome`. This module turns the outcome into the result line:
end-to-end metrics untraced, per-layer metrics (one reader each, found by
name) traced, and the checks against the plain reference.
"""
from __future__ import annotations

import math
from dataclasses import dataclass, field

from bench.harness import spec as S
from bench.harness.peaks import peaks_for


@dataclass
class Context:
    cell: S.Cell
    devs: list
    seed: int
    seconds: float
    trace: bool
    t_process: float        # host clock at process start (set-up starts)


@dataclass
class Outcome:
    attempted: int
    failed: int
    end_to_end: dict                      # name -> value (untraced runs)
    checks: dict                          # name -> {"value", "limit"}
    device: dict                          # read before the reference ran
    trace: object = None                  # harness.trace.Trace (traced runs)
    work: dict = field(default_factory=dict)  # counts the readers use
    config: dict = field(default_factory=dict)
    peaks: dict = field(default_factory=dict)


def _finite(v):
    return v is not None and isinstance(v, (int, float)) and math.isfinite(v)


def checks_pass(checks: dict) -> bool:
    return all(_finite(c["value"]) and c["value"] <= c["limit"]
               for c in checks.values())


def run_cell(cell: S.Cell, devs, *, seed: int, seconds: float, trace: bool,
             t_process: float) -> dict:
    loop = S.load_module("loops", cell.traffic["loop"])
    ctx = Context(cell, devs, seed, seconds, trace, t_process)
    out: Outcome = loop.run(ctx)
    out.config = cell.config
    out.peaks = peaks_for(devs[0].device_kind)
    units = {m["name"]: m["unit"] for m in cell.end_to_end + cell.per_layer}
    metrics = {}
    if trace:
        from bench.harness.trace import breakdown, busy_s
        for m in cell.per_layer:
            v = S.load_module("metrics", m["name"]).read(out)
            if _finite(v):
                metrics[m["name"]] = {"value": float(v), "unit": units[m["name"]]}
        dev = dict(out.device, busy_s=busy_s(out.trace),
                   window_s=out.trace.window_s)
    else:
        for m in cell.end_to_end:
            v = out.end_to_end.get(m["name"])
            if _finite(v):
                metrics[m["name"]] = {"value": float(v), "unit": units[m["name"]]}
        dev = out.device
    result = {"correct": checks_pass(out.checks) and out.attempted > 0,
              "attempted": int(out.attempted), "failed": int(out.failed),
              "metrics": metrics, "device": dev}
    if trace:
        result["breakdown"] = breakdown(out.trace)
    result["checks"] = {k: {"value": (float(c["value"]) if _finite(c["value"])
                                      else None),
                            "limit": c["limit"]} for k, c in out.checks.items()}
    return result
