#!/usr/bin/env python3
"""Readings that a cell's limits are set from, on the chip, in one process.

    python3 bench/calibrate.py --workload <name> --seeds 1,2,3 \
        [--control-seeds 1,2,3] [--seconds S] [--out FILE]

For each seed the cell's loop runs as `bench/run.py` would (a window of
`--seconds`) and prints the numbers it compares with the plain reference;
on the control seeds it also prints the control's numbers (the reference
in the precision one below the configuration's, in the program's place)
and those of the faults that can be planted in the reference. With
`--rates`, a serving cell's knee is swept instead (one engine, the first
seed, each rate in turn). One JSON object per seed or rate on standard
output and in `--out`. The benchmark's own runs never run this.
"""
from __future__ import annotations

import json
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
sys.path.insert(0, ROOT)


def main(argv=None) -> int:
    import argparse
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--control-seeds", default="")
    ap.add_argument("--seconds", type=float, default=0.0)
    ap.add_argument("--out", default="")
    ap.add_argument("--rates", default="",
                    help="serving cells: sweep these rates (requests/s) "
                         "instead, to find the knee")
    args = ap.parse_args(argv)
    sys.path.insert(0, os.path.join(ROOT, "src"))
    import jax
    from repro.launch.compile_cache import setup_compile_cache
    setup_compile_cache()
    from bench.harness import device, spec
    from bench.harness.run import Context
    cell = spec.load_cell(args.workload, ROOT)
    devs = device.devices_for(cell.chips)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    loop = spec.load_module("loops", cell.traffic["loop"])
    ctrl = {int(s) for s in args.control_seeds.split(",") if s}
    sink = open(args.out, "a") if args.out else None
    if args.rates:
        ctx = Context(cell, devs, int(args.seeds.split(",")[0]),
                      args.seconds, False, time.monotonic())
        for rec in loop.sweep(ctx, [float(r) for r in
                                      args.rates.split(",")]):
            if sink:
                sink.write(json.dumps(rec) + "\n")
        return 0
    for s in (int(x) for x in args.seeds.split(",")):
        t0 = time.monotonic()
        rec = loop.calibrate(Context(cell, devs, s, args.seconds, False,
                                       t0), control=s in ctrl)
        rec["wall_s"] = time.monotonic() - t0
        line = json.dumps(rec)
        print(line, flush=True)
        if sink:
            sink.write(line + "\n")
            sink.flush()
    return 0


if __name__ == "__main__":
    sys.exit(main())
