#!/usr/bin/env python3
"""Run one benchmark cell once and print its result line.

    python3 bench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

Everything about the cell is found by name from BENCHMARK.json: its
configuration file, its traffic file (`bench/traffic/<traffic>.json`), the
loop that the traffic names (`bench/loops/<loop>.py`) and, with
`--trace 1`, one reader per per-layer metric (`bench/metrics/<name>.py`).

The run refuses (exit code 2, no result) without an accelerator, with
fewer chips than the cell asks for, or without the program (`src/repro`).
Otherwise the last line of standard output is one JSON object: `correct`,
`attempted`, `failed`, `metrics` (the cell's end-to-end metrics, or with
`--trace 1` its per-layer metrics), `device`, with `--trace 1` also
`breakdown`, and last `checks`: each number compared with the plain
reference, beside its limit. The same checks are the last lines of
standard error.

JAX's persistent compilation cache is the program's own
(`launch/compile_cache.py`): `$JAX_COMPILATION_CACHE_DIR` when set, else
`.jax_cache/` at the root of the checkout, so only a cell's first run there
compiles.
"""
from __future__ import annotations

import time

T_PROCESS = time.monotonic()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
sys.path.insert(0, ROOT)


def refuse(msg: str) -> int:
    print(f"bench: {msg}", file=sys.stderr, flush=True)
    return 2


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    from bench.harness.spec import SpecError, load_cell
    try:
        cell = load_cell(args.workload, ROOT)
    except (SpecError, OSError, KeyError) as e:
        return refuse(str(e))
    if not os.path.isdir(os.path.join(ROOT, "src", "repro")):
        return refuse(f"no program under {ROOT}/src/repro")
    sys.path.insert(0, os.path.join(ROOT, "src"))

    import jax
    from bench.harness import device, run as R
    from repro.launch.compile_cache import setup_compile_cache
    setup_compile_cache()
    try:
        devs = device.devices_for(cell.chips)
    except device.NoAccelerator as e:
        return refuse(str(e))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)

    result = R.run_cell(cell, devs, seed=args.seed, seconds=args.seconds,
                        trace=bool(args.trace), t_process=T_PROCESS)
    for name, c in result["checks"].items():
        print(f"check {name}: {c['value']!r} (limit {c['limit']!r})",
              file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result, allow_nan=False), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
