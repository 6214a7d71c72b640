#!/usr/bin/env python3
"""Chip benchmark of the matching-LP solver and allocation service.

    python3 benchmarks/chip/run.py --workload <cell> --seed <n> \
        --seconds <s> --trace <0|1>

runs one cell of BENCHMARK.json once on the TPU chips of this machine: it
builds the cell's inputs from the seed, warms up, measures for `--seconds`
seconds (whole solves or cadences), checks what the measured window
produced against the plain reference, and prints one JSON line last on
stdout: `correct`, `attempted`, `failed`, `metrics` (the end-to-end metrics,
or with `--trace 1` the per-layer ones read from a profiler trace of the
window), `device`, with `--trace 1` `breakdown`, and last `checks`, each
compared number with its limit (also the last lines on stderr).

Without a TPU, with fewer chips than the cell asks for, or without the
program's source (`src/repro`) in the checkout, it exits 2 and prints no
result.  Two options are for measuring the benchmark itself and
are not used by its runs: `--control bf16` stores the slabs in bfloat16 (the
lower precision whose answers the checks must refuse), and `--seeds a,b,..`
repeats set-up and check for several seeds in one process, printing each
seed's compared numbers (`--sweep r1,r2,..` likewise runs one window per
query rate, for the cells that serve queries).
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)
sys.path.insert(0, os.path.join(os.path.dirname(os.path.dirname(HERE)), "src"))


def _finite(obj):
    """JSON has no NaN or infinity: a compared number that is not finite
    is written as null (and fails its check)."""
    if isinstance(obj, dict):
        return {k: _finite(v) for k, v in obj.items()}
    if isinstance(obj, list):
        return [_finite(v) for v in obj]
    if isinstance(obj, float) and not math.isfinite(obj):
        return None
    return obj


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--control", default="none", choices=("none", "bf16"))
    ap.add_argument("--seeds", default=None,
                    help="comma-separated seeds, checked in one process")
    ap.add_argument("--sweep", default=None,
                    help="comma-separated query rates, one window each")
    args = ap.parse_args(argv)

    def log(msg):
        print(msg, file=sys.stderr, flush=True)

    program = os.path.join(os.path.dirname(os.path.dirname(HERE)), "src", "repro")
    if not os.path.isdir(program):
        log(f"run.py: the program under test is not in this checkout ({program})")
        return 2
    from chipbench import harness

    try:
        if args.seeds or args.sweep:
            from chipbench import readings

            return readings.main(args, log=log)
        t_start = T_START - harness.process_age()
        result = harness.run_cell(
            args.workload, seed=args.seed, seconds=args.seconds,
            trace=bool(args.trace), control=args.control, t_start=t_start,
            log=log)
    except harness.NoChip as e:
        log(f"run.py: {e}")
        return 2
    for line in result.pop("_stdout", ()):
        print(line, flush=True)
    for name, c in result["checks"].items():
        ok = c["value"] is not None and c["value"] <= c["limit"]
        log(f"check {name} = {c['value']!r} limit {c['limit']!r} "
            f"{'ok' if ok else 'FAILED'}")
    print(json.dumps(_finite(result)), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
