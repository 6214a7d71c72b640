"""Measurements of the benchmark itself, which its runs do not make.

`--seeds a,b,..` runs the cell's set-up, a short window and the check for
each seed in one process and prints one JSON line per seed with the
compared numbers: the readings the limits are set from (and, with
`--control bf16`, the control's readings).  `--sweep r1,r2,..` sets a
serving cell up once and runs one window of `--seconds` per query rate,
printing each rate's latency percentiles and how late the generator ran in
the first and second half of the window, to find the highest rate the
cell sustains.
"""
from __future__ import annotations

import gc
import json
import time

import numpy as np

from chipbench import harness


def main(args, log=print) -> int:
    if args.sweep:
        return sweep(args, log)
    for seed in [int(s) for s in args.seeds.split(",")]:
        t0 = time.perf_counter()
        res = harness.run_cell(args.workload, seed=seed, seconds=args.seconds,
                               trace=False, control=args.control, log=log)
        print(json.dumps({
            "seed": seed, "control": args.control, "correct": res["correct"],
            "checks": {k: v["value"] for k, v in res["checks"].items()},
            "metrics": {k: v["value"] for k, v in res["metrics"].items()},
            "seconds": time.perf_counter() - t0,
        }), flush=True)
        gc.collect()
    return 0


def sweep(args, log) -> int:
    spec = harness.cell(args.workload)
    drv_name = spec["traffic"]["driver"]
    harness.device_info(int(spec["workload"]["chips"]), True)
    harness.enable_compile_cache()
    rates = [float(r) for r in args.sweep.split(",")]
    ctx = harness.Context(spec, seed=args.seed, seconds=args.seconds,
                          trace=False, control=args.control,
                          t_start=time.perf_counter())
    drv = harness.load_module("drivers", drv_name)
    st = drv.setup(ctx, windows=len(rates))
    windows = []
    for i, rate in enumerate(rates):
        w = drv.window(ctx, st, rate=rate, seconds=args.seconds, index=i,
                       sample=int(ctx.traffic["sample_batches"]))
        windows.append(w)
        lat, late = w["latency"] * 1e3, w["lateness"] * 1e3
        half = lat.size // 2
        if half == 0:
            print(json.dumps({"rate": rate, "batches": int(lat.size)}), flush=True)
            continue
        print(json.dumps({
            "rate": rate, "batches": int(lat.size), "failed": w["failed"],
            "cadences": len(w["cadences"]), "window_s": w["seconds"],
            "p50_ms": float(np.percentile(lat, 50)),
            "p95_ms": float(np.percentile(lat, 95)),
            "p99_ms": float(np.percentile(lat, 99)),
            "p95_first_half_ms": float(np.percentile(lat[:half], 95)),
            "p95_second_half_ms": float(np.percentile(lat[half:], 95)),
            "late_p95_first_half_ms": float(np.percentile(late[:half], 95)),
            "late_p95_second_half_ms": float(np.percentile(late[half:], 95)),
            "late_max_ms": float(late.max()),
        }), flush=True)
    ctx.close_window()
    got = drv.collect(ctx, st, windows)
    checks = drv.check(ctx, st, got)
    print(json.dumps({"checks": {c.name: [c.value, c.limit, c.ok] for c in checks}}),
          flush=True)
    return 0
