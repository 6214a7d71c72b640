"""The Pallas dual-oracle kernel's share of its roofline over the
cold-solve window: the least time the chip needs for the oracle work of the
window's evaluations (work/<config>.py at peaks.json's rates) over the
summed device time of the kernel's events in the profiler trace.

The trace does not carry a Pallas kernel's own name (its HLO op reads
`custom_call_target="tpu_custom_call"` with empty kernel metadata), so the
kernel's events are the window's Mosaic custom calls.  On the fused solve
path these are the dual oracle's alone: one per bucket in each iteration
and in the final primal recovery."""
from chipbench.harness import load_module

KERNEL = 'custom_call_target="tpu_custom_call"'


def read(rec):
    run, t = rec["run"], rec["trace"]
    if t is None or run.get("driver") != "solve" or run.get("oracle") != "fused":
        return None
    lo, hi = t.window
    kernel = sum(min(e.end, hi) - max(e.start, lo)
                 for c in range(rec["chips"]) for e in t.ops(c)
                 if KERNEL in e.name) * 1e-9
    if kernel <= 0:
        return None
    work = load_module("work", rec["config"]["work"])
    size = (run["nnz"], run["num_families"], run["num_destinations"],
            run["slab_bytes"])
    least = run["oracle_evals"] * work.least_seconds(work.oracle_eval(*size), rec["peaks"])
    return 100.0 * least / kernel
