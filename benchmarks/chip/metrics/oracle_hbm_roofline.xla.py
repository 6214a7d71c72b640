"""The XLA oracle's share of its roofline over the cold-solve window: the
least time the chip needs for the window's oracle evaluations and power
steps (work/<config>.py at peaks.json's rates) over the chip's busy time in
the window (union of op intervals in the profiler trace)."""
from chipbench import trace as tr
from chipbench.harness import load_module


def read(rec):
    run, t = rec["run"], rec["trace"]
    if t is None or run.get("driver") != "solve" or run.get("oracle") != "xla":
        return None
    busy = sum(tr.busy_ns(t, c) for c in range(rec["chips"])) * 1e-9
    if busy <= 0:
        return None
    work = load_module("work", rec["config"]["work"])
    size = (run["nnz"], run["num_families"], run["num_destinations"],
            run["slab_bytes"])
    least = (run["oracle_evals"] * work.least_seconds(work.oracle_eval(*size), rec["peaks"])
             + run["power_steps"] * work.least_seconds(work.power_step(*size), rec["peaks"]))
    return 100.0 * least / busy
