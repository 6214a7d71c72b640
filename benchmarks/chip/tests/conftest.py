"""The benchmark's own tests: the trace reduction on a synthetic trace, and
whole runs of each driver at a small size on the CPU (the chip check
skipped) that must come out correct when sound, and not correct with the
lower-precision control or with a fault planted in the timed path.

    JAX_PLATFORMS=cpu PYTHONPATH=src python -m pytest benchmarks/chip/tests
"""
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
for p in (BENCH, os.path.join(os.path.dirname(os.path.dirname(BENCH)), "src")):
    if p not in sys.path:
        sys.path.insert(0, p)

# small sizes at which a CPU run takes seconds; the traffic's other
# parameters are the cell's own
SMALL = {
    "matching": {"config": {"num_sources": 3000, "num_destinations": 60}},
    "service": {
        "config": {"num_sources": 800, "num_destinations": 30},
        "traffic": {"query_rate_per_s": 20, "batch_users": 64,
                    "max_window_cadences": 3, "sample_batches": 6},
    },
}


# cells whose files are kept but which BENCHMARK.json does not list yet
# (PERF.md, Open questions): their paths are tested all the same
KEPT = ("matching-s2M-d10K.cold-fused", "service-4x250K-d1K.cadence-query")


def kept_cell(workload: str) -> dict:
    """A kept cell's files, as `harness.cell` loads a listed one's."""
    from chipbench import harness

    config, traffic = workload.split(".", 1)
    return {
        "workload": {"name": workload, "config": config, "traffic": traffic,
                     "chips": 1},
        "config": harness.load_json("configs", f"{config}.json"),
        "traffic": harness.load_json("traffic", f"{traffic}.json"),
        "limits": harness.load_json("limits", f"{workload}.json"),
        "end_to_end": [m for m in harness.benchmark()["end_to_end"]
                       if "workloads" not in m],
        "per_layer": [],
    }


def cells() -> tuple:
    from chipbench import harness

    listed = tuple(w["name"] for w in harness.benchmark()["workloads"])
    return listed + tuple(w for w in KEPT if w not in listed)


def small_run(workload: str, *, seed: int = 2**31 + 7, control: str = "none"):
    from chipbench import harness

    kind = "service" if workload.startswith("service") else "matching"
    listed = {w["name"] for w in harness.benchmark()["workloads"]}
    cell = harness.cell
    if workload not in listed:
        harness.cell = kept_cell
    try:
        return harness.run_cell(
            workload, seed=seed, seconds=1.0, trace=False, control=control,
            require_tpu=False, overrides=SMALL[kind], log=lambda *_: None)
    finally:
        harness.cell = cell
