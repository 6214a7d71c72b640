"""Cold solves back to back: the one-shot user of `repro.launch.solve`.

Set-up draws the configuration's edge list (topology from its cache file,
values from the seed), packs and scales it with the program's own ingest
(`bucketize`, `normalize_rows`), builds the column-sharded driver the way
`launch.solve.run_solve` does over the cell's chips, places the instance
and runs one whole solve to warm every program up.  The window then
runs whole cold solves (from zero duals, power iteration included) of the
placed instance, each fetching its duals and objective to the host, and
starts no solve after `--seconds`.  solve_s = window / solves.

Every solve's duals are compared with the plain reference's solve of the
same edge list and schedule (reference/matching.py), after the program's
state is freed.
"""
from __future__ import annotations

import gc
import time

import numpy as np

from chipbench import appendix_a
from chipbench.harness import CACHE, Check, Outcome, load_module

# program spans the breakdown attributes idle time to
SPANS = ("dist_solve", "power_iteration", "stage")


def schedule(cfg: dict) -> dict:
    s = cfg["solver"]
    return dict(gammas=tuple(s["gammas"]), iters_per_stage=s["iters_per_stage"],
                tol=s["tol"], check_every=s["check_every"],
                power_iters=s["power_iters"])


def edge_list(cfg: dict, seed) -> appendix_a.EdgeList:
    src, dst = appendix_a.structure(cfg, CACHE)
    return appendix_a.edge_values(cfg, src, dst, seed)


def build(ctx, el, slab_dtype: str, fused: bool):
    """The program's ingest and driver for one edge list: (driver, nnz)."""
    import jax

    from repro.core import DistConfig, DistributedMaximizer, MaximizerConfig
    from repro.core import normalize_rows
    from repro.formulation import scenario_formulation
    from repro.instances import (
        EdgeListInstance, MatchingInstanceSpec, bucketize,
    )
    from repro.launch.mesh import data_mesh

    cfg = ctx.config
    inst = EdgeListInstance(
        spec=MatchingInstanceSpec(
            num_sources=el.num_sources, num_destinations=el.num_destinations,
            avg_degree=float(cfg["avg_degree"]), num_families=el.num_families),
        src=el.src, dst=el.dst, values=el.values, coeff=el.coeff, rhs=el.rhs)
    with ctx.phase("host_build"):
        packed = bucketize(inst, shard_multiple=ctx.chips, dtype=slab_dtype)
        scaled, _ = normalize_rows(packed)
        comp = scenario_formulation("matching").compile(scaled)
    s = schedule(cfg)
    mcfg = MaximizerConfig(
        gammas=s["gammas"], iters_per_stage=s["iters_per_stage"],
        tol_grad=s["tol"], tol_viol=s["tol"], check_every=s["check_every"],
        power_iters=s["power_iters"])
    with ctx.phase("upload"):
        # as launch.solve.run_solve builds it, columns sharded over the
        # cell's chips
        dm = DistributedMaximizer(
            comp.sharded_instance(), data_mesh(ctx.chips), mcfg,
            DistConfig(axes="data", fused_oracle=fused, slab_dtype=slab_dtype),
            projection=comp.projection)
        dm.place()
        jax.block_until_ready(dm.inst)
    return dm


def one_solve(ctx, dm) -> dict:
    with ctx.span("solve"):
        res = dm.solve()
        with ctx.span("fetch"):
            out = {"lam": np.asarray(res.lam, np.float64), "g": float(res.g),
                   "iters": [int(i) for i in res.iters_used]}
    return out


def reference(ctx, el) -> dict:
    ref = load_module("reference", ctx.config["reference"])
    t = ref.table(el.src, el.dst, el.values, el.coeff, el.rhs,
                  el.num_destinations)
    out = ref.solve(t, ref.Schedule(**schedule(ctx.config)))
    del t
    return out


def compare(ctx, solves: list, ref: dict) -> list:
    # g is not compared: its float32 sum over every edge reads 0 to 2e-7 of
    # the reference's in sound runs and as little as 3e-7 in the bfloat16
    # control, whose rounding cancels in the sum (PERF.md, section 2)
    rel_l2 = max(np.linalg.norm(s["lam"] - ref["lam"]) /
                 max(np.linalg.norm(ref["lam"]), 1e-30) for s in solves)
    return [Check("duals_rel_l2", float(rel_l2), ctx.limits["duals_rel_l2"])]


def run(ctx, log=print) -> Outcome:
    cfg, tr = ctx.config, ctx.traffic
    fused = tr["oracle"] == "fused"
    slab_dtype = "bfloat16" if ctx.control == "bf16" else cfg["slab_dtype"]
    with ctx.phase("generate"):
        el = edge_list(cfg, ctx.seed)
    dm = build(ctx, el, slab_dtype, fused)
    with ctx.phase("warmup"):
        solves = [one_solve(ctx, dm)]
    t0 = ctx.open_window()
    window = []
    while not window or time.perf_counter() - t0 < ctx.seconds:
        window.append(one_solve(ctx, dm))
    seconds = ctx.close_window()
    del dm
    gc.collect()
    with ctx.phase("reference"):
        ref = reference(ctx, el)
    checks = compare(ctx, solves + window, ref)
    s = schedule(cfg)
    iters = sum(sum(w["iters"]) for w in window)
    return Outcome(
        end_to_end={"solve_s": seconds / len(window)},
        attempted=len(window),
        failed=0,
        checks=checks,
        records={
            "driver": "solve",
            "oracle": tr["oracle"],
            "nnz": el.nnz,
            "num_destinations": el.num_destinations,
            "num_families": el.num_families,
            "slab_bytes": 2 if slab_dtype == "bfloat16" else 4,
            "solves": len(window),
            # oracle evaluations in the window: power steps, iterations and
            # the final primal recovery of every solve
            "power_steps": s["power_iters"] * len(window),
            "oracle_evals": iters + len(window),
            "host_build_s": ctx.phases.get("host_build"),
            "span_names": SPANS,
        },
        notes=[f"solves in the window: {len(window)} in {seconds:.3f} s; "
               f"iterations per stage {window[-1]['iters']}; reference "
               f"{ctx.phases['reference']:.3f} s, iterations {ref['iters']}"],
    )
