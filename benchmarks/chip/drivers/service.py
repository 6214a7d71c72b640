"""Recurring multi-tenant cadences with open-loop allocation queries: the
user of `repro.service.Scheduler` and `repro.serving.DualStore`.

Set-up draws one topology for the configuration and each tenant's values
from (seed, tenant), registers the tenants with a `Scheduler` that
publishes into a `DualStore`, draws every cadence's deltas (the benchmark's
copy of the service's random delta, on its own copy of each tenant's edge
list) and the query schedule, runs the cold cadence and `warmup_cadences`
warm ones, and compiles the query program for every batch shape the
schedule can produce.

The window runs whole `Scheduler.run_cadence` calls back to back with the
drawn deltas and starts none after `--seconds`; cadence_s = window /
cadences.  Meanwhile one thread of the same process sends query batches
open loop at the traffic's fixed rate, each timed from when it was due to
when its allocations are on the host; query_p95_ms is the 95th percentile
over every batch due in the window, a failed batch counting as infinite.

The check rebuilds every tenant's edge list through the same deltas and
compares, after the program's state is freed: the published duals of the
last cadence with the plain reference's chain of solves (cold, then warm
from its own previous duals), each cadence's objective, the chip's slabs
after the deltas (per-destination counts and sums), and a sample of the
served batches, drawn from the seed, with the direct projection, on the
rebuilt edge list, of the published duals each batch was served from
(the duals themselves are held to the reference by the first check).
"""
from __future__ import annotations

import gc
import math
import threading
import time

import numpy as np

from chipbench import appendix_a
from chipbench.harness import CACHE, Check, Outcome, load_module

# program spans the breakdown attributes idle time to
SPANS = ("cadence", "ingest", "dispatch", "solve_fence", "absorb",
         "tenant_absorb", "tenant_solve")


def _pow2(n: int) -> int:
    return 1 << max(int(n) - 1, 0).bit_length()


class State:
    pass


def _schedules(cfg):
    s = cfg["solver"]
    base = dict(iters_per_stage=s["iters_per_stage"], tol=s["tol"],
                check_every=s["check_every"], power_iters=s["power_iters"])
    return (dict(base, gammas=tuple(s["gammas"])),
            dict(base, gammas=tuple(cfg["service"]["warm_gammas"])))


def _program_delta(d: appendix_a.Delta):
    from repro.instances import InstanceDelta

    return InstanceDelta(
        insert_src=d.insert_src, insert_dst=d.insert_dst,
        insert_values=d.insert_values, insert_coeff=d.insert_coeff,
        delete_src=d.delete_src, delete_dst=d.delete_dst,
        update_src=d.update_src, update_dst=d.update_dst,
        update_values=d.update_values, rhs=d.rhs,
    )


def setup(ctx, *, windows: int = 1) -> State:
    from repro import telemetry
    from repro.core import MaximizerConfig
    from repro.instances import EdgeListInstance, MatchingInstanceSpec
    from repro.service import Scheduler, ServiceConfig
    from repro.serving import DualStore

    cfg, tr = ctx.config, ctx.traffic
    st = State()
    st.names = [f"tenant{t}" for t in range(int(cfg["tenants"]))]
    with ctx.phase("generate"):
        src, dst = appendix_a.structure(cfg, CACHE)
        st.base = [appendix_a.edge_values(cfg, src, dst, [ctx.seed, t])
                   for t in range(len(st.names))]
    cold, warm = _schedules(cfg)
    st.cold, st.warm = cold, warm
    svc = cfg["service"]
    slab_dtype = "bfloat16" if ctx.control == "bf16" else cfg["slab_dtype"]
    scfg = ServiceConfig(
        cold=MaximizerConfig(
            gammas=cold["gammas"], iters_per_stage=cold["iters_per_stage"],
            tol_grad=cold["tol"], tol_viol=cold["tol"],
            check_every=cold["check_every"], power_iters=cold["power_iters"]),
        warm_gammas=warm["gammas"], normalize=svc["normalize"],
        fused_oracle=svc["fused_oracle"], engine=svc["engine"],
        row_headroom=svc["row_headroom"], slab_dtype=slab_dtype,
        sigma_reuse_dc_threshold=svc["sigma_reuse_dc_threshold"])
    n_cadences = int(tr["warmup_cadences"]) + windows * int(tr["max_window_cadences"])
    st.tracer = telemetry.get_tracer()
    st.store = DualStore(history=n_cadences + 2)
    st.sched = Scheduler(scfg, dual_store=st.store)
    with ctx.phase("host_build"):
        for name, el in zip(st.names, st.base):
            st.sched.add_tenant(name, EdgeListInstance(
                spec=MatchingInstanceSpec(
                    num_sources=el.num_sources,
                    num_destinations=el.num_destinations,
                    avg_degree=float(cfg["avg_degree"]),
                    num_families=el.num_families),
                src=el.src, dst=el.dst, values=el.values, coeff=el.coeff,
                rhs=el.rhs))
    with ctx.phase("deltas"):
        # every cadence's deltas, drawn on the benchmark's own edge lists
        rng = np.random.default_rng([ctx.seed, 1 << 20])
        cur = list(st.base)
        st.deltas = []
        for _ in range(n_cadences):
            row = []
            for t in range(len(cur)):
                d = appendix_a.random_delta(cur[t], rng, **tr["delta"])
                cur[t] = appendix_a.apply_delta(cur[t], d)
                row.append(d)
            st.deltas.append(row)
        del cur
    st.next = 0  # index of the next delta row
    st.cadences = []  # (window index or None, CadenceReport, seconds)
    with ctx.phase("cold_cadence"):
        _cadence(st, None, deltas=False)
    with ctx.phase("warm_cadences"):
        for _ in range(int(tr["warmup_cadences"])):
            _cadence(st, None)
    with ctx.phase("query_warmup"):
        _warm_queries(st, int(tr["batch_users"]))
    st.live = [np.unique(el.src) for el in st.base]
    st.rng = np.random.default_rng([ctx.seed, 1 << 21])
    st.windows = []
    return st


def _cadence(st: State, window, *, deltas: bool = True):
    if deltas:
        row = st.deltas[st.next]
        st.next += 1
        batch = {n: _program_delta(d) for n, d in zip(st.names, row)}
    else:
        batch = {}
    t0 = time.perf_counter()
    out = st.sched.run_cadence(batch)
    st.cadences.append((window, out, time.perf_counter() - t0))
    return out


def _warm_queries(st: State, batch_users: int) -> None:
    """Compile the query program for every (slab, padded batch) shape a
    batch of `batch_users` uniform users can produce: per slab, the powers
    of two covering six standard deviations around the expected count."""
    snap = st.store.snapshot(st.names[0])
    live = snap.deg > 0
    n_live = int(live.sum())
    for t in range(len(snap.instance.buckets)):
        users = np.flatnonzero(live & (snap.bucket_of == t))
        if users.size == 0:
            continue
        p = users.size / n_live
        mu, sd = batch_users * p, math.sqrt(batch_users * p * (1 - p))
        lo = max(1, int(math.floor(mu - 6 * sd)))
        hi = min(batch_users, int(math.ceil(mu + 6 * sd)))
        for size in sorted({_pow2(k) for k in range(lo, hi + 1)}):
            st.store.query(st.names[0], np.resize(users, size))


def window(ctx, st: State, *, rate: float, seconds: float, index: int = 0,
           sample: int = 0) -> dict:
    """One measured window at a query rate (batches per second)."""
    tr = ctx.traffic
    B = int(tr["batch_users"])
    horizon = seconds + float(tr["max_window_overrun_s"])
    n = int(math.ceil(rate * horizon)) + 1
    tenants = st.rng.integers(0, len(st.names), size=n)
    users = [st.live[t][st.rng.integers(0, st.live[t].size, size=B)]
             for t in tenants]
    log = []
    kept = {}
    stop = {"at": None}
    wake = threading.Event()

    t0 = ctx.open_window() if index == 0 else time.perf_counter()

    def sender():
        for k in range(n):
            due = t0 + k / rate
            while True:
                end = stop["at"]
                if end is not None and due >= end:
                    return
                wait = due - time.perf_counter()
                if wait <= 0:
                    break
                wake.wait(min(wait, 0.05))
            start = time.perf_counter()
            name = st.names[int(tenants[k])]
            try:
                res = st.store.query(name, users[k])
                done = time.perf_counter()
                log.append((k, due, start - due, done - due, res.generation, True))
                kept[k] = (name, users[k], res)
            except Exception as e:  # a failed batch counts as missing
                log.append((k, due, start - due, math.inf, None, False))
                kept[k] = (name, users[k], e)

    thread = threading.Thread(target=sender, name="query-sender", daemon=True)
    thread.start()
    first = len(st.cadences)
    n0 = len(st.tracer.events())
    cap = min(int(tr["max_window_cadences"]), len(st.deltas) - st.next)
    while len(st.cadences) == first or (
            time.perf_counter() - t0 < seconds and len(st.cadences) - first < cap):
        with ctx.span("cadence"):
            _cadence(st, index)
    t_end = time.perf_counter()
    stop["at"] = t_end
    wake.set()
    thread.join()
    cads = st.cadences[first:]
    # the answers checked: a sample, drawn from the seed, of every batch
    # that was due in the window
    due = sorted(kept)
    pick = st.rng.choice(len(due), size=min(sample, len(due)), replace=False)
    kept = {due[i]: kept[due[i]] for i in sorted(pick.tolist())}
    spans = st.tracer.events()[n0:]
    lat = np.asarray([r[3] for r in log if r[1] < t_end])
    late = np.asarray([r[2] for r in log if r[1] < t_end])
    return {
        "t0": t0, "t_end": t_end, "seconds": t_end - t0,
        "cadences": cads, "log": log, "kept": kept,
        "latency": lat, "lateness": late,
        "failed": int(sum(1 for r in log if not r[5])),
        "ingest_s": [e["dur"] * 1e-6 for e in spans if e["name"] == "ingest"],
        "upload_bytes": [out.upload_bytes for _, out, _ in cads],
        "ran_out": len(cads) >= cap and t_end - t0 < seconds,
    }


def _device_sums(inst, J: int, m: int):
    """Per destination: edges, sum of cost and of each family's
    coefficients in the chip's (raw) slabs."""
    import jax.numpy as jnp

    count = np.zeros(J)
    cost = np.zeros(J)
    coeff = np.zeros((m, J))
    for b in inst.buckets:
        idx = jnp.asarray(b.idx).reshape(-1)
        mask = jnp.asarray(b.mask, jnp.float32).reshape(-1)
        count += np.asarray(jnp.zeros(J).at[idx].add(mask), np.float64)
        cost += np.asarray(jnp.zeros(J).at[idx].add(
            jnp.asarray(b.cost, jnp.float32).reshape(-1) * mask), np.float64)
        for k in range(m):
            coeff[k] += np.asarray(jnp.zeros(J).at[idx].add(
                jnp.asarray(b.coeff[k], jnp.float32).reshape(-1) * mask),
                np.float64)
    return count, cost, coeff


def collect(ctx, st: State, windows: list) -> dict:
    """Bring to the host what the check compares, then free the program's
    state on the chip."""
    cfg = ctx.config
    J, m = int(cfg["num_destinations"]), int(cfg.get("num_families", 1))
    got = {"final": {}, "sums": {}, "served": [], "reports": []}
    for name in st.names:
        snap = st.store.snapshot(name)
        got["final"][name] = (snap.generation, np.asarray(snap.lam_eff, np.float64))
        got["sums"][name] = (snap.generation, _device_sums(snap.instance, J, m))
    for _, out, _ in st.cadences:
        got["reports"].append({n: (r["published_generation"], r["g"])
                               for n, r in out.reports.items()})
    for w in windows:
        for k, (name, sent, res) in sorted(w["kept"].items()):
            if isinstance(res, Exception):
                got["served"].append((name, None, sent, None, None))
                continue
            snap = st.store.get(name, res.generation)
            got["served"].append((name, res.generation, sent, res,
                                  (np.asarray(snap.lam_eff, np.float32), snap.gamma)))
    st.sched = st.store = None
    gc.collect()
    return got


def check(ctx, st: State, got: dict) -> list:
    """The plain reference's answers against what the program produced."""
    ref = load_module("reference", ctx.config["reference"])
    cold = ref.Schedule(**st.cold)
    warm = ref.Schedule(**st.warm)
    J = st.base[0].num_destinations
    lim = ctx.limits
    n_gen = st.next  # deltas applied to every tenant
    want_served = {}
    for i, (name, gen, _, _, _) in enumerate(got["served"]):
        want_served.setdefault((name, gen), []).append(i)
    duals = g_rel = x_abs = 0.0
    edge_miss = count_miss = 0
    sum_rel = 0.0
    missing = sum(1 for s in got["served"] if s[3] is None)
    g_by_gen = {}
    for rep in got["reports"]:
        for name, (gen, g) in rep.items():
            g_by_gen[(name, gen)] = g
    for t, name in enumerate(st.names):
        el = st.base[t]
        lam = None
        for gen in range(n_gen + 1):
            if gen:
                el = appendix_a.apply_delta(el, st.deltas[gen - 1][t])
            table = ref.table(el.src, el.dst, el.values, el.coeff, el.rhs, J)
            out = ref.solve(table, cold if lam is None else warm, lam0=lam)
            lam = out["lam"]
            del table
            if (name, gen) in g_by_gen:
                g_rel = max(g_rel, abs(g_by_gen[(name, gen)] - out["g"])
                            / max(abs(out["g"]), 1e-30))
            fgen, flam = got["final"][name]
            if gen == fgen:
                duals = max(duals, float(np.linalg.norm(flam - out["lam_eff"])
                                         / max(np.linalg.norm(out["lam_eff"]), 1e-30)))
            sgen, (cnt, cost, coeff) = got["sums"][name]
            if gen == sgen:
                ref_cnt = np.bincount(el.dst, minlength=J)
                count_miss += int(np.sum(ref_cnt != np.rint(cnt)))
                vals = [(cost, -np.asarray(el.values, np.float32).astype(np.float64))]
                vals += [(coeff[k], np.asarray(el.coeff[k], np.float32).astype(np.float64))
                         for k in range(el.num_families)]
                for mine, theirs in vals:
                    scale = np.bincount(el.dst, weights=np.abs(theirs), minlength=J)
                    want = np.bincount(el.dst, weights=theirs, minlength=J)
                    sum_rel = max(sum_rel, float(np.max(
                        np.abs(mine - want) / np.maximum(scale, 1e-30))))
            for i in want_served.get((name, gen), ()):
                # the published duals of the batch's generation, projected
                # directly on the rebuilt edge list
                _, _, users, res, (lam_eff, gamma) = got["served"][i]
                ref_x = ref.allocations(el.src, el.dst, el.values, el.coeff,
                                        users, lam_eff, gamma, J)
                served = {}
                for ba in res.slabs:
                    for r, u in enumerate(ba.users.tolist()):
                        sel = ba.mask[r] > 0
                        served[u] = (ba.idx[r][sel].astype(np.int64), ba.x[r][sel])
                for u in res.unmatched.tolist():
                    served[u] = (np.zeros(0, np.int64), np.zeros(0, np.float32))
                for u, (rd, rx) in ref_x.items():
                    sd, sx = served.get(u, (None, None))
                    if sd is None or not np.array_equal(np.sort(sd), np.sort(rd)):
                        edge_miss += 1
                        continue
                    if rd.size:
                        x_abs = max(x_abs, float(np.max(np.abs(
                            sx[np.argsort(sd)].astype(np.float64)
                            - rx[np.argsort(rd)].astype(np.float64)))))
    return [
        Check("duals_rel_l2", duals, lim["duals_rel_l2"]),
        Check("g_rel", g_rel, lim["g_rel"]),
        Check("served_x_max_abs", x_abs, lim["served_x_max_abs"]),
        Check("served_edge_mismatch", float(edge_miss + missing),
              lim["served_edge_mismatch"]),
        Check("ingest_count_mismatch", float(count_miss),
              lim["ingest_count_mismatch"]),
        Check("ingest_sum_rel", sum_rel, lim["ingest_sum_rel"]),
    ]


def lateness_line(w: dict) -> str:
    late = w["lateness"] * 1e3
    if late.size == 0:
        return "query generator: no batch due in the window"
    return (f"query generator: {late.size} batches due in the window, "
            f"started late by p50 {np.percentile(late, 50):.3f} ms, "
            f"p95 {np.percentile(late, 95):.3f} ms, max {late.max():.3f} ms")


def run(ctx, log=print) -> Outcome:
    tr = ctx.traffic
    st = setup(ctx)
    w = window(ctx, st, rate=float(tr["query_rate_per_s"]), seconds=ctx.seconds,
               sample=int(tr["sample_batches"]))
    ctx.close_window()
    got = collect(ctx, st, [w])
    with ctx.phase("reference"):
        checks = check(ctx, st, got)
    n_cad = len(w["cadences"])
    lat = w["latency"]
    notes = [
        f"cadences in the window: {n_cad} in {w['seconds']:.3f} s "
        f"({', '.join(f'{s:.3f}' for _, _, s in w['cadences'])} s); "
        f"query batches due {lat.size}, failed {w['failed']}, p50 "
        f"{np.percentile(lat, 50) * 1e3:.3f} ms" if lat.size else "no batches",
        f"reference {ctx.phases['reference']:.3f} s",
    ]
    if w["ran_out"]:
        notes.append("the window ran out of drawn deltas; raise max_window_cadences")
    for _, out, s in st.cadences:
        bad = [n for n, r in out.ingest.items() if r.rebucketized]
        if bad:
            notes.append(f"re-bucketized: {bad}")
    return Outcome(
        end_to_end={
            "cadence_s": w["seconds"] / n_cad,
            "query_p95_ms": float(np.percentile(lat, 95)) * 1e3 if lat.size else math.inf,
        },
        attempted=n_cad + int(lat.size),
        failed=w["failed"],
        checks=checks,
        records={
            "driver": "service",
            "cadences": n_cad,
            "query_batches": int(lat.size),
            "ingest_s": w["ingest_s"],
            "upload_bytes": w["upload_bytes"],
            "span_names": SPANS,
        },
        notes=notes,
        stdout=[lateness_line(w)],
    )
