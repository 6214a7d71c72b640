"""Whole runs of the cells at a small size on the CPU: sound runs come out
correct; the bfloat16 control and each fault planted in the timed path
come out not correct."""
import dataclasses

import jax.numpy as jnp
import numpy as np
import pytest

from chipbench.harness import load_json
from conftest import cells, small_run

# every cell, listed or kept, by the driver its traffic names
CELLS = cells()
DRIVER = {w: load_json("traffic", f"{w.split('.', 1)[1]}.json")["driver"]
          for w in CELLS}
SOLVE = tuple(w for w in CELLS if DRIVER[w] == "solve")
SERVICE = tuple(w for w in CELLS if DRIVER[w] == "service")


@pytest.mark.parametrize("workload", CELLS)
def test_sound_run_is_correct(workload):
    r = small_run(workload)
    assert r["correct"], r["checks"]
    assert r["attempted"] > 0 and r["failed"] == 0


@pytest.mark.parametrize("workload", CELLS)
def test_bf16_control_is_not_correct(workload):
    r = small_run(workload, control="bf16")
    assert not r["correct"], r["checks"]


# -- faults in the solve path ------------------------------------------------


def _stage_unchanged(monkeypatch):
    """Every stage returns the duals it was given."""
    import repro.core.sharding as sh

    orig = sh._stage_scan_early

    def stage(calculate, lam0, *a, **k):
        _, stats, comm, used = orig(calculate, lam0, *a, **k)
        return lam0, stats, comm, used

    monkeypatch.setattr(sh, "_stage_scan_early", stage)


def _half_batch(monkeypatch):
    """The oracle sees the first half of every slab's rows and doubles
    what they give."""
    from repro.core.objective import MatchingObjective

    orig = MatchingObjective.calculate

    def calculate(self, lam, gamma):
        inst = self.instance
        half = dataclasses.replace(inst, buckets=tuple(
            dataclasses.replace(
                b, idx=b.idx[: max(1, b.idx.shape[0] // 2)],
                coeff=b.coeff[:, : max(1, b.idx.shape[0] // 2)],
                cost=b.cost[: max(1, b.idx.shape[0] // 2)],
                mask=b.mask[: max(1, b.idx.shape[0] // 2)])
            for b in inst.buckets))
        ev = orig(dataclasses.replace(self, instance=half), lam, gamma)
        ax = 2 * ev.ax
        lin, ridge = 2 * ev.primal_linear, 2 * ev.primal_ridge
        if self.include_rhs:
            grad = ax - inst.rhs
        else:
            grad = ax
        return ev._replace(ax=ax, grad=grad, primal_linear=lin,
                           primal_ridge=ridge,
                           g=lin + ridge + jnp.vdot(lam, grad))

    monkeypatch.setattr(MatchingObjective, "calculate", calculate)


def _duals_altered(monkeypatch):
    """The solve's duals come back with one entry moved by a thousandth of
    their norm."""
    from repro.core.sharding import DistributedMaximizer

    orig = DistributedMaximizer.solve

    def solve(self, *a, **k):
        res = orig(self, *a, **k)
        return res._replace(lam=res.lam.at[0].add(1e-3 * jnp.linalg.norm(res.lam)))

    monkeypatch.setattr(DistributedMaximizer, "solve", solve)


@pytest.mark.parametrize("workload", SOLVE)
@pytest.mark.parametrize("fault", [_stage_unchanged, _half_batch, _duals_altered])
def test_solve_fault_is_not_correct(workload, fault, monkeypatch):
    fault(monkeypatch)
    r = small_run(workload)
    assert not r["correct"], r["checks"]


# -- faults in the service path ----------------------------------------------


def _service_stage_unchanged(monkeypatch):
    """Every engine solve returns the duals it started from."""
    import repro.engines.agd as agd
    import repro.service.engine as engine

    orig = agd.agd_raw_solve
    # the service keeps its jitted solvers across runs: trace anew
    for cache in ("_SINGLE", "_SINGLE_SIGMA", "_BATCH", "_BATCH_SIGMA"):
        monkeypatch.setattr(engine, cache, {})

    def raw_solve(inst, lam0, *a, **k):
        return orig(inst, lam0, *a, **k)._replace(lam=lam0)

    monkeypatch.setattr(agd, "agd_raw_solve", raw_solve)


def _query_half_served(monkeypatch):
    """A query batch answers the first half of its users only."""
    from repro.serving.duals import DualStore

    orig = DualStore.query_snapshot

    def query(self, snap, users, **k):
        users = np.asarray(users)
        return orig(self, snap, users[: max(1, users.size // 2)], **k)

    monkeypatch.setattr(DualStore, "query_snapshot", query)


def _answer_altered(monkeypatch):
    """One allocation of every served batch is moved by 1e-3."""
    from repro.serving.duals import DualStore

    orig = DualStore.query_snapshot

    def query(self, snap, users, **k):
        res = orig(self, snap, users, **k)
        if not res.slabs:
            return res
        first = res.slabs[0]
        x = np.array(first.x)
        x[0, 0] += 1e-3
        return dataclasses.replace(
            res, slabs=(dataclasses.replace(first, x=x),) + res.slabs[1:])

    monkeypatch.setattr(DualStore, "query_snapshot", query)


@pytest.mark.parametrize("workload", SERVICE)
@pytest.mark.parametrize(
    "fault", [_service_stage_unchanged, _query_half_served, _answer_altered])
def test_service_fault_is_not_correct(workload, fault, monkeypatch):
    fault(monkeypatch)
    r = small_run(workload)
    assert not r["correct"], r["checks"]
