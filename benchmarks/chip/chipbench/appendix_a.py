"""The benchmark's own copy of the paper's Appendix-A instance generator and
of the service's random delta, so that later changes to the program cannot
change the inputs it is measured on.

The generator is split in two draws:

* the topology (steps 1-2 of Appendix A: lognormal destination breadth,
  Poisson destination degrees, distinct sources per destination) comes from
  the configuration's fixed ``structure_seed``, so every run of a cell packs
  into the same bucket shapes and finds its compiled programs in the cache;
* the edge values, constraint coefficients and budgets (steps 3-5) come from
  the run's ``--seed``, so every seed is a different LP on that topology.

Edges are kept sorted by (source, destination) throughout, as the program's
packing expects.
"""
from __future__ import annotations

import dataclasses
import hashlib
import json
import os

import numpy as np

__all__ = [
    "EdgeList",
    "structure",
    "edge_values",
    "random_delta",
    "apply_delta",
    "edge_class",
]


@dataclasses.dataclass
class EdgeList:
    """One matching LP as an edge list: value is the positive matched value
    (the solver minimises -value), coeff is [m, nnz], rhs is [m * J]."""

    num_sources: int
    num_destinations: int
    src: np.ndarray  # int64, sorted with dst
    dst: np.ndarray  # int64
    values: np.ndarray  # float64
    coeff: np.ndarray  # float64 [m, nnz]
    rhs: np.ndarray  # float64 [m * J]

    @property
    def nnz(self) -> int:
        return int(self.src.size)

    @property
    def num_families(self) -> int:
        return int(self.coeff.shape[0])

    def keys(self) -> np.ndarray:
        return self.src * self.num_destinations + self.dst

    def copy(self) -> "EdgeList":
        return dataclasses.replace(
            self, src=self.src.copy(), dst=self.dst.copy(),
            values=self.values.copy(), coeff=self.coeff.copy(),
            rhs=self.rhs.copy(),
        )


def _lognormal(rng, sigma: float, size) -> np.ndarray:
    # mean-1 lognormal: exp(N(-sigma^2/2, sigma^2))
    return rng.lognormal(mean=-0.5 * sigma * sigma, sigma=sigma, size=size)


def _draw_structure(I: int, J: int, avg_degree: float, breadth_sigma: float,
                    seed: int) -> tuple[np.ndarray, np.ndarray]:
    rng = np.random.default_rng(seed)
    breadth = _lognormal(rng, breadth_sigma, J)
    p = breadth / breadth.sum()
    K = np.minimum(rng.poisson(p * I * avg_degree), I)
    dst = np.repeat(np.arange(J, dtype=np.int64), K)
    src = rng.integers(0, I, size=dst.size, dtype=np.int64)
    # np.unique sorts the keys, so the edges come out in (src, dst) order
    keys = np.unique(src * J + dst)
    return (keys // J).astype(np.int32), (keys % J).astype(np.int32)


def structure(cfg: dict, cache_dir: str | None) -> tuple[np.ndarray, np.ndarray]:
    """(src, dst) of the configuration's topology, int64, sorted.

    Drawn once from ``structure_seed`` and kept in ``cache_dir`` (a file
    keyed by the sizes), so later runs in the same checkout load it.
    """
    I, J = int(cfg["num_sources"]), int(cfg["num_destinations"])
    params = dict(I=I, J=J, nu=float(cfg["avg_degree"]),
                  breadth_sigma=float(cfg.get("breadth_sigma", 1.0)),
                  seed=int(cfg["structure_seed"]))
    tag = hashlib.sha1(json.dumps(params, sort_keys=True).encode()).hexdigest()[:12]
    path = None if cache_dir is None else os.path.join(
        cache_dir, f"structure-{tag}.npz")
    if path is not None and os.path.exists(path):
        with np.load(path) as f:
            return f["src"].astype(np.int64), f["dst"].astype(np.int64)
    src, dst = _draw_structure(I, J, params["nu"], params["breadth_sigma"],
                               params["seed"])
    if path is not None:
        os.makedirs(cache_dir, exist_ok=True)
        tmp = f"{path}.{os.getpid()}.tmp.npz"
        np.savez(tmp, src=src, dst=dst)
        os.replace(tmp, path)
    return src.astype(np.int64), dst.astype(np.int64)


def _segment_first_argmax(a: np.ndarray, seg: np.ndarray) -> np.ndarray:
    """Per run of equal ids in the sorted `seg`, the first position holding
    the run's largest `a`."""
    starts = np.flatnonzero(np.r_[True, seg[1:] != seg[:-1]])
    run = np.repeat(np.arange(starts.size), np.diff(np.r_[starts, seg.size]))
    hits = np.flatnonzero(a == np.maximum.reduceat(a, starts)[run])
    return hits[np.r_[True, run[hits][1:] != run[hits][:-1]]]


def edge_values(cfg: dict, src: np.ndarray, dst: np.ndarray, seed) -> EdgeList:
    """Steps 3-5 of Appendix A on a given topology, drawn from ``seed`` (an
    int or a sequence of ints, as numpy's SeedSequence takes them)."""
    I, J = int(cfg["num_sources"]), int(cfg["num_destinations"])
    m = int(cfg.get("num_families", 1))
    rng = np.random.default_rng(seed)
    v = _lognormal(rng, cfg.get("value_sigma", 0.5), J)
    u = _lognormal(rng, cfg.get("responsiveness_sigma", 0.5), I)
    eps = _lognormal(rng, cfg.get("noise_sigma", 0.25), src.size)
    values = np.minimum(v[dst] * u[src] * eps, cfg.get("c_max", 10.0))
    coeff = np.empty((m, src.size))
    for k in range(m):
        coeff[k] = _lognormal(rng, cfg.get("scale_sigma", 0.5), J)[dst] * values
    rhs = np.empty(m * J)
    for k in range(m):
        # greedy load: each source's largest incident a_ij, at its resource
        win = _segment_first_argmax(coeff[k], src)
        load = np.bincount(dst[win], weights=coeff[k][win], minlength=J)
        rho = rng.uniform(0.5, 1.0, size=J)
        rhs[k * J:(k + 1) * J] = rho * (load + cfg.get("rhs_eps", 1e-3))
    return EdgeList(I, J, src, dst, values, coeff, rhs)


def edge_class(el: EdgeList) -> np.ndarray:
    """Per edge, the power of two at or above its source's degree: the slab
    width the source's row is packed at."""
    deg = np.bincount(el.src, minlength=el.num_sources)
    d = deg[el.src]
    return 1 << np.ceil(np.log2(np.maximum(d, 1))).astype(np.int64)


@dataclasses.dataclass
class Delta:
    """One cadence's edits to one tenant (the fields of the program's
    InstanceDelta, with values positive)."""

    insert_src: np.ndarray
    insert_dst: np.ndarray
    insert_values: np.ndarray
    insert_coeff: np.ndarray  # [m, k]
    delete_src: np.ndarray
    delete_dst: np.ndarray
    update_src: np.ndarray
    update_dst: np.ndarray
    update_values: np.ndarray
    rhs: np.ndarray


def random_delta(el: EdgeList, rng, *, frac_update: float, n_insert: int,
                 n_delete: int, rhs_jitter: float) -> Delta:
    """The service's random delta (cost updates of +-10 % on a share of the
    edges, a few inserts and deletes, a jitter of every budget).

    The updated edges are drawn per slab-width class, the same share of each
    class, and among the edges at even positions of their source's row, so
    that no two are neighbours: every seed then touches the same number of
    cells, in the same number of contiguous runs, in each packed slab, and
    the device replays deltas of one set of sizes.
    """
    m, J, I = el.num_families, el.num_destinations, el.num_sources
    cls = edge_class(el)
    first = np.r_[True, el.src[1:] != el.src[:-1]]
    starts = np.flatnonzero(first)
    even = (np.arange(el.nnz) - starts[np.cumsum(first) - 1]) % 2 == 0
    upd = []
    for c in np.unique(cls):
        members = np.flatnonzero((cls == c) & even)
        k = min(int(round(frac_update * np.sum(cls == c))), members.size)
        if k:
            upd.append(members[rng.choice(members.size, size=k, replace=False)])
    upd = np.sort(np.concatenate(upd)) if upd else np.zeros(0, np.int64)
    taken = np.zeros(el.nnz, bool)
    taken[upd] = True
    free = np.flatnonzero(~taken)
    dele = free[rng.choice(free.size, size=n_delete, replace=False)]
    keys = el.keys()
    ins_s, ins_d, new = [], [], set()
    while len(ins_s) < n_insert:
        s, d = int(rng.integers(I)), int(rng.integers(J))
        k = s * J + d
        pos = np.searchsorted(keys, k)
        if (pos < keys.size and keys[pos] == k) or k in new:
            continue
        new.add(k)
        ins_s.append(s)
        ins_d.append(d)
    return Delta(
        insert_src=np.asarray(ins_s, np.int64),
        insert_dst=np.asarray(ins_d, np.int64),
        insert_values=rng.uniform(0.1, 3.0, n_insert),
        insert_coeff=rng.uniform(0.1, 2.0, (m, n_insert)),
        delete_src=el.src[dele],
        delete_dst=el.dst[dele],
        update_src=el.src[upd],
        update_dst=el.dst[upd],
        update_values=el.values[upd] * rng.uniform(0.9, 1.1, upd.size),
        rhs=el.rhs * rng.uniform(1 - rhs_jitter, 1 + rhs_jitter, m * J),
    )


def apply_delta(el: EdgeList, d: Delta) -> EdgeList:
    """The edge list after one delta: deletes, then inserts, then updates."""
    J = el.num_destinations
    keys = el.keys()
    gone = np.searchsorted(keys, d.delete_src * J + d.delete_dst)
    if not np.array_equal(keys[gone], d.delete_src * J + d.delete_dst):
        raise KeyError("delete of an edge that is not there")
    keep = np.ones(keys.size, bool)
    keep[gone] = False
    keys, values, coeff = keys[keep], el.values[keep], el.coeff[:, keep]
    new_keys = d.insert_src * J + d.insert_dst
    at = np.searchsorted(keys, new_keys)
    if np.any(keys[np.minimum(at, keys.size - 1)] == new_keys):
        raise KeyError("insert of an edge that is there")
    order = np.argsort(new_keys)
    keys = np.insert(keys, at[order], new_keys[order])
    values = np.insert(values, at[order], d.insert_values[order])
    coeff = np.insert(coeff, at[order], d.insert_coeff[:, order], axis=1)
    pos = np.searchsorted(keys, d.update_src * J + d.update_dst)
    if not np.array_equal(keys[pos], d.update_src * J + d.update_dst):
        raise KeyError("update of an edge that is not there")
    values = values.copy()
    values[pos] = d.update_values
    return EdgeList(el.num_sources, J, keys // J, keys % J, values, coeff,
                    np.asarray(d.rhs, np.float64).copy())
