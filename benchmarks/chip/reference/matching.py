"""Plain reference of the ridge-regularized matching LP solve.

Straightforward float32 jax.numpy on the raw edge list, with nothing taken
from the program.  Every source's edges sit in one dense row, and rows are
kept in blocks by width (the power of two at or above the source's degree)
so that the table holds little padding and fits.  The dual oracle is

    x(lam)  = projection of -(A^T lam + c) / gamma onto {x >= 0, sum x <= 1}
              per source (sorted prefix sums),
    grad    = A x - b,
    g       = c'x + gamma/2 ||x||^2 + lam'(A x - b),

and the solve is the paper's accelerated projected dual ascent with gamma
continuation: Jacobi row scaling of A and b, a power iteration for
sigma_max(A)^2 from the normal vector of key 0, step gamma / sigma^2 clipped
to [min_step, max_step], momentum (t-1)/(t+2) with a reset whenever g falls,
and a convergence check every `check_every` iterations.  Matrix products run
at `highest` precision (there are none in the oracle; the setting guards
any the compiler would form).
"""
from __future__ import annotations

import dataclasses
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

__all__ = ["Schedule", "Table", "table", "solve", "allocations"]


@dataclasses.dataclass(frozen=True)
class Schedule:
    gammas: tuple[float, ...]
    iters_per_stage: int
    tol: float | None
    check_every: int
    power_iters: int = 30
    min_step: float = 1e-5
    max_step: float = 1e-1


ROWS = 4096  # a block's rows are a multiple of this


@dataclasses.dataclass
class Table:
    """The edge list as dense per-source rows, Jacobi-scaled, in blocks of
    rows of one width each."""

    blocks: tuple  # per width: (idx [n, W] int32 destination (0 on padding),
    #                a [m, n, W] f32 scaled coefficients (0 on padding),
    #                c [n, W] f32 cost = -value (0 on padding), valid [n, W])
    b: jax.Array  # [m * J] float32 scaled budgets
    d: np.ndarray  # [m * J] float64 row scaling (duals descale as d * lam)
    num_destinations: int


def table(src, dst, values, coeff, rhs, num_destinations: int) -> Table:
    """Dense rows of the edge list (sorted by source), with A and b scaled
    by 1 / ||A_r|| per coupling row.  The data is the float32 the
    configuration stores; norms and scaling are taken in float64."""
    J = int(num_destinations)
    m = coeff.shape[0]
    a32 = np.asarray(coeff, np.float32).astype(np.float64)
    norms = np.stack([
        np.bincount(dst, weights=a32[k] ** 2, minlength=J) for k in range(m)
    ])
    norms = np.sqrt(norms)
    d = np.where(norms > 1e-30, 1.0 / np.maximum(norms, 1e-30), 1.0)  # [m, J]
    a_scaled = (a32 * d[:, dst]).astype(np.float32)
    cost = -np.asarray(values, np.float32)
    first = np.r_[True, src[1:] != src[:-1]]
    row = np.cumsum(first) - 1
    starts = np.flatnonzero(first)
    slot = np.arange(src.size) - starts[row]
    deg = np.diff(np.r_[starts, src.size])
    width = 1 << np.ceil(np.log2(deg)).astype(np.int64)  # per source row
    blocks = []
    for W in np.unique(width):
        rows = np.flatnonzero(width == W)
        where = np.full(deg.size, -1)
        where[rows] = np.arange(rows.size)
        e = np.flatnonzero(where[row] >= 0)
        r, k = where[row[e]], slot[e]
        # empty rows up to a multiple of ROWS keep the block's shape, and so
        # the compiled steps, from one edit of the edge list to the next
        n = -(-rows.size // ROWS) * ROWS
        idx = np.zeros((n, W), np.int32)
        idx[r, k] = dst[e]
        c = np.zeros((n, W), np.float32)
        c[r, k] = cost[e]
        valid = np.zeros((n, W), bool)
        valid[r, k] = True
        a = np.zeros((m, n, W), np.float32)
        a[:, r, k] = a_scaled[:, e]
        blocks.append(tuple(jnp.asarray(v) for v in (idx, a, c, valid)))
    b = (np.asarray(rhs, np.float32).astype(np.float64) * d.reshape(-1))
    return Table(blocks=tuple(blocks), b=jnp.asarray(b.astype(np.float32)),
                 d=d.reshape(-1), num_destinations=J)


def _project(z, valid, xp=jnp):
    """Per row, the nearest point of {x >= 0, sum x <= 1} to z (xp is
    jax.numpy or numpy)."""
    zero = xp.zeros((), z.dtype)
    w0 = xp.where(valid, xp.maximum(z, zero), zero)
    inside = xp.sum(w0, axis=1, keepdims=True) <= 1.0
    u = -xp.sort(xp.where(valid, -z, xp.inf), axis=1)  # descending
    k = xp.arange(1, z.shape[1] + 1, dtype=z.dtype)
    prefix = xp.cumsum(xp.where(xp.isfinite(u), u, zero), axis=1)
    # the threshold is the largest (prefix_k - 1) / k over the row's entries
    theta = xp.max(xp.where(xp.isfinite(u), (prefix - 1) / k, -xp.inf),
                   axis=1, keepdims=True).astype(z.dtype)
    w1 = xp.where(valid, xp.maximum(z - theta, zero), zero)
    return xp.where(inside, w0, w1)


# The jitted pieces take the table as one tuple `T` = (blocks, b) and the
# number of destinations J as a static argument.


def _at_lam(blk, J, lam):
    idx, a = blk[0], blk[1]
    lam2 = lam.reshape(a.shape[0], J)
    return sum(a[k] * lam2[k][idx] for k in range(a.shape[0]))


def _a_times(blocks, J, xs):
    m = blocks[0][1].shape[0]
    out = jnp.zeros((m, J), jnp.float32)
    for (idx, a, _, _), x in zip(blocks, xs):
        for k in range(m):
            out = out.at[k, idx.reshape(-1)].add((a[k] * x).reshape(-1))
    return out.reshape(-1)


def _oracle(T, J, lam, gamma):
    blocks, b = T
    xs = [_project(-(_at_lam(blk, J, lam) + blk[2]) / gamma, blk[3])
          for blk in blocks]
    grad = _a_times(blocks, J, xs) - b
    g = sum(jnp.sum(blk[2] * x) + 0.5 * gamma * jnp.sum(x * x)
            for blk, x in zip(blocks, xs)) + jnp.dot(lam, grad)
    return g, grad


@partial(jax.jit, static_argnums=1)
def _power_step(T, J, u):
    blocks, v = T[0], u / jnp.linalg.norm(u)
    return _a_times(blocks, J, [_at_lam(blk, J, v) for blk in blocks])


@partial(jax.jit, static_argnums=1)
def _agd_step(T, J, lam_prev, lam, tk, g_prev, gamma, eta):
    beta = (tk - 1.0) / (tk + 2.0)
    mu = jnp.maximum(lam + beta * (lam - lam_prev), 0.0)
    g, grad = _oracle(T, J, mu, gamma)
    lam_next = jnp.maximum(mu + eta * grad, 0.0)
    tk_next = jnp.where(g < g_prev, 1.0, tk + 1.0)
    return (lam, lam_next, tk_next, g, jnp.linalg.norm(grad),
            jnp.max(jnp.maximum(grad, 0.0)))


_final = jax.jit(_oracle, static_argnums=1)


def solve(t: Table, sched: Schedule, lam0=None) -> dict:
    """The continuation solve from `lam0` (zeros when None) in the scaled
    dual space.  Returns lam (scaled), lam_eff (d * lam), g, sigma_sq and
    the iterations each stage ran."""
    with jax.default_matmul_precision("highest"):
        T, J = (t.blocks, t.b), t.num_destinations
        dim = t.b.shape[0]
        lam = jnp.zeros((dim,), jnp.float32) if lam0 is None else jnp.asarray(
            lam0, jnp.float32)
        u = jax.random.normal(jax.random.key(0), (dim,), jnp.float32)
        for _ in range(sched.power_iters):
            u = _power_step(T, J, u)
        sigma_sq = jnp.linalg.norm(u)
        chunk = max(1, min(sched.check_every, sched.iters_per_stage))
        chunks = -(-sched.iters_per_stage // chunk)
        used = []
        for gamma in sched.gammas:
            eta = jnp.clip(gamma / jnp.maximum(sigma_sq, 1e-20),
                           sched.min_step, sched.max_step).astype(jnp.float32)
            gamma32 = jnp.float32(gamma)
            lam_prev, tk, g_prev = lam, jnp.float32(1.0), jnp.float32(-jnp.inf)
            n = 0
            for _ in range(chunks):
                for _ in range(chunk):
                    lam_prev, lam, tk, g_prev, gn, viol = _agd_step(
                        T, J, lam_prev, lam, tk, g_prev, gamma32, eta)
                    n += 1
                if sched.tol is not None and (
                        float(gn) <= sched.tol * max(1.0, abs(float(g_prev)))
                        and float(viol) <= sched.tol):
                    break
            used.append(n)
        g, _ = _final(T, J, lam, jnp.float32(sched.gammas[-1]))
        lam_host = np.asarray(lam, np.float64)
        return {
            "lam": lam_host,
            "lam_eff": lam_host * t.d,
            "g": float(g),
            "sigma_sq": float(sigma_sq),
            "iters": used,
        }


def allocations(el_src, el_dst, values, coeff, users, lam_eff, gamma: float,
                num_destinations: int) -> dict:
    """Direct projection of published (unscaled) duals for the given users,
    from the raw edge list, in float32 numpy: per user, (destinations,
    allocations)."""
    J = int(num_destinations)
    users = np.unique(np.asarray(users, np.int64))
    lam_eff = np.asarray(lam_eff, np.float32)
    lo = np.searchsorted(el_src, users, side="left")
    deg = np.searchsorted(el_src, users, side="right") - lo
    W = max(int(deg.max()), 1) if users.size else 1
    slot = np.arange(W)
    valid = slot[None, :] < deg[:, None]
    e = np.where(valid, lo[:, None] + slot[None, :], 0)
    dst = el_dst[e]
    atl = np.zeros(e.shape, np.float32)
    for k in range(coeff.shape[0]):
        atl = atl + np.asarray(coeff[k], np.float32)[e] * lam_eff[k * J + dst]
    c = -np.asarray(values, np.float32)[e]
    x = _project(-(atl + c) / np.float32(gamma), valid, np)
    return {
        int(u): (dst[i][valid[i]].astype(np.int64), x[i][valid[i]])
        for i, u in enumerate(users)
    }
