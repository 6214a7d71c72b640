"""Structured PDHG engine on the bucketed-ELL form (cuPDLP/D-PDLP family).

The seed repo carried PDHG only as a COO strawman (`repro.core.pdhg`): K and
K' as unstructured scatter-adds over an edge list, fixed ergodic restarts, no
warm starts, no fused kernels.  This module is the production engine the
ROADMAP's "Solver diversity" item calls for — the same algorithm family, but
run directly on the bucketed slabs the rest of the system already uses:

  minimize   c'x   s.t.  A x <= b,   x in C  (per-source simplex rows)

with the standard primal-dual hybrid gradient iteration

  x+ = Proj_C(x - tau * (c + A'y))
  y+ = max(0, y + sig * (A (2 x+ - x) - b)),      tau * sig * ||A||^2 < 1.

Four systems points (see docs/solvers.md):

  * **Fused applies.**  The primal prox step is the dual oracle in disguise:
    `x - tau*(c + A'y) = -(A'y + (c - x/tau)) / (1/tau)`, so the one-pass
    fused oracle kernel (`kernels.ops.fused_pdhg_step`) performs the prox AND
    emits this bucket's `A x+` histogram from a single slab read — one launch
    per bucket per iteration where the COO path needs a gather plus a
    scatter-add.
  * **Restarts.**  `none | ergodic | adaptive | halpern` (PAPERS.md, GPU
    first-order-methods overview).  Ergodic resets to the running average on
    a fixed cadence; Halpern anchors (`x <- (t+1)/(t+2) x+ + 1/(t+2) x0`)
    with periodic re-anchoring; adaptive evaluates current-vs-average merit
    `max(rel_primal, rel_dual, rel_gap)` at every check and restarts to the
    better candidate when it beats the last restart's merit by a fixed
    factor (the D-PDLP sufficient-decay rule).
  * **Dense small-shard fast path.**  When a shard is small enough
    (`PDHGEngineConfig.dense`), the per-length buckets are coalesced into a
    single padded slab, the per-row simplex prox switches to the sort-free
    comparison-matrix projection (`core.projections.project_simplex_cmp`)
    and `A x` becomes one dense contraction against a precomputed one-hot
    destination matrix.  The iteration collapses from
    `num_buckets x (gather, sort, cumsum, reductions, segment-scatter)` to
    roughly four XLA thunks, which is what the per-iteration wall time of a
    small shard is actually made of — the math is bit-for-bit the same
    polytope and the iterates agree with the bucketed path to fp rounding.
  * **Termination.**  D-PDLP-style relative residuals, checked every
    `cfg.check_every` iterations through the SAME chunked early-stop
    machinery as AGD (`maximizer._chunked_early_scan`), including the psum'd
    all-shards-agree predicate in the distributed wrapper — so early exit
    keeps every shard at the same while_loop trip count.

Warm starts: `lam0` is the previous cadence's duals (the engine contract
keeps both engines in the same [m*J] dual space) and the primal is
reconstructed as `x0 = Proj_C(-(A'lam0 + c) / gamma_floor)` — exactly the
primal that serving publishes for those duals, so a warm cadence resumes
from the pair the system last acted on.

PDHG solves the *unsmoothed* LP: `ridge_weight` never enters the iteration
(there is no gamma), which is exactly why the scheduler may prefer it for
formulations where AGD's smoothing bias hurts (`repro.engines.selector`).
"""
from __future__ import annotations

import dataclasses
from functools import partial
from typing import Callable, Optional

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from repro.core.maximizer import (
    MaximizerConfig,
    SolveResult,
    StageStats,
    _chunked_early_scan,
)
from repro.core.objective import (
    MatchingObjective,
    _gather_at_lam,
    normalize_rows_traced,
)
from repro.core.projections import UnitSimplexProjection
from repro.engines.base import RawSolve
from repro.instances.buckets import BucketedInstance
from repro.kernels import ops as kops

__all__ = [
    "PDHGEngine",
    "PDHG_ENGINE",
    "PDHGEngineConfig",
    "RESTART_SCHEMES",
    "pdhg_raw_solve",
    "solve_pdhg_sharded",
]

RESTART_SCHEMES = ("none", "ergodic", "adaptive", "halpern")


@dataclasses.dataclass(frozen=True)
class PDHGEngineConfig:
    """PDHG-specific knobs; everything budget/tolerance comes from
    `MaximizerConfig` so the two engines stay swappable under one service
    config (total iteration budget = `cfg.total_iter_budget`, check cadence =
    `cfg.check_every`, tolerance = `cfg.tol_grad` falling back to
    `cfg.tol_viol`)."""

    restart: str = "adaptive"
    restart_every: int = 100  # ergodic/halpern cadence (iterations)
    step_ratio: float = 1.0  # omega = tau/sig balance
    step_margin: float = 0.9  # tau*sig*||A||^2 = margin^2 < 1
    restart_threshold: float = 0.8  # adaptive sufficient-decay factor
    # dense small-shard fast path: coalesce buckets + sort-free projection +
    # one-hot A-apply.  "auto" enables it when the one-hot matrix stays under
    # `dense_max_cells` entries and padding doesn't blow the slab up.
    dense: str = "auto"
    dense_max_cells: int = 1 << 22

    def __post_init__(self):
        if self.restart not in RESTART_SCHEMES:
            raise ValueError(
                f"restart={self.restart!r} not in {RESTART_SCHEMES}"
            )
        if not (0.0 < self.step_margin < 1.0):
            raise ValueError("step_margin must lie in (0, 1)")
        if self.dense not in ("auto", "on", "off"):
            raise ValueError('dense must be one of "auto" | "on" | "off"')


def _uniform_simplex(obj: MatchingObjective) -> UnitSimplexProjection:
    """PDHG's dual objective needs a closed-form min over C; simplex only.

    `min_{x in C} (c + A'y)'x` decomposes per source row as
    `radius * min(0, min_j r_j)` (inequality simplex) or
    `radius * min_j r_j` (equality); other feasible sets would need their own
    support function, so they are rejected rather than silently mis-scored.
    """
    projs = {obj._proj(i) for i in range(len(obj.instance.buckets))}
    if len(projs) != 1 or not isinstance(
        next(iter(projs)), UnitSimplexProjection
    ):
        raise NotImplementedError(
            "PDHG engine supports a uniform simplex feasible set; "
            f"got {projs}"
        )
    return next(iter(projs))


def _use_dense(buckets, num_destinations: int, pcfg: PDHGEngineConfig) -> bool:
    """Static (shape-only) decision for the dense small-shard fast path."""
    if pcfg.dense == "off" or not buckets:
        return False
    if pcfg.dense == "on":
        return True
    l_max = max(int(b.idx.shape[-1]) for b in buckets)
    rows = sum(int(b.idx.shape[0]) for b in buckets)
    slots = sum(int(b.idx.shape[0]) * int(b.idx.shape[-1]) for b in buckets)
    merged = rows * l_max
    # the one-hot apply matrix is [merged, J]; padding every row to the
    # longest bucket must also not blow the working set up
    return (
        merged * num_destinations <= pcfg.dense_max_cells
        and merged <= 4 * max(slots, 1)
    )


def _merge_buckets(buckets, costs):
    """Coalesce per-length bucket slabs into one [rows, L_max] pseudo-bucket.

    Pad entries carry mask 0 / coeff 0, so they behave exactly like the pad
    slots the bucketed form already has; `_gather_at_lam` and the residual
    loop work on the result unchanged.
    """
    from repro.instances.buckets import Bucket

    l_max = max(int(b.idx.shape[-1]) for b in buckets)

    def padded(a):
        pad = [(0, 0)] * (a.ndim - 1) + [(0, l_max - a.shape[-1])]
        return jnp.pad(jnp.asarray(a), pad)

    return Bucket(
        idx=jnp.concatenate(
            [padded(b.idx) for b in buckets], axis=0
        ).astype(jnp.int32),
        coeff=jnp.concatenate([padded(b.coeff) for b in buckets], axis=1),
        cost=jnp.concatenate(
            [padded(c) for c in costs], axis=0
        ).astype(jnp.float32),
        mask=jnp.concatenate(
            [padded(b.mask) for b in buckets], axis=0
        ).astype(jnp.float32),
        length=l_max,
    )


def _dense_onehot(mb, num_destinations: int) -> jax.Array:
    """[J, slots] one-hot destination matrix: `A x` = one dense contraction.

    Built once per solve (a single scatter); pad slots point at bin 0 with
    weight 0 so they contribute nothing.  Stored destination-major so the
    in-loop matvec streams each destination's row contiguously — the
    [slots, J] orientation costs ~20% more per iteration on CPU.
    """
    flat_idx = mb.idx.reshape(-1)
    onehot = jnp.zeros((num_destinations, flat_idx.shape[0]), jnp.float32)
    return onehot.at[
        flat_idx, jnp.arange(flat_idx.shape[0])
    ].set(mb.mask.reshape(-1).astype(jnp.float32))


def _pdhg_core(
    obj: MatchingObjective,
    lam0: jax.Array,
    cfg: MaximizerConfig,
    pcfg: PDHGEngineConfig,
    *,
    fused_oracle: bool,
    kernel_interpret: Optional[bool],
    sigma_sq: jax.Array,
    reduce_sum: Optional[Callable] = None,
    stop_reduce: Optional[Callable] = None,
) -> RawSolve:
    """Pure traced PDHG solve; `reduce_sum` sums partials across shards
    (identity on a single device, `psum` under shard_map)."""
    inst = obj.instance
    m, J = inst.num_families, inst.num_destinations
    proj = _uniform_simplex(obj)
    radius, inequality = proj.radius, proj.inequality
    if reduce_sum is None:
        reduce_sum = lambda v: v  # noqa: E731 - single-shard identity

    buckets = obj._buckets  # fp32 compute views (no-op for fp32 storage)
    costs = tuple(obj._scaled_cost(b) for b in buckets)
    rhs = jnp.asarray(inst.rhs, jnp.float32)
    rhs_norm = jnp.linalg.norm(rhs)
    c_sq_local = sum(
        jnp.vdot(c * b.mask, c * b.mask) for b, c in zip(buckets, costs)
    )
    c_norm = jnp.sqrt(reduce_sum(jnp.asarray(c_sq_local, jnp.float32)))

    sigma = jnp.sqrt(jnp.maximum(jnp.asarray(sigma_sq, jnp.float32), 1e-20))
    tau = jnp.asarray(pcfg.step_margin * pcfg.step_ratio, jnp.float32) / sigma
    sig = jnp.asarray(pcfg.step_margin / pcfg.step_ratio, jnp.float32) / sigma

    # ---- dense small-shard fast path (see module docstring) ---------------
    dense = _use_dense(buckets, J, pcfg)
    if dense:
        from repro.core.projections import project_simplex_cmp

        split_shapes = [
            (int(b.idx.shape[0]), int(b.idx.shape[-1])) for b in buckets
        ]
        mb = _merge_buckets(buckets, costs)
        onehot = _dense_onehot(mb, J)
        buckets = (mb,)
        costs = (mb.cost,)
        projs = [
            lambda z, mask: project_simplex_cmp(
                z, mask, radius, inequality=inequality
            )
        ]

        def dense_apply_a(xs):
            contrib = (mb.coeff * xs).reshape(m, -1)
            # contract slots against the [J, slots] one-hot: rows stream
            # contiguously, result is [m, J]
            # fp32 products: at default precision the TPU would multiply
            # them in bf16
            return jax.lax.dot_general(
                contrib, onehot, (((1,), (1,)), ((), ())),
                precision=jax.lax.Precision.HIGHEST,
            ).reshape(-1)

    else:
        projs = [obj._proj(i) for i in range(len(buckets))]

    # ---- one primal prox step + the A x+ apply ----------------------------
    if dense:
        # ax-free iteration: A is linear, so the dual step's extrapolated
        # apply folds into the single dense contraction, A(2 x+ - x).  The
        # scan then carries only (x, y) — no A x buffer, no carry copies —
        # and residual checks recompute A x with one extra dot per check.
        def primal_step(x, y):
            y2 = y.reshape(m, J)
            z = x[0] - tau * (_gather_at_lam(mb, y2) + mb.cost)
            xn = projs[0](z, mb.mask)
            axbar = reduce_sum(dense_apply_a(2.0 * xn - x[0]))
            return (xn,), axbar

    elif fused_oracle:

        def primal_step(x, y):
            new = []
            ax = jnp.zeros((m, J), jnp.float32)
            for b, c, xs in zip(buckets, costs, x):
                xn, hist = kops.fused_pdhg_step(
                    b.idx, b.coeff, c, b.mask, xs, y, tau,
                    num_destinations=J,
                    radius=radius,
                    inequality=inequality,
                    interpret=kernel_interpret,
                )
                new.append(xn)
                ax = ax + hist
            return tuple(new), reduce_sum(ax.reshape(-1))

    else:

        def primal_step(x, y):
            y2 = y.reshape(m, J)
            new = []
            for i, (b, c, xs) in enumerate(zip(buckets, costs, x)):
                z = xs - tau * (_gather_at_lam(b, y2) + c)
                new.append(obj._proj(i)(z, b.mask))
            xt = tuple(new)
            return xt, reduce_sum(obj.apply_A(xt))

    # ---- D-PDLP relative residuals ----------------------------------------
    def residuals(x, y, ax):
        """(primal_obj, dual_obj, rel_primal, rel_dual, rel_gap)."""
        viol = jnp.maximum(ax - rhs, 0.0)
        pr = jnp.linalg.norm(viol) / (1.0 + rhs_norm)
        y2 = y.reshape(m, J)
        pobj_loc = jnp.float32(0.0)
        dr_loc = jnp.float32(0.0)
        dual_loc = jnp.float32(0.0)
        for i, (b, c, xs) in enumerate(zip(buckets, costs, x)):
            r = _gather_at_lam(b, y2) + c
            pg = xs - projs[i](xs - r, b.mask)
            pobj_loc = pobj_loc + jnp.vdot(c * b.mask, xs)
            dr_loc = dr_loc + jnp.vdot(pg, pg)
            rmin = jnp.min(jnp.where(b.mask > 0, r, jnp.inf), axis=-1)
            has = jnp.any(b.mask > 0, axis=-1)
            contrib = radius * (
                jnp.minimum(rmin, 0.0) if inequality else rmin
            )
            dual_loc = dual_loc + jnp.sum(jnp.where(has, contrib, 0.0))
        sums = reduce_sum(jnp.stack([pobj_loc, dual_loc, dr_loc]))
        pobj = sums[0]
        dobj = sums[1] - jnp.vdot(rhs, y)
        dr = jnp.sqrt(jnp.maximum(sums[2], 0.0)) / (1.0 + c_norm)
        gap = jnp.abs(pobj - dobj) / (1.0 + jnp.abs(pobj) + jnp.abs(dobj))
        return pobj, dobj, pr, dr, gap

    # ---- iteration body with the selected restart scheme ------------------
    scheme = pcfg.restart
    every = int(pcfg.restart_every)

    def one_iter(state, _):
        x, y, ax, it, restarts, extra = state
        xn, axn = primal_step(x, y)
        if dense:
            # primal_step returned A(2 x+ - x) directly; nothing is carried
            yn = jnp.maximum(y + sig * (axn - rhs), 0.0)
            axn = None
        else:
            yn = jnp.maximum(y + sig * (2.0 * axn - ax - rhs), 0.0)
        # only the fixed-cadence schemes read the in-loop counter; keeping it
        # frozen otherwise saves a whole dispatch per iteration on the dense
        # fast path (reported iteration counts come from `checks_used`)
        it1 = it + 1 if scheme in ("ergodic", "halpern") else it
        if scheme == "none":
            return (xn, yn, axn, it1, restarts, extra), None
        if scheme in ("ergodic", "adaptive"):
            xs_sum, y_sum, ax_sum, win = extra[:4]
            xs_sum = jax.tree.map(lambda s, v: s + v, xs_sum, xn)
            y_sum, win = y_sum + yn, win + 1
            ax_sum = None if dense else ax_sum + axn
            if scheme == "ergodic":
                do = (it1 % every) == 0
                wf = jnp.maximum(win.astype(jnp.float32), 1.0)
                xn = jax.tree.map(
                    lambda s, v: jnp.where(do, s / wf, v), xs_sum, xn
                )
                yn = jnp.where(do, y_sum / wf, yn)
                if not dense:
                    axn = jnp.where(do, ax_sum / wf, axn)
                zero = lambda s: jnp.where(do, jnp.zeros_like(s), s)  # noqa: E731
                xs_sum = jax.tree.map(zero, xs_sum)
                y_sum = zero(y_sum)
                ax_sum = None if dense else zero(ax_sum)
                win = jnp.where(do, 0, win)
                restarts = restarts + do.astype(jnp.int32)
            extra = (xs_sum, y_sum, ax_sum, win) + extra[4:]
            return (xn, yn, axn, it1, restarts, extra), None
        # halpern: blend toward the anchor, re-anchor on a fixed cadence
        xa, ya, axa, t = extra
        w = (t + 1.0) / (t + 2.0)
        xn = jax.tree.map(lambda v, a: w * v + (1.0 - w) * a, xn, xa)
        yn = w * yn + (1.0 - w) * ya
        if not dense:
            axn = w * axn + (1.0 - w) * axa
        do = (it1 % every) == 0
        xa = jax.tree.map(lambda a, v: jnp.where(do, v, a), xa, xn)
        ya = jnp.where(do, yn, ya)
        axa = None if dense else jnp.where(do, axn, axa)
        t = jnp.where(do, 0.0, t + 1.0)
        restarts = restarts + do.astype(jnp.int32)
        return (xn, yn, axn, it1, restarts, (xa, ya, axa, t)), None

    total = int(cfg.total_iter_budget)
    inner = max(1, min(int(cfg.check_every), total))
    n_checks = -(-total // inner)
    tol = cfg.tol_grad if cfg.tol_grad is not None else cfg.tol_viol

    def body(carry, _):
        carry, _ = jax.lax.scan(one_iter, carry, None, length=inner)
        x, y, ax, it, restarts, extra = carry
        if dense:
            # the ax-free dense carry recomputes A x once per check
            ax = reduce_sum(dense_apply_a(x[0]))
        if scheme == "adaptive":
            # D-PDLP sufficient-decay restart: compare the current iterate
            # against the window average by merit, adopt the better one when
            # it beats the merit at the last restart by `restart_threshold`.
            xs_sum, y_sum, ax_sum, win, merit_last = extra
            wf = jnp.maximum(win.astype(jnp.float32), 1.0)
            x_avg = jax.tree.map(lambda s: s / wf, xs_sum)
            y_avg = y_sum / wf
            ax_avg = (
                reduce_sum(dense_apply_a(x_avg[0])) if dense
                else ax_sum / wf
            )
            po_c, _, pr_c, dr_c, gap_c = residuals(x, y, ax)
            po_a, _, pr_a, dr_a, gap_a = residuals(x_avg, y_avg, ax_avg)
            merit_c = jnp.maximum(gap_c, jnp.maximum(pr_c, dr_c))
            merit_a = jnp.maximum(gap_a, jnp.maximum(pr_a, dr_a))
            use_avg = merit_a < merit_c
            merit_cand = jnp.minimum(merit_a, merit_c)
            do = merit_cand <= pcfg.restart_threshold * merit_last
            adopt_avg = jnp.logical_and(do, use_avg)
            sel = lambda a, c: jnp.where(adopt_avg, a, c)  # noqa: E731
            x = jax.tree.map(sel, x_avg, x)
            y, ax = sel(y_avg, y), sel(ax_avg, ax)
            po, pr = sel(po_a, po_c), sel(pr_a, pr_c)
            dr, gap = sel(dr_a, dr_c), sel(gap_a, gap_c)
            zero = lambda s: jnp.where(do, jnp.zeros_like(s), s)  # noqa: E731
            xs_sum = jax.tree.map(zero, xs_sum)
            y_sum = zero(y_sum)
            ax_sum = None if dense else zero(ax_sum)
            win = jnp.where(do, 0, win)
            merit_last = jnp.where(do, merit_cand, merit_last)
            restarts = restarts + do.astype(jnp.int32)
            extra = (xs_sum, y_sum, ax_sum, win, merit_last)
        else:
            po, _, pr, dr, gap = residuals(x, y, ax)
        if dense:
            ax = None  # keep the scan carry ax-free
        f32 = lambda v: v.astype(jnp.float32)  # noqa: E731
        return (
            (x, y, ax, it, restarts, extra),
            (f32(po), f32(dr), f32(pr), f32(gap)),
        )

    def stop_predicate(traces):
        if tol is None:
            return jnp.asarray(False)
        _, dr, pr, gap = traces
        t = jnp.float32(tol)
        return jnp.logical_and(
            jnp.logical_and(pr[-1] <= t, dr[-1] <= t), gap[-1] <= t
        )

    # ---- initial point: reconstruct the primal serving publishes ----------
    y0 = jnp.asarray(lam0, jnp.float32)
    x0 = obj.primal_candidate(y0, jnp.float32(cfg.gammas[-1]))
    x0 = tuple(xs.astype(jnp.float32) for xs in x0)
    if dense:
        l_max = mb.idx.shape[-1]
        x0 = (
            jnp.concatenate(
                [
                    jnp.pad(xs, ((0, 0), (0, l_max - xs.shape[-1])))
                    for xs in x0
                ],
                axis=0,
            ),
        )
        ax0 = None  # ax-free carry; recomputed from x at check boundaries
    else:
        ax0 = reduce_sum(obj.apply_A(x0)).astype(jnp.float32)
    zero_x = jax.tree.map(jnp.zeros_like, x0)
    i32 = partial(jnp.asarray, dtype=jnp.int32)
    if scheme in ("ergodic", "adaptive"):
        ax_sum0 = None if dense else jnp.zeros_like(ax0)
        extra0 = (zero_x, jnp.zeros_like(y0), ax_sum0, i32(0))
        if scheme == "adaptive":
            extra0 = extra0 + (jnp.float32(jnp.inf),)
    elif scheme == "halpern":
        extra0 = (x0, y0, ax0, jnp.float32(0.0))
    else:
        extra0 = ()
    carry0 = (x0, y0, ax0, i32(0), i32(0), extra0)

    final, bufs, checks_used = _chunked_early_scan(
        body,
        carry0,
        n_checks,
        check_every=1,  # `body` already runs `inner` iterations per call
        trace_dtype=jnp.float32,
        num_traces=4,
        stop_predicate=stop_predicate,
        stop_reduce=stop_reduce,
    )
    x, y, ax, _, restarts, _ = final
    if dense:
        ax = reduce_sum(dense_apply_a(x[0]))
    pobj, _, _, _, _ = residuals(x, y, ax)
    iters = (checks_used * inner).astype(jnp.int32)
    if dense:
        # hand back per-bucket slabs (the RawSolve contract serving relies
        # on); pad columns beyond each bucket's true length are exact zeros
        merged_x, parts, off = x[0], [], 0
        for rows_i, len_i in split_shapes:
            parts.append(merged_x[off:off + rows_i, :len_i])
            off += rows_i
        x = tuple(parts)
    stats = (
        StageStats(g=bufs[0], grad_norm=bufs[1], max_violation=bufs[2]),
    )
    return RawSolve(
        lam=y,
        x_slabs=x,
        g=pobj,
        stats=stats,
        sigma_sq=jnp.asarray(sigma_sq, jnp.float32),
        etas=jnp.stack([tau]),
        iters=jnp.stack([iters]),
        restarts=restarts,
    )


def pdhg_raw_solve(
    inst: BucketedInstance,
    lam0: jax.Array,
    cfg: MaximizerConfig,
    normalize: bool,
    fused_oracle: bool = False,
    sigma_sq: Optional[jax.Array] = None,
    pcfg: PDHGEngineConfig = PDHGEngineConfig(),
    kernel_interpret: Optional[bool] = None,
) -> RawSolve:
    """Single-shard (or vmapped) structured PDHG solve -> RawSolve.

    Mirrors `agd_raw_solve`'s contract exactly: pure in the instance pytree,
    Jacobi-normalizes device-side when asked, runs the power iteration only
    when no `sigma_sq` is supplied (the service's engine-agnostic sigma
    cache feeds both engines — sigma_max(A) doesn't care which solver uses
    it).
    """
    if normalize:
        inst, _ = normalize_rows_traced(inst)
    obj = MatchingObjective(inst, kernel_interpret=kernel_interpret)
    if sigma_sq is None:
        sigma_sq = obj.power_iteration(
            jax.random.key(cfg.seed), iters=cfg.power_iters
        )
    return _pdhg_core(
        obj, lam0, cfg, pcfg,
        fused_oracle=fused_oracle,
        kernel_interpret=kernel_interpret,
        sigma_sq=sigma_sq,
    )


class PDHGEngine:
    """Engine-protocol wrapper over `pdhg_raw_solve`."""

    name = "pdhg"

    @staticmethod
    def raw_solve(
        inst,
        lam0,
        cfg: MaximizerConfig,
        *,
        normalize: bool,
        fused_oracle: bool = False,
        sigma_sq=None,
    ) -> RawSolve:
        return pdhg_raw_solve(
            inst, lam0, cfg, normalize, fused_oracle, sigma_sq
        )


PDHG_ENGINE = PDHGEngine()


# ---------------------------------------------------------------------------
# Distributed wrapper: same core, psum hooks, collective early stop.
# ---------------------------------------------------------------------------


def _sharded_fns(inst, mesh, cfg, dist, pcfg, projection):
    """Build the shard_map'ped (power_fn, solve_fn) pair for `inst`'s shapes.

    Shared by the run path (`solve_pdhg_sharded`) and the dry-run lowering
    path (`lower_pdhg_sharded`) so both compile the identical program.
    """
    from repro.core.sharding import instance_pspecs, num_shards, scoped_psum
    axes = dist.axes_tuple
    specs = instance_pspecs(inst, dist.axes)
    slab_specs = tuple(P(dist.axes, None) for _ in inst.buckets)
    n_shards = num_shards(mesh, dist)
    psum = lambda v: scoped_psum(v, axes)  # noqa: E731

    def psum_all_converged(done):
        votes = psum(done.astype(jnp.int32))
        return votes == n_shards

    def local_objective(inst_local):
        return MatchingObjective(
            inst_local,
            projection=projection or UnitSimplexProjection(),
            include_rhs=False,
            kernel_interpret=dist.kernel_interpret,
        )

    @partial(
        jax.shard_map,
        mesh=mesh,
        in_specs=(P(), specs),
        out_specs=P(),
        check_vma=False,
    )
    def power_fn(u0, inst_local):
        obj = local_objective(inst_local)

        def body(u, _):
            atl = obj.apply_AT(u / jnp.linalg.norm(u))
            au = psum(obj.apply_A(atl))
            return au, jnp.linalg.norm(au)

        _, norms = jax.lax.scan(body, u0, None, length=cfg.power_iters)
        return norms[-1]

    @partial(
        jax.shard_map,
        mesh=mesh,
        in_specs=(P(), P(), specs),
        out_specs=(
            P(),
            slab_specs,
            P(),
            StageStats(P(), P(), P()),
            P(),
            P(),
            P(),
        ),
        check_vma=False,
    )
    def solve_fn(lam_in, sigma_sq_in, inst_local):
        obj = local_objective(inst_local)
        raw = _pdhg_core(
            obj, lam_in, cfg, pcfg,
            fused_oracle=dist.fused_oracle,
            kernel_interpret=dist.kernel_interpret,
            sigma_sq=sigma_sq_in,
            reduce_sum=psum,
            stop_reduce=psum_all_converged,
        )
        return (
            raw.lam, raw.x_slabs, raw.g, raw.stats[0],
            raw.etas, raw.iters, raw.restarts,
        )

    return power_fn, solve_fn


def solve_pdhg_sharded(
    inst: BucketedInstance,
    mesh: Mesh,
    cfg: MaximizerConfig = MaximizerConfig(),
    dist=None,
    pcfg: PDHGEngineConfig = PDHGEngineConfig(),
    lam0: Optional[jax.Array] = None,
    projection=None,
) -> SolveResult:
    """Column-sharded PDHG over a device mesh (paper §4.4 layout).

    The engine core is reused verbatim with two hooks swapped in: partial
    sums cross shards through ONE `psum` per iteration (the `A x+` vector;
    residual scalars piggyback once per check), and the early-stop predicate
    is reduced with the same unanimous-vote psum as the distributed AGD path
    (`core.sharding.DistributedMaximizer`), keeping every shard at an
    identical while_loop trip count.

    Instances should be pre-normalized host-side (`normalize_rows`) when
    Jacobi conditioning is wanted — row norms are a global reduction, so the
    traced per-shard `normalize_rows_traced` doesn't apply here (same policy
    as the distributed AGD driver).  PDHG ignores `dist.comm_mode`/`compress`
    (always plain psum, no error feedback).
    """
    from repro.core.sharding import DistConfig

    dist = dist or DistConfig()
    power_fn, solve_fn = _sharded_fns(inst, mesh, cfg, dist, pcfg, projection)
    dual_dim = inst.dual_dim
    lam = (
        jnp.zeros((dual_dim,), jnp.float32) if lam0 is None
        else jnp.asarray(lam0, jnp.float32)
    )
    u0 = jax.random.normal(
        jax.random.key(cfg.seed), (dual_dim,), jnp.float32
    )
    with jax.set_mesh(mesh):
        sigma_sq = jax.jit(power_fn)(u0, inst)
        lam, x_slabs, g, st, etas, iters, restarts = jax.jit(solve_fn)(
            lam, sigma_sq, inst
        )
    return SolveResult(
        lam=lam,
        x_slabs=x_slabs,
        g=g,
        stats=(st,),
        sigma_sq=sigma_sq,
        steps=(float(etas[0]),),
        iters_used=(int(iters[0]),),
        restarts=int(restarts),
    )


def lower_pdhg_sharded(
    inst: BucketedInstance,
    mesh: Mesh,
    cfg: MaximizerConfig = MaximizerConfig(),
    dist=None,
    pcfg: PDHGEngineConfig = PDHGEngineConfig(),
    projection=None,
):
    """Lower (without running) the sharded PDHG solve under its production
    shardings — the dry-run coherence proof (`launch/dryrun.py`): the
    returned Lowered yields memory/cost analysis and collective bytes after
    `.compile()`.  Accepts a spec-shaped instance (ShapeDtypeStruct leaves).
    """
    from repro.core.sharding import DistConfig

    dist = dist or DistConfig()
    _, solve_fn = _sharded_fns(inst, mesh, cfg, dist, pcfg, projection)
    lam = jax.ShapeDtypeStruct((inst.dual_dim,), jnp.float32)
    sigma_sq = jax.ShapeDtypeStruct((), jnp.float32)
    with jax.set_mesh(mesh):
        return jax.jit(solve_fn).lower(lam, sigma_sq, inst)
