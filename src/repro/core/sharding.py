"""Column-sharded distributed execution (paper §4.4), TPU-native.

The paper launches one process per GPU and, per iteration, performs a
reduce-to-rank-0 of the |lam|-sized gradient, a serialized AGD update on rank
0, and a broadcast of the new duals.  The TPU-native schedule here is a single
`psum` inside `shard_map` followed by a *replicated* dual update on every
shard — mathematically identical, one collective instead of two, and no
serialized rank.  Both schedules are implemented (`comm_mode`):

  "psum"  (default) one all-reduce of [m*J (+2 packed scalars)] per iteration
  "rank0" paper-faithful: reduce + rank-0 update + broadcast (2 collectives)

Either way, per-iteration communication volume depends only on the dual
dimension m*J — never on sources, nonzeros, or shard count — which is the
paper's central scaling property.  Beyond the paper, `compress="bf16_ef"`
halves the reduce payload with per-shard error-feedback accumulators.

Sharding layout (the paper's balanced column split):
  bucket.idx/cost/mask [n, L]   -> P(axes, None)       n is the source axis
  bucket.coeff       [m, n, L]  -> P(None, axes, None)
  rhs                  [m*J]    -> P()                  replicated
  lam                  [m*J]    -> P()                  replicated

Buckets are padded to a row-multiple of the shard count at pack time
(`bucketize(shard_multiple=...)`), so every shard sees identical shapes.
"""
from __future__ import annotations

import dataclasses
from functools import partial
from typing import Optional, Sequence, Union

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro import telemetry
from repro.core.maximizer import (
    MaximizerConfig,
    SolveResult,
    StageStats,
    _stage_scan,
    _stage_scan_early,
    step_size,
)
from repro.core.objective import DualEval, MatchingObjective
from repro.core.projections import ProjectionMap, UnitSimplexProjection
from repro.instances.buckets import Bucket, BucketedInstance

__all__ = [
    "DistConfig",
    "instance_pspecs",
    "shard_instance",
    "DistributedMaximizer",
    "num_shards",
    "scoped_psum",
]


def scoped_psum(x, axes):
    """`jax.lax.psum` under the `dist.psum` scope, which every collective of
    the sharded solvers carries (docs/observability.md): a profiler trace
    then tells the all-reduces, and any wait of a fast shard for a slow one,
    from the oracle's work.  `x` is computed outside the scope, so it holds
    the collective alone.  On a one-device mesh the TPU compiler drops the
    collective and nothing carries the name."""
    with jax.named_scope("dist.psum"):
        return jax.lax.psum(x, axes)


@dataclasses.dataclass(frozen=True)
class DistConfig:
    axes: Union[str, tuple[str, ...]] = "data"  # mesh axes carrying the column shard
    comm_mode: str = "psum"  # "psum" | "rank0"
    compress: str = "none"  # "none" | "bf16" | "bf16_ef"
    fused_kernel: bool = False
    # One-pass fused dual oracle (kernels/dual_oracle.py): each shard's local
    # calculate() reads its slab rows once per iteration and emits the
    # pre-psum (ax, c'x, ||x||^2) contributions directly from the kernel's
    # partial histograms (include_rhs=False local mode, b applied after the
    # reduction as before).  Subsumes fused_kernel when set.
    fused_oracle: bool = False
    kernel_interpret: Optional[bool] = None
    # Slab storage dtype ("float32" | "bfloat16" | "int8"); the launch layer
    # bucketizes with it and the per-shard oracles load the narrow slabs with
    # fp32 accumulation (kernels/).  Dual space (lam, rhs, the psum payload)
    # stays fp32 regardless — wire compression is the separate `compress`.
    slab_dtype: str = "float32"

    def __post_init__(self):
        from repro.instances.buckets import SLAB_DTYPES

        if self.slab_dtype not in SLAB_DTYPES:
            raise ValueError(
                f"DistConfig.slab_dtype={self.slab_dtype!r}; "
                f"choose from {SLAB_DTYPES}"
            )

    @property
    def axes_tuple(self) -> tuple[str, ...]:
        return (self.axes,) if isinstance(self.axes, str) else tuple(self.axes)


def num_shards(mesh: Mesh, dist: DistConfig) -> int:
    return int(np.prod([mesh.shape[a] for a in dist.axes_tuple]))


def instance_pspecs(
    inst: BucketedInstance, axes: Union[str, tuple[str, ...]]
) -> BucketedInstance:
    """Pytree of PartitionSpecs matching a BucketedInstance."""
    row = P(axes, None)
    # int8 dequant scales (when present) are tiny [m,1,1]/[1,1] arrays and
    # ride along fully replicated; None mirrors None so treedefs match.
    buckets = tuple(
        Bucket(idx=row, coeff=P(None, axes, None), cost=row, mask=row,
               length=b.length,
               coeff_scale=None if b.coeff_scale is None else P(),
               cost_scale=None if b.cost_scale is None else P())
        for b in inst.buckets
    )
    return BucketedInstance(
        buckets=buckets,
        rhs=P(),
        num_sources=inst.num_sources,
        num_destinations=inst.num_destinations,
        num_families=inst.num_families,
    )


def shard_instance(
    inst: BucketedInstance, mesh: Mesh, dist: DistConfig
) -> BucketedInstance:
    """Place instance arrays on the mesh with the column-shard layout.

    One host process holds the whole instance in its memory and
    `jax.device_put` sends each device its rows.  Placement in which each
    host materialises only its own rows (the paper's shards read the shared
    instance from a network filesystem) waits for a deployment larger than
    one host (ROADMAP Queue 2 (b)).
    """
    specs = instance_pspecs(inst, dist.axes)
    return jax.tree.map(
        lambda x, s: jax.device_put(x, NamedSharding(mesh, s)), inst, specs
    )


# ---------------------------------------------------------------------------


def _make_calculate(local_obj: MatchingObjective, dist: DistConfig, rhs):
    """Distributed ObjectiveFunction.calculate: local work + one reduction.

    Packs the two scalar reductions (objective decomposition) into the same
    all-reduce payload as the gradient vector, so `psum` mode issues exactly
    one collective per iteration.
    """
    axes = dist.axes_tuple

    def calculate(lam, gamma, comm):
        ev = local_obj.calculate(lam, gamma)  # include_rhs=False: local parts
        contrib = jnp.concatenate(
            [ev.ax, jnp.stack([ev.primal_linear, ev.primal_ridge])]
        )
        if dist.compress in ("bf16", "bf16_ef"):
            if dist.compress == "bf16_ef":
                contrib = contrib + comm  # add carried quantization error
            sent = contrib.astype(jnp.bfloat16)  # the wire payload IS bf16
            if dist.compress == "bf16_ef":
                comm = contrib - sent.astype(jnp.float32)
            contrib = sent
        if dist.comm_mode == "rank0":
            # paper-faithful: reduce to rank 0, update there, broadcast back.
            # In SPMD both hops are all-reduces; the second one broadcasts the
            # rank-0 update by summing a one-hot-masked copy.
            total = scoped_psum(contrib, axes)  # 'reduce' hop
            rank = _linear_rank(axes)
            masked = jnp.where(rank == 0, total, jnp.zeros_like(total))
            total = scoped_psum(masked, axes)  # 'broadcast' hop
        else:
            total = scoped_psum(contrib, axes)
        total = total.astype(jnp.float32)
        ax, lin, ridge = total[:-2], total[-2], total[-1]
        grad = ax - rhs
        g = lin + ridge + jnp.vdot(lam, grad)
        return (
            DualEval(g=g, grad=grad, x_slabs=ev.x_slabs,
                     primal_linear=lin, primal_ridge=ridge, ax=ax),
            comm,
        )

    return calculate


def _allreduce_bytes(dual_dim: int, dist: DistConfig, *, evaluations: int,
                     power_steps: int, votes: int) -> int:
    """Bytes one shard contributes to the solve's all-reduces: per oracle
    evaluation (AGD iteration or final recovery) the m*J gradient and the
    two packed scalars, at 2 B per element under bf16 compression and twice
    under `comm_mode="rank0"`; per power step the m*J vector A u in fp32;
    per stop vote one int32.  It depends on the dual dimension and the
    schedule, never on sources or nonzeros (the paper's scaling property)."""
    width = 2 if dist.compress in ("bf16", "bf16_ef") else 4
    hops = 2 if dist.comm_mode == "rank0" else 1
    return (evaluations * (dual_dim + 2) * width * hops
            + power_steps * dual_dim * 4 + votes * 4)


def _linear_rank(axes: tuple[str, ...]) -> jax.Array:
    rank = jnp.int32(0)
    for a in axes:
        rank = rank * jax.lax.axis_size(a) + jax.lax.axis_index(a)
    return rank


class DistributedMaximizer:
    """Maximizer over a column-sharded instance (paper §4.4).

    The continuation driver and AGD stage logic are *shared* with the
    single-device Maximizer (`_stage_scan` / `_stage_scan_early`); this class
    contributes only the sharded `calculate`, the shard_map plumbing, and —
    when `config.tol_grad`/`tol_viol` are set — a psum'd convergence
    predicate so the chunked early-stop stage variant can run collectively:
    every shard votes on the stop decision and the stage exits only on a
    unanimous vote, keeping all shards at the same while_loop trip count.
    Up to the stop iteration the trajectory is bit-for-bit the fixed-budget
    one (same AGD body, same chunked scan).
    """

    def __init__(
        self,
        inst: BucketedInstance,  # host or already-sharded arrays
        mesh: Mesh,
        config: MaximizerConfig = MaximizerConfig(),
        dist: DistConfig = DistConfig(),
        projection: Optional[ProjectionMap] = None,
    ):
        self.mesh = mesh
        self.config = config
        self.dist = dist
        self.projection = projection or UnitSimplexProjection()
        self.inst = inst
        self._specs = instance_pspecs(inst, dist.axes)
        self._rhs_host = inst.rhs

        axes = dist.axes_tuple
        cfg = config

        def local_objective(inst_local: BucketedInstance) -> MatchingObjective:
            return MatchingObjective(
                inst_local,
                projection=self.projection,
                include_rhs=False,
                fused_kernel=dist.fused_kernel,
                fused_oracle=dist.fused_oracle,
                kernel_interpret=dist.kernel_interpret,
            )

        # ---- stage function (jit once; gamma/eta are traced scalars) -------
        slab_specs = tuple(P(axes, None) for _ in inst.buckets)
        n_shards = num_shards(mesh, dist)

        def psum_all_converged(done):
            """Collective stop predicate: every shard must vote converged.

            The per-shard predicate is computed from the psum'd global
            gradient, so the votes agree mathematically; reducing them with
            one more psum makes the agreement *structural* — the while_loop
            trip count is identical on every shard by construction, which is
            what keeps the collectives inside the loop body from deadlocking.
            """
            votes = scoped_psum(done.astype(jnp.int32), axes)
            return votes == n_shards

        @partial(
            jax.shard_map,
            mesh=mesh,
            in_specs=(P(), P(), P(), self._specs),
            out_specs=(P(), StageStats(P(), P(), P()), P()),
            check_vma=False,
        )
        def stage_fn(lam0, gamma, eta, inst_local):
            obj = local_objective(inst_local)
            calculate = _make_calculate(obj, dist, inst_local.rhs)
            comm0 = (
                jnp.zeros((obj.dual_dim + 2,), jnp.float32)
                if dist.compress == "bf16_ef"
                else None
            )
            if cfg.early_stop:
                lam, stats, _, iters_used = _stage_scan_early(
                    calculate,
                    lam0,
                    gamma,
                    eta,
                    cfg.iters_per_stage,
                    acceleration=cfg.acceleration,
                    adaptive_restart=cfg.adaptive_restart,
                    tol_grad=cfg.tol_grad,
                    tol_viol=cfg.tol_viol,
                    check_every=cfg.check_every,
                    comm0=comm0,
                    stop_reduce=psum_all_converged,
                )
                return lam, stats, iters_used
            lam, stats, _ = _stage_scan(
                calculate,
                lam0,
                gamma,
                eta,
                cfg.iters_per_stage,
                acceleration=cfg.acceleration,
                adaptive_restart=cfg.adaptive_restart,
                comm0=comm0,
            )
            return lam, stats, jnp.asarray(cfg.iters_per_stage, jnp.int32)

        self._stage_fn = jax.jit(stage_fn)

        # ---- one-time sigma_max^2 power iteration (sharded) ----------------
        @partial(
            jax.shard_map,
            mesh=mesh,
            in_specs=(P(), self._specs),
            out_specs=P(),
            check_vma=False,
        )
        def power_fn(u0, inst_local):
            obj = local_objective(inst_local)

            def body(u, _):
                atl = obj.apply_AT(u / jnp.linalg.norm(u))
                au = scoped_psum(obj.apply_A(atl), axes)
                return au, jnp.linalg.norm(au)

            with jax.named_scope("power_iteration"):
                _, norms = jax.lax.scan(body, u0, None, length=cfg.power_iters)
            return norms[-1]

        self._power_fn = jax.jit(power_fn)

        # ---- final primal recovery ------------------------------------------
        @partial(
            jax.shard_map,
            mesh=mesh,
            in_specs=(P(), P(), self._specs),
            out_specs=(slab_specs, P()),
            check_vma=False,
        )
        def final_fn(lam, gamma, inst_local):
            obj = local_objective(inst_local)
            calculate = _make_calculate(obj, dist, inst_local.rhs)
            ev, _ = calculate(lam, gamma, jnp.zeros((obj.dual_dim + 2,), jnp.float32)
                              if dist.compress == "bf16_ef" else None)
            return ev.x_slabs, ev.g

        self._final_fn = jax.jit(final_fn)

    def place(self) -> None:
        """Device-put the instance with the column-shard layout."""
        self.inst = shard_instance(self.inst, self.mesh, self.dist)

    def solve(self, lam0: Optional[jax.Array] = None) -> SolveResult:
        cfg = self.config
        shards = num_shards(self.mesh, self.dist)
        dual_dim = self.inst.dual_dim
        lam = jnp.zeros((dual_dim,), jnp.float32) if lam0 is None else lam0
        u0 = jax.random.normal(jax.random.key(cfg.seed), (dual_dim,), jnp.float32)
        with telemetry.span(
            "dist_solve", shards=shards, comm_mode=self.dist.comm_mode
        ), jax.set_mesh(self.mesh):
            with telemetry.span("power_iteration"):
                sigma_sq = self._power_fn(u0, self.inst)
            stats, steps, used_stages = [], [], []
            for k, gamma in enumerate(cfg.gammas):
                eta = step_size(cfg, sigma_sq, gamma)
                with telemetry.span("stage", stage=k, gamma=float(gamma)):
                    lam, st, used = self._stage_fn(
                        lam, jnp.float32(gamma), eta.astype(jnp.float32),
                        self.inst,
                    )
                stats.append(st)
                steps.append(float(eta))
                used_stages.append(used)
            x_slabs, g = self._final_fn(
                lam, jnp.float32(cfg.gammas[-1]), self.inst
            )
        # host-convert the per-stage counts only after every stage has been
        # dispatched — int() blocks on the stage's device result, and the
        # fixed-budget path should keep its dispatch pipelining
        iters_used = (
            tuple(int(u) for u in used_stages) if cfg.early_stop else None
        )
        reg = telemetry.get_registry()
        reg.inc("dist_solves_total", 1, shards=shards)
        iters, checks = len(cfg.gammas) * cfg.iters_per_stage, 0
        if iters_used is not None:
            # every shard votes once per check_every-chunk actually executed,
            # and budget minus iters_used is the work early stopping skipped
            iters = sum(iters_used)
            checks = sum(-(-u // cfg.check_every) for u in iters_used)
            reg.inc("dist_early_stop_checks_total", checks * shards)
            reg.inc(
                "dist_iters_saved_total",
                sum(cfg.iters_per_stage - u for u in iters_used),
            )
        reg.inc(
            "dist_allreduce_bytes_total",
            _allreduce_bytes(dual_dim, self.dist, evaluations=iters + 1,
                             power_steps=cfg.power_iters, votes=checks),
            shards=shards,
        )
        return SolveResult(
            lam=lam, x_slabs=x_slabs, g=g, stats=tuple(stats),
            sigma_sq=sigma_sq, steps=tuple(steps),
            iters_used=iters_used,
        )

    # -- dry-run hooks (launch/dryrun.py) ------------------------------------

    def lower_stage(self):
        """jax.jit(...).lower() of one continuation stage on abstract inputs."""
        sds = self.inst.shape_dtype_structs()
        lam = jax.ShapeDtypeStruct((self.inst.dual_dim,), jnp.float32)
        scalar = jax.ShapeDtypeStruct((), jnp.float32)
        with jax.set_mesh(self.mesh):
            return self._stage_fn.lower(lam, scalar, scalar, sds)
