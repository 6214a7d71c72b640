"""ObjectiveFunction — encodes (A, b, c) and the dual oracle (paper Table 1, §3.2).

`MatchingObjective.calculate(lam, gamma)` returns (g(lam), grad g(lam), x*(lam))
for the ridge-regularized matching LP:

    x*_gamma(lam) = Pi_C( -(A^T lam + c) / gamma )          (eq. 3)
    grad g(lam)   = A x*_gamma(lam) - b                      (eq. 4)
    g(lam)        = c'x* + (gamma/2)||x*||^2 + lam'(A x* - b)

over the bucketed-ELL layout of Def. 1 coupling matrices:

    A^T lam  — per-bucket vectorized *gather*  lam[k*J + idx] * coeff[k]
    A x      — per-bucket *segment-sum* (scatter-add) of coeff[k] * x into J bins

Both SpMVs touch only real nonzeros (padding is masked to exact zeros), so the
cost matches the paper's CSC complexity while staying dense-slab shaped for the
VPU/MXU.  All methods are pure functions of jax arrays — safe under jit,
shard_map and grad.

`fused_oracle=True` routes the whole of `calculate` through the one-pass
fused dual-oracle kernel (kernels/dual_oracle.py): one launch per bucket
emits the primal slab plus this bucket's A x histogram and (c'x, ||x||^2)
partials from a single slab read, instead of the ~3 passes the unfused
composition pays (docs/architecture.md "one-pass dual oracle").

`MatchingObjective` is a thin shim over the operator-centric formulation
layer (repro.formulation, docs/formulation.md): when the instance carries a
compiled `FormulationSpec` (a static pytree field), `__post_init__` resolves
it into per-bucket projections and the lowered term scales, so any
composition of feasible-set/term/coupling primitives dispatches through this
same oracle — and through every solver/service layer built on it — without
solve-loop changes.  A spec-free instance with default parameters is the
legacy ridge-regularized matching formulation, bit-for-bit.
"""
from __future__ import annotations

import contextlib
import dataclasses
from functools import partial
from typing import NamedTuple, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro import telemetry
from repro.core.projections import ProjectionMap, UnitSimplexProjection
from repro.instances.buckets import (
    Bucket,
    BucketedInstance,
    _quantize_sym,
    dequantize_bucket,
)

__all__ = [
    "DualEval",
    "MatchingObjective",
    "binned_segment_sum",
    "gather_atl",
    "normalize_rows",
    "normalize_rows_traced",
    "oracle_scope",
]

# The oracle's scope names (`oracle_scope`) are the compiled program's own
# telemetry.  JAX's persistent compilation cache keys a program without its
# op metadata unless told otherwise, and would hand back an executable
# compiled under another version's names.
jax.config.update("jax_compilation_cache_include_metadata_in_key", True)


class DualEval(NamedTuple):
    g: jax.Array  # scalar dual objective g(lam)
    grad: jax.Array  # [m*J] gradient of g
    x_slabs: tuple[jax.Array, ...]  # per-bucket primal slabs
    # decomposition useful for logging / distributed reduction:
    primal_linear: jax.Array  # c'x
    primal_ridge: jax.Array  # (gamma/2)||x||^2
    ax: jax.Array  # [m*J] A x


def _acc32(x: jax.Array) -> jax.Array:
    """Widen narrow primal slabs to fp32 before self-reductions (host-level
    dtype branch: identity object, identical jaxpr, for fp32 inputs)."""
    return x if x.dtype == jnp.float32 else x.astype(jnp.float32)


@contextlib.contextmanager
def oracle_scope(sublayer: str, length: int):
    """Name the ops of one oracle sub-layer on one bucket: their op names in
    the compiled program (and `tf_op` in a profiler trace) read
    `.../oracle.<sublayer>/L<length>/...` (docs/observability.md)."""
    with jax.named_scope(f"oracle.{sublayer}"), jax.named_scope(f"L{length}"):
        yield


def gather_atl(idx: jax.Array, coeff: jax.Array, lam2: jax.Array) -> jax.Array:
    """sum_k coeff[k] * lam2[k, idx]: A^T lam on one slab (any orientation).

    One gather per family from a 1-D dual row, multiplied and summed
    elementwise: a single gather of [m]-wide dual columns would give TPU an
    [.., m] intermediate padded to 128 lanes, and an einsum over m may lower
    to an MXU dot, which multiplies fp32 in bf16 on TPU at default precision.
    """
    atl = coeff[0] * jnp.take(lam2[0], idx)
    for k in range(1, coeff.shape[0]):
        atl = atl + coeff[k] * jnp.take(lam2[k], idx)
    return atl


def _gather_at_lam(bucket: Bucket, lam2: jax.Array) -> jax.Array:
    """(A^T lam) restricted to this bucket: [n, L].

    Gathered slot-major, through the transposed [L, n] index slab.  Keyed by
    some on-chip-sized [n, L <= 8] slabs in row-major order, a gather or
    scatter-add takes the TPU compiler about two minutes (e.g. [1521027, 8]
    and [132923, 4]; [1521024, 8] takes two seconds); slot-major, a second
    or two for every shape.

    On TPU the one-hot gather kernel does the work
    (`kernels.ops.dual_gather`, bit for bit `gather_atl`'s at m = 1).
    """
    from repro.kernels import ops as kops

    with oracle_scope("gather", bucket.idx.shape[1]):
        return kops.dual_gather(
            bucket.idx.T, bucket.coeff.swapaxes(1, 2), lam2
        ).T


def binned_segment_sum(idx: jax.Array, contrib: jax.Array, J: int) -> jax.Array:
    """Scatter-add [m, ...] contributions into [m, J] bins keyed by `idx`.

    One `segment_sum` over family-offset indices (`idx + k*J`, flattened
    once) replaces the previous per-family vmap'd `.at[].add` plus the
    materialised [m, n, L] broadcast of the index tensor — XLA lowers the
    single flat segment-sum without the batched-scatter loop.
    """
    m = contrib.shape[0]
    # flattened slot-major, as `_gather_at_lam` gathers
    idx, contrib = idx.T, jnp.swapaxes(contrib, -1, -2)
    flat_idx = idx.reshape(-1).astype(jnp.int32)
    offs = (jnp.arange(m, dtype=jnp.int32) * J)[:, None]  # [m, 1]
    seg = (flat_idx[None, :] + offs).reshape(-1)
    out = jax.ops.segment_sum(contrib.reshape(-1), seg, num_segments=m * J)
    return out.reshape(m, J)


def _segment_sum_ax(bucket: Bucket, x: jax.Array, J: int) -> jax.Array:
    """This bucket's contribution to A x: [m, J], from the bucket as stored.

    Scattered slot-major, through the transposed [L, n] slabs (see
    `_gather_at_lam`).  On TPU the one-hot scatter kernel does the work
    (`kernels.ops.dual_scatter`), reading narrow coefficients at their
    storage width and dequantizing them in VMEM; else `binned_segment_sum`
    of the fp32 contributions, as `dequantize_bucket` would give them.
    """
    from repro.kernels import ops as kops

    with oracle_scope("scatter", bucket.idx.shape[1]):
        return kops.dual_scatter(
            bucket.idx.T, bucket.coeff.swapaxes(1, 2),
            (_acc32(x) * bucket.mask).T, J, coeff_scale=bucket.coeff_scale,
        )


@dataclasses.dataclass
class MatchingObjective:
    """ObjectiveFunction over a (possibly device-local shard of a) BucketedInstance.

    In distributed execution each shard holds its local rows of every bucket
    (column shard of A, paper §4.4); `calculate` then returns the *local*
    contributions, and `repro.core.sharding` performs the single |lam|-sized
    reduction.  `rhs_in_local=True` (default) subtracts b and adds -lam'b here,
    which is correct for single-shard use; the sharded driver sets it False and
    applies b once after the psum.
    """

    instance: BucketedInstance
    projection: ProjectionMap = dataclasses.field(
        default_factory=UnitSimplexProjection
    )
    include_rhs: bool = True
    # Route the primal step through the fused Pallas dual-primal kernel
    # (gather + axpy + scale + projection in one kernel; see kernels/).
    # Only valid for UnitSimplexProjection feasible sets.
    fused_kernel: bool = False
    # Route the ENTIRE oracle through the one-pass fused dual-oracle kernel:
    # the same one-pass-over-VMEM-tiles launch that computes x also emits
    # per-grid-step partial A x histograms and (c'x, ||x||^2) partials, so
    # `calculate` reads each slab once per iteration instead of ~3x (see
    # kernels/dual_oracle.py and docs/architecture.md "one-pass dual
    # oracle").  Subsumes fused_kernel; simplex feasible sets only.
    fused_oracle: bool = False
    kernel_interpret: bool | None = None
    # Lowered objective-term scales (repro.formulation.terms):
    #   g = cost_scale * c'x + ridge_weight * (gamma/2)||x||^2 + lam'(Ax - b)
    #   x* = Pi_C( -(A^T lam + cost_scale * c) / (ridge_weight * gamma) )
    # Defaults reproduce the legacy matching objective bit-for-bit (the
    # scale-application branches below are host-level, so the jaxpr is
    # unchanged when both scales are exactly 1.0).
    cost_scale: float = 1.0
    ridge_weight: float = 1.0

    def __post_init__(self):
        # Formulation shim: a compiled FormulationSpec riding the instance's
        # static `formulation` field carries the per-bucket feasible sets and
        # term scales; resolve them here (trace-time host logic only), so
        # every caller that constructs a MatchingObjective from the instance
        # — Maximizer, core.sharding, the whole service engine — dispatches
        # compiled formulations with zero changes.
        self._projections: Optional[tuple[ProjectionMap, ...]] = None
        spec = getattr(self.instance, "formulation", None)
        if spec is None:
            return
        from repro.formulation.spec import lower_spec

        lowered = lower_spec(spec, self.instance)
        self.cost_scale = self.cost_scale * lowered.cost_scale
        self.ridge_weight = self.ridge_weight * lowered.ridge_weight
        # An explicitly passed non-default projection (e.g. the distributed
        # layer's `projection=` argument) wins over the spec's lowering.
        if self.projection == UnitSimplexProjection():
            self._projections = lowered.projections
            if len(set(lowered.projections)) == 1:
                self.projection = lowered.projections[0]

    @property
    def dual_dim(self) -> int:
        return self.instance.dual_dim

    @property
    def _buckets(self) -> tuple[Bucket, ...]:
        """fp32 compute views of the buckets for the unfused (pure-jnp) paths.

        For fp32 storage this returns the instance's own bucket objects — a
        host-level no-op keeping the default path's jaxpr bit-identical.
        Narrow storage builds the widening converts (+ int8 scale multiplies)
        at the call site, inside the consumer's trace: XLA fuses the convert
        into the consuming op, so HBM reads stay at the storage width and no
        fp32 slab copy is ever materialized.  The fused kernel paths bypass
        this view and take the raw storage arrays (+ scales), dequantizing
        in VMEM.
        """
        return tuple(dequantize_bucket(b) for b in self.instance.buckets)

    def _proj(self, i: int) -> ProjectionMap:
        return self._projections[i] if self._projections else self.projection

    def _scaled_cost(self, b: Bucket) -> jax.Array:
        return b.cost if self.cost_scale == 1.0 else self.cost_scale * b.cost

    def _scaled_gamma(self, gamma):
        return gamma if self.ridge_weight == 1.0 else self.ridge_weight * gamma

    def _assert_fused_ok(self, kind: str) -> UnitSimplexProjection:
        assert self.cost_scale == 1.0 and self.ridge_weight == 1.0, (
            f"{kind} implements unit term scales; lower non-unit "
            "LinearCost/RidgeSmoothing through the unfused oracle"
        )
        projs = {self._proj(i) for i in range(len(self.instance.buckets))}
        assert len(projs) == 1 and isinstance(
            next(iter(projs)), UnitSimplexProjection
        ), f"{kind} implements the simplex feasible set"
        return next(iter(projs))

    def primal_candidate(self, lam: jax.Array, gamma) -> tuple[jax.Array, ...]:
        """x*_gamma(lam) per bucket (eq. 3)."""
        inst = self.instance
        if self.fused_kernel:
            from repro.kernels import ops as kops

            proj = self._assert_fused_ok("fused dual-primal kernel")
            gamma = jnp.asarray(gamma, jnp.float32)
            return tuple(
                kops.fused_dual_primal(
                    b.idx, b.coeff, b.cost, b.mask, lam, gamma,
                    num_destinations=inst.num_destinations,
                    radius=proj.radius,
                    inequality=proj.inequality,
                    interpret=self.kernel_interpret,
                    coeff_scale=b.coeff_scale,
                    cost_scale=b.cost_scale,
                )
                for b in inst.buckets
            )
        lam2 = lam.reshape(inst.num_families, inst.num_destinations)
        gamma_eff = self._scaled_gamma(gamma)
        slabs = []
        for i, b in enumerate(self._buckets):
            atl = _gather_at_lam(b, lam2)
            with oracle_scope("project", b.idx.shape[1]):
                z = -(atl + self._scaled_cost(b)) / gamma_eff
                slabs.append(self._proj(i)(z, b.mask))
        return tuple(slabs)

    def apply_A(self, x_slabs: Sequence[jax.Array]) -> jax.Array:
        """A x as a [m*J] vector (accumulated at >= fp32 for narrow slabs).

        Takes the stored buckets, not the fp32 views of `_buckets`: a
        widening convert cannot fuse into the scatter kernel.
        """
        inst = self.instance
        ax = jnp.zeros(
            (inst.num_families, inst.num_destinations),
            jnp.promote_types(x_slabs[0].dtype, jnp.float32),
        )
        for b, x in zip(inst.buckets, x_slabs):
            ax = ax + _segment_sum_ax(b, x, inst.num_destinations)
        return ax.reshape(-1)

    def apply_AT(self, lam: jax.Array) -> tuple[jax.Array, ...]:
        """A^T lam per bucket (for power iteration / diagnostics)."""
        inst = self.instance
        lam2 = lam.reshape(inst.num_families, inst.num_destinations)
        return tuple(_gather_at_lam(b, lam2) * b.mask for b in self._buckets)

    def calculate(self, lam: jax.Array, gamma) -> DualEval:
        """(g, grad g, x*) — the paper's ObjectiveFunction.calculate (Table 1)."""
        if self.fused_oracle:
            return self._calculate_fused(lam, gamma)
        inst = self.instance
        gamma = jnp.asarray(gamma, lam.dtype)
        x_slabs = self.primal_candidate(lam, gamma)
        ax = self.apply_A(x_slabs)
        lin = sum(
            jnp.vdot(self._scaled_cost(b), x)
            for b, x in zip(self._buckets, x_slabs)
        )
        ridge = (
            0.5 * self._scaled_gamma(gamma)
            * sum(jnp.vdot(_acc32(x), _acc32(x)) for x in x_slabs)
        )
        return self._finish_eval(lam, ax, lin, ridge, x_slabs)

    def _finish_eval(
        self, lam, ax, lin, ridge, x_slabs: tuple[jax.Array, ...]
    ) -> DualEval:
        """Shared tail of both oracle paths: grad/g from the reduced pieces.

        `include_rhs=False` is the sharded-local mode: b is applied once
        globally after the psum, so grad/g here are pre-reduction
        contributions (see core.sharding._make_calculate).
        """
        if self.include_rhs:
            grad = ax - self.instance.rhs
            g = lin + ridge + jnp.vdot(lam, grad)
        else:
            grad = ax
            g = lin + ridge + jnp.vdot(lam, ax)
        return DualEval(
            g=g, grad=grad, x_slabs=x_slabs, primal_linear=lin,
            primal_ridge=ridge, ax=ax,
        )

    def _calculate_fused(self, lam: jax.Array, gamma) -> DualEval:
        """One-pass oracle: per bucket, ONE fused launch emits the primal slab
        plus partial A x histograms and the objective scalars; `calculate`
        finishes with the O(m*J) tree-sums.  Same DualEval as the unfused
        path (including the `include_rhs=False` sharded-local mode, where
        the returned ax/lin/ridge are this shard's pre-psum contributions).
        """
        from repro.kernels import ops as kops

        inst = self.instance
        proj = self._assert_fused_ok("fused dual-oracle kernel")
        gamma = jnp.asarray(gamma, jnp.float32)
        ax2 = jnp.zeros(
            (inst.num_families, inst.num_destinations), jnp.float32
        )
        lin = jnp.float32(0.0)
        sq = jnp.float32(0.0)
        x_slabs = []
        for b in inst.buckets:
            x, hist, b_lin, b_sq = kops.fused_dual_oracle(
                b.idx, b.coeff, b.cost, b.mask, lam, gamma,
                num_destinations=inst.num_destinations,
                radius=proj.radius,
                inequality=proj.inequality,
                interpret=self.kernel_interpret,
                coeff_scale=b.coeff_scale,
                cost_scale=b.cost_scale,
            )
            x_slabs.append(x)
            ax2 = ax2 + hist
            lin = lin + b_lin
            sq = sq + b_sq
        return self._finish_eval(
            lam, ax2.reshape(-1), lin, 0.5 * gamma * sq, tuple(x_slabs)
        )

    # -- diagnostics --------------------------------------------------------

    def primal_objective(self, x_slabs: Sequence[jax.Array], gamma) -> jax.Array:
        lin = sum(
            jnp.vdot(self._scaled_cost(b), x)
            for b, x in zip(self._buckets, x_slabs)
        )
        ridge = (
            0.5 * self._scaled_gamma(gamma)
            * sum(jnp.vdot(_acc32(x), _acc32(x)) for x in x_slabs)
        )
        return lin + ridge

    def max_violation(self, x_slabs: Sequence[jax.Array]) -> jax.Array:
        """max(0, Ax - b) infinity-norm — the paper's Table-4 'slack'."""
        return jnp.max(jnp.maximum(self.apply_A(x_slabs) - self.instance.rhs, 0.0))

    def power_iteration(
        self, key: jax.Array, iters: int = 30
    ) -> jax.Array:
        """sigma_max(A)^2 estimate via power iteration on A A^T.

        Drives the analytic AGD step size 1/L, L = sigma_max^2 / gamma
        (paper §3.1: 'a fixed step size derived analytically from A and gamma').
        """
        u0 = jax.random.normal(key, (self.dual_dim,), jnp.float32)

        def body(u, _):
            atl = self.apply_AT(u / jnp.linalg.norm(u))
            au = self.apply_A(atl)
            return au, jnp.linalg.norm(au)

        with jax.named_scope("power_iteration"):
            _, norms = jax.lax.scan(body, u0, None, length=iters)
        return norms[-1]  # ~ sigma_max^2


def normalize_rows_traced(
    inst: BucketedInstance, eps: float = 1e-30
) -> tuple[BucketedInstance, jax.Array]:
    """Jacobi row normalization as a traced (device-side) transform.

    Same math as `normalize_rows` (A' = D A, b' = D b, D_r = 1/||A_r||_2)
    but expressed in jnp so it can run *inside* a compiled solve.  The
    recurring-solve service needs this: delta ingestion mutates the raw
    slabs in place, and re-running the host-side O(nnz) normalization every
    cadence would defeat the O(delta) update path.  One extra segment-sum +
    gather per solve is amortised over hundreds of AGD iterations.

    The costs `c` and the feasible set are untouched, so the primal solution
    is that of the original problem; returned duals live in the scaled space
    (lam_original = D lam'), which is consistent cadence-over-cadence as long
    as every solve applies the same transform.
    """
    m, J = inst.num_families, inst.num_destinations
    # Narrow slab dtypes: norms and the Jacobi scaling run on fp32 compute
    # views; float storage casts the scaled coeff back to the storage dtype
    # (keeping the slab HBM width through the solve), while quantized (int8)
    # slabs stay dequantized-fp32 for the remainder of the traced solve —
    # in-trace requantization would need data-dependent scales.  fp32
    # storage takes the exact pre-slab_dtype expressions (host branch).
    compute = tuple(dequantize_bucket(b) for b in inst.buckets)
    norms_sq = jnp.zeros((m, J), jnp.float32)
    for b in compute:
        contrib = (b.coeff**2) * b.mask[None]  # [m, n, L]
        norms_sq = norms_sq + binned_segment_sum(b.idx, contrib, J)
    norms = jnp.sqrt(norms_sq)
    d2 = jnp.where(norms > eps, 1.0 / jnp.maximum(norms, eps), 1.0)  # [m, J]

    def _scaled_bucket(b: Bucket, cb: Bucket) -> Bucket:
        # gathered slot-major, as in `_gather_at_lam`
        coeff = cb.coeff * jnp.take(d2, b.idx.T, axis=1).swapaxes(1, 2)
        if b.coeff_scale is None and coeff.dtype != b.coeff.dtype:
            coeff = coeff.astype(b.coeff.dtype)  # bf16 storage: cast back
        if b.coeff_scale is None:
            return Bucket(
                idx=b.idx, coeff=coeff, cost=b.cost, mask=b.mask,
                length=b.length,
            )
        return Bucket(
            idx=b.idx, coeff=coeff, cost=cb.cost, mask=cb.mask,
            length=b.length,
        )

    buckets = tuple(
        _scaled_bucket(b, cb) for b, cb in zip(inst.buckets, compute)
    )
    # dataclasses.replace keeps the static fields — including an attached
    # FormulationSpec, so compiled formulations survive the device-side
    # normalization inside the service engine's solves
    scaled = dataclasses.replace(
        inst, buckets=buckets, rhs=jnp.asarray(inst.rhs) * d2.reshape(-1)
    )
    return scaled, d2.reshape(-1)


def normalize_rows(
    inst: BucketedInstance, eps: float = 1e-30
) -> tuple[BucketedInstance, np.ndarray]:
    """Jacobi preconditioning / row normalization (paper §6, Appendix B.2).

    Returns (scaled instance with A' = D A, b' = D b) and the diagonal D as a
    [m*J] vector, D_r = 1/||A_r||_2 (rows with zero norm keep D_r = 1).  The
    feasible set is unchanged; duals map back as lam_original = D lam'.
    Host-side transform: runs once at instance build time, before sharding.
    """
    with telemetry.span("normalize_rows"):
        m, J = inst.num_families, inst.num_destinations
        norms = np.sqrt(inst.row_norms_sq())
        d = np.where(norms > eps, 1.0 / np.maximum(norms, eps), 1.0)
        d2 = d.reshape(m, J)
        buckets = []
        for b in inst.buckets:
            idx = np.asarray(b.idx)
            scale = d2[:, idx]  # [m, n, L]
            if b.coeff_scale is not None:
                # quantized (int8) slabs: dequantize, apply the Jacobi scaling in
                # fp32, requantize with fresh symmetric per-family scales
                coeff_f32 = np.asarray(b.coeff, np.float32) * np.asarray(
                    b.coeff_scale, np.float32
                )
                q, new_scale = _quantize_sym(
                    (coeff_f32 * scale).astype(np.float32), axes=(1, 2)
                )
                buckets.append(
                    dataclasses.replace(
                        b, idx=idx, coeff=q, coeff_scale=new_scale,
                        cost=np.asarray(b.cost), mask=np.asarray(b.mask),
                    )
                )
                continue
            buckets.append(
                Bucket(
                    idx=idx,
                    coeff=(np.asarray(b.coeff) * scale).astype(b.coeff.dtype),
                    cost=np.asarray(b.cost),
                    mask=np.asarray(b.mask),
                    length=b.length,
                )
            )
        scaled = dataclasses.replace(
            inst,
            buckets=tuple(buckets),
            rhs=(np.asarray(inst.rhs) * d).astype(inst.rhs.dtype),
        )
        return scaled, d
