"""jit'd wrappers around the Pallas kernels (block sizing, padding, fallbacks).

Responsibilities (mirrors the paper's dispatch policy, §4.3):
  * hand the kernels every slab transposed, `[L, n]`: XLA stores a `[n, L]`
    bucket slab with n as the minor dimension, so the transpose is free,
    while a `[n, L]` kernel operand would be copied into a layout that pads
    L up to 128 lanes (kernels/simplex_proj.py);
  * pick the sources per grid step (`pick_block_rows`) from the tile's VMEM
    footprint: slot depth rounded up to the sublane tiling of the slab dtype
    (8 for fp32, 16 for bf16, 32 for int8), sources a multiple of 128 lanes;
    the last grid step may be partial and the kernels mask it;
  * gather `A^T lam` ahead of the kernels (`dual_gather`) and scatter-add
    `A x` (`dual_scatter`): on TPU for J <= 16384 per family, as many
    families as their VMEM holds, through the one-hot MXU kernels
    (kernels/gather.py, kernels/scatter.py; `onehot_takes_kernel`), else
    through XLA's gather (`gather_atl`) and segment sum
    (`binned_segment_sum`);
  * fall back to the multi-op reference implementation for non-power-of-two
    slab widths and for widths beyond KERNEL_MAX_LENGTH = 512, where a
    single 128-source tile fills the VMEM budget (the paper's Triton kernel
    goes to MAX_FUSED_LENGTH = 8192) — "beyond this limit, execution falls
    back to the multi-launch implementation";
  * route unsupported slab storage dtypes (anything outside fp32 / bf16 /
    int8-with-scales) through the dtype-faithful reference oracle, which
    widens to fp32 on load and accumulates in fp32 exactly like the kernel;
  * `interpret=None` auto-selects: real Mosaic lowering on TPU backends,
    interpret mode (Python execution of the same kernel body) on CPU.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

from repro.core.objective import binned_segment_sum, gather_atl, oracle_scope
from repro.kernels import ref as kref
from repro.kernels.dual_oracle import (
    _padded_destinations,
    fits_onehot_budget,
    make_dual_oracle_call,
)
from repro.kernels.gather import (
    dual_table,
    fits_vmem_budget,
    make_gather_call,
    tile_rows,
)
from repro.kernels.scatter import make_scatter_call
from repro.kernels.simplex_proj import MAX_FUSED_LENGTH, make_simplex_call

__all__ = [
    "dual_gather",
    "dual_scatter",
    "fused_project_simplex",
    "fused_dual_primal",
    "fused_dual_oracle",
    "fused_pdhg_step",
    "KERNEL_MAX_LENGTH",
    "onehot_gather",
    "onehot_takes_kernel",
    "oracle_hist_partial_bytes",
    "oracle_takes_kernel",
    "oracle_slab_slot_bytes",
    "pick_block_rows",
]

# Elements of one (L, block) tile as laid out in VMEM, slot depth padded to
# the sublane tiling: 256 KiB per fp32 tile.  The oracle kernel keeps ~30
# such tiles live (double-buffered inputs and output, the projection's
# temporaries, the coeff * x scratch) beside its partial-histogram block
# (`dual_oracle.fits_onehot_budget`), inside the 16 MiB of scoped VMEM that
# Mosaic allows a kernel on TPU v5e by default.
_VMEM_TILE_ELEMS = 1 << 16
_LANES = 128
# Widest slab the kernels take: one 128-source block at full slot depth
# fills _VMEM_TILE_ELEMS.  A wider slab cannot be cut to fit, since each
# source's slots are projected together.
KERNEL_MAX_LENGTH = min(MAX_FUSED_LENGTH, _VMEM_TILE_ELEMS // _LANES)


def _is_pow2(n: int) -> bool:
    return n > 0 and (n & (n - 1)) == 0


def _kernel_length(length: int) -> bool:
    """Whether a slab this wide fits the kernels (else the reference)."""
    return _is_pow2(length) and length <= KERNEL_MAX_LENGTH


# Slab storage dtypes the Pallas kernels load natively (widened to fp32 in
# VMEM; see kernels/dual_oracle.py).  int8 additionally needs
# its per-bucket dequant scales; anything else takes the reference path.
_KERNEL_SLAB_DTYPES = (jnp.float32, jnp.bfloat16, jnp.int8)


def _kernel_supports_dtype(dtype, quantized: bool) -> bool:
    d = jnp.dtype(dtype)
    if d == jnp.dtype(jnp.int8):
        return quantized
    return d in (jnp.dtype(jnp.float32), jnp.dtype(jnp.bfloat16))


def _primal_out_dtype(storage_dtype, quantized: bool):
    """The dtype the primal slab x is written back in: the storage dtype for
    float storage (so the x write shares the slab's HBM width), fp32 for
    quantized slabs (x is a simplex point, not a scaled integer)."""
    return jnp.dtype(jnp.float32) if quantized else jnp.dtype(storage_dtype)


def oracle_slab_slot_bytes(num_families: int, slab_dtype="float32") -> int:
    """Analytic per-slot HBM bytes of one fused-oracle iteration.

    The gather of `A^T lam` (`dual_gather`) reads idx (int32) and the m
    coeff families at the storage width and writes the fp32 atl slab (its
    O(m·J) dual tables are not per slot); the kernel reads
    atl, idx, the coeff families, cost and mask, and writes x at the
    primal-out width (storage width for float slabs, fp32 for int8).
    Shared by `launch.dryrun` and `benchmarks.table2_iteration_time` — the
    two records must agree for the perf trajectory to be comparable."""
    d = jnp.dtype(jnp.bfloat16) if slab_dtype == "bfloat16" else jnp.dtype(slab_dtype)
    quantized = d == jnp.dtype(jnp.int8)
    x_bytes = _primal_out_dtype(d, quantized).itemsize
    gather = 4 + num_families * d.itemsize + 4
    kernel = 4 + 4 + (num_families + 2) * d.itemsize + x_bytes
    return gather + kernel


def _sublane_rows(dtype) -> int:
    """Row tiling of a 2-D array of `dtype` in VMEM: 8 sublanes of 32 bits,
    so 8 rows for fp32/int32, 16 for bf16, 32 for int8."""
    return 8 * max(1, 4 // jnp.dtype(dtype).itemsize)


def pick_block_rows(n_rows: int, length: int, dtype=jnp.float32) -> int:
    """Sources per grid step: a multiple of 128 lanes, with the (L, block)
    tile — L rounded up to `dtype`'s sublane tiling — at most
    _VMEM_TILE_ELEMS elements, and no more than the slab needs."""
    depth = -(-max(length, 1) // _sublane_rows(dtype)) * _sublane_rows(dtype)
    block = max(_LANES, (_VMEM_TILE_ELEMS // depth) // _LANES * _LANES)
    return min(block, -(-max(n_rows, 1) // _LANES) * _LANES)


def oracle_hist_partial_bytes(
    n_rows: int, length: int, num_families: int, num_destinations: int
) -> int:
    """Fused-oracle per-iteration partial-histogram HBM traffic for one
    bucket: one [m, Jp] fp32 write + read per grid step (the tree-sum), Jp
    the destination count rounded up to 128.

    The single source of the analytic model — `launch.dryrun` and
    `benchmarks.table2_iteration_time` both report it, and the two records
    must agree for the perf trajectory to be comparable.
    """
    grid = -(-n_rows // pick_block_rows(n_rows, length))
    return 2 * 4 * grid * num_families * _padded_destinations(num_destinations)


def _use_interpret(interpret) -> bool:
    if interpret is not None:
        return interpret
    return jax.default_backend() != "tpu"


def onehot_takes_kernel(
    num_destinations: int, num_families: int, dtype, quantized: bool,
    interpret,
) -> bool:
    """Whether `dual_gather` and `dual_scatter` run their one-hot kernels
    (kernels/gather.py, kernels/scatter.py) or XLA's gather and segment
    sum, for m families of J duals and coefficients stored as `dtype`.

    The kernels take the TPU backend (`interpret=None`), or interpret mode
    when asked (`interpret=True`), with a family's table at most
    MAX_TABLE_ROWS = 128 rows of 128 duals (J <= 16384) and their VMEM,
    which grows with m·R, within budget (`gather.fits_vmem_budget`: m <= 4
    at J = 16384, m <= 5 at J = 10^4).  Cost model, per slot and family,
    R = ceil(J/128): the gather's MXU work is 2·4·128·R flops and its
    vector work about 5R + 260 operations, the scatter's 2·3·128·R flops
    and about 4R + 256 operations; XLA's gather costs ~7.5 ns per slot on a
    v5e and its scatter-add ~7 ns, whatever J is.  At R = 80 (J = 10^4) the
    gather kernel, MXU-bound, took 9.4 ms for 2.05·10^7 slots on a v5e
    against XLA's 157 ms: 0.46 ns per slot, growing in proportion to R, so
    the two would cross near R = 1300 (J ~ 1.7·10^5).  The kernels stop at
    R = 128, where their tables still leave room in VMEM for several
    families; wider duals, or more families than fit, take XLA.  Off-TPU
    without `interpret=True`, XLA.
    """
    if interpret is None and jax.default_backend() != "tpu":
        return False
    return fits_vmem_budget(num_families, num_destinations) and (
        _kernel_supports_dtype(dtype, quantized)
    )


def dual_gather(idx, coeff, lam2, *, coeff_scale=None, interpret=None):
    """A^T lam on one transposed [L, n] slab, in fp32:
    sum_k coeff[k] · lam2[k, idx], with int8 coefficients dequantized by
    `coeff_scale` ([m] values in any shape).

    idx [L, n] int32, coeff [m, L, n], lam2 [m, J].  Through the one-hot
    kernel where `onehot_takes_kernel` says so and the duals are fp32 (its
    result is `gather_atl`'s bit for bit at m = 1), under a nested
    `onehot` scope; else `gather_atl`.
    """
    m, J = lam2.shape
    quantized = coeff_scale is not None
    if lam2.dtype == jnp.float32 and onehot_takes_kernel(
        J, m, coeff.dtype, quantized, interpret
    ):
        return onehot_gather(
            idx, coeff, lam2, coeff_scale, interpret=interpret
        )
    c = coeff.astype(jnp.float32)
    if quantized:
        c = c * jnp.asarray(coeff_scale, jnp.float32).reshape(
            (m,) + (1,) * (coeff.ndim - 1)
        )
    return gather_atl(idx, c, lam2)


def onehot_gather(idx, coeff, lam2, coeff_scale=None, *, interpret=None):
    """A^T lam [L, n] fp32 on one transposed [L, n] slab through the one-hot
    gather kernel, under a nested `onehot` scope: idx [L, n], coeff
    [m, L, n] (with `coeff_scale` for int8), lam2 [m, J] fp32."""
    L, n = idx.shape
    m, J = lam2.shape
    narrow = min(coeff.dtype, idx.dtype, key=lambda d: jnp.dtype(d).itemsize)
    call = make_gather_call(
        n, L, m, J, pick_block_rows(n, tile_rows(L), narrow), coeff.dtype,
        interpret=_use_interpret(interpret), quantized=coeff_scale is not None,
    )
    operands = [*dual_table(lam2), idx, coeff]
    if coeff_scale is not None:
        operands.append(jnp.asarray(coeff_scale, jnp.float32).reshape(m, 1))
    with jax.named_scope("onehot"):
        return call(*operands)


def dual_scatter(idx, coeff, v, num_destinations, *, coeff_scale=None,
                 interpret=None):
    """A x on one transposed [L, n] slab, [m, J] fp32: bin j of family k
    sums coeff[k] · v over the slots whose idx is j, with int8
    coefficients dequantized by `coeff_scale` ([m] values in any shape).

    idx [L, n] int32, coeff [m, L, n] as stored (fp32, bf16, or int8 with
    its scales: the kernel reads it at that width), v [L, n] (x · mask in
    the oracle, A^T u · mask in a power step).  Through the one-hot kernel
    where `onehot_takes_kernel` says so and v is fp32, under a nested
    `onehot` scope; else `binned_segment_sum`.  The two sum in different
    orders, so they agree to fp32 rounding, not bit for bit.  One more
    difference: in the kernel a non-finite contribution (inf or NaN) at
    index e makes NaN the whole row of 128 bins that holds e (bins
    128·(e >> 7) to 128·(e >> 7) + 127), not only bin e.  A x, and so the
    gradient, is non-finite either way, so a diverging solve still shows.
    """
    L, n = idx.shape
    m = coeff.shape[0]
    quantized = coeff_scale is not None
    if v.dtype == jnp.float32 and onehot_takes_kernel(
        num_destinations, m, coeff.dtype, quantized, interpret
    ):
        narrow = min(coeff.dtype, idx.dtype, key=lambda d: jnp.dtype(d).itemsize)
        call = make_scatter_call(
            n, L, m, num_destinations, pick_block_rows(n, tile_rows(L), narrow),
            coeff.dtype, interpret=_use_interpret(interpret), quantized=quantized,
        )
        operands = [idx, coeff, v]
        if quantized:
            operands.append(jnp.asarray(coeff_scale, jnp.float32).reshape(m, 1))
        with jax.named_scope("onehot"):
            table = call(*operands)
        return table.reshape(m, -1)[:, :num_destinations]
    if quantized:
        coeff = coeff.astype(jnp.float32) * jnp.asarray(
            coeff_scale, jnp.float32
        ).reshape((m,) + (1,) * (coeff.ndim - 1))
    return binned_segment_sum(
        idx.T, (coeff * v[None]).swapaxes(-1, -2), num_destinations
    )


def _gather_atl(idx, coeff, lam, num_destinations, coeff_scale=None,
                interpret=None):
    """A^T lam on one transposed [L, n] bucket slab in fp32 (`dual_gather`
    of the flat duals)."""
    lam2 = lam.astype(jnp.float32).reshape(coeff.shape[0], num_destinations)
    return dual_gather(
        idx, coeff, lam2, coeff_scale=coeff_scale, interpret=interpret
    )


@functools.partial(
    jax.jit, static_argnames=("radius", "inequality", "interpret")
)
def fused_project_simplex(
    v: jax.Array,
    mask: jax.Array,
    *,
    radius: float = 1.0,
    inequality: bool = True,
    interpret: bool | None = None,
) -> jax.Array:
    """Fused Duchi simplex projection of slab rows (paper §4.3).

    v, mask: [n, L].  Falls back to the reference pipeline when L is not a
    power of two or exceeds KERNEL_MAX_LENGTH.
    """
    n, L = v.shape
    if not _kernel_length(L):
        return kref.simplex_ref(v, mask, radius, inequality=inequality)
    return _simplex_kernel(
        v.T, mask.T, radius=radius, inequality=inequality,
        interpret=_use_interpret(interpret), out_dtype=v.dtype,
    ).T


def _simplex_kernel(v_t, mask_t, *, radius, inequality, interpret, out_dtype):
    """Project the columns of transposed [L, n] slabs through the kernel."""
    L, n = v_t.shape
    narrow = min(v_t.dtype, mask_t.dtype, key=lambda d: jnp.dtype(d).itemsize)
    call = make_simplex_call(
        n,
        L,
        pick_block_rows(n, L, narrow),
        v_t.dtype,
        radius=radius,
        inequality=inequality,
        interpret=interpret,
        out_dtype=out_dtype,
    )
    return call(v_t, mask_t)


@functools.partial(
    jax.jit,
    static_argnames=("num_destinations", "radius", "inequality", "interpret"),
)
def fused_dual_primal(
    idx: jax.Array,  # [n, L] int32
    coeff: jax.Array,  # [m, n, L] slab dtype
    cost: jax.Array,  # [n, L] slab dtype
    mask: jax.Array,  # [n, L] slab dtype
    lam: jax.Array,  # [m * J] fp32
    gamma: jax.Array,  # scalar
    *,
    num_destinations: int,
    radius: float = 1.0,
    inequality: bool = True,
    interpret: bool | None = None,
    coeff_scale: Optional[jax.Array] = None,  # [m, 1, 1] f32 (int8 slabs)
    cost_scale: Optional[jax.Array] = None,  # [1, 1] f32 (int8 slabs)
) -> jax.Array:
    """Whole primal step  x = Pi( -(A^T lam + c)/gamma )  for one bucket.

    `dual_gather` gathers `A^T lam`, XLA forms the candidate (axpy and
    scale) and the simplex kernel projects it, writing x at the storage
    width.
    """
    n, L = cost.shape
    quantized = coeff_scale is not None
    if not _kernel_length(L) or not _kernel_supports_dtype(cost.dtype, quantized):
        return kref.dual_primal_ref(
            idx, coeff, cost, mask, lam, gamma, num_destinations,
            radius, inequality=inequality,
            coeff_scale=coeff_scale, cost_scale=cost_scale,
        )
    c = cost.T.astype(jnp.float32)
    if quantized:
        c = c * jnp.asarray(cost_scale, jnp.float32).reshape(1, 1)
    with oracle_scope("gather", L):
        atl = _gather_atl(
            idx.T, coeff.swapaxes(1, 2), lam, num_destinations, coeff_scale,
            interpret,
        )
    v = -(atl + c) / jnp.asarray(gamma, jnp.float32)
    return _simplex_kernel(
        v, mask.T, radius=radius, inequality=inequality,
        interpret=_use_interpret(interpret),
        out_dtype=_primal_out_dtype(cost.dtype, quantized),
    ).T


def fused_dual_oracle(
    idx: jax.Array,  # [n, L] int32
    coeff: jax.Array,  # [m, n, L] slab dtype
    cost: jax.Array,  # [n, L] slab dtype
    mask: jax.Array,  # [n, L] slab dtype
    lam: jax.Array,  # [m * J] fp32
    gamma: jax.Array,  # scalar
    *,
    num_destinations: int,
    radius: float = 1.0,
    inequality: bool = True,
    interpret: bool | None = None,
    coeff_scale: Optional[jax.Array] = None,  # [m, 1, 1] f32 (int8 slabs)
    cost_scale: Optional[jax.Array] = None,  # [1, 1] f32 (int8 slabs)
) -> tuple[jax.Array, jax.Array, jax.Array, jax.Array]:
    """One-pass fused dual oracle for one bucket: `(x, hist, lin, sq)`.

    `dual_gather` gathers `A^T lam` onto the slab (the one-hot gather kernel
    on TPU, kernels/gather.py); one kernel launch then computes the
    projected primal slab `x` AND this bucket's gradient/objective partials
    (`hist = A x` contribution [m, J], `lin = c'x`, `sq = ||x||^2`) from one
    read of the slab; the per-grid-step partials are tree-summed here
    (O(grid*m*J)).

    Storage dtypes: fp32, bf16, and int8-with-scales slabs take the kernel
    path (loaded narrow into VMEM, widened to fp32, partials accumulated in
    fp32; the x slab is written back in the storage dtype — fp32 for int8);
    any other dtype routes to the dtype-faithful reference below.

    Fallback matrix (see also docs/architecture.md):
      * L not a power of two or L > KERNEL_MAX_LENGTH -> `dual_oracle_ref`
        (the paper's multi-launch fallback policy, §4.3);
      * slab dtype outside {fp32, bf16, int8+scales} -> `dual_oracle_ref`
        (same widen-to-fp32 accumulation contract, so quality is identical
        up to reduction order);
      * m * J beyond the VMEM share of the kernel's partial-histogram block
        (`fits_onehot_budget`: m * Jp <= 131072) -> `dual_oracle_ref`;
      * `interpret=None` off-TPU -> `dual_oracle_ref` as well: the kernel's
        histogram costs J compares per slot, which the Pallas interpreter
        runs as scalar code, while XLA-CPU fuses the reference's segment-sum
        formulation natively, so interpret mode is kept for *validation*,
        not execution;
      * `interpret=True` -> Pallas interpret mode (kernel-body semantics on
        any backend; what the parity tests exercise);
      * `interpret=False`/None on TPU -> real Mosaic lowering.
    Padded rows are mask-zero and contribute exact zeros to `hist`/`lin`/`sq`
    on every path.

    Deliberately NOT wrapped in its own `jax.jit` (unlike the standalone
    `fused_dual_primal`): the oracle is only ever called from inside an
    already-jitted `calculate`, and a nested jit boundary would fence off
    cross-bucket/cross-pass fusion in the surrounding program.
    """
    n, L = cost.shape
    m = coeff.shape[0]
    quantized = coeff_scale is not None
    if not oracle_takes_kernel(L, num_destinations, m, cost.dtype, quantized,
                               interpret):
        return kref.dual_oracle_ref(
            idx, coeff, cost, mask, lam, gamma, num_destinations,
            radius, inequality=inequality,
            coeff_scale=coeff_scale, cost_scale=cost_scale,
        )
    call = make_dual_oracle_call(
        n,
        L,
        m,
        num_destinations,
        pick_block_rows(n, L, cost.dtype),
        cost.dtype,
        radius=radius,
        inequality=inequality,
        interpret=bool(interpret),
        quantized=quantized,
        out_dtype=_primal_out_dtype(cost.dtype, quantized),
    )
    idx_t, coeff_t = idx.T, coeff.swapaxes(1, 2)
    with oracle_scope("gather", L):
        atl = _gather_atl(
            idx_t, coeff_t, lam, num_destinations, coeff_scale, interpret
        )
    operands = [
        atl,
        idx_t,
        coeff_t,
        cost.T,
        mask.T,
        (1.0 / gamma).astype(jnp.float32).reshape(1, 1),
    ]
    if quantized:
        operands += [
            jnp.asarray(coeff_scale, jnp.float32).reshape(m, 1),
            jnp.asarray(cost_scale, jnp.float32).reshape(1, 1),
        ]
    with oracle_scope("kernel", L):
        x, hist_p, scal_p = call(*operands)
        return (
            x.T,
            hist_p.sum(axis=0)[:, :num_destinations],
            scal_p[:, 0, 0].sum(),
            scal_p[:, 0, 1].sum(),
        )


def oracle_takes_kernel(
    length: int, num_destinations: int, num_families: int, dtype,
    quantized: bool, interpret,
) -> bool:
    """Whether `fused_dual_oracle` runs the kernel for a bucket of this
    shape and dtype, or the reference (see its fallback matrix)."""
    if interpret is None and jax.default_backend() != "tpu":
        return False
    return (
        _kernel_length(length)
        and fits_onehot_budget(num_destinations, num_families)
        and _kernel_supports_dtype(dtype, quantized)
    )


def fused_pdhg_step(
    idx: jax.Array,  # [n, L] int32
    coeff: jax.Array,  # [m, n, L] fp32 compute view
    cost: jax.Array,  # [n, L] fp32
    mask: jax.Array,  # [n, L] fp32
    x: jax.Array,  # [n, L] fp32 current primal slab
    y: jax.Array,  # [m * J] fp32 current duals
    tau: jax.Array,  # scalar primal step
    *,
    num_destinations: int,
    radius: float = 1.0,
    inequality: bool = True,
    interpret: bool | None = None,
) -> tuple[jax.Array, jax.Array]:
    """One structured-PDHG primal prox step for one bucket: `(x_new, hist)`.

    The PDHG primal update `x+ = Proj_C(x - tau * (c + A'y))` is exactly the
    dual oracle's `Proj_C(-(A'y + cost_eff) / gamma)` with the identification
    `cost_eff = c - x / tau`, `gamma = 1 / tau` — so ONE fused launch both
    takes the prox step and emits this bucket's `hist = A x+` partial [m, J],
    which is what the extrapolated dual update needs.  That single-read fusion
    (vs the seed COO path's gather for `A'y` plus scatter-add for `A x`) is
    the structured engine's per-iteration win; see `repro.engines.pdhg`.

    Inputs must be fp32 compute views (`BucketedInstance` dequantized slabs):
    `cost_eff` is iterate-dependent, so the quantized-storage kernel variants
    (which assume a static per-bucket cost scale) don't apply here.
    """
    inv_tau = (1.0 / tau).astype(jnp.float32)
    cost_eff = cost - x * inv_tau
    x_new, hist, _, _ = fused_dual_oracle(
        idx,
        coeff,
        cost_eff,
        mask,
        y,
        inv_tau,
        num_destinations=num_destinations,
        radius=radius,
        inequality=inequality,
        interpret=interpret,
        coeff_scale=None,
        cost_scale=None,
    )
    return x_new, hist
