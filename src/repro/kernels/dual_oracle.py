"""One-pass fused dual-oracle Pallas kernel: projection, A x and the scalars.

Every AGD iteration needs the primal slab (eq. 3), the gradient half `A x`
(eq. 4) and the objective scalars `c'x` and `||x||^2`.  The unfused path
re-reads the slab for each: a segment-sum pass over (idx, coeff, x) for
`A x` and two more reduction passes for the scalars, with a materialised
`[m, n, L]` `coeff * x` intermediate in between.

This kernel computes all of it in one pass over VMEM tiles.  Slabs come in
transposed, `[L, n]` (slots down the sublanes, sources along the lanes; see
kernels/simplex_proj.py for why), and the grid runs over blocks of sources:

  per grid step i over (L, block) tiles:
    x_tile   = Pi_simplex( -(atl + c)/gamma )          -> x[:, i]   [L, block]
    hist[i]  = this tile's binned contribution to A x  -> [1, m, Jp]
    scal[i]  = (sum c*x_tile, sum x_tile^2)            -> [1, 8, 128]

`atl = A^T lam` restricted to the slab is gathered before the call
(`ops.dual_gather`), on TPU by its own kernel (kernels/gather.py): Mosaic
gathers only within one vector register, so that kernel writes the gather
as an exact one-hot contraction on the MXU, and this kernel reads the
gathered `atl` slab.

The in-kernel binned scatter is a **one-hot accumulation**, not a scatter:
TPU has no VMEM scatter-add, but `hist[k, j] += coeff[k,e] * x[e]` over the
tile's edges e with `idx[e] == j` is a sum of `where(idx[e] == j, .., 0)`
terms.  Destinations go in blocks of 128 (Jp = J rounded up to 128).  For
each block, every slot row of every 128-source chunk (rows taken in aligned
groups of 8 sublanes) is compared against a `[128, 128]` iota of the
block's destination ids, and the selected contributions are added into a
`[128, 128]` fp32 accumulator held in vector registers; the accumulator is
then transposed, summed over its 128 source lanes and stored into the
block's 128 columns of the `[1, m, Jp]` partial-histogram output block.
That is Jp compares, selects and adds per slot on the VPU, all in fp32 (no
MXU pass, so no bf16 rounding).  What grows with J in VMEM is only that
output block, double-buffered: `fits_onehot_budget` bounds it.  Partial histograms per grid step plus a tree-sum outside the
kernel replace global atomics, which TPU lacks; every partial has a fixed
slot in the [grid, m, Jp] output, so the result is deterministic.

Padded slots are mask-zero, so their x is exactly 0.0.  Source lanes past
the end of the slab (the last, partial grid step) are masked out of the
histogram and both scalars.

Correctness is validated in interpret mode on CPU (tests/test_dual_oracle.py)
and the kernel is compiled for a described TPU v5e in tests/test_tpu_compile.py.
`kernels/ops.py` dispatches to the fused one-pass reference
(`kernels/ref.dual_oracle_ref`) off-TPU; see ops.fused_dual_oracle for the
full fallback matrix.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.simplex_proj import MAX_FUSED_LENGTH, project_tile

__all__ = ["make_dual_oracle_call", "fits_onehot_budget"]

# VMEM share of the double-buffered [1, m, Jp] fp32 partial-histogram
# output block: 1 MiB beside the slab tiles (kernels/ops._VMEM_TILE_ELEMS),
# so m * Jp <= 131072.
_HIST_BLOCK_BYTES = 1 << 20
_LANES = 128


def _padded_destinations(num_destinations: int) -> int:
    return -(-num_destinations // _LANES) * _LANES


def fits_onehot_budget(num_destinations: int, num_families: int = 1) -> bool:
    """True iff the kernel's partial-histogram output block, [1, m, Jp] fp32
    and double-buffered, fits its VMEM share: the dispatch-level gate
    `ops.fused_dual_oracle` checks before taking the kernel path (more
    destinations fall back to the reference oracle, like the paper's
    multi-launch fallback for wide slabs)."""
    block_bytes = 2 * 4 * num_families * _padded_destinations(num_destinations)
    return block_bytes <= _HIST_BLOCK_BYTES


def dual_oracle_kernel_body(
    atl_ref,  # [L, block] f32  A^T lam on this tile (gathered by XLA)
    idx_ref,  # [L, block] int32
    coeff_ref,  # [m, L, block] slab dtype (fp32 / bf16 / int8)
    cost_ref,  # [L, block] slab dtype
    mask_ref,  # [L, block] slab dtype
    ginv_ref,  # [1, 1]  1/gamma (traced; continuation changes it per stage)
    *rest,  # quantized: (coeff_scale_ref [m,1], cost_scale_ref [1,1]) first
    # then the outputs:
    #   x_ref     [L, block] out: primal tile (storage dtype; f32 for int8)
    #   hist_ref  [1, m, Jp] out: this grid step's partial A x (f32)
    #   scal_ref  [1, 8, 128] out: (c'x, ||x||^2) at [0, 0, 0:2] (f32)
    # and the scratch:
    #   con_ref   [m, L, block] f32: coeff * x, read back row by row
    radius: float,
    inequality: bool,
    num_rows: int,
):
    if len(rest) == 6:
        coeff_scale_ref, cost_scale_ref, x_ref, hist_ref, scal_ref, con_ref = rest
    else:
        coeff_scale_ref = cost_scale_ref = None
        x_ref, hist_ref, scal_ref, con_ref = rest
    m = coeff_ref.shape[0]
    L, block = x_ref.shape
    Jp = hist_ref.shape[2]

    # source lanes past the end of the slab hold unspecified data in the
    # last grid step; they are selected out of every reduction below
    first = pl.program_id(0) * block
    valid = first + jax.lax.broadcasted_iota(jnp.int32, (1, block), 1) < num_rows

    cost = cost_ref[...].astype(jnp.float32)
    if cost_scale_ref is not None:
        cost = cost * cost_scale_ref[0, 0]
    mask = mask_ref[...].astype(jnp.float32)
    v = -(atl_ref[...] + cost) * ginv_ref[0, 0]
    x = project_tile(v, mask, radius=radius, inequality=inequality)
    x_ref[...] = x.astype(x_ref.dtype)
    x = jnp.where(valid, x, 0.0)

    # scalar partials (cost and x are exact zeros on padded slots), written
    # into one lane-dense (8, 128) tile
    sub = jax.lax.broadcasted_iota(jnp.int32, (8, 128), 0)
    lane = jax.lax.broadcasted_iota(jnp.int32, (8, 128), 1)
    top = sub == 0
    scal_ref[0] = jnp.where(
        top & (lane == 0), jnp.sum(jnp.where(valid, cost * x, 0.0)),
        jnp.where(top & (lane == 1), jnp.sum(x * x), 0.0),
    )

    for k in range(m):  # m is tiny (constraint families); unrolled
        coeff_k = coeff_ref[k].astype(jnp.float32)
        if coeff_scale_ref is not None:
            coeff_k = coeff_k * coeff_scale_ref[k, 0]
        con_ref[k] = jnp.where(valid, coeff_k * x, 0.0)

    # binned scatter as a one-hot accumulation, one slot row and one
    # 128-source chunk at a time:
    #   hist[k, j] += sum_{l,s} con[k, l, s] * [idx[l, s] == j]
    # Destinations go in blocks of 128, outermost, so each block's [128, 128]
    # accumulator stays in vector registers across the whole tile; slot rows
    # go in aligned groups of up to 8 sublanes, unrolled.
    rows = min(L, 8)
    groups = L // rows
    inner = (block // _LANES) * groups
    for k in range(m):

        def jblock_step(jb, carry, k=k):
            j0 = pl.multiple_of(jb * _LANES, _LANES)
            dest = j0 + jax.lax.broadcasted_iota(jnp.int32, (_LANES, _LANES), 0)

            def group_step(t, acc):
                r0 = pl.multiple_of((t % groups) * rows, rows)
                s0 = pl.multiple_of((t // groups) * _LANES, _LANES)
                ids = idx_ref[pl.ds(r0, rows), pl.ds(s0, _LANES)]
                con = con_ref[k, pl.ds(r0, rows), pl.ds(s0, _LANES)]
                for r in range(rows):
                    hit = dest == ids[r : r + 1]
                    acc = acc + jnp.where(hit, con[r : r + 1], 0.0)
                return acc

            acc = jax.lax.fori_loop(
                0, inner, group_step, jnp.zeros((_LANES, _LANES), jnp.float32)
            )
            hist_ref[0, k : k + 1, pl.ds(j0, _LANES)] = jnp.sum(
                acc.T, axis=0, keepdims=True
            )
            return carry

        jax.lax.fori_loop(0, Jp // _LANES, jblock_step, 0)


def make_dual_oracle_call(
    n_rows: int,
    length: int,
    num_families: int,
    num_destinations: int,
    block: int,
    dtype,
    *,
    radius: float = 1.0,
    inequality: bool = True,
    interpret: bool = True,
    quantized: bool = False,
    out_dtype=None,
):
    """pallas_call for one transposed bucket slab returning
    (x, hist_partials, scalar_partials).

    Call-time arguments: (atl, idx, coeff, cost, mask, gamma_inv), every
    slab transposed to [length, n_rows] (coeff [m, length, n_rows]), atl the
    fp32 slab of A^T lam, gamma_inv a (1, 1) array (traced, so continuation
    changes it per stage without retracing); with ``quantized`` two more,
    (coeff_scale [m, 1] f32, cost_scale [1, 1] f32), dequantized in-kernel.
    `block` is the sources per grid step: a multiple of 128.  Outputs:
      x               [length, n_rows]       projected primal slab, written
                                             in ``out_dtype`` (defaults to
                                             the storage ``dtype``; ops.py
                                             passes fp32 for int8 slabs)
      hist_partials   [grid, m, Jp] fp32     per-grid-step partial A x, J
                                             rounded up to Jp (multiple of
                                             128); columns >= J stay 0
      scalar_partials [grid, 8, 128] fp32    per-grid-step (c'x, ||x||^2)
                                             at [:, 0, 0] and [:, 0, 1]
    The caller tree-sums the partials over the grid axis (O(grid*(m*J + 2))).
    All partials accumulate in fp32 regardless of the storage dtype.
    """
    assert block % _LANES == 0
    assert length <= MAX_FUSED_LENGTH
    m = num_families
    Jp = _padded_destinations(num_destinations)
    grid_n = pl.cdiv(n_rows, block)
    slab_spec = pl.BlockSpec((length, block), lambda i: (0, i))
    coeff_spec = pl.BlockSpec((m, length, block), lambda i: (0, 0, i))
    one_spec = pl.BlockSpec((1, 1), lambda i: (0, 0))
    in_specs = [slab_spec, slab_spec, coeff_spec, slab_spec, slab_spec, one_spec]
    if quantized:
        in_specs += [pl.BlockSpec((m, 1), lambda i: (0, 0)), one_spec]
    body = functools.partial(
        dual_oracle_kernel_body,
        radius=radius,
        inequality=inequality,
        num_rows=n_rows,
    )
    return pl.pallas_call(
        body,
        out_shape=(
            jax.ShapeDtypeStruct(
                (length, n_rows), dtype if out_dtype is None else out_dtype
            ),
            jax.ShapeDtypeStruct((grid_n, m, Jp), jnp.float32),
            jax.ShapeDtypeStruct((grid_n, 8, 128), jnp.float32),
        ),
        grid=(grid_n,),
        in_specs=in_specs,
        out_specs=(
            slab_spec,
            pl.BlockSpec((1, m, Jp), lambda i: (i, 0, 0)),
            pl.BlockSpec((1, 8, 128), lambda i: (i, 0, 0)),
        ),
        scratch_shapes=[pltpu.VMEM((m, length, block), jnp.float32)],
        interpret=interpret,
        name="dual_oracle",
        metadata={
            "length": str(length),
            "families": str(m),
            "destinations": str(num_destinations),
            "block": str(block),
            "dtype": jnp.dtype(dtype).name,
        },
    )
