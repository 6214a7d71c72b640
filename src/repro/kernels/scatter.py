"""Scatter-add of `A x` into a VMEM-resident bin table, as an exact one-hot
contraction on the MXU: the transpose of the gather (kernels/gather.py).

Every oracle evaluation and power step needs, per bucket slab, the bins

    ax[k, j] = sum_{l, s : idx[l, s] = j} coeff[k, l, s] * v[l, s]

(v = x · mask in the oracle, A^T u · mask in a power step).  TPU has no
VMEM scatter-add, and XLA's scatter-add takes ~7 ns per slot on a v5e.  The
sum is a contraction with two one-hot matrices instead, and that
contraction is exact up to the fp32 sums:

  * bins are a table [R, 128] per family, R = ceil(J/128) rounded up to a
    multiple of 8 (`gather.table_rows`); for an index e, its row is
    hi = e >> 7 and its lane lo = e & 127;
  * each slot's fp32 contribution c = coeff_k · v (int8 coefficients
    dequantized first, as `gather_atl` does) is split into three bf16
    pieces: p0 = bf16(c), p1 = bf16(c - p0), p2 = bf16(c - p0 - p1).  The
    residuals are exact in fp32 and each holds at most 16 and then 8
    significant bits, so p0 + p1 + p2 = c exactly (24 bits; subnormal
    pieces flush to zero, as every TPU operand does);
  * per piece the row one-hot [R, chunk], its 1s replaced by the piece
    (a select), is contracted over the chunk's sources with the lane
    one-hot [128, chunk] on the MXU, with fp32 accumulation.  Each product
    is one bf16 value times 1 or 0, so exact; only the fp32 sums round, as
    `segment_sum`'s do in another order.  The three pieces keep their own
    accumulators [R, 128] and are added once at the end, (p0 + p1) + p2.

The accumulator is one VMEM block resident across the whole grid, which
runs in order, so the result is deterministic; the grid runs over (slot-row
tiles, source blocks) as the gather's does, and within a tile one slot row
of `chunk` sources at a time.  Slabs come in transposed, [L, n].  The
contributions of the tile are formed once per grid step, with selects, so
the slot rows and source lanes past the end of the slab in the last,
partial grid steps add 0 whatever they hold (NaN included: on the MXU
0 · NaN is NaN).  An index outside [0, R·128) selects no row and adds
nothing; one in [J, R·128) lands in a padding bin that is dropped.

Per slot and family this costs 2·3·128·R MXU flops (three pieces against a
128-lane one-hot; the gather's four byte planes take 2·4·128·R) and about
(3m + 1)·R + 2·128 elementwise vector operations for m families.  The one
rule of `ops.onehot_takes_kernel` (J <= 16384 per family, VMEM within
`gather.fits_vmem_budget`) decides for both kernels.

Validated in interpret mode on CPU (tests/test_scatter_kernel.py) and
compiled for a described TPU v5e in tests/test_tpu_compile.py.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.gather import fits_vmem_budget, table_rows, tile_rows

__all__ = ["make_scatter_call", "split_bf16"]

_LANES = 128
# contract the chunk's sources (the lane axis) of both one-hots
_NT = (((1,), (1,)), ((), ()))


def split_bf16(c: jax.Array) -> tuple[jax.Array, jax.Array, jax.Array]:
    """fp32 `c` as three bf16-representable fp32 pieces whose exact sum is
    `c` (module docstring), for |c| below bf16's largest finite value
    (~3.39e38: above, p0 is infinite and the pieces are not finite)."""
    p0 = c.astype(jnp.bfloat16).astype(jnp.float32)
    r1 = c - p0
    p1 = r1.astype(jnp.bfloat16).astype(jnp.float32)
    return p0, p1, r1 - p1


def scatter_kernel_body(
    idx_ref,  # [rows, block] int32
    coeff_ref,  # [m, rows, block] slab dtype
    v_ref,  # [rows, block] f32
    *rest,  # quantized: coeff_scale_ref [m, 1] f32 first; then out_ref
    # [m·R, 128] f32 (A x), and the scratch acc_ref [3·m·R, 128] f32 (one
    # accumulator per family and piece) and con_ref [m, rows, block] f32
    # (the tile's contributions)
    chunk: int,
    num_families: int,
    quantized: bool,
    n_rows: int,
    length: int,
):
    scale_ref = rest[0] if quantized else None
    out_ref, acc_ref, con_ref = rest[-3:]
    m = num_families
    rows, block = idx_ref.shape
    R = out_ref.shape[0] // m
    group = 8 if rows % 8 == 0 else rows
    groups = rows // group
    i, j = pl.program_id(0), pl.program_id(1)

    @pl.when((i == 0) & (j == 0))
    def _():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    # slot rows and source lanes past the end of the slab hold unspecified
    # data in the last grid steps: select them to 0
    valid = (
        i * rows + jax.lax.broadcasted_iota(jnp.int32, (rows, block), 0) < length
    ) & (
        j * block + jax.lax.broadcasted_iota(jnp.int32, (rows, block), 1) < n_rows
    )
    v = v_ref[...]
    for k in range(m):
        c = coeff_ref[k].astype(jnp.float32)
        if scale_ref is not None:
            c = c * scale_ref[k, 0]
        con_ref[k] = jnp.where(valid, c * v, 0.0)

    lane_id = jax.lax.broadcasted_iota(jnp.int32, (_LANES, chunk), 0)
    table_row = jax.lax.broadcasted_iota(jnp.int32, (R, chunk), 0)

    def step(t, carry):
        r0 = pl.multiple_of((t % groups) * group, group)
        s0 = pl.multiple_of((t // groups) * chunk, chunk)
        ids = idx_ref[pl.ds(r0, group), pl.ds(s0, chunk)]
        lo = ids & (_LANES - 1)
        hi = ids >> 7
        pieces = [
            p for k in range(m)
            for p in split_bf16(con_ref[k, pl.ds(r0, group), pl.ds(s0, chunk)])
        ]
        for r in range(group):
            onehot = jnp.where(lane_id == lo[r : r + 1], 1.0, 0.0).astype(
                jnp.bfloat16
            )
            pick = table_row == hi[r : r + 1]
            scaled = jnp.concatenate(
                [jnp.where(pick, p[r : r + 1], 0.0) for p in pieces], axis=0
            ).astype(jnp.bfloat16)  # [3·m·R, chunk]
            acc_ref[...] += jax.lax.dot_general(
                scaled, onehot, _NT, preferred_element_type=jnp.float32
            )
        return carry

    jax.lax.fori_loop(0, groups * (block // chunk), step, 0)

    @pl.when((i == pl.num_programs(0) - 1) & (j == pl.num_programs(1) - 1))
    def _():
        for k in range(m):
            a = [acc_ref[pl.ds((3 * k + p) * R, R), :] for p in range(3)]
            out_ref[pl.ds(k * R, R), :] = (a[0] + a[1]) + a[2]


def make_scatter_call(
    n_rows: int,
    length: int,
    num_families: int,
    num_destinations: int,
    block: int,
    dtype,
    *,
    interpret: bool = True,
    quantized: bool = False,
):
    """pallas_call `dual_scatter` for one transposed [length, n_rows] slab.

    Call-time arguments: idx [length, n_rows] int32, coeff [m, length,
    n_rows] in `dtype`, v [length, n_rows] fp32, and with ``quantized``
    coeff_scale [m, 1] fp32.  Returns the bin table [m·R, 128] fp32, family
    k's A x in rows k·R .. (k+1)·R, bin e at row e >> 7 and lane e & 127
    (`ops.dual_scatter` cuts it to [m, J]).  `block` is the sources per
    grid step, a multiple of 128; the slot rows per step are
    `gather.tile_rows(length)`; sources go 512 at a time where the block
    allows, else 256 or 128.
    """
    assert block % _LANES == 0
    chunk = next(c for c in (4 * _LANES, 2 * _LANES, _LANES) if block % c == 0)
    m = num_families
    R = table_rows(num_destinations)
    assert fits_vmem_budget(m, num_destinations)
    rows = tile_rows(length)
    grid = (pl.cdiv(length, rows), pl.cdiv(n_rows, block))
    slab_spec = pl.BlockSpec((rows, block), lambda i, j: (i, j))
    in_specs = [
        slab_spec,
        pl.BlockSpec((m, rows, block), lambda i, j: (0, i, j)),
        slab_spec,
    ]
    if quantized:
        in_specs.append(pl.BlockSpec((m, 1), lambda i, j: (0, 0)))
    body = functools.partial(
        scatter_kernel_body, chunk=chunk, num_families=m, quantized=quantized,
        n_rows=n_rows, length=length,
    )
    return pl.pallas_call(
        body,
        out_shape=jax.ShapeDtypeStruct((m * R, _LANES), jnp.float32),
        grid=grid,
        in_specs=in_specs,
        out_specs=pl.BlockSpec((m * R, _LANES), lambda i, j: (0, 0)),
        scratch_shapes=[
            pltpu.VMEM((3 * m * R, _LANES), jnp.float32),
            pltpu.VMEM((m, rows, block), jnp.float32),
        ],
        interpret=interpret,
        name="dual_scatter",
        metadata={
            "length": str(length),
            "families": str(m),
            "destinations": str(num_destinations),
            "block": str(block),
            "dtype": jnp.dtype(dtype).name,
        },
    )
