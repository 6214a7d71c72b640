"""Gather of `A^T lam` from a VMEM-resident dual table, as an exact one-hot
contraction on the MXU.

Every oracle evaluation and power step needs, on each slot of a bucket slab,

    atl[l, s] = sum_k coeff[k, l, s] * lam_k[idx[l, s]]

Mosaic gathers only within one vector register, so a gather from a J-wide
dual row by an index tile has no vector lowering.  It can be written as a
contraction with a one-hot matrix instead, and that contraction is exact:

  * the dual row lam_k, padded to R·128 entries (R = ceil(J/128), rounded
    up to a multiple of 8), is viewed as [R, 128], and its 32 bits are split
    into the bytes b0..b3 (0..255 each).  XLA lays out two tables before
    the call (`dual_table`), in bf16, where a byte times a power of two is
    exact:
        lo24_k [R, 384] = [b0 | 2^8 b1 | 2^16 b2]        hi_k [R, 128] = b3
  * for an index e, lo = e & 127 and hi = e >> 7.  The bf16 one-hot column
    of lo, stacked thrice, contracted with lo24_k on the MXU with fp32
    accumulation gives T[r] = b0 + 2^8 b1 + 2^16 b2 of lam_k[128 r + lo]:
    three exact terms whose sum is below 2^24, so exact in fp32.  One more
    contraction with hi_k gives b3;
  * row r = hi is picked by a masked sum over the R rows (one nonzero term,
    exact), and  bits = int(lo24) | int(b3) << 24, bitcast to fp32, is
    lam_k[e] bit for bit: 0, -0.0, subnormals, inf and NaN included.

The one-hot is shared by the m families and by both tables.  Slabs come in
transposed, [L, n] (slot rows down the sublanes, sources along the lanes,
as in every kernel here); the grid runs over (slot-row tiles, source
blocks), and within a tile one slot row of `chunk` sources at a time.  The
tables are one resident VMEM block (their block index never changes).
No lane reads another lane's index, so the source lanes past the end of the
slab in the last, partial grid step need no mask: they compute garbage that
is never written back.  Indices must lie in [0, J), as the slabs' do
(padded slots hold index 0); an index past the table reads 0.0.

Per slot and family this costs 2·4·128·R MXU flops (four byte planes
against a 128-row one-hot) and about 5R + 260 elementwise vector
operations; on a v5e at R = 80 the MXU bounds it (9.4 ms for 2.05·10^7
slots, ~91 % of the bf16 peak), against ~7.5 ns per slot for XLA's
per-element gather.  The work grows with R, the XLA gather's does not:
`ops.onehot_takes_kernel` takes the kernel up to R = 128 (J <= 16384 per
family), and while its VMEM, which grows with m·R, fits (`fits_vmem_budget`:
m <= 4 at J = 16384).

The coefficients (fp32, bf16, or int8 with per-family scales) are widened
and scaled, and the families summed, in `core.objective.gather_atl`'s order:
coeff[0]·g_0, then + coeff[k]·g_k.

Validated in interpret mode on CPU (tests/test_gather_kernel.py) and
compiled for a described TPU v5e in tests/test_tpu_compile.py.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = [
    "dual_table",
    "fits_vmem_budget",
    "make_gather_call",
    "table_rows",
    "MAX_TABLE_ROWS",
]

_LANES = 128
# Rows R of a family's dual table the kernel takes (R = ceil(J/128)).
MAX_TABLE_ROWS = 128
# A slab tile of one 4-byte word per slot: at most 2^16 slots, sublane
# padding included (ops.pick_block_rows).
_TILE_BYTES = 4 << 16
# VMEM the kernel may plan for: half of a v5e's 16 MiB of scoped VMEM, the
# rest left to Mosaic's temporaries.
_VMEM_BUDGET = 8 << 20


def table_rows(num_destinations: int) -> int:
    """Rows of one family's table: J / 128 rounded up to a whole number of
    8-row sublane tiles."""
    rows = -(-num_destinations // _LANES)
    return -(-rows // 8) * 8


def fits_vmem_budget(num_families: int, num_destinations: int) -> bool:
    """True iff a family's table is at most MAX_TABLE_ROWS rows and the
    kernel's VMEM, at the widest slab tile, fits _VMEM_BUDGET: idx and atl
    tiles double-buffered, per family the coefficient tile (fp32 at most)
    double-buffered and its gathered duals in scratch, and per table row
    and family the bf16 tables (1 KiB, double-buffered) and the two fp32
    contraction results of a 512-source chunk (4 KiB).  At J = 16384 that
    takes m <= 4; at J = 10^4, m <= 5.

    The scatter kernel (kernels/scatter.py), which the same rule admits,
    holds other buffers (per table row and family three fp32 piece
    accumulators and the output block; per chunk the piece-scaled row
    one-hot), but as the TPU compiler allocates them it fits this budget
    at the rule's limits too: `test_scatter_compiles_at_its_vmem_limit`
    (tests/test_tpu_compile.py) compiles it there for a v5e under a
    scoped-VMEM limit of _VMEM_BUDGET."""
    R = table_rows(num_destinations)
    m = num_families
    need = 4 * _TILE_BYTES + 3 * m * _TILE_BYTES + 6 * 1024 * m * R
    return R <= MAX_TABLE_ROWS and need <= _VMEM_BUDGET


def dual_table(lam2: jax.Array) -> tuple[jax.Array, jax.Array]:
    """The kernel's dual tables from [m, J] fp32 duals: (lo24 [m·R, 384],
    hi [m·R, 128]), bf16, family k in rows k·R .. (k+1)·R (module
    docstring)."""
    m, J = lam2.shape
    R = table_rows(J)
    lam = jnp.pad(lam2.astype(jnp.float32), ((0, 0), (0, R * _LANES - J)))
    bits = jax.lax.bitcast_convert_type(lam, jnp.uint32).reshape(m, R, _LANES)
    byte = [((bits >> (8 * p)) & 0xFF).astype(jnp.float32) for p in range(4)]
    lo24 = jnp.concatenate(
        [byte[0], byte[1] * 2.0**8, byte[2] * 2.0**16], axis=-1
    )
    return (
        lo24.astype(jnp.bfloat16).reshape(m * R, 3 * _LANES),
        byte[3].astype(jnp.bfloat16).reshape(m * R, _LANES),
    )


def gather_kernel_body(
    lo_ref,  # [m·R, 384] bf16  b0 | 2^8 b1 | 2^16 b2 of every dual
    hi_ref,  # [m·R, 128] bf16  b3
    idx_ref,  # [rows, block] int32
    coeff_ref,  # [m, rows, block] slab dtype
    *rest,  # quantized: coeff_scale_ref [m, 1] f32 first; then out_ref
    # [rows, block] f32 (A^T lam) and the scratch g_ref [m, rows, block]
    # f32 (lam_k[idx])
    chunk: int,
    num_families: int,
    quantized: bool,
):
    scale_ref = rest[0] if quantized else None
    out_ref, g_ref = rest[-2:]
    m = num_families
    rows, block = idx_ref.shape
    R = hi_ref.shape[0] // m
    group = 8 if rows % 8 == 0 else rows
    groups = rows // group

    lane_id = jax.lax.broadcasted_iota(jnp.int32, (_LANES, chunk), 0)
    table_row = jax.lax.broadcasted_iota(jnp.int32, (R, chunk), 0)
    row_id = jax.lax.broadcasted_iota(jnp.int32, (group, chunk), 0)

    def select(t_all, k, pick):
        """Family k's row `hi` of a contraction: its only nonzero term."""
        return jnp.sum(
            jnp.where(pick, t_all[k * R : (k + 1) * R], 0.0), axis=0,
            keepdims=True,
        ).astype(jnp.int32)

    def step(t, carry):
        r0 = pl.multiple_of((t % groups) * group, group)
        s0 = pl.multiple_of((t // groups) * chunk, chunk)
        ids = idx_ref[pl.ds(r0, group), pl.ds(s0, chunk)]
        lo = ids & (_LANES - 1)
        hi = ids >> 7
        # each row's duals go into the group's tile, stored aligned once
        words = [jnp.zeros((group, chunk), jnp.int32)] * m
        for r in range(group):
            onehot = jnp.where(lane_id == lo[r : r + 1], 1.0, 0.0).astype(
                jnp.bfloat16
            )
            t_lo = jnp.dot(
                lo_ref[...], jnp.concatenate([onehot] * 3, axis=0),
                preferred_element_type=jnp.float32,
            )
            t_hi = jnp.dot(hi_ref[...], onehot, preferred_element_type=jnp.float32)
            pick = table_row == hi[r : r + 1]
            for k in range(m):
                bits = select(t_lo, k, pick) | (select(t_hi, k, pick) << 24)
                words[k] = jnp.where(row_id == r, bits, words[k])
        for k in range(m):
            g_ref[k, pl.ds(r0, group), pl.ds(s0, chunk)] = (
                jax.lax.bitcast_convert_type(words[k], jnp.float32)
            )
        return carry

    jax.lax.fori_loop(0, groups * (block // chunk), step, 0)
    atl = None
    for k in range(m):  # gather_atl's order
        c = coeff_ref[k].astype(jnp.float32)
        if scale_ref is not None:
            c = c * scale_ref[k, 0]
        term = c * g_ref[k]
        atl = term if atl is None else atl + term
    out_ref[...] = atl


def tile_rows(length: int) -> int:
    """Slot rows per grid step: the whole slab up to 8 rows, else whole
    8-row groups, at most 64 (a wider slab takes several row tiles)."""
    if length <= 8:
        return length
    return min(64, length - length % 8)


def make_gather_call(
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
    """pallas_call `dual_gather` for one transposed [length, n_rows] slab.

    Call-time arguments: (lo24, hi) from `dual_table`, idx [length, n_rows]
    int32, coeff [m, length, n_rows] in `dtype`, and with ``quantized``
    coeff_scale [m, 1] fp32.  Returns atl [length, n_rows] fp32.  `block`
    is the sources per grid step, a multiple of 128; the slot rows per step
    are `tile_rows(length)`.  The kernel takes the block's sources 512 at a
    time where the block allows (on a v5e the L = 16 bucket of the
    s2M-d10K instance, 2·10^7 slots, took 11.4 ms 128 at a time, 10.1 ms
    256 and 9.4 ms 512), else 256 or 128.
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
        pl.BlockSpec((m * R, 3 * _LANES), lambda i, j: (0, 0)),
        pl.BlockSpec((m * R, _LANES), lambda i, j: (0, 0)),
        slab_spec,
        pl.BlockSpec((m, rows, block), lambda i, j: (0, i, j)),
    ]
    if quantized:
        in_specs.append(pl.BlockSpec((m, 1), lambda i, j: (0, 0)))
    body = functools.partial(
        gather_kernel_body, chunk=chunk, num_families=m, quantized=quantized,
    )
    return pl.pallas_call(
        body,
        out_shape=jax.ShapeDtypeStruct((length, n_rows), jnp.float32),
        grid=grid,
        in_specs=in_specs,
        out_specs=slab_spec,
        scratch_shapes=[pltpu.VMEM((m, rows, block), jnp.float32)],
        interpret=interpret,
        name="dual_gather",
        metadata={
            "length": str(length),
            "families": str(m),
            "destinations": str(num_destinations),
            "block": str(block),
            "dtype": jnp.dtype(dtype).name,
        },
    )
