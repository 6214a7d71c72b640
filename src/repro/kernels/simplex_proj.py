"""Fused simplex-projection Pallas kernel — TPU adaptation of the paper's §4.3.

The paper fuses the Duchi pipeline into one Triton kernel where each *program*
owns one column and sorts in registers.  That design leans on CUDA warp
semantics; the TPU-native formulation instead processes a whole VMEM tile per
grid step and runs the sort as a **bitonic compare-exchange network** along
the slot axis, data-parallel across sources on the VPU:

  * bitonic sort (descending): log2(L)*(log2(L)+1)/2 compare-exchange stages,
    each expressed as roll + elementwise min/max/where (no gather, no scatter,
    no divergence).  Bucket widths are powers of two by construction (§4.2),
    so the network needs no padding logic.
  * inclusive prefix sum: Hillis-Steele scan, log2(L) shifted adds.
  * cutoff rho via a boolean reduction over the monotone Duchi condition,
    threshold theta via a masked reduction, then subtract-and-clamp — all in
    the same tile, nothing is materialised to HBM between stages.
  * inequality early exit (paper: "in-kernel early exit"): feasible sources
    take the clamp-only path, selected per source with a vector `where`
    (branchless — on TPU a uniform early `return` would stall the pipeline).

Layout: the kernels take slabs transposed, `[L, n]`, so the slots of one
source run down the sublanes and sources run along the 128 lanes.  A bucket
slab `[n, L]` with L < 128 is stored by XLA with n as the minor (lane)
dimension, so the transpose the wrapper applies is free; a `[n, L]` operand
would make XLA copy every slab into a layout that pads L up to 128 lanes
(16x the bytes at L = 8).  The grid runs over blocks of sources.

Matching the paper's Triton kernel: fp32 internally, column lengths up to
MAX_FUSED_LENGTH = 8192, multi-op fallback beyond (see ops.py).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

__all__ = [
    "MAX_FUSED_LENGTH",
    "bitonic_sort_desc",
    "inclusive_scan",
    "make_simplex_call",
    "project_tile",
]

MAX_FUSED_LENGTH = 8192
_NEG = -1.0e30


def _iota(shape, axis: int, dtype=jnp.int32):
    return jax.lax.broadcasted_iota(dtype, shape, axis % len(shape))


def _roll(x: jax.Array, shift: int, axis: int) -> jax.Array:
    """Circular roll along `axis` via two static slices (Pallas-friendly)."""
    L = x.shape[axis]
    shift = shift % L
    if shift == 0:
        return x
    hi = jax.lax.slice_in_dim(x, L - shift, L, axis=axis)
    lo = jax.lax.slice_in_dim(x, 0, L - shift, axis=axis)
    return jnp.concatenate([hi, lo], axis=axis)


def bitonic_sort_desc(x: jax.Array, axis: int = -1) -> jax.Array:
    """Descending bitonic sort along `axis` (length must be a power of 2).

    Every stage is roll + min/max/where over the whole tile: the partner of
    slot i at substage j is i XOR j, reached by rolling left for slots with
    bit j clear and right for slots with bit j set.
    """
    L = x.shape[axis]
    assert L & (L - 1) == 0, f"bitonic sort needs power-of-2 length, got {L}"
    if L == 1:
        return x
    iota = _iota(x.shape, axis)
    log_l = L.bit_length() - 1
    for k_exp in range(1, log_l + 1):
        k = 1 << k_exp
        for j_exp in range(k_exp - 1, -1, -1):
            j = 1 << j_exp
            partner = jnp.where(
                (iota & j) == 0, _roll(x, -j, axis), _roll(x, j, axis)
            )
            mn = jnp.minimum(x, partner)
            mx = jnp.maximum(x, partner)
            # descending overall: invert the classic ascending direction bit.
            # (At the final merge k == L the bit is always clear, making every
            # comparison descending — the whole row comes out descending.)
            asc = (iota & k) != 0
            lower = (iota & j) == 0
            x = jnp.where(lower == asc, mn, mx)
    return x


def inclusive_scan(x: jax.Array, axis: int = -1) -> jax.Array:
    """Hillis-Steele inclusive prefix sum along `axis` (log2 L shifted adds)."""
    L = x.shape[axis]
    iota = _iota(x.shape, axis)
    s = 1
    while s < L:
        x = x + jnp.where(iota >= s, _roll(x, s, axis), 0.0)
        s *= 2
    return x


def project_tile(
    v: jax.Array,
    mask: jax.Array,
    *,
    radius: float,
    inequality: bool,
    axis: int = 0,
) -> jax.Array:
    """The fused Duchi pipeline on one fp32 tile, projecting along `axis`.

    Shared by the simplex kernel and the dual-oracle kernel.  Mask-zero
    slots come out exactly 0.0.
    """
    z = jnp.float32(radius)
    vm = jnp.where(mask > 0, v, _NEG)
    u = bitonic_sort_desc(vm, axis)
    css = inclusive_scan(u, axis)
    j = _iota(v.shape, axis).astype(jnp.float32) + 1.0
    cond = u * j > css - z  # monotone Duchi condition
    rho = jnp.maximum(
        jnp.sum(cond.astype(jnp.float32), axis=axis, keepdims=True), 1.0
    )
    css_rho = jnp.sum(jnp.where(j == rho, css, 0.0), axis=axis, keepdims=True)
    theta = (css_rho - z) / rho
    w_eq = jnp.maximum(vm - theta, 0.0) * mask
    if not inequality:
        return w_eq
    w0 = jnp.maximum(v, 0.0) * mask
    feasible = jnp.sum(w0, axis=axis, keepdims=True) <= z
    return jnp.where(feasible, w0, w_eq)


def simplex_kernel_body(
    v_ref, mask_ref, out_ref, *, radius: float, inequality: bool
):
    """Kernel body: one (L, block) tile, entire Duchi pipeline fused."""
    out = project_tile(
        v_ref[...].astype(jnp.float32),
        mask_ref[...].astype(jnp.float32),
        radius=radius,
        inequality=inequality,
    )
    out_ref[...] = out.astype(out_ref.dtype)


def make_simplex_call(
    n_rows: int,
    length: int,
    block: int,
    dtype,
    *,
    radius: float = 1.0,
    inequality: bool = True,
    interpret: bool = True,
    out_dtype=None,
):
    """Build the pallas_call for a transposed (length, n_rows) slab.

    Each grid step owns `block` sources (lanes) at full slot depth — the
    projection reduces over slots, so the slot axis stays unsplit.  A last
    partial block is fine: sources are projected independently and
    out-of-range lanes are never written back.
    """
    assert length <= MAX_FUSED_LENGTH
    spec = pl.BlockSpec((length, block), lambda i: (0, i))
    body = functools.partial(
        simplex_kernel_body, radius=radius, inequality=inequality
    )
    return pl.pallas_call(
        body,
        out_shape=jax.ShapeDtypeStruct(
            (length, n_rows), dtype if out_dtype is None else out_dtype
        ),
        grid=(pl.cdiv(n_rows, block),),
        in_specs=[spec, spec],
        out_specs=spec,
        interpret=interpret,
        name="simplex_proj",
        metadata={
            "length": str(length),
            "block": str(block),
            "dtype": jnp.dtype(dtype).name,
        },
    )
