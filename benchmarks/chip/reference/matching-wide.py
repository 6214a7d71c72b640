"""The plain reference of `matching.py` with no row narrower than 16 slots.

The TPU compiler takes minutes over a gather or scatter-add indexed by a
row-major `[n, W <= 8]` slab for some n: at 8M sources, compiling the
three programs with the `[212992, 4]` block took ~4 of the reference's
5.5 minutes on a v5e host.  Here every row narrower than 16 slots is
padded to 16 (the padding is invalid and holds nothing, as in any row
shorter than its block) and joins the 16-wide block, for ~20 % more slots
and compiles of seconds.  Oracle, schedule and solve are `matching.py`'s.
"""
from __future__ import annotations

import dataclasses

import jax.numpy as jnp

from chipbench.harness import load_module

_base = load_module("reference", "matching")
Schedule, Table, solve = _base.Schedule, _base.Table, _base.solve

__all__ = ["Schedule", "Table", "table", "solve"]

MIN_WIDTH = 16


def table(src, dst, values, coeff, rhs, num_destinations: int) -> Table:
    t = _base.table(src, dst, values, coeff, rhs, num_destinations)
    narrow = [b for b in t.blocks if b[0].shape[1] <= MIN_WIDTH]
    wide = [b for b in t.blocks if b[0].shape[1] > MIN_WIDTH]
    if not narrow:
        return t

    def pad(x, W):  # slots on the last axis, rows on the one before
        return jnp.pad(x, [(0, 0)] * (x.ndim - 1) + [(0, MIN_WIDTH - W)])

    joined = tuple(
        jnp.concatenate([pad(b[i], b[0].shape[1]) for b in narrow], axis=-2)
        for i in range(4))
    return dataclasses.replace(t, blocks=(joined, *wide))
