"""Bucketed-ELL packing of matching LPs — the TPU analogue of the paper's §4.1+§4.2.

The paper stores A in CSC (one column per source) and separately buckets the
per-source slices by length for batched projection.  On TPU both collapse into
one structure: sources whose eligible-degree d lies in (2^{t-1}, 2^t] are packed
into a dense slab of width L_t = 2^t.  Each bucket is a fixed-shape set of
arrays (gather/segment-sum friendly, shardable along rows); padding within a
bucket is bounded by 2x, exactly the paper's bound, and the number of distinct
kernel launches is 1 + floor(log2 s_max), exactly the paper's launch count.

Layout per bucket (n rows = sources, L = slab width):
  idx   [n, L] int32  destination id of each eligible edge (0 for padding)
  coeff [m, n, L]     constraint coefficient per family    (0 for padding)
  cost  [n, L]        minimisation cost c_ij               (0 for padding)
  mask  [n, L]        1.0 for real edges, 0.0 for padding

Rows are padded up to a multiple of ``shard_multiple`` so `shard_map` sees
equal per-device shapes; padded rows are all-mask-zero and contribute exact
zeros to gradients.

Slab storage dtype (``slab_dtype``): coeff/cost/mask are stored in fp32
(default), bf16, or int8.  Narrow storage halves/quarters the per-iteration
HBM traffic of the dual oracle; *accumulation* (the Ax histogram, c'x,
||x||^2, all dual/continuation math) stays fp32 on every path.  int8 slabs
carry symmetric per-bucket scales — ``coeff_scale [m,1,1]`` (per family) and
``cost_scale [1,1]``, both fp32 — and are dequantized in-kernel (value =
q * scale); mask is exact in any dtype (0/1).  The rhs and the duals stay
fp32 for narrow slab dtypes (`rhs_dtype`).
"""
from __future__ import annotations

import dataclasses
import math
import weakref
from typing import Optional, Sequence

import jax
import ml_dtypes
import numpy as np

from repro import telemetry
from repro.instances.generator import EdgeListInstance

__all__ = [
    "Bucket",
    "BucketedInstance",
    "SLAB_DTYPES",
    "bucketize",
    "convert_bucket",
    "dequantize_bucket",
    "pack_single_slab",
    "pack_source_ids",
    "resolve_slab_dtype",
    "rhs_dtype",
    "slab_dtype_name",
    "unpack_primal",
]

# Supported slab storage dtypes, by canonical name.  "bfloat16" maps to
# ml_dtypes.bfloat16 on the host (numpy slabs) and jnp.bfloat16 on device.
SLAB_DTYPES = ("float32", "bfloat16", "int8")

_INT8_QMAX = 127.0  # symmetric quantization range [-127, 127]


def resolve_slab_dtype(dtype) -> np.dtype:
    """Canonical numpy dtype of a slab-dtype name/dtype (raises on unknown)."""
    if isinstance(dtype, str) and dtype == "bfloat16":
        return np.dtype(ml_dtypes.bfloat16)
    d = np.dtype(dtype)
    if slab_dtype_name(d) not in SLAB_DTYPES:
        raise ValueError(
            f"unsupported slab dtype {dtype!r}; choose from {SLAB_DTYPES}"
        )
    return d


def slab_dtype_name(dtype) -> str:
    """Canonical name ("float32" | "bfloat16" | "int8") of a slab dtype."""
    return np.dtype(dtype).name


def rhs_dtype(slab_dtype) -> np.dtype:
    """Storage dtype of the rhs for a given slab dtype: the duals (and
    everything in dual space, rhs included) stay fp32 when slabs go narrow."""
    d = resolve_slab_dtype(slab_dtype)
    return d if slab_dtype_name(d) == "float32" else np.dtype(np.float32)


@jax.tree_util.register_dataclass
@dataclasses.dataclass
class Bucket:
    idx: jax.Array | np.ndarray  # [n, L] int32
    coeff: jax.Array | np.ndarray  # [m, n, L] slab dtype
    cost: jax.Array | np.ndarray  # [n, L] slab dtype
    mask: jax.Array | np.ndarray  # [n, L] slab dtype (exact 0/1 in any dtype)
    length: int = dataclasses.field(metadata=dict(static=True))
    # int8 storage only: symmetric per-bucket dequantization scales
    # (value = q * scale), fp32.  None for float storage — None contributes
    # no pytree leaves, so fp32/bf16 treedefs are unchanged by these fields.
    coeff_scale: Optional[jax.Array | np.ndarray] = None  # [m, 1, 1] f32
    cost_scale: Optional[jax.Array | np.ndarray] = None  # [1, 1] f32

    @property
    def rows(self) -> int:
        return int(self.idx.shape[0])

    @property
    def slab_dtype(self) -> str:
        return slab_dtype_name(self.coeff.dtype)


@jax.tree_util.register_dataclass
@dataclasses.dataclass
class BucketedInstance:
    buckets: tuple[Bucket, ...]
    rhs: jax.Array | np.ndarray  # [m * J] f32
    num_sources: int = dataclasses.field(metadata=dict(static=True))
    num_destinations: int = dataclasses.field(metadata=dict(static=True))
    num_families: int = dataclasses.field(metadata=dict(static=True))
    # Optional compiled-formulation metadata (repro.formulation.FormulationSpec,
    # hashable+frozen).  Static: it is part of the treedef, so the shape-keyed
    # jit caches in service/engine.py key executables on the formulation too,
    # and MatchingObjective (the shim) resolves it at trace time — which is how
    # a compiled formulation dispatches through the solve/service layers with
    # zero edits to maximizer/sharding/service.  None = legacy matching.
    formulation: Optional[object] = dataclasses.field(
        default=None, metadata=dict(static=True)
    )

    @property
    def dual_dim(self) -> int:
        return self.num_families * self.num_destinations

    @property
    def nnz(self) -> int:
        return int(sum(float(np.sum(np.asarray(b.mask))) for b in self.buckets))

    def row_norms_sq(self) -> np.ndarray:
        """||A_r||_2^2 per coupling row r = k*J + j (for Jacobi / Lemma B.1)."""
        m, J = self.num_families, self.num_destinations
        out = np.zeros(m * J)
        for b in self.buckets:
            idx = np.asarray(b.idx)
            coeff, _, mask = _host_dequant(b)
            for k in range(m):
                np.add.at(out, k * J + idx.ravel(), (coeff[k] ** 2 * mask).ravel())
        return out

    @property
    def slab_dtype(self) -> str:
        return self.buckets[0].slab_dtype

    def shape_dtype_structs(self) -> "BucketedInstance":
        """ShapeDtypeStruct twin of this instance (for .lower() dry-runs)."""
        as_sds = lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype)
        return jax.tree.map(as_sds, self)


# -- slab dtype conversion ---------------------------------------------------


def _host_dequant(b: Bucket) -> tuple[np.ndarray, np.ndarray, np.ndarray]:
    """(coeff, cost, mask) of one bucket as fp32 numpy arrays (host side)."""
    coeff = np.asarray(b.coeff)
    cost = np.asarray(b.cost)
    mask = np.asarray(b.mask)
    if b.slab_dtype == "float32":
        return coeff, cost, mask
    coeff = coeff.astype(np.float32)
    cost = cost.astype(np.float32)
    mask = mask.astype(np.float32)
    if b.coeff_scale is not None:
        coeff = coeff * np.asarray(b.coeff_scale, np.float32)
    if b.cost_scale is not None:
        cost = cost * np.asarray(b.cost_scale, np.float32)
    return coeff, cost, mask


def _quantize_sym(values: np.ndarray, axes: tuple[int, ...]) -> tuple[np.ndarray, np.ndarray]:
    """Symmetric int8 quantization over `axes`: (q, scale) with q = round(v/s)
    clipped to [-127, 127] and s = max|v| / 127 (1/127 when all-zero, so the
    padding invariant q == 0 on mask-zero slots is preserved exactly)."""
    amax = np.abs(values).max(axis=axes, keepdims=True).astype(np.float32)
    scale = np.where(amax > 0, amax, 1.0) / _INT8_QMAX
    q = np.clip(np.rint(values / scale), -_INT8_QMAX, _INT8_QMAX)
    return q.astype(np.int8), scale


def convert_bucket(b: Bucket, dtype) -> Bucket:
    """Host-side conversion of one fp32 bucket to a storage dtype.

    bf16: plain rounding cast of coeff/cost/mask.  int8: symmetric per-bucket
    quantization (per family for coeff) with fp32 scales; mask stores its
    exact 0/1 pattern as int8.  fp32 in -> the bucket unchanged.
    """
    d = resolve_slab_dtype(dtype)
    name = slab_dtype_name(d)
    if name == slab_dtype_name(b.coeff.dtype) and b.coeff_scale is None:
        return b
    if b.slab_dtype != "float32":
        raise ValueError("convert_bucket expects an fp32 source bucket")
    coeff = np.asarray(b.coeff)
    cost = np.asarray(b.cost)
    mask = np.asarray(b.mask)
    if name == "float32":
        return b
    if name == "bfloat16":
        return dataclasses.replace(
            b, coeff=coeff.astype(d), cost=cost.astype(d), mask=mask.astype(d)
        )
    q_coeff, coeff_scale = _quantize_sym(coeff, axes=(1, 2))
    q_cost, cost_scale = _quantize_sym(cost[None], axes=(1, 2))
    return dataclasses.replace(
        b,
        coeff=q_coeff,
        cost=q_cost[0],
        mask=mask.astype(np.int8),
        coeff_scale=coeff_scale,
        cost_scale=cost_scale[0],
    )


def dequantize_bucket(b: Bucket):
    """fp32 compute view of one bucket (trace-safe; jnp ops on narrow dtypes).

    fp32 storage returns the bucket object unchanged — a host-level branch,
    so the default path's jaxpr is bit-identical to the pre-slab_dtype one
    (same trick as the formulation layer's ==1.0 scale branches).  Narrow
    storage dequantizes coeff/cost/mask to fp32; XLA fuses the convert into
    the consuming op, so HBM reads stay at the storage width.
    """
    import jax.numpy as jnp

    if b.slab_dtype == "float32":
        return b
    coeff = jnp.asarray(b.coeff).astype(jnp.float32)
    cost = jnp.asarray(b.cost).astype(jnp.float32)
    mask = jnp.asarray(b.mask).astype(jnp.float32)
    if b.coeff_scale is not None:
        coeff = coeff * jnp.asarray(b.coeff_scale, jnp.float32)
    if b.cost_scale is not None:
        cost = cost * jnp.asarray(b.cost_scale, jnp.float32)
    return dataclasses.replace(
        b, coeff=coeff, cost=cost, mask=mask,
        coeff_scale=None, cost_scale=None,
    )


# ---------------------------------------------------------------------------


@dataclasses.dataclass
class _PackInfo:
    """Host-side bookkeeping to map packed slabs back to edge order."""

    # per bucket: source id per row (-1 pad), edge offset of each row's slice
    source_ids: list[np.ndarray]
    edge_starts: list[np.ndarray]
    degrees: list[np.ndarray]


_PACK_INFO: dict[int, _PackInfo] = {}


def _next_pow2(x: int) -> int:
    return 1 << max(0, (int(x) - 1).bit_length())


def _pad_rows(n: int, multiple: int) -> int:
    return int(math.ceil(max(n, 1) / multiple) * multiple)


def bucketize(
    inst: EdgeListInstance,
    *,
    shard_multiple: int = 1,
    min_length: int = 1,
    max_length: Optional[int] = None,
    dtype=np.float32,
) -> BucketedInstance:
    """Pack an edge list into the bucketed-ELL layout.

    Edges in ``inst`` must be sorted by (source, destination) — the generator
    guarantees this.  ``shard_multiple`` pads every bucket's row count so it
    divides evenly across that many shards.  ``dtype`` is the slab storage
    dtype ("float32" | "bfloat16" | "int8"; see module docstring): slabs are
    packed in fp32 and converted per bucket, and the rhs stays fp32 for
    narrow dtypes (dual space is always fp32).
    """
    with telemetry.span("bucketize"):
        slab_dt = resolve_slab_dtype(dtype)
        spec = inst.spec
        I, J, m = spec.num_sources, spec.num_destinations, spec.num_families

        deg = np.bincount(inst.src, minlength=I)
        active = np.flatnonzero(deg)  # sources with at least one edge
        if active.size == 0:
            raise ValueError("instance has no edges")
        # edge offsets per source (sorted by src)
        starts = np.zeros(I + 1, dtype=np.int64)
        np.cumsum(deg, out=starts[1:])

        max_deg = int(deg.max())
        cap = _next_pow2(max_deg)
        if max_length is not None:
            if cap > max_length:
                raise ValueError(
                    f"max degree {max_deg} exceeds max bucket length {max_length}"
                )
        lengths = []
        L = max(1, _next_pow2(min_length))
        cap = max(cap, L)
        while L <= cap:
            lengths.append(L)
            L *= 2
        # bucket index per active source: smallest L >= degree, but >= min length
        b_of = np.searchsorted(np.asarray(lengths), deg[active])

        buckets: list[Bucket] = []
        info = _PackInfo(source_ids=[], edge_starts=[], degrees=[])
        for t, Lt in enumerate(lengths):
            rows_src = active[b_of == t]
            n = _pad_rows(rows_src.size, shard_multiple)
            idx = np.zeros((n, Lt), dtype=np.int32)
            coeff = np.zeros((m, n, Lt), dtype=np.float32)
            cost = np.zeros((n, Lt), dtype=np.float32)
            mask = np.zeros((n, Lt), dtype=np.float32)
            d = deg[rows_src]
            st = starts[rows_src]
            # vectorised slab fill: flat positions of each (row, within-slice) pair
            if rows_src.size:
                r = np.repeat(np.arange(rows_src.size), d)
                o = np.concatenate([np.arange(k) for k in d]) if d.size else np.empty(0, int)
                e = np.repeat(st, d) + o
                idx[r, o] = inst.dst[e]
                cost[r, o] = inst.cost[e]
                mask[r, o] = 1.0
                for k in range(m):
                    coeff[k, r, o] = inst.coeff[k, e]
            buckets.append(
                convert_bucket(
                    Bucket(idx=idx, coeff=coeff, cost=cost, mask=mask, length=Lt),
                    slab_dt,
                )
            )
            sid = np.full(n, -1, dtype=np.int64)
            sid[: rows_src.size] = rows_src
            info.source_ids.append(sid)
            info.edge_starts.append(st)
            info.degrees.append(d)

        out = BucketedInstance(
            buckets=tuple(buckets),
            rhs=inst.rhs.astype(rhs_dtype(slab_dt)),
            num_sources=I,
            num_destinations=J,
            num_families=m,
        )
        _PACK_INFO[id(out)] = info
        weakref.finalize(out, _PACK_INFO.pop, id(out), None)
        return out


def pack_single_slab(
    inst: EdgeListInstance, *, shard_multiple: int = 1, dtype=np.float32
) -> BucketedInstance:
    """The paper's `batching=False` baseline: one slab of width next_pow2(s_max).

    Used by benchmarks/fig2_bucketing.py to reproduce Figure 2 (padding waste of
    the single-slab layout vs geometric bucketing).
    """
    deg = np.bincount(inst.src, minlength=inst.spec.num_sources)
    width = _next_pow2(int(deg.max()))
    return bucketize(
        inst, shard_multiple=shard_multiple, min_length=width, dtype=dtype
    )


def pack_source_ids(packed: BucketedInstance) -> list[np.ndarray]:
    """Per-bucket source id of each slab row (-1 for padded rows).

    Only available for instances produced by `bucketize` in this process; the
    delta-ingest layer (`repro.instances.deltas`) uses it to seed its
    row-occupancy maps.
    """
    info = _PACK_INFO.get(id(packed))
    if info is None:
        raise KeyError("pack_source_ids: packing info not found for this instance")
    return [a.copy() for a in info.source_ids]


def unpack_primal(
    packed: BucketedInstance, x_slabs: Sequence[np.ndarray | jax.Array]
) -> np.ndarray:
    """Scatter per-bucket primal slabs back to edge order (sorted by src,dst)."""
    info = _PACK_INFO.get(id(packed))
    if info is None:
        raise KeyError("unpack_primal: packing info not found for this instance")
    nnz = int(sum(d.sum() for d in info.degrees))
    x_edges = np.zeros(nnz)
    for bi, slab in enumerate(x_slabs):
        slab = np.asarray(slab)
        d = info.degrees[bi]
        st = info.edge_starts[bi]
        if d.size == 0:
            continue
        r = np.repeat(np.arange(d.size), d)
        o = np.concatenate([np.arange(k) for k in d])
        e = np.repeat(st, d) + o
        x_edges[e] = slab[r, o]
    return x_edges
