"""The Pallas kernels compile for a TPU v5e chip at production widths.

Each case lowers a kernel wrapper for one device of a described (not
attached) v5e topology with `interpret=False` and asks the installed TPU
compiler for the executable, at J = 10^4 destinations and n = 10^5 rows,
up to the widest slab the kernels take.
This catches what interpret mode cannot: operations Mosaic has no lowering
for, tiles that are not aligned to the chip's tiling, and kernels that need
more VMEM than the chip allows.  Nothing runs; results are checked by the
interpret-mode parity tests.
"""
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels import ops as kops

ROWS, J = 100_000, 10_000


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    """One v5e device, with the persistent compilation cache off: an entry
    compiled for a described chip cannot be read back without one."""
    from jax.experimental.compilation_cache import compilation_cache

    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)


def _slab_args(sharding, L, m, dtype):
    def sds(shape, dt):
        return jax.ShapeDtypeStruct(shape, dt, sharding=sharding)

    args = [
        sds((ROWS, L), jnp.int32),
        sds((m, ROWS, L), dtype),
        sds((ROWS, L), dtype),
        sds((ROWS, L), dtype),
        sds((m * J,), jnp.float32),
        sds((), jnp.float32),
    ]
    if dtype == jnp.int8:
        args += [sds((m, 1, 1), jnp.float32), sds((1, 1), jnp.float32)]
    return args


def _gather(idx, coeff, lam2, *scale):
    return kops.dual_gather(idx, coeff, lam2,
                            coeff_scale=scale[0] if scale else None,
                            interpret=False)


def _lower_gather(sharding, L, m, dtype, destinations=J):
    """`ops.dual_gather` on one transposed [L, n] slab with m families of
    J duals, as the oracle calls it."""
    def sds(shape, dt):
        return jax.ShapeDtypeStruct(shape, dt, sharding=sharding)

    args = [sds((L, ROWS), jnp.int32), sds((m, L, ROWS), dtype),
            sds((m, destinations), jnp.float32)]
    if dtype == jnp.int8:
        args.append(sds((m, 1, 1), jnp.float32))
    return jax.jit(_gather).lower(*args)


def _scatter(idx, coeff, v, *scale, destinations=J):
    return kops.dual_scatter(idx, coeff, v, destinations,
                             coeff_scale=scale[0] if scale else None,
                             interpret=False)


def _lower_scatter(sharding, L, m, dtype, destinations=J):
    """`ops.dual_scatter` on one transposed [L, n] slab into m families of
    J bins, as the oracle calls it."""
    def sds(shape, dt):
        return jax.ShapeDtypeStruct(shape, dt, sharding=sharding)

    args = [sds((L, ROWS), jnp.int32), sds((m, L, ROWS), dtype),
            sds((L, ROWS), jnp.float32)]
    if dtype == jnp.int8:
        args.append(sds((m, 1, 1), jnp.float32))
    return jax.jit(
        lambda *a: _scatter(*a, destinations=destinations)).lower(*args)


def _primal_step(fn):
    def call(idx, coeff, cost, mask, lam, gamma, *scales):
        return fn(
            idx, coeff, cost, mask, lam, gamma, num_destinations=J,
            interpret=False,
            coeff_scale=scales[0] if scales else None,
            cost_scale=scales[1] if scales else None,
        )

    return call


CASES = [
    ("simplex", 8, 1, jnp.float32),
    ("simplex", 32, 1, jnp.float32),
    ("simplex", 16, 1, jnp.bfloat16),
    ("dual_primal", 8, 1, jnp.float32),
    ("dual_primal", 32, 1, jnp.float32),
    ("dual_primal", 16, 1, jnp.bfloat16),
    ("dual_primal", 16, 1, jnp.int8),
    ("dual_oracle", 8, 1, jnp.float32),
    ("dual_oracle", 32, 1, jnp.float32),
    ("dual_oracle", 16, 1, jnp.bfloat16),
    ("dual_oracle", 16, 1, jnp.int8),
    ("dual_oracle", 16, 3, jnp.float32),
    # the widest slabs the kernels take (ops.KERNEL_MAX_LENGTH)
    ("simplex", 512, 1, jnp.float32),
    ("dual_primal", 512, 1, jnp.float32),
    ("dual_oracle", 512, 1, jnp.float32),
    # the one-hot gather of A^T lam: every bucket width of the benchmark's
    # instances in each storage dtype, and three families
    *[("dual_gather", L, 1, dt) for dt in (jnp.float32, jnp.bfloat16, jnp.int8)
      for L in (1, 2, 4, 8, 16, 32)],
    *[("dual_gather", L, 3, dt) for dt in (jnp.float32, jnp.bfloat16, jnp.int8)
      for L in (1, 16)],
    # the one-hot scatter-add of A x, likewise
    *[("dual_scatter", L, 1, dt) for dt in (jnp.float32, jnp.bfloat16, jnp.int8)
      for L in (1, 2, 4, 8, 16, 32)],
    *[("dual_scatter", L, 3, dt) for dt in (jnp.float32, jnp.bfloat16, jnp.int8)
      for L in (1, 16)],
]


@pytest.mark.parametrize(
    "kernel,L,m,dtype", CASES,
    ids=[f"{k}-L{L}-m{m}-{jnp.dtype(d).name}" for k, L, m, d in CASES],
)
def test_kernel_compiles_for_v5e(one_chip, kernel, L, m, dtype):
    assert L <= kops.KERNEL_MAX_LENGTH
    if kernel == "simplex":
        v = jax.ShapeDtypeStruct((ROWS, L), dtype, sharding=one_chip)
        lowered = jax.jit(
            lambda v, mask: kops.fused_project_simplex(v, mask, interpret=False)
        ).lower(v, v)
    elif kernel == "dual_gather":
        lowered = _lower_gather(one_chip, L, m, dtype)
    elif kernel == "dual_scatter":
        lowered = _lower_scatter(one_chip, L, m, dtype)
    else:
        fn = kops.fused_dual_primal if kernel == "dual_primal" else (
            kops.fused_dual_oracle
        )
        lowered = jax.jit(_primal_step(fn)).lower(
            *_slab_args(one_chip, L, m, dtype)
        )
    compiled = lowered.compile()
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize(
    "kernel", ["dual_oracle", "simplex_proj", "dual_gather", "dual_scatter"])
def test_kernel_name_and_metadata_in_compiled_text(one_chip, kernel):
    """The kernels carry their names and metadata into the compiled op's
    HLO text, which is the op's name in a profiler trace, and the dual
    oracle's ops sit under the program's `oracle.*` scopes
    (docs/observability.md): on the chip the oracle's gather is the
    `dual_gather` kernel, under `oracle.gather/L<w>/onehot`, and its
    scatter-add the `dual_scatter` kernel, under `oracle.scatter/L<w>/onehot`
    (test_unfused_oracle_scatters_through_the_kernel)."""
    L = 16
    if kernel == "simplex_proj":
        v = jax.ShapeDtypeStruct((ROWS, L), jnp.float32, sharding=one_chip)
        lowered = jax.jit(
            lambda v, mask: kops.fused_project_simplex(v, mask, interpret=False)
        ).lower(v, v)
    elif kernel == "dual_gather":
        lowered = _lower_gather(one_chip, L, 1, jnp.float32)
    elif kernel == "dual_scatter":
        lowered = _lower_scatter(one_chip, L, 1, jnp.float32)
    else:
        lowered = jax.jit(_primal_step(kops.fused_dual_oracle)).lower(
            *_slab_args(one_chip, L, 1, jnp.float32)
        )
    text = lowered.compile().as_text()
    call = next(line for line in text.splitlines()
                if 'custom_call_target="tpu_custom_call"' in line
                and re.match(rf"\s*(ROOT )?%{kernel}\b", line))
    meta = re.search(r"kernel_metadata=\{(.*?)\}", text[text.index(call):], re.S)
    assert meta is not None
    assert f'"length":"{L}"' in meta.group(1)
    assert '"dtype":"float32"' in meta.group(1)
    if kernel != "simplex_proj":
        assert f'"destinations":"{J}"' in meta.group(1)
    if kernel == "dual_oracle":
        assert f"oracle.kernel/L{L}/dual_oracle" in text
        assert f"oracle.gather/L{L}/onehot/dual_gather" in text
        # no gather left to XLA, and none outside the oracle's scope
        gathers = re.findall(r" gather\(.*?op_name=\"([^\"]*)\"", text)
        assert all(f"oracle.gather/L{L}/" in n for n in gathers)
    if kernel == "dual_gather":
        assert "onehot/dual_gather" in text
        assert not re.findall(r" gather\(", text)
    if kernel == "dual_scatter":
        assert "onehot/dual_scatter" in text
        assert not re.findall(r" scatter\(", text)


@pytest.mark.parametrize("destinations,m,L", [
    (16_384, 3, 16), (16_384, 4, 1), (16_384, 4, 16), (10_000, 5, 16),
    (1000, 8, 16)])
def test_gather_compiles_at_its_vmem_limit(one_chip, destinations, m, L):
    """The most families `gather.fits_vmem_budget` lets the kernel take,
    at the widest fp32 tiles: the kernel compiles."""
    from repro.kernels.gather import fits_vmem_budget

    assert fits_vmem_budget(m, destinations)
    text = _lower_gather(one_chip, L, m, jnp.float32, destinations).compile(
    ).as_text()
    assert "tpu_custom_call" in text and not re.findall(r" gather\(", text)


@pytest.mark.parametrize("destinations,m,L", [
    (16_384, 3, 16), (16_384, 4, 1), (16_384, 4, 16), (10_000, 5, 16),
    (1000, 8, 16)])
def test_scatter_compiles_at_its_vmem_limit(one_chip, monkeypatch,
                                           destinations, m, L):
    """The most families `gather.fits_vmem_budget` lets the one-hot kernels
    take, at the widest fp32 tiles: the scatter kernel compiles too, within
    the budget that rule plans for (its scoped VMEM held to _VMEM_BUDGET)."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    from repro.kernels.gather import _VMEM_BUDGET, fits_vmem_budget

    assert fits_vmem_budget(m, destinations)
    pallas_call = pl.pallas_call
    monkeypatch.setattr(pl, "pallas_call", lambda *a, **k: pallas_call(
        *a, **k, compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=_VMEM_BUDGET)))
    text = _lower_scatter(one_chip, L, m, jnp.float32, destinations).compile(
    ).as_text()
    assert "tpu_custom_call" in text and not re.findall(r" scatter\(", text)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16, jnp.int8])
def test_unfused_oracle_scatters_through_the_kernel(one_chip, monkeypatch,
                                                    dtype):
    """The unfused oracle's `calculate` compiled for the chip: A x is the
    `dual_scatter` kernel in every bucket, under `oracle.scatter/L<w>/
    onehot`, and no XLA scatter is left under `oracle.scatter`; narrow
    slabs reach the kernel at their storage width.  The dispatch asks
    `jax.default_backend()`, which here is the CPU: the test answers for
    the chip it compiles for."""
    from repro.core import MatchingObjective
    from repro.instances.buckets import Bucket, BucketedInstance

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")

    def sds(shape, dt):
        return jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)

    widths = (1, 8, 16)
    scales = {}
    if dtype == jnp.int8:
        scales = dict(coeff_scale=sds((1, 1, 1), jnp.float32),
                      cost_scale=sds((1, 1), jnp.float32))
    inst = BucketedInstance(
        buckets=tuple(
            Bucket(idx=sds((ROWS, L), jnp.int32),
                   coeff=sds((1, ROWS, L), dtype),
                   cost=sds((ROWS, L), dtype),
                   mask=sds((ROWS, L), dtype), length=L, **scales)
            for L in widths),
        rhs=sds((J,), jnp.float32), num_sources=len(widths) * ROWS,
        num_destinations=J, num_families=1)

    def grad(inst, lam):
        return MatchingObjective(inst).calculate(lam, 0.1).grad

    text = jax.jit(grad).lower(inst, sds((J,), jnp.float32)).compile().as_text()
    for L in widths:
        assert f"oracle.scatter/L{L}/onehot/dual_scatter" in text
    calls = [line for line in text.splitlines()
             if 'custom_call_target="tpu_custom_call"' in line
             and re.match(r"\s*(ROOT )?%dual_scatter\b", line)]
    assert len(calls) == len(widths)
    for call in calls:
        meta = re.search(r"kernel_metadata=\{(.*?)\}",
                         text[text.index(call):], re.S)
        assert f'"dtype":"{jnp.dtype(dtype).name}"' in meta.group(1)
    scatters = re.findall(r" scatter\(.*?op_name=\"([^\"]*)\"", text)
    assert not [n for n in scatters if "oracle.scatter" in n]


@pytest.mark.parametrize("shared", [False, True], ids=["batched", "shared"])
@pytest.mark.parametrize("destinations", [1000, J])
def test_gather_compiles_under_vmap(one_chip, shared, destinations):
    """The service's batched pool vmaps the solve over tenants: the
    gather's pallas_call under jax.vmap, with each tenant's slabs or with
    one slab shared by the tenants, and each tenant's duals."""
    T, L, m, n = 4, 8, 1, ROWS // 4

    def sds(shape, dt):
        return jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)

    lead = () if shared else (T,)
    args = [sds(lead + (L, n), jnp.int32), sds(lead + (m, L, n), jnp.float32),
            sds((T, m, destinations), jnp.float32)]
    batched = jax.vmap(_gather, in_axes=(None if shared else 0,) * 2 + (0,))
    text = jax.jit(batched).lower(*args).compile().as_text()
    assert "tpu_custom_call" in text and not re.findall(r" gather\(", text)


def test_gather_compiles_inside_shard_map(one_chip, topo):
    """The sharded driver gathers inside shard_map (core/sharding.py:
    sources split over the mesh, duals replicated, check_vma off): the
    kernel compiles on every device of a v5e:2x2."""
    from jax.sharding import Mesh, NamedSharding
    from jax.sharding import PartitionSpec as P

    mesh = Mesh(np.asarray(topo.devices).reshape(-1), ("data",))
    L, m = 16, 1
    specs = (P(None, "data"), P(None, None, "data"), P())
    args = [jax.ShapeDtypeStruct(shape, dt, sharding=NamedSharding(mesh, spec))
            for shape, dt, spec in zip(
                [(L, ROWS), (m, L, ROWS), (m, J)],
                [jnp.int32, jnp.float32, jnp.float32], specs)]
    sharded = jax.shard_map(_gather, mesh=mesh, in_specs=specs,
                            out_specs=P(None, "data"), check_vma=False)
    compiled = jax.jit(sharded).lower(*args).compile()
    text = compiled.as_text()
    assert mesh.size == 4
    assert "tpu_custom_call" in text and not re.findall(r" gather\(", text)


@pytest.mark.parametrize("shared", [False, True], ids=["batched", "shared"])
def test_scatter_compiles_under_vmap(one_chip, shared):
    """The service's batched pool vmaps the solve over tenants (J = 10^3):
    the scatter's pallas_call under jax.vmap, with each tenant's slabs or
    one slab shared by the tenants, and each tenant's values."""
    T, L, m, n, destinations = 4, 8, 1, ROWS // 4, 1000

    def sds(shape, dt):
        return jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)

    lead = () if shared else (T,)
    args = [sds(lead + (L, n), jnp.int32), sds(lead + (m, L, n), jnp.float32),
            sds((T, L, n), jnp.float32)]
    batched = jax.vmap(
        lambda i, c, v: _scatter(i, c, v, destinations=destinations),
        in_axes=(None if shared else 0,) * 2 + (0,))
    text = jax.jit(batched).lower(*args).compile().as_text()
    assert "tpu_custom_call" in text and not re.findall(r" scatter\(", text)


def test_scatter_compiles_inside_shard_map(topo):
    """The sharded solve scatter-adds inside shard_map (sources split over
    the mesh, each chip's bins all-reduced): the kernel compiles on every
    device of a v5e:2x2."""
    from jax.sharding import Mesh, NamedSharding
    from jax.sharding import PartitionSpec as P

    mesh = Mesh(np.asarray(topo.devices).reshape(-1), ("data",))
    L, m = 16, 1
    specs = (P(None, "data"), P(None, None, "data"), P(None, "data"))
    args = [jax.ShapeDtypeStruct(shape, dt, sharding=NamedSharding(mesh, spec))
            for shape, dt, spec in zip(
                [(L, ROWS), (m, L, ROWS), (L, ROWS)],
                [jnp.int32, jnp.float32, jnp.float32], specs)]
    sharded = jax.shard_map(
        lambda i, c, v: jax.lax.psum(_scatter(i, c, v), "data"), mesh=mesh,
        in_specs=specs, out_specs=P(), check_vma=False)
    text = jax.jit(sharded).lower(*args).compile().as_text()
    assert mesh.size == 4
    assert "tpu_custom_call" in text and not re.findall(r" scatter\(", text)
    assert "all-reduce" in text
