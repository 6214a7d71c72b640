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
    else:
        fn = kops.fused_dual_primal if kernel == "dual_primal" else (
            kops.fused_dual_oracle
        )
        lowered = jax.jit(_primal_step(fn)).lower(
            *_slab_args(one_chip, L, m, dtype)
        )
    compiled = lowered.compile()
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("kernel", ["dual_oracle", "simplex_proj"])
def test_kernel_name_and_metadata_in_compiled_text(one_chip, kernel):
    """The kernels carry their names and metadata into the compiled op's
    HLO text, which is the op's name in a profiler trace, and the dual
    oracle's ops sit under the program's `oracle.*` scopes
    (docs/observability.md)."""
    L = 16
    if kernel == "simplex_proj":
        v = jax.ShapeDtypeStruct((ROWS, L), jnp.float32, sharding=one_chip)
        lowered = jax.jit(
            lambda v, mask: kops.fused_project_simplex(v, mask, interpret=False)
        ).lower(v, v)
    else:
        lowered = jax.jit(_primal_step(kops.fused_dual_oracle)).lower(
            *_slab_args(one_chip, L, 1, jnp.float32)
        )
    text = lowered.compile().as_text()
    call = next(line for line in text.splitlines()
                if 'custom_call_target="tpu_custom_call"' in line)
    assert call.lstrip().startswith(f"%{kernel}")
    meta = re.search(r"kernel_metadata=\{(.*?)\}", text, re.S)
    assert meta is not None
    assert f'"length":"{L}"' in meta.group(1)
    assert '"dtype":"float32"' in meta.group(1)
    if kernel == "dual_oracle":
        assert f'"destinations":"{J}"' in meta.group(1)
        assert f"oracle.kernel/L{L}/dual_oracle" in text
        gathers = re.findall(r" gather\(.*?op_name=\"([^\"]*)\"", text)
        assert gathers
        assert all(f"oracle.gather/L{L}/" in n for n in gathers)
