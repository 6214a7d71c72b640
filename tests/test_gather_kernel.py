"""The one-hot gather of A^T lam (kernels/gather.py) in interpret mode.

The kernel's gathered duals lam_k[idx] are `jnp.take`'s bit for bit (0,
-0.0, negatives, the smallest normals, inf and NaN among them; subnormals
flush to signed zeros in the product, as in XLA's), read through its
coefficient multiply with one family's coefficients at 1; its A^T lam is
`gather_atl`'s bit for bit at m = 1 and, with coefficients whose products
with the duals are exact, at m = 3; and `dual_gather` takes it only where
`onehot_takes_kernel` says so.
"""
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.objective import gather_atl
from repro.kernels import gather as kgather
from repro.kernels import ops as kops

# 1.2e-38 and -1.5e-38 are the smallest normals, whose top byte (0x00,
# 0x80) only they and the subnormals have
SPECIAL = np.array(
    [0.0, -0.0, -1.5, np.inf, -np.inf, np.nan, 1e-40, -3e-39, 1.4e-45,
     1.2e-38, -1.5e-38],
    np.float32,
)


def _duals(rng, m, J):
    lam = rng.normal(size=(m, J)).astype(np.float32)
    for k in range(m):
        lam[k, : SPECIAL.size] = SPECIAL
        lam[k, J - 1] = -np.float32(1e-38) / 3  # subnormal in the last row
    return jnp.asarray(lam)


def _indices(rng, L, n, J):
    idx = rng.integers(0, J, size=(L, n)).astype(np.int32)
    idx.reshape(-1)[: SPECIAL.size + 1] = [*range(SPECIAL.size), J - 1]
    return jnp.asarray(idx)


def _coeff(rng, m, L, n, dtype, exact=False):
    """Coefficients [m, L, n] in `dtype` (int8 with scales [m, 1, 1]).
    `exact`: powers of two from 1 to 64 with random signs (int8 scales 1,
    2 or 4), whose products with fp32 duals round nowhere, so a fused
    multiply-add and a multiply then add give the same bits."""
    if exact:
        mag = 2 ** rng.integers(0, 7, size=(m, L, n))
        val = mag * rng.choice([-1, 1], size=(m, L, n))
        if dtype == "int8":
            scale = 2.0 ** rng.integers(0, 3, size=m)
            return (jnp.asarray(val, jnp.int8),
                    jnp.asarray(scale, jnp.float32).reshape(m, 1, 1))
        return jnp.asarray(val, jnp.float32).astype(dtype), None
    if dtype == "int8":
        coeff = jnp.asarray(rng.integers(-127, 128, size=(m, L, n)), jnp.int8)
        return coeff, jnp.asarray(rng.random(m) + 0.5, jnp.float32).reshape(m, 1, 1)
    coeff = rng.normal(size=(m, L, n)).astype(np.float32)
    return jnp.asarray(coeff).astype(dtype), None


def _bits(x):
    return np.asarray(x).view(np.uint32)


def _assert_atl(got, idx, coeff, lam2, scale=None):
    """`got` is gather_atl's A^T lam bit for bit."""
    c = coeff.astype(jnp.float32)
    if scale is not None:
        c = c * scale.reshape(-1, 1, 1)
    np.testing.assert_array_equal(_bits(got), _bits(gather_atl(idx, c, lam2)))


@pytest.mark.parametrize("L", [1, 2, 4, 8, 16, 32])
@pytest.mark.parametrize("m,J", [(1, 10_000), (3, 1000), (1, 16_384)])
def test_gathered_duals_bit_exact(L, m, J):
    """lam_k[idx] through the coefficient multiply: family k's coefficients
    1, the others' 0 against negative finite duals, so their terms are
    -0.0 and leave the sum's bits alone.  Each family in turn, so each
    family's rows of the table are read back.  A subnormal dual times 1
    is a zero of its sign, in XLA on the CPU as on the TPU (both treat
    subnormal operands as zero): that is gather_atl's result, and every
    other dual comes back as jnp.take's bits."""
    rng = np.random.default_rng(L * 7 + m + J)
    n = 300  # one 384-source block, its last 84 lanes past the slab
    idx = _indices(rng, L, n, J)
    for k in range(m):
        lam = -1.0 - np.abs(rng.normal(size=(m, J))).astype(np.float32)
        lam[k] = np.asarray(_duals(rng, 1, J))[0]
        lam2 = jnp.asarray(lam)
        coeff = jnp.zeros((m, L, n), jnp.float32).at[k].set(1.0)
        got = kops.onehot_gather(idx, coeff, lam2, interpret=True)
        assert got.shape == (L, n) and got.dtype == jnp.float32
        _assert_atl(got, idx, coeff, lam2)
        want = np.asarray(jnp.take(lam2[k], idx))
        normal = (want == 0) | ~(np.abs(want) < np.finfo(np.float32).tiny)
        np.testing.assert_array_equal(_bits(got)[normal], _bits(want)[normal])
        assert (~normal).any() and np.all(np.asarray(got)[~normal] == 0)


@pytest.mark.parametrize("L,n,block", [
    (1, 300, 128), (8, 300, 128), (16, 700, 512), (32, 129, 128)])
@pytest.mark.parametrize("m", [1, 3])
def test_partial_last_grid_step(L, n, block, m):
    """Blocks of 128 or 512 sources, so the slab ends inside its last grid
    step."""
    J = 10_000
    rng = np.random.default_rng(L + n + m)
    lam2, idx = _duals(rng, m, J), _indices(rng, L, n, J)
    coeff, _ = _coeff(rng, m, L, n, jnp.float32, exact=m > 1)
    atl = kgather.make_gather_call(n, L, m, J, block, jnp.float32)(
        *kgather.dual_table(lam2), idx, coeff)
    _assert_atl(atl, idx, coeff, lam2)


@pytest.mark.parametrize("dtype", ["float32", "bfloat16", "int8"])
@pytest.mark.parametrize("m,J", [(1, 10_000), (3, 16_384), (3, 1000)])
@pytest.mark.parametrize("L", [4, 16])
def test_atl_matches_gather_atl(dtype, m, J, L):
    """Bit for bit: at m = 1 with any coefficients; at m = 3 with
    coefficients whose products round nowhere (`_coeff(exact=True)`), so
    that the families' sum, which rounds, is compared in gather_atl's
    order.  XLA on the CPU fuses a product into the sum that follows it
    (in interpret mode too), which with general coefficients moves the
    sum by up to ~1.5 ulps of sum_k |coeff_k · lam_k[idx]|."""
    rng = np.random.default_rng(m + J + L)
    n = 257
    lam2, idx = _duals(rng, m, J), _indices(rng, L, n, J)
    coeff, scale = _coeff(rng, m, L, n, dtype, exact=m > 1)
    got = kops.dual_gather(idx, coeff, lam2, coeff_scale=scale, interpret=True)
    assert got.dtype == jnp.float32
    _assert_atl(got, idx, coeff, lam2, scale)


def test_under_vmap():
    """The service pool vmaps the solve over tenants: batched duals over
    batched slabs, and batched duals over one shared slab."""
    rng = np.random.default_rng(3)
    T, L, n, m, J = 3, 8, 200, 1, 10_000
    lam = jnp.stack([_duals(rng, m, J) for _ in range(T)])
    idx = jnp.stack([_indices(rng, L, n, J) for _ in range(T)])
    coeff = jnp.asarray(rng.normal(size=(T, m, L, n)), jnp.float32)

    def atl(i, c, l):
        return kops.dual_gather(i, c, l, interpret=True)

    got = jax.vmap(atl)(idx, coeff, lam)
    shared = jax.vmap(atl, in_axes=(None, None, 0))(idx[0], coeff[0], lam)
    for t in range(T):
        want = gather_atl(idx[t], coeff[t], lam[t])
        np.testing.assert_array_equal(_bits(got[t]), _bits(want))
        np.testing.assert_array_equal(
            _bits(shared[t]), _bits(gather_atl(idx[0], coeff[0], lam[t])))


def test_inside_shard_map():
    """The sharded driver gathers inside shard_map: sources split over two
    virtual CPU devices, duals replicated."""
    from conftest import run_with_devices

    out = run_with_devices(textwrap.dedent("""
        import jax, jax.numpy as jnp, numpy as np
        from jax.sharding import PartitionSpec as P
        from repro.core.objective import gather_atl
        from repro.kernels import ops as kops

        rng = np.random.default_rng(5)
        L, n, m, J = 16, 512, 1, 10_000
        idx = jnp.asarray(rng.integers(0, J, size=(L, n)), jnp.int32)
        coeff = jnp.asarray(rng.normal(size=(m, L, n)), jnp.float32)
        lam = jnp.asarray(rng.normal(size=(m, J)), jnp.float32)
        mesh = jax.make_mesh((2,), ("data",))
        f = jax.shard_map(
            lambda i, c, l: kops.dual_gather(i, c, l, interpret=True),
            mesh=mesh, in_specs=(P(None, "data"), P(None, None, "data"), P()),
            out_specs=P(None, "data"), check_vma=False)  # as core.sharding
        got = jax.jit(f)(idx, coeff, lam)
        want = gather_atl(idx, coeff, lam)
        assert np.array_equal(np.asarray(got).view(np.uint32),
                              np.asarray(want).view(np.uint32))
        print("SHARDED OK")
    """), n_devices=2)
    assert "SHARDED OK" in out


def _takes_kernel(J, m, interpret):
    idx = jnp.zeros((8, 256), jnp.int32)
    coeff = jnp.ones((m, 8, 256), jnp.float32)
    lam = jnp.ones((m, J), jnp.float32)
    jaxpr = jax.make_jaxpr(
        lambda i, c, l: kops.dual_gather(i, c, l, interpret=interpret)
    )(idx, coeff, lam)
    return "pallas_call" in str(jaxpr)


@pytest.mark.parametrize("J,m,interpret,kernel", [
    (16_384, 1, True, True),
    (16_385, 1, True, False),  # R = 129 rows of 128 duals: XLA's gather
    (100_000, 1, True, False),
    (10_000, 1, None, False),  # off-TPU: XLA's gather
    # the kernel's VMEM grows with m·R (gather.fits_vmem_budget)
    (16_384, 4, True, True),
    (16_384, 5, True, False),
    (10_000, 5, True, True),
    (10_000, 6, True, False),
    (1000, 8, True, True),
    (1000, 9, True, False),
])
def test_dispatch_rule(J, m, interpret, kernel):
    assert jax.default_backend() != "tpu"
    assert kops.onehot_takes_kernel(J, m, jnp.float32, False, interpret) == kernel
    assert _takes_kernel(J, m, interpret) == kernel


def test_dispatch_dtypes():
    def takes(dtype, quantized):
        return kops.onehot_takes_kernel(10_000, 1, dtype, quantized, True)

    assert takes(jnp.bfloat16, False)
    assert takes(jnp.int8, True)
    assert not takes(jnp.int8, False)  # int8 needs its scales
    assert not takes(jnp.float16, False)
    # duals that are not fp32 take XLA's gather
    idx = jnp.zeros((8, 256), jnp.int32)
    coeff = jnp.ones((1, 8, 256), jnp.float32)
    jaxpr = jax.make_jaxpr(
        lambda l: kops.dual_gather(idx, coeff, l, interpret=True)
    )(jnp.ones((1, 100), jnp.bfloat16))
    assert "pallas_call" not in str(jaxpr)
