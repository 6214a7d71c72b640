"""The one-hot scatter-add of A x (kernels/scatter.py) in interpret mode.

The kernel's bins agree with `binned_segment_sum`'s to fp32 rounding on
random data (the two sum in different orders), and bit for bit where every
contribution and partial sum is exact; slot rows and source lanes past the
end of the slab, whatever they hold, add nothing; it runs under vmap and
inside shard_map; and `dual_scatter` takes it only where
`onehot_takes_kernel` says so.  Off the kernel, `MatchingObjective.apply_A`
is the segment sum it was, bit for bit.
"""
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.objective import binned_segment_sum
from repro.kernels import ops as kops
from repro.kernels import scatter as kscatter
from repro.kernels.gather import table_rows


def _bits(x):
    return np.asarray(x).view(np.uint32)


def _slab(rng, m, L, n, J, dtype, exact=False):
    """idx [L, n] (with 0 and J - 1 among them), coeff [m, L, n] in
    `dtype` (int8 with scales [m, 1, 1]) and v [L, n] fp32.  `exact`:
    coefficients ±2^0..2^6 (int8 scales 1, 2 or 4) and v small integers,
    so every contribution and every partial sum is an integer below 2^24,
    exact in fp32 in any order."""
    idx = rng.integers(0, J, size=(L, n)).astype(np.int32)
    idx.reshape(-1)[:4] = [0, J - 1, 0, J - 1]
    scale = None
    if exact:
        val = 2 ** rng.integers(0, 7, size=(m, L, n)) * rng.choice(
            [-1, 1], size=(m, L, n))
        v = rng.integers(0, 16, size=(L, n)).astype(np.float32)
        if dtype == "int8":
            scale = 2.0 ** rng.integers(0, 3, size=m)
            coeff = jnp.asarray(val, jnp.int8)
        else:
            coeff = jnp.asarray(val, jnp.float32).astype(dtype)
    else:
        v = rng.normal(size=(L, n)).astype(np.float32)
        if dtype == "int8":
            coeff = jnp.asarray(rng.integers(-127, 128, size=(m, L, n)), jnp.int8)
            scale = rng.random(m) + 0.5
        else:
            coeff = jnp.asarray(rng.normal(size=(m, L, n)), jnp.float32).astype(
                dtype)
    if scale is not None:
        scale = jnp.asarray(scale, jnp.float32).reshape(m, 1, 1)
    return jnp.asarray(idx), coeff, jnp.asarray(v), scale


def _segment_sum(idx, coeff, v, J, scale=None):
    """binned_segment_sum of the same contributions (dual_scatter off the
    kernel)."""
    return kops.dual_scatter(idx, coeff, v, J, coeff_scale=scale,
                             interpret=None)


def _reference(idx, coeff, v, J, scale=None):
    """The bins in float64: [m, J]."""
    c = np.asarray(coeff.astype(jnp.float32), np.float64)
    if scale is not None:
        c = c * np.asarray(scale, np.float64).reshape(-1, 1, 1)
    out = np.zeros((c.shape[0], J))
    for k in range(c.shape[0]):
        np.add.at(out[k], np.asarray(idx).ravel(),
                  (c[k] * np.asarray(v, np.float64)).ravel())
    return out


def _assert_close(got, idx, coeff, v, J, scale=None):
    """Within 1e-6 of the largest bin, of the float64 bins and of
    binned_segment_sum's."""
    assert got.shape == (coeff.shape[0], J) and got.dtype == jnp.float32
    got = np.asarray(got, np.float64)
    ref = _reference(idx, coeff, v, J, scale)
    tol = 1e-6 * np.abs(ref).max()
    np.testing.assert_allclose(got, ref, rtol=0, atol=tol)
    np.testing.assert_allclose(
        got, np.asarray(_segment_sum(idx, coeff, v, J, scale), np.float64),
        rtol=0, atol=tol)


@pytest.mark.parametrize("L", [1, 2, 4, 8, 16, 32])
@pytest.mark.parametrize("m", [1, 3])
def test_matches_segment_sum(L, m):
    """Random fp32 data at J = 10^4: one 384-source block, its last 84
    lanes past the slab."""
    J, n = 10_000, 300
    rng = np.random.default_rng(L * 7 + m)
    idx, coeff, v, _ = _slab(rng, m, L, n, J, "float32")
    got = kops.dual_scatter(idx, coeff, v, J, interpret=True)
    _assert_close(got, idx, coeff, v, J)


@pytest.mark.parametrize("dtype", ["float32", "bfloat16", "int8"])
@pytest.mark.parametrize("m,J", [(1, 10_000), (3, 16_384), (3, 1000)])
@pytest.mark.parametrize("L", [4, 16])
def test_storage_dtypes(dtype, m, J, L):
    rng = np.random.default_rng(m + J + L)
    idx, coeff, v, scale = _slab(rng, m, L, 257, J, dtype)
    got = kops.dual_scatter(idx, coeff, v, J, coeff_scale=scale,
                            interpret=True)
    _assert_close(got, idx, coeff, v, J, scale)


@pytest.mark.parametrize("dtype", ["float32", "bfloat16", "int8"])
@pytest.mark.parametrize("L,m,J", [(1, 1, 10_000), (8, 3, 1000),
                                   (16, 1, 16_384), (32, 2, 10_000)])
def test_bit_exact_where_sums_are_exact(dtype, L, m, J):
    """Contributions ±2^k · (0..15) and their sums are integers below 2^24,
    so any order of summation gives the same bits."""
    rng = np.random.default_rng(L + m + J)
    idx, coeff, v, scale = _slab(rng, m, L, 300, J, dtype, exact=True)
    got = kops.dual_scatter(idx, coeff, v, J, coeff_scale=scale,
                            interpret=True)
    want = _segment_sum(idx, coeff, v, J, scale)
    np.testing.assert_array_equal(_bits(got), _bits(want))
    np.testing.assert_array_equal(np.asarray(got),
                                  _reference(idx, coeff, v, J, scale))


@pytest.mark.parametrize("J", [1000, 10_000, 16_383])
def test_first_and_last_bins(J):
    """Indices 0 and J - 1 alone, J not a multiple of 128: bin 0 takes
    the even slots' contributions and bin J - 1 the odd slots'; the
    bins past J in the table's last row are dropped."""
    L, n = 8, 130
    idx = jnp.asarray(np.where(np.arange(L * n).reshape(L, n) % 2, J - 1, 0),
                      jnp.int32)
    v = jnp.asarray(np.arange(L * n).reshape(L, n) % 7, jnp.float32)
    coeff = jnp.full((1, L, n), 0.5, jnp.float32)
    got = np.asarray(kops.dual_scatter(idx, coeff, v, J, interpret=True))
    want = _reference(idx, coeff, v, J)
    assert J % 128 and got.shape == (1, J)
    np.testing.assert_array_equal(got, want)
    assert got[0, 0] > 0 and got[0, J - 1] > 0 and not got[0, 1:J - 1].any()


@pytest.mark.parametrize("L,rows,n,block", [
    (1, 1, 300, 128), (8, 8, 300, 128), (16, 16, 700, 512), (32, 32, 129, 128),
    (12, 16, 300, 128), (24, 32, 200, 128)])
@pytest.mark.parametrize("m", [1, 3])
def test_past_the_slab_adds_nothing(L, rows, n, block, m):
    """The slab handed to the call is `rows` x whole blocks, wider than the
    `L` x `n` it declares: past them, v and the coefficients hold NaN and
    the indices lie outside [0, J).  Blocks of 128 or 512 sources end the
    slab inside its last grid step, and L = 12, 24 inside a row tile."""
    J = 10_000
    rng = np.random.default_rng(L + n + m)
    width = -(-n // block) * block
    idx, coeff, v, _ = _slab(rng, m, L, n, J, "float32")
    pad = ((0, rows - L), (0, width - n))
    idx_p = jnp.pad(idx, pad, constant_values=J + 77)
    idx_p = idx_p.at[L:, :].set(-5)
    v_p = jnp.pad(v, pad, constant_values=np.nan)
    coeff_p = jnp.pad(coeff, ((0, 0),) + pad, constant_values=np.nan)
    table = kscatter.make_scatter_call(n, L, m, J, block, jnp.float32)(
        idx_p, coeff_p, v_p)
    assert table.shape == (m * table_rows(J), 128)
    got = table.reshape(m, -1)[:, :J]
    assert np.isfinite(np.asarray(table)).all()
    _assert_close(got, idx, coeff, v, J)


def test_out_of_range_indices_add_nothing():
    """Inside the slab, an index below 0 or past the table selects no
    row; one in [J, R·128) lands in a padding bin that is cut off."""
    J, L, n = 1000, 8, 256
    rng = np.random.default_rng(9)
    idx, coeff, v, _ = _slab(rng, 1, L, n, J, "float32")
    bad = jnp.asarray(rng.choice([-1, -128, J, J + 23, 1 << 20], size=(L, n)),
                      jnp.int32)
    keep = jnp.asarray(rng.random((L, n)) < 0.5)
    got = kops.dual_scatter(jnp.where(keep, idx, bad), coeff, v, J,
                            interpret=True)
    _assert_close(got, idx, coeff, jnp.where(keep, v, 0.0), J)


def test_non_finite_poisons_its_row_of_bins():
    """An infinite contribution makes its index's row of 128 bins NaN,
    and no other bin non-finite."""
    J, L, n = 1000, 8, 256
    rng = np.random.default_rng(4)
    idx, coeff, v, _ = _slab(rng, 1, L, n, J, "float32")
    idx = idx.at[3, 7].set(300)
    v = v.at[3, 7].set(np.inf)
    got = np.asarray(kops.dual_scatter(idx, coeff, v, J, interpret=True))[0]
    assert np.isnan(got[256:384]).all()
    assert np.isfinite(np.delete(got, np.arange(256, 384))).all()
    assert not np.isfinite(np.asarray(_segment_sum(idx, coeff, v, J))).all()


def test_split_is_exact():
    """p0 + p1 + p2 is c, and each piece is a bf16 value, for every c
    whose residuals are normal numbers and that bf16 can round to a
    finite value; below, only a subnormal residual is lost; above,
    the pieces are not finite."""
    rng = np.random.default_rng(2)
    c = jnp.asarray(np.concatenate([
        rng.normal(size=4096) * 10.0 ** rng.integers(-25, 30, size=4096),
        [0.0, -0.0, 1.0, -3.0, 3.3e38, -3.3e38]]), jnp.float32)
    pieces = kscatter.split_bf16(c)
    for p in pieces:
        np.testing.assert_array_equal(
            np.asarray(p), np.asarray(p.astype(jnp.bfloat16).astype(jnp.float32)))
    total = sum(np.asarray(p, np.float64) for p in pieces)
    np.testing.assert_array_equal(total, np.asarray(c, np.float64))
    tiny = np.asarray(sum(np.asarray(p, np.float64) for p in kscatter.split_bf16(
        jnp.asarray([1.2e-38, -3e-38], jnp.float32))))
    assert np.all(np.abs(tiny - np.asarray([1.2e-38, -3e-38], np.float32)) < 2.0**-133)
    huge = kscatter.split_bf16(jnp.asarray([np.finfo(np.float32).max], jnp.float32))
    assert not np.isfinite(np.asarray(sum(huge))).all()


def test_under_vmap():
    """The service pool vmaps the solve over tenants: batched slabs, and
    one shared slab with each tenant's values."""
    rng = np.random.default_rng(3)
    T, L, n, m, J = 3, 8, 200, 1, 1000
    slabs = [_slab(rng, m, L, n, J, "float32") for _ in range(T)]
    idx, coeff, v = (jnp.stack([s[i] for s in slabs]) for i in range(3))

    def ax(i, c, x):
        return kops.dual_scatter(i, c, x, J, interpret=True)

    got = jax.vmap(ax)(idx, coeff, v)
    shared = jax.vmap(ax, in_axes=(None, None, 0))(idx[0], coeff[0], v)
    for t in range(T):
        _assert_close(got[t], idx[t], coeff[t], v[t], J)
        _assert_close(shared[t], idx[0], coeff[0], v[t], J)


def test_inside_shard_map():
    """The sharded solve scatters inside shard_map: sources split over two
    virtual CPU devices, each shard's bins all-reduced."""
    from conftest import run_with_devices

    out = run_with_devices(textwrap.dedent("""
        import jax, jax.numpy as jnp, numpy as np
        from jax.sharding import PartitionSpec as P
        from repro.kernels import ops as kops

        rng = np.random.default_rng(5)
        L, n, m, J = 16, 512, 1, 10_000
        idx = jnp.asarray(rng.integers(0, J, size=(L, n)), jnp.int32)
        coeff = jnp.asarray(rng.normal(size=(m, L, n)), jnp.float32)
        v = jnp.asarray(rng.normal(size=(L, n)), jnp.float32)
        mesh = jax.make_mesh((2,), ("data",))
        f = jax.shard_map(
            lambda i, c, x: jax.lax.psum(
                kops.dual_scatter(i, c, x, J, interpret=True), "data"),
            mesh=mesh, in_specs=(P(None, "data"), P(None, None, "data"),
                                 P(None, "data")),
            out_specs=P(), check_vma=False)  # as core.sharding
        got = np.asarray(jax.jit(f)(idx, coeff, v), np.float64)
        want = np.asarray(kops.dual_scatter(idx, coeff, v, J), np.float64)
        assert np.abs(got - want).max() <= 1e-6 * np.abs(want).max()
        print("SHARDED OK")
    """), n_devices=2)
    assert "SHARDED OK" in out


def _takes_kernel(J, m, interpret, dtype=jnp.float32, scale=None,
                  v_dtype=jnp.float32):
    idx = jnp.zeros((8, 256), jnp.int32)
    coeff = jnp.ones((m, 8, 256), dtype)
    v = jnp.ones((8, 256), v_dtype)
    jaxpr = jax.make_jaxpr(
        lambda i, c, x: kops.dual_scatter(i, c, x, J, coeff_scale=scale,
                                          interpret=interpret)
    )(idx, coeff, v)
    return "pallas_call" in str(jaxpr)


@pytest.mark.parametrize("J,m,interpret,kernel", [
    (16_384, 1, True, True),
    (16_385, 1, True, False),  # R = 129 rows of 128 bins: XLA's segment sum
    (100_000, 1, True, False),
    (10_000, 1, None, False),  # off-TPU: XLA's segment sum
    # the kernels' VMEM grows with m·R (gather.fits_vmem_budget)
    (16_384, 4, True, True),
    (16_384, 5, True, False),
    (10_000, 5, True, True),
    (10_000, 6, True, False),
    (1000, 8, True, True),
    (1000, 9, True, False),
])
def test_dispatch_rule(J, m, interpret, kernel):
    """One rule for the gather and the scatter."""
    assert jax.default_backend() != "tpu"
    assert kops.onehot_takes_kernel(J, m, jnp.float32, False, interpret) == kernel
    assert _takes_kernel(J, m, interpret) == kernel


def test_dispatch_dtypes():
    scale = jnp.ones((1, 1, 1), jnp.float32)
    assert _takes_kernel(10_000, 1, True, jnp.bfloat16)
    assert _takes_kernel(10_000, 1, True, jnp.int8, scale)
    assert not _takes_kernel(10_000, 1, True, jnp.int8)  # needs its scales
    assert not _takes_kernel(10_000, 1, True, jnp.float16)
    # values that are not fp32 take XLA's segment sum
    assert not _takes_kernel(10_000, 1, True, v_dtype=jnp.bfloat16)


@pytest.mark.parametrize("x_dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("slab_dtype", ["float32", "bfloat16", "int8"])
def test_apply_A_off_the_kernel_is_the_segment_sum(slab_dtype, x_dtype):
    """On the CPU, A x is `binned_segment_sum` of coeff · x · mask on the
    dequantized [n, L] slabs, bit for bit, as before the kernel, though
    `apply_A` now hands the stored slabs and their scales on."""
    from repro.core import MatchingObjective
    from repro.instances import (
        MatchingInstanceSpec, bucketize, generate_matching_instance)
    from repro.instances.buckets import dequantize_bucket

    spec = MatchingInstanceSpec(num_sources=600, num_destinations=40,
                                avg_degree=6.0, num_families=2, seed=1)
    inst = bucketize(generate_matching_instance(spec), dtype=slab_dtype)
    J = inst.num_destinations
    rng = np.random.default_rng(0)
    x_slabs = tuple(jnp.asarray(rng.random(b.mask.shape), x_dtype)
                    for b in inst.buckets)

    def segment_sum_ax(x_slabs):
        ax = jnp.zeros((inst.num_families, J), jnp.float32)
        for b, x in zip(inst.buckets, x_slabs):
            b = dequantize_bucket(b)
            ax = ax + binned_segment_sum(b.idx, b.coeff * (x * b.mask)[None], J)
        return ax.reshape(-1)

    got = jax.jit(MatchingObjective(inst).apply_A)(x_slabs)
    want = jax.jit(segment_sum_ax)(x_slabs)
    np.testing.assert_array_equal(_bits(got), _bits(want))
