"""`reference/matching-wide.py` solves the same LP as `reference/matching.py`:
its table has no row narrower than 16 slots, and at a small size on the CPU
both give the same duals.  The four-chip cell that names it needs four
devices in `test_runs.py`:

    XLA_FLAGS=--xla_force_host_platform_device_count=4 JAX_PLATFORMS=cpu \\
        PYTHONPATH=src python -m pytest benchmarks/chip/tests
"""
import numpy as np
import pytest

from chipbench import harness


@pytest.mark.parametrize("sources,destinations", [(3000, 60), (20000, 400)])
def test_wide_table_gives_the_narrow_solve(sources, destinations):
    drv = harness.load_module("drivers", "solve")
    cfg = dict(harness.cell("matching-s8M-d10K.cold-xla")["config"],
               num_sources=sources, num_destinations=destinations)
    el = drv.edge_list(cfg, 2**31 + 11)
    out = {}
    for name in ("matching", "matching-wide"):
        ref = harness.load_module("reference", name)
        t = ref.table(el.src, el.dst, el.values, el.coeff, el.rhs,
                      el.num_destinations)
        out[name] = (t, ref.solve(t, ref.Schedule(**drv.schedule(cfg))))
    narrow, wide = out["matching"], out["matching-wide"]
    widths = [b[0].shape[1] for b in wide[0].blocks]
    assert min(widths) == 16 and min(b[0].shape[1] for b in narrow[0].blocks) < 16
    # every valid slot is kept, and the padding holds nothing
    def mass(t, i):
        return sum(float(np.abs(np.asarray(b[i])).sum()) for b in t.blocks)

    for i in (1, 2):  # coefficients, costs
        assert mass(wide[0], i) == pytest.approx(mass(narrow[0], i))
    assert sum(int(b[3].sum()) for b in wide[0].blocks) == el.nnz
    lam = narrow[1]["lam"]
    np.testing.assert_allclose(wide[1]["lam"], lam, rtol=0,
                               atol=1e-6 * np.abs(lam).max())
    assert wide[1]["iters"] == narrow[1]["iters"]
