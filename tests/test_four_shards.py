"""The column-sharded solve on four shards (paper §4.4), each check in a
subprocess with four forced host devices, at the chip benchmark's small size
(3000 sources, J = 60):

* the four-chip benchmark cell `matching-s8M-d10K.cold-xla` through its own
  harness comes out correct against the plain reference, and its bfloat16
  control does not;
* every all-reduce of the stage, power and final programs carries the
  `dist.psum` scope in its op name, and on a one-device mesh the scope
  changes nothing but metadata;
* `dist_allreduce_bytes_total` per solve is the payload the schedule sends,
  the same at two source counts (the paper's communication property).
"""
import json
import os

import pytest

from conftest import run_with_devices

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(ROOT, "benchmarks", "chip")
CELL = "matching-s8M-d10K.cold-xla"
J = 60  # destinations at the small size; one family

CELL_RUNS = r"""
import json, os, sys
os.environ["JAX_COMPILATION_CACHE_DIR"] = sys.argv[2]
sys.path.insert(0, sys.argv[1])
from chipbench import harness

small = {"config": {"num_sources": 3000, "num_destinations": %(J)d}}
out = {}
for control in ("none", "bf16"):
    r = harness.run_cell("%(cell)s", seed=2**31 + 7, seconds=1.0, trace=False,
                         control=control, require_tpu=False, overrides=small,
                         log=lambda *_: None)
    out[control] = {k: r[k] for k in ("correct", "attempted", "failed", "checks")}
    out[control]["count"] = r["device"]["count"]
print("RESULT:" + json.dumps(out))
"""

PROGRAMS = r"""
import json, re
import jax, jax.numpy as jnp
from repro.core import DistConfig, DistributedMaximizer, MaximizerConfig, sharding
from repro.instances.specs import solver_input_specs
from repro.launch.mesh import data_mesh

cfg = MaximizerConfig(gammas=(1.0, 0.1, 0.01), iters_per_stage=10, tol_grad=1e-4,
                      tol_viol=1e-4, check_every=10, power_iters=30)


def lowered(n):
    inst = solver_input_specs(3000, %(J)d, shard_multiple=n)
    dm = DistributedMaximizer(inst, data_mesh(n), cfg, DistConfig(axes="data"))
    lam = jax.ShapeDtypeStruct((inst.dual_dim,), jnp.float32)
    s = jax.ShapeDtypeStruct((), jnp.float32)
    sds = inst.shape_dtype_structs()
    with jax.set_mesh(dm.mesh):
        return {"stage": dm._stage_fn.lower(lam, s, s, sds),
                "power": dm._power_fn.lower(lam, sds),
                "final": dm._final_fn.lower(lam, s, sds)}


out = {"named": {}, "same_text": {}}
for name, low in lowered(4).items():
    hlo = low.compile().as_text()
    ops = [ln for ln in hlo.splitlines() if re.search(r"\ball-reduce(-start)?\(", ln)]
    out["named"][name] = [
        (re.search(r'op_name="([^"]*)"', ln) or [None, ""])[1] for ln in ops]
scoped = {k: v.as_text() for k, v in lowered(1).items()}
sharding.scoped_psum = lambda x, axes: jax.lax.psum(x, axes)
plain = {k: v.as_text() for k, v in lowered(1).items()}
for name in scoped:
    # without debug info the text holds no scope names: equal text is a
    # program that differs in metadata alone
    out["same_text"][name] = scoped[name] == plain[name]
print("RESULT:" + json.dumps(out))
"""

BYTES = r"""
import json
import numpy as np
from repro import telemetry
from repro.core import DistConfig, DistributedMaximizer, MaximizerConfig
from repro.core import normalize_rows
from repro.instances import MatchingInstanceSpec, bucketize, generate_matching_instance
from repro.launch.mesh import data_mesh

cfg = MaximizerConfig(gammas=(1.0, 0.1, 0.01), iters_per_stage=10, tol_grad=1e-4,
                      tol_viol=1e-4, check_every=10, power_iters=30)
out = {}
for sources in (3000, 6000):
    spec = MatchingInstanceSpec(num_sources=sources, num_destinations=%(J)d,
                                avg_degree=10.0, num_families=1, seed=5)
    inst, _ = normalize_rows(bucketize(generate_matching_instance(spec),
                                       shard_multiple=4))
    for mode, compress in (("psum", "none"), ("rank0", "none"), ("psum", "bf16")):
        telemetry.set_registry(telemetry.MetricsRegistry())
        dm = DistributedMaximizer(inst, data_mesh(4), cfg,
                                  DistConfig(axes="data", comm_mode=mode,
                                             compress=compress))
        dm.place()
        for _ in range(2):
            res = dm.solve()
        reg = telemetry.get_registry()
        out[f"{mode}-{compress}-{sources}"] = {
            "per_solve": reg.counter_value("dist_allreduce_bytes_total", shards=4)
                         / reg.counter_value("dist_solves_total", shards=4),
            "iters": list(res.iters_used),
        }
print("RESULT:" + json.dumps(out))
"""


def _run(script: str, *args) -> dict:
    code = script % {"J": J, "cell": CELL}
    if args:
        code = "import sys; sys.argv[1:] = %r\n" % (list(args),) + code
    return json.loads(run_with_devices(code, 4).split("RESULT:")[1])


@pytest.fixture(scope="module")
def cell_runs(tmp_path_factory):
    return _run(CELL_RUNS, BENCH, str(tmp_path_factory.mktemp("jax_cache")))


@pytest.fixture(scope="module")
def programs():
    return _run(PROGRAMS)


@pytest.fixture(scope="module")
def comm_bytes():
    return _run(BYTES)


def test_four_chip_cell_is_correct(cell_runs):
    r = cell_runs["none"]
    assert r["count"] == 4
    assert r["correct"], r["checks"]
    assert r["attempted"] > 0 and r["failed"] == 0
    assert r["checks"]["duals_rel_l2"]["value"] <= 1e-4


def test_four_chip_cell_bf16_control_is_not_correct(cell_runs):
    r = cell_runs["bf16"]
    assert not r["correct"], r["checks"]
    assert r["checks"]["duals_rel_l2"]["value"] > 1e-4


@pytest.mark.parametrize("program,count", [("stage", 2), ("power", 1), ("final", 1)])
def test_every_allreduce_is_named(programs, program, count):
    """The stage holds the gradient's all-reduce and the stop vote's; the
    power program one per step (in its scan); the final recovery one."""
    names = programs["named"][program]
    assert len(names) == count, names
    assert all("/dist.psum/" in n for n in names), names
    if program == "power":
        assert "power_iteration/" in names[0]


@pytest.mark.parametrize("program", ["stage", "power", "final"])
def test_one_device_programs_differ_in_metadata_alone(programs, program):
    assert programs["same_text"][program]


def _formula(mode: str, compress: str) -> int:
    """Payload per solve of the schedule: 30 iterations and the final
    recovery send m*J + 2 elements (bf16: 2 B; rank0: two hops), 30 power
    steps m*J fp32, 3 stop votes one int32."""
    width = 2 if compress == "bf16" else 4
    hops = 2 if mode == "rank0" else 1
    return 31 * (J + 2) * width * hops + 30 * J * 4 + 3 * 4


@pytest.mark.parametrize("mode,compress", [("psum", "none"), ("rank0", "none"),
                                           ("psum", "bf16")])
def test_allreduce_bytes_per_solve(comm_bytes, mode, compress):
    expected = _formula(mode, compress)
    assert mode != "psum" or compress != "none" or expected == 14900
    for sources in (3000, 6000):
        got = comm_bytes[f"{mode}-{compress}-{sources}"]
        assert got["iters"] == [10, 10, 10]
        assert got["per_solve"] == expected
