"""The collective layer's readers, `psum_exposed_ms` and `allreduce_bytes`,
on a synthetic four-chip trace (tests/data/psum_trace.pbtxt, and its binary
encoding psum_trace.xplane.pb) whose numbers are worked out by hand here.

In the 100 ms window from 1 ms to 101 ms, own device times (ms) on each of
chips 0-3: jit_stage_fn (program 8): the while 32 on chip 0 (60 less its
body), the scatter-add 20, the gradient's all-reduce under `dist.psum` 4, 6,
8 and 2 on chips 0-3, the stop vote's under `dist.psum` 1, an all-reduce
outside the scope 3; jit_power_fn (program 15): the while 16 (28 less 12),
the scatter-add 10, the power step's all-reduce under
`power_iteration/.../dist.psum` 2.  So `dist.psum` holds 7, 9, 11 and 5 ms
on chips 0-3, 8 ms averaged over the chips (11 if the unscoped all-reduce
counted), and `power_iteration` 28 ms on every chip, the power step's
all-reduce among them.  Busy 88 ms per chip.

The cell that lists these metrics runs on four chips, so the small runs of
`test_runs.py` need four devices on the CPU too:

    XLA_FLAGS=--xla_force_host_platform_device_count=4 JAX_PLATFORMS=cpu \\
        PYTHONPATH=src python -m pytest benchmarks/chip/tests
"""
import os
import shutil

import jax
import pytest

from chipbench import scopes
from chipbench import trace as tr
from chipbench.harness import load_module

HERE = os.path.dirname(os.path.abspath(__file__))
DATA = os.path.join(HERE, "data")


def _trace_dir(tmp_path, monkeypatch, name, chips):
    """The synthetic trace `name` where a traced run leaves its profile,
    and its reduction over `chips` chips."""
    run = tmp_path / name / "plugins" / "profile" / "run"
    run.mkdir(parents=True)
    src = os.path.join(DATA, f"{name}.xplane.pb")
    shutil.copy(src, run / "host.xplane.pb")
    monkeypatch.setattr(scopes, "TRACE_DIR", str(tmp_path / name))
    with open(src, "rb") as f:
        return tr.from_profile(
            jax.profiler.ProfileData.from_serialized_xspace(f.read()), chips)


def rec(trace, chips=4, **run):
    return {"trace": trace, "run": run, "chips": chips, "peaks": {},
            "config": {"work": "matching"}, "traffic": {}}


def test_psum_scope_per_chip(tmp_path, monkeypatch):
    trace = _trace_dir(tmp_path, monkeypatch, "psum_trace", 4)
    paths = scopes.tf_ops(chips=4)
    # 8 ops on each of 4 planes, under two programs
    assert len(paths) == 8
    # chip 0 alone
    assert scopes.scope_seconds(trace, paths, "dist.psum", 1) == pytest.approx(0.007)
    secs = {s: scopes.scope_seconds(trace, paths, s, 4)
            for s in ("dist.psum", "power_iteration", "oracle.scatter")}
    assert secs == pytest.approx({"dist.psum": 0.008, "power_iteration": 0.028,
                                  "oracle.scatter": 0.030})
    assert [tr.busy_ns(trace, c) * 1e-9 for c in range(4)] == pytest.approx([0.088] * 4)


def test_psum_exposed_ms(tmp_path, monkeypatch):
    read = load_module("metrics", "psum_exposed_ms").read
    trace = _trace_dir(tmp_path, monkeypatch, "psum_trace", 4)
    # averaged over the chips, over the window's two solves, in ms; the
    # all-reduce outside the scope does not count
    assert read(rec(trace, driver="solve", solves=2)) == pytest.approx(4.0)
    assert read(rec(trace, driver="solve", solves=1)) == pytest.approx(8.0)
    # chip 0 alone: 7 ms over two solves
    one = _trace_dir(tmp_path / "one", monkeypatch, "psum_trace", 1)
    assert read(rec(one, chips=1, driver="solve", solves=2)) == pytest.approx(3.5)
    assert read(rec(trace, driver="service", solves=2)) is None
    assert read(rec(None, driver="solve", solves=2)) is None


def test_power_iteration_holds_its_allreduce(tmp_path, monkeypatch):
    read = load_module("metrics", "power_iteration_s").read
    trace = _trace_dir(tmp_path, monkeypatch, "psum_trace", 4)
    assert read(rec(trace, driver="solve", solves=2)) == pytest.approx(0.014)


def test_psum_exposed_ms_without_the_scope(tmp_path, monkeypatch):
    """A program that does not name its collectives gives no reading."""
    read = load_module("metrics", "psum_exposed_ms").read
    trace = _trace_dir(tmp_path, monkeypatch, "scoped_trace", 1)
    assert scopes.per_solve(rec(trace, chips=1, driver="solve", solves=2),
                            "oracle.scatter") == pytest.approx(0.015 / 2)
    assert read(rec(trace, chips=1, driver="solve", solves=2)) is None
    # no profile where the run leaves it
    monkeypatch.setattr(scopes, "TRACE_DIR", str(tmp_path / "none"))
    assert read(rec(trace, chips=1, driver="solve", solves=2)) is None


def test_allreduce_bytes(monkeypatch):
    from repro import telemetry

    read = load_module("metrics", "allreduce_bytes").read
    monkeypatch.setattr(telemetry, "get_registry", telemetry.MetricsRegistry)
    # a program without the counter
    assert read(rec(None, driver="solve")) is None
    reg = telemetry.MetricsRegistry()
    reg.inc("dist_solves_total", 3, shards=4)
    monkeypatch.setattr(telemetry, "get_registry", lambda: reg)
    assert read(rec(None, driver="solve")) is None
    reg.inc("dist_allreduce_bytes_total", 3 * 2_440_260, shards=4)
    assert read(rec(None, driver="solve")) == 2_440_260
    assert read(rec(None, driver="service")) is None


def test_binary_is_the_text():
    xplane_pb2 = pytest.importorskip(
        "tensorflow.tsl.profiler.protobuf.xplane_pb2")
    from google.protobuf import text_format

    with open(os.path.join(DATA, "psum_trace.pbtxt")) as f:
        text = text_format.Parse(f.read(), xplane_pb2.XSpace())
    with open(os.path.join(DATA, "psum_trace.xplane.pb"), "rb") as f:
        assert xplane_pb2.XSpace.FromString(f.read()) == text
