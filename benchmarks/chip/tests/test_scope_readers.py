"""The op-path decoder and the per-layer readers of the program's own layer
names, on a synthetic trace (tests/data/scoped_trace.pbtxt, and its binary
encoding scoped_trace.xplane.pb) whose numbers are worked out by hand here.

In the 100 ms window from 1 ms to 101 ms, own device times (ms):
jit_power_fn (program 15427155708406647243): the while 6 (28 less its
body), gather fusion.1 10, scatter-add fusion.2 10, norm fusion.3 2;
jit_stage_fn (program 8): the while 2 (60 less 58), gather fusion.2 20 (its
op text is the power program's scatter-add's), kernel dual_oracle.5 20,
projection fusion.6 9, scatter-add fusion.7 5, copy.8 3 (no tf_op), copy.9
1 (a path that only looks like a scope); jit_final_fn (program 9): gather
fusion.9 9 (92-105 cut at 101, its tf_op a reference).  Busy 97 ms.
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
POWER, STAGE, FINAL = 15427155708406647243, 8, 9


def _binary() -> bytes:
    with open(os.path.join(DATA, "scoped_trace.xplane.pb"), "rb") as f:
        return f.read()


@pytest.fixture(scope="module")
def trace():
    return tr.from_profile(jax.profiler.ProfileData.from_serialized_xspace(
        _binary()), 1)


@pytest.fixture
def trace_dir(tmp_path, monkeypatch):
    """The synthetic trace where a traced run leaves its profile."""
    run = tmp_path / "plugins" / "profile" / "run"
    run.mkdir(parents=True)
    shutil.copy(os.path.join(DATA, "scoped_trace.xplane.pb"),
                run / "host.xplane.pb")
    monkeypatch.setattr(scopes, "TRACE_DIR", str(tmp_path))
    return tmp_path


def rec(trace, **run):
    return {"trace": trace, "run": run, "chips": 1, "peaks": {},
            "config": {"work": "matching"}, "traffic": {}}


def _op(paths, program, prefix):
    return next(v for (p, name), v in paths.items()
                if p == program and name.startswith(prefix))


def test_decode():
    paths = scopes.decode(_binary(), chips=1)
    # every op with a tf_op on chip 0; copy.8 has none
    assert len(paths) == 11
    assert sorted({p for p, _ in paths}) == [STAGE, FINAL, POWER]
    # one op text, two programs, two paths
    assert _op(paths, POWER, "%fusion.2 ").endswith("oracle.scatter/L16/scatter-add:")
    assert _op(paths, STAGE, "%fusion.2 ").endswith("oracle.gather/L16/jit(_take)/gather:")
    # a tf_op given as a reference to a stat metadata's name
    assert _op(paths, FINAL, "%fusion.9 ") == (
        "jit(final_fn)/oracle.gather/L32/jit(_take)/gather:")
    assert not any(n.startswith("%copy.8 ") for _, n in paths)
    # chip 1 only when every chip is read; the host's plane never
    everything = scopes.decode(_binary())
    assert set(everything) - set(paths) == {
        (99, next(n for p, n in paths if p == POWER and n.startswith("%fusion.1 ")))}


def test_decode_matches_xplane_pb2():
    xplane_pb2 = pytest.importorskip(
        "tensorflow.tsl.profiler.protobuf.xplane_pb2")
    from google.protobuf import text_format

    with open(os.path.join(DATA, "scoped_trace.pbtxt")) as f:
        text = text_format.Parse(f.read(), xplane_pb2.XSpace())
    space = xplane_pb2.XSpace.FromString(_binary())
    # the binary is its text source's encoding
    assert space == text
    expected = {}
    for plane in space.planes:
        if not plane.name.startswith("/device:TPU:"):
            continue
        names = {k: v.name for k, v in plane.stat_metadata.items()}
        for meta in plane.event_metadata.values():
            stats = {}
            for s in meta.stats:
                kind = s.WhichOneof("value")
                value = names[s.ref_value] if kind == "ref_value" else getattr(s, kind)
                stats[names[s.metadata_id]] = value
            if "tf_op" in stats and "program_id" in stats:
                expected[(stats["program_id"], meta.name)] = stats["tf_op"]
    assert scopes.decode(_binary()) == expected


def test_scope_seconds(trace):
    paths = scopes.decode(_binary(), chips=1)
    secs = {s: scopes.scope_seconds(trace, paths, s) for s in (
        "oracle.gather", "oracle.scatter", "oracle.project", "oracle.kernel",
        "power_iteration", "L16", "oracle")}
    assert secs == pytest.approx({
        "oracle.gather": 0.039, "oracle.scatter": 0.015,
        "oracle.project": 0.009, "oracle.kernel": 0.020,
        "power_iteration": 0.028, "L16": 0.060,
        # a scope is a whole component of the path
        "oracle": 0.0})
    assert tr.busy_ns(trace) * 1e-9 == pytest.approx(0.097)


@pytest.mark.parametrize("metric,seconds", [
    ("oracle_gather_s", 0.039), ("oracle_scatter_s", 0.015),
    ("oracle_project_s", 0.009), ("power_iteration_s", 0.028)])
def test_device_readers(trace, trace_dir, metric, seconds):
    read = load_module("metrics", metric).read
    # over the window's two solves
    assert read(rec(trace, driver="solve", solves=2)) == pytest.approx(seconds / 2)
    assert read(rec(trace, driver="service", solves=2)) is None
    assert read(rec(None, driver="solve", solves=2)) is None


def test_device_readers_without_names(trace, trace_dir, monkeypatch):
    # no op carries the scope (a program that does not name it)
    assert scopes.per_solve(rec(trace, driver="solve", solves=2), "oracle.none") is None
    # no profile where the run leaves it
    monkeypatch.setattr(scopes, "TRACE_DIR", str(trace_dir / "plugins" / "none"))
    read = load_module("metrics", "oracle_gather_s").read
    assert read(rec(trace, driver="solve", solves=2)) is None


class _Tracer:
    def events(self):
        return [{"name": "bucketize", "dur": 1.5e6},
                {"name": "normalize_rows", "dur": 2.5e6},
                {"name": "stage", "dur": 9.0e6},
                {"name": "bucketize", "dur": 0.5e6}]


def test_host_span_readers(monkeypatch):
    from repro import telemetry

    monkeypatch.setattr(telemetry, "get_tracer", _Tracer)
    bucketize = load_module("metrics", "bucketize_s").read
    normalize = load_module("metrics", "normalize_rows_s").read
    assert bucketize(rec(None, driver="solve")) == pytest.approx(2.0)
    assert normalize(rec(None, driver="solve")) == pytest.approx(2.5)
    assert bucketize(rec(None, driver="service")) is None
    monkeypatch.setattr(telemetry, "get_tracer", telemetry.Tracer)
    assert bucketize(rec(None, driver="solve")) is None
