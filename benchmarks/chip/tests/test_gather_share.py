"""`gather_onehot_share` on a synthetic trace (tests/data/onehot_trace.pbtxt,
and its binary encoding onehot_trace.xplane.pb) whose numbers are worked
out by hand here.

In the 100 ms window from 1 ms to 101 ms, own device times (ms) of
jit_stage_fn (program 8): the while 31 (90 less its body); under
`oracle.gather`: the L16 dual table fusion.1 2, its `dual_gather` kernel
18 under `onehot`, the L8 bucket's kernel 8 under `onehot`, the L1
bucket's XLA gather 2; the dual-oracle kernel 20; copy.6 5, whose path
holds `onehot` only inside a longer name; fusion.7 4, under a `onehot`
scope in `power_iteration`, not in `oracle.gather`.  So `oracle.gather`
30 ms, of them `oracle.gather/.../onehot` 26 ms: 86.67 %.  The scope
readers' scoped_trace, whose program gathers through XLA alone, gives no
share.
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


def _trace_dir(tmp_path, monkeypatch, name):
    """The synthetic trace `name` where a traced run leaves its profile,
    and its reduction."""
    run = tmp_path / name / "plugins" / "profile" / "run"
    run.mkdir(parents=True)
    src = os.path.join(DATA, f"{name}.xplane.pb")
    shutil.copy(src, run / "host.xplane.pb")
    monkeypatch.setattr(scopes, "TRACE_DIR", str(tmp_path / name))
    with open(src, "rb") as f:
        return tr.from_profile(
            jax.profiler.ProfileData.from_serialized_xspace(f.read()), 1)


def rec(trace, **run):
    return {"trace": trace, "run": run, "chips": 1, "peaks": {},
            "config": {"work": "matching"}, "traffic": {}}


def test_onehot_scope_seconds(tmp_path, monkeypatch):
    trace = _trace_dir(tmp_path, monkeypatch, "onehot_trace")
    paths = scopes.tf_ops(chips=1)
    assert len(paths) == 8
    secs = {s: scopes.scope_seconds(trace, paths, s)
            for s in ("oracle.gather", "onehot", "oracle.kernel", "L16",
                      "power_iteration")}
    # `onehot` anywhere: the gather's 26 ms and power_iteration's 4 ms
    assert secs == pytest.approx({"oracle.gather": 0.030, "onehot": 0.030,
                                  "oracle.kernel": 0.020, "L16": 0.040,
                                  "power_iteration": 0.004})
    assert tr.busy_ns(trace) * 1e-9 == pytest.approx(0.090)


def test_gather_onehot_share(tmp_path, monkeypatch):
    read = load_module("metrics", "gather_onehot_share").read
    trace = _trace_dir(tmp_path, monkeypatch, "onehot_trace")
    # a ratio: the same over any number of solves
    for solves in (1, 3):
        got = read(rec(trace, driver="solve", solves=solves))
        assert got == pytest.approx(100.0 * 26 / 30)  # not 30 / 30
    assert read(rec(trace, driver="service", solves=2)) is None
    assert read(rec(None, driver="solve", solves=2)) is None


def test_gather_onehot_share_without_the_kernel(tmp_path, monkeypatch):
    """A program whose gathers are XLA's alone names no `onehot` scope."""
    read = load_module("metrics", "gather_onehot_share").read
    trace = _trace_dir(tmp_path, monkeypatch, "scoped_trace")
    assert scopes.per_solve(rec(trace, driver="solve", solves=2),
                            "oracle.gather") == pytest.approx(0.039 / 2)
    assert read(rec(trace, driver="solve", solves=2)) is None
    # no profile where the run leaves it
    monkeypatch.setattr(scopes, "TRACE_DIR", str(tmp_path / "none"))
    assert read(rec(trace, driver="solve", solves=2)) is None


def test_binary_is_the_text(tmp_path):
    xplane_pb2 = pytest.importorskip(
        "tensorflow.tsl.profiler.protobuf.xplane_pb2")
    from google.protobuf import text_format

    with open(os.path.join(DATA, "onehot_trace.pbtxt")) as f:
        text = text_format.Parse(f.read(), xplane_pb2.XSpace())
    with open(os.path.join(DATA, "onehot_trace.xplane.pb"), "rb") as f:
        assert xplane_pb2.XSpace.FromString(f.read()) == text
