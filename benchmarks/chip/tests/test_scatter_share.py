"""`scatter_onehot_share` on a synthetic trace (tests/data/scatter_trace.pbtxt,
and its binary encoding scatter_trace.xplane.pb) whose numbers are worked
out by hand here.

In the 100 ms window from 1 ms to 101 ms, own device times (ms) of
jit_stage_fn (program 8): the while 42 (90 less its body); under
`oracle.scatter`: the L16 bucket's `dual_scatter` kernel 18 under
`onehot` and the slice of its table fusion.3 1, the L8 bucket's kernel 8
under `onehot`, the L1 bucket's XLA scatter-add 2, copy.7 5, whose path
holds `onehot` only inside a longer name; the `dual_gather` kernel 10,
under `oracle.gather/L16/onehot`; fusion.8 4, under a `onehot` scope in
`power_iteration`, not in `oracle.scatter`.  So `oracle.scatter` 34 ms, of
them `oracle.scatter/.../onehot` 26 ms: 76.47 %.  The onehot_trace's
program scatter-adds nothing through the kernel, and gives no share.
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


def test_scatter_scope_seconds(tmp_path, monkeypatch):
    trace = _trace_dir(tmp_path, monkeypatch, "scatter_trace")
    paths = scopes.tf_ops(chips=1)
    assert len(paths) == 8
    secs = {s: scopes.scope_seconds(trace, paths, s)
            for s in ("oracle.scatter", "onehot", "oracle.gather", "L16")}
    # `onehot` anywhere: the scatter's 26 ms, the gather's 10 ms and
    # power_iteration's 4 ms
    assert secs == pytest.approx({"oracle.scatter": 0.034, "onehot": 0.040,
                                  "oracle.gather": 0.010, "L16": 0.029})
    assert tr.busy_ns(trace) * 1e-9 == pytest.approx(0.090)


def test_scatter_onehot_share(tmp_path, monkeypatch):
    read = load_module("metrics", "scatter_onehot_share").read
    trace = _trace_dir(tmp_path, monkeypatch, "scatter_trace")
    # a ratio: the same over any number of solves
    for solves in (1, 3):
        got = read(rec(trace, driver="solve", solves=solves))
        assert got == pytest.approx(100.0 * 26 / 34)  # not 40 / 34
    assert read(rec(trace, driver="service", solves=2)) is None
    assert read(rec(None, driver="solve", solves=2)) is None


@pytest.mark.parametrize("name", ["onehot_trace", "scoped_trace"])
def test_scatter_onehot_share_without_the_kernel(tmp_path, monkeypatch, name):
    """A program whose scatter-adds are XLA's alone names no `onehot` scope
    in `oracle.scatter`, though it may gather through the kernel."""
    read = load_module("metrics", "scatter_onehot_share").read
    trace = _trace_dir(tmp_path, monkeypatch, name)
    assert read(rec(trace, driver="solve", solves=2)) is None
    # no profile where the run leaves it
    monkeypatch.setattr(scopes, "TRACE_DIR", str(tmp_path / "none"))
    assert read(rec(trace, driver="solve", solves=2)) is None


def test_binary_is_the_text():
    xplane_pb2 = pytest.importorskip(
        "tensorflow.tsl.profiler.protobuf.xplane_pb2")
    from google.protobuf import text_format

    with open(os.path.join(DATA, "scatter_trace.pbtxt")) as f:
        text = text_format.Parse(f.read(), xplane_pb2.XSpace())
    with open(os.path.join(DATA, "scatter_trace.xplane.pb"), "rb") as f:
        assert xplane_pb2.XSpace.FromString(f.read()) == text
