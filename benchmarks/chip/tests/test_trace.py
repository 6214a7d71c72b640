"""The trace reduction and the per-layer readers on a synthetic profiler
trace (tests/data/synthetic_trace.pbtxt), whose numbers are worked out by
hand here: a 100 ms window from 1 ms to 101 ms; device ops (ms) fusion.1
2-12, the Pallas kernel closed_call.3 12-42 and scatter-add.7 42-45 in
jit_stage_fn; fusion.9 46-48 and 92-95 in jit_q; while.5 60-90 holding
fusion.2 62-80 and fusion.1 80-88 in jit_final_fn; fusion.1 100-105 in
jit_stage_fn again; host spans bench:solve 1.5-50, ingest 50-70 and
bench:fetch 85-100 ms."""
import os

import jax
import pytest

from chipbench import trace as tr
from chipbench.harness import load_json, load_module

HERE = os.path.dirname(os.path.abspath(__file__))
MS = 1e6


@pytest.fixture(scope="module")
def trace():
    with open(os.path.join(HERE, "data", "synthetic_trace.pbtxt")) as f:
        return tr.from_profile(jax.profiler.ProfileData.from_text_proto(f.read()), 1)


def rec(trace, **run):
    return {"trace": trace, "run": run, "chips": 1,
            "peaks": load_json("peaks.json")["devices"]["TPU v5 lite"],
            "config": {"work": "matching"}, "traffic": {}}


def test_window_and_busy(trace):
    assert trace.window == (1 * MS, 101 * MS)
    # [2, 45) + [46, 48) + [60, 90) + [92, 95) + [100, 101): nested ops
    # counted once, the last op clipped
    assert tr.busy_ns(trace) == pytest.approx(79 * MS)
    assert tr.idle_gaps(trace) == [(1 * MS, 2 * MS), (45 * MS, 46 * MS),
                                   (48 * MS, 60 * MS), (90 * MS, 92 * MS),
                                   (95 * MS, 100 * MS)]


def test_device_idle(trace):
    read = load_module("metrics", "device_idle.solve").read
    assert read(rec(trace, driver="solve")) == pytest.approx(21.0)
    assert read(rec(trace, driver="service")) is None
    assert read(rec(None, driver="solve")) is None
    svc = load_module("metrics", "device_idle.service").read
    assert svc(rec(trace, driver="service")) == pytest.approx(21.0)


def test_breakdown(trace):
    b = tr.breakdown(trace, span_names=("ingest",))
    idle = dict(b["idle_gaps"])
    # [1, 1.5) under the window alone; [1.5, 2), [45, 46) and [48, 50) in
    # bench:solve; [50, 60) in ingest; [90, 92) and [95, 100) in bench:fetch
    assert idle == pytest.approx({"ingest": 0.010, "bench:fetch": 0.007,
                                  "bench:solve": 0.0035, "other": 0.0005})
    ops = {k.split(" = ")[0]: v for k, v in b["device_ops"]}
    # time of their own: the while less its body; fusion.1 per program
    assert ops == pytest.approx({
        "jit_stage_fn/closed_call.3": 0.030, "jit_final_fn/fusion.2": 0.018,
        "jit_stage_fn/fusion.1": 0.011, "jit_final_fn/fusion.1": 0.008,
        "jit_q/fusion.9": 0.005, "jit_final_fn/while.5": 0.004,
        "jit_stage_fn/scatter-add.7": 0.003})
    assert b["device_ops"][0][0].startswith("jit_stage_fn/closed_call.3 = (f32[16,128], ")
    assert sum(ops.values()) == pytest.approx(tr.busy_ns(trace) * 1e-9)


def test_kernel_roofline(trace):
    work = load_module("work", "matching")
    peaks = load_json("peaks.json")["devices"]["TPU v5 lite"]
    size = dict(nnz=10**6, num_families=1, num_destinations=1000, slab_bytes=4)
    r = rec(trace, driver="solve", oracle="fused", oracle_evals=10,
            power_steps=30, **size)
    # 10 evaluations of 1e6 * 12 B + 8000 B at 819 GB/s over 30 ms of kernel
    least = 10 * (12e6 + 8000) / 819e9
    assert least == pytest.approx(10 * work.least_seconds(
        work.oracle_eval(10**6, 1, 1000, 4), peaks))
    value = load_module("metrics", "dual_oracle_roofline").read(r)
    assert value == pytest.approx(100 * least / 0.030)
    assert load_module("metrics", "dual_oracle_roofline").read(
        rec(trace, driver="solve", oracle="xla", **size)) is None


def test_oracle_roofline(trace):
    size = dict(nnz=10**6, num_families=1, num_destinations=1000, slab_bytes=4)
    r = rec(trace, driver="solve", oracle="xla", oracle_evals=10,
            power_steps=30, **size)
    least = (10 * (12e6 + 8000) + 30 * (16e6 + 8000)) / 819e9
    value = load_module("metrics", "oracle_hbm_roofline.xla").read(r)
    assert value == pytest.approx(100 * least / 0.079)


def test_query_device_ms(trace):
    read = load_module("metrics", "query_device_ms").read
    # 2 ms + 3 ms of jit_q programs over 2 batches
    assert read(rec(trace, driver="service", query_batches=2)) == pytest.approx(2.5)
    assert read(rec(trace, driver="solve", query_batches=2)) is None


def test_counters():
    run = {"driver": "service", "ingest_s": [1.0, 3.0], "upload_bytes": [10, 30]}
    assert load_module("metrics", "ingest_ms").read(rec(None, **run)) == 2000.0
    assert load_module("metrics", "upload_bytes").read(rec(None, **run)) == 20.0
    assert load_module("metrics", "host_build_s").read(
        rec(None, driver="solve", host_build_s=4.5)) == 4.5
