"""Run one cell of BENCHMARK.json once: find its files by name, set up,
measure, check, and print the result line.

A cell `<config>.<traffic>` takes `configs/<config>.json` (the deployment),
`traffic/<traffic>.json` (the mix, which names its driver
`drivers/<driver>.py`), `limits/<cell>.json` (the limits of the numbers
that decide `correct`), and the readers `metrics/<metric>.py` of the
per-layer metrics that list it.  Adding a cell or a metric adds files and
entries; nothing here changes.
"""
from __future__ import annotations

import contextlib
import dataclasses
import gc
import importlib.util
import json
import math
import os
import shutil
import sys
import time
from typing import Any, Optional

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CHECKOUT = os.path.dirname(os.path.dirname(BENCH))
CACHE = os.path.join(BENCH, ".cache")

__all__ = ["BENCH", "CHECKOUT", "CACHE", "Context", "Outcome", "Check",
           "load_json", "load_module", "benchmark", "cell", "run_cell"]


def load_json(*parts: str) -> dict:
    with open(os.path.join(BENCH, *parts)) as f:
        return json.load(f)


def load_module(kind: str, name: str):
    """benchmarks/chip/<kind>/<name>.py, imported once by path."""
    key = f"chipbench_{kind}_{name}".replace(".", "_").replace("-", "_")
    if key in sys.modules:
        return sys.modules[key]
    path = os.path.join(BENCH, kind, f"{name}.py")
    spec = importlib.util.spec_from_file_location(key, path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[key] = mod
    spec.loader.exec_module(mod)
    return mod


def benchmark() -> dict:
    with open(os.path.join(CHECKOUT, "BENCHMARK.json")) as f:
        return json.load(f)


def applies(entry: dict, workload: str) -> bool:
    return "workloads" not in entry or workload in entry["workloads"]


def cell(workload: str) -> dict:
    """The workload's entry of BENCHMARK.json with its files loaded."""
    bench = benchmark()
    w = next((w for w in bench["workloads"] if w["name"] == workload), None)
    if w is None:
        raise SystemExit(f"no workload {workload!r} in BENCHMARK.json")
    c = next(c for c in bench["configs"] if c["name"] == w["config"])
    with open(os.path.join(CHECKOUT, c["file"])) as f:
        config = json.load(f)
    return {
        "workload": w,
        "config": config,
        "traffic": load_json("traffic", f"{w['traffic']}.json"),
        "limits": load_json("limits", f"{workload}.json"),
        "end_to_end": [m for m in bench["end_to_end"] if applies(m, workload)],
        "per_layer": [m for m in bench["per_layer"] if applies(m, workload)],
    }


@dataclasses.dataclass
class Check:
    name: str
    value: float
    limit: float

    @property
    def ok(self) -> bool:
        return math.isfinite(self.value) and self.value <= self.limit


@dataclasses.dataclass
class Outcome:
    """What a driver returns: the end-to-end values it measured, the count
    of operations attempted and failed in the window, the numbers compared
    with their limits, and the records the per-layer readers take."""

    end_to_end: dict
    attempted: int
    failed: int
    checks: list
    records: dict
    notes: list = dataclasses.field(default_factory=list)  # to stderr
    stdout: list = dataclasses.field(default_factory=list)  # before the result


class CompileCount:
    """Compilations and persistent-cache loads, from JAX's own events."""

    COMPILE = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        import jax

        self.compiles = 0
        self.seconds = 0.0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, duration, **_):
        if event == self.COMPILE:
            self.compiles += 1
        if event.startswith("/jax/core/compile/"):
            self.seconds += duration


class Context:
    """What a driver gets: the cell's files, the run's arguments, and the
    hooks that open and close the measured window."""

    def __init__(self, spec: dict, *, seed: int, seconds: float, trace: bool,
                 control: str = "none", t_start: float, chips: int = 1,
                 overrides: Optional[dict] = None):
        self.workload = spec["workload"]["name"]
        self.config = dict(spec["config"], **(overrides or {}).get("config", {}))
        self.traffic = dict(spec["traffic"], **(overrides or {}).get("traffic", {}))
        self.limits = spec["limits"]
        self.seed = int(seed)
        self.seconds = float(seconds)
        self.trace = bool(trace)
        self.control = control
        self.chips = chips
        self.t_start = t_start
        self.phases: dict = {}
        self.compile = CompileCount()
        self.setup_s: Optional[float] = None
        self.window_t: Optional[tuple] = None
        self.memory_peak: Optional[int] = None
        self.trace_dir = os.path.join(CACHE, "trace")
        self._annotation = None
        self._compiles_open = 0

    # -- set-up bookkeeping -------------------------------------------------

    @contextlib.contextmanager
    def phase(self, name: str):
        """Host seconds of one part of set-up (or of the check)."""
        t0 = time.perf_counter()
        try:
            yield
        finally:
            self.phases[name] = self.phases.get(name, 0.0) + (
                time.perf_counter() - t0)

    def span(self, name: str):
        """A host span on the profiler's clock in a traced run."""
        if not self.trace:
            return contextlib.nullcontext()
        import jax

        return jax.profiler.TraceAnnotation(f"bench:{name}")

    # -- the window ---------------------------------------------------------

    def open_window(self) -> float:
        """Ends set-up; in a traced run starts the profiler.  Returns the
        window's start on the perf_counter clock."""
        if self.trace:
            import jax

            shutil.rmtree(self.trace_dir, ignore_errors=True)
            os.makedirs(self.trace_dir, exist_ok=True)
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            jax.profiler.start_trace(self.trace_dir, profiler_options=opts)
            self._annotation = jax.profiler.TraceAnnotation("bench:window")
            self._annotation.__enter__()
        self._compiles_open = self.compile.compiles
        t0 = time.perf_counter()
        self.setup_s = t0 - self.t_start
        self.window_t = (t0, None)
        return t0

    def close_window(self) -> float:
        """Ends the window (after its last whole unit of work); stops the
        profiler and reads the chip's peak memory.  Returns the window's
        length in seconds."""
        t1 = time.perf_counter()
        self.window_t = (self.window_t[0], t1)
        self.compiles_in_window = self.compile.compiles - self._compiles_open
        if self._annotation is not None:
            self._annotation.__exit__(None, None, None)
            import jax

            jax.profiler.stop_trace()
        self.memory_peak = peak_memory(self.chips)
        return t1 - self.window_t[0]


def peak_memory(chips: int) -> Optional[int]:
    import jax

    peaks = []
    for d in jax.devices()[:chips]:
        stats = d.memory_stats() or {}
        if "peak_bytes_in_use" in stats:
            peaks.append(int(stats["peak_bytes_in_use"]))
    return max(peaks) if peaks else None


def process_age() -> float:
    """Seconds since this process started, from /proc (0 where absent)."""
    try:
        with open("/proc/self/stat") as f:
            start_ticks = float(f.read().rsplit(")", 1)[1].split()[19])
        with open("/proc/uptime") as f:
            uptime = float(f.read().split()[0])
        return max(0.0, uptime - start_ticks / os.sysconf("SC_CLK_TCK"))
    except (OSError, ValueError, IndexError):
        return 0.0


def device_info(chips: int, require_tpu: bool) -> dict:
    import jax

    devs = jax.devices()
    if require_tpu and (devs[0].platform != "tpu" or len(devs) < chips):
        raise NoChip(f"needs {chips} TPU chip(s); JAX found {len(devs)} "
                     f"{devs[0].platform} device(s)")
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


class NoChip(RuntimeError):
    pass


def enable_compile_cache() -> str:
    """JAX's persistent compilation cache at a fixed path in the checkout
    (or where JAX_COMPILATION_CACHE_DIR points), for every program."""
    import jax

    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or os.path.join(CACHE, "jax")
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


def per_layer(spec: dict, ctx: Context, out: Outcome, trace, peaks: dict) -> dict:
    """Each per-layer metric of the cell from its reader; a reader that
    finds nothing to read returns None and the metric is left out."""
    rec = {"trace": trace, "run": out.records, "peaks": peaks,
           "config": ctx.config, "traffic": ctx.traffic, "chips": ctx.chips}
    metrics = {}
    for m in spec["per_layer"]:
        value = load_module("metrics", m["name"]).read(rec)
        if value is not None:
            metrics[m["name"]] = {"value": float(value), "unit": m["unit"]}
    return metrics


def run_cell(workload: str, *, seed: int, seconds: float, trace: bool,
             control: str = "none", require_tpu: bool = True,
             overrides: Optional[dict] = None, t_start: Optional[float] = None,
             log=print) -> dict:
    """One run of one cell; returns the result line as a dict."""
    if t_start is None:
        t_start = time.perf_counter() - process_age()
    spec = cell(workload)
    chips = int(spec["workload"]["chips"])
    device = device_info(chips, require_tpu)
    peaks_all = load_json("peaks.json")["devices"]
    if require_tpu and device["kind"] not in peaks_all:
        raise SystemExit(f"no peaks for device kind {device['kind']!r} in peaks.json")
    peaks = peaks_all.get(device["kind"], next(iter(peaks_all.values())))
    enable_compile_cache()
    if trace:
        # the program's spans also go on the profiler's clock
        from repro import telemetry

        telemetry.set_tracer(telemetry.Tracer(jax_annotations=True))
    ctx = Context(spec, seed=seed, seconds=seconds, trace=trace,
                  control=control, t_start=t_start, chips=chips,
                  overrides=overrides)
    driver = load_module("drivers", ctx.traffic["driver"])
    out: Outcome = driver.run(ctx, log=log)
    gc.collect()
    log(f"set-up {ctx.setup_s:.3f} s: " + ", ".join(
        f"{k} {v:.3f} s" for k, v in ctx.phases.items()))
    log(f"compilations inside the window: {ctx.compiles_in_window}; "
        f"JAX compile-event seconds in the run: {ctx.compile.seconds:.3f}")
    for note in out.notes:
        log(note)
    device["memory_peak_bytes"] = ctx.memory_peak
    result: dict[str, Any] = {
        "correct": bool(out.failed == 0 and all(c.ok for c in out.checks)),
        "attempted": int(out.attempted),
        "failed": int(out.failed),
    }
    if trace:
        from chipbench import trace as tr

        t = tr.load(ctx.trace_dir, chips)
        if t is not None:
            device["busy_s"] = sum(tr.busy_ns(t, c) for c in range(chips)) / chips * 1e-9
            device["window_s"] = t.window_ns * 1e-9
            result["breakdown"] = tr.breakdown(
                t, out.records.get("span_names", ()))
        result["metrics"] = per_layer(spec, ctx, out, t, peaks)
    else:
        values = dict(out.end_to_end, setup_s=ctx.setup_s)
        result["metrics"] = {
            m["name"]: {"value": float(values[m["name"]]), "unit": m["unit"]}
            for m in spec["end_to_end"]
        }
    result["device"] = device
    if "breakdown" in result:
        result["breakdown"] = result.pop("breakdown")
    result["checks"] = {c.name: {"value": c.value, "limit": c.limit}
                        for c in out.checks}
    result["_stdout"] = list(out.stdout)
    return result
