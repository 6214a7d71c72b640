"""Reduction of a JAX profiler trace (XSpace) to the numbers the per-layer
metrics read.

The device planes are `/device:TPU:<n>`; on each, the `XLA Ops` line holds
one event per executed operation and `XLA Modules` one per executed
program.  Host planes carry the `jax.profiler.TraceAnnotation` spans, the
benchmark's own (`bench:...`) and the program's, on the same clock.  The
measured window is the host span `bench:window`.
"""
from __future__ import annotations

import bisect
import dataclasses
import glob
import os
import re
from typing import Iterable, Optional

__all__ = [
    "Event",
    "Trace",
    "from_profile",
    "load",
    "union",
    "busy_ns",
    "idle_gaps",
    "self_seconds",
    "breakdown",
]

WINDOW = "bench:window"
_DEVICE = re.compile(r"^/device:TPU:(\d+)$")


@dataclasses.dataclass(frozen=True)
class Event:
    start: float  # ns
    end: float  # ns
    name: str
    line: str
    stats: tuple  # ((name, value), ...)

    def stat(self, key: str):
        for k, v in self.stats:
            if k == key:
                return v
        return None

    def text(self) -> str:
        """The name and every string stat, for matching by pattern."""
        return " ".join([self.name] + [str(v) for _, v in self.stats
                                        if isinstance(v, str)])


@dataclasses.dataclass
class Trace:
    device: dict  # chip index -> list[Event] (all lines)
    host: list  # list[Event] on host planes
    window: tuple  # (start ns, end ns)

    @property
    def window_ns(self) -> float:
        return self.window[1] - self.window[0]

    def ops(self, chip: int) -> list:
        """The chip's operation events in the window (its `XLA Ops` line,
        or its `XLA Modules` line where it has no op line)."""
        ev = self.device.get(chip, [])
        lines = {e.line for e in ev}
        line = "XLA Ops" if "XLA Ops" in lines else "XLA Modules"
        return [e for e in ev if e.line == line and self._inside(e)]

    def modules(self, chip: int) -> list:
        return [e for e in self.device.get(chip, [])
                if e.line == "XLA Modules" and self._inside(e)]

    def _inside(self, e: Event) -> bool:
        return e.end > self.window[0] and e.start < self.window[1]


def from_profile(profile, chips: int) -> Trace:
    """A Trace from `jax.profiler.ProfileData`, device planes 0..chips-1."""
    device, host = {}, []
    for plane in profile.planes:
        m = _DEVICE.match(plane.name)
        if m and int(m.group(1)) >= chips:
            continue
        if not m and not plane.name.startswith("/host:"):
            continue
        out = device.setdefault(int(m.group(1)), []) if m else host
        for line in plane.lines:
            for e in line.events:
                out.append(Event(
                    float(e.start_ns), float(e.start_ns + e.duration_ns),
                    e.name, line.name, tuple((k, v) for k, v in e.stats),
                ))
    windows = [e for e in host if e.name == WINDOW]
    if windows:
        w = max(windows, key=lambda e: e.end - e.start)
        window = (w.start, w.end)
    else:
        allev = [e for evs in device.values() for e in evs]
        window = ((min(e.start for e in allev), max(e.end for e in allev))
                  if allev else (0.0, 0.0))
    return Trace(device=device, host=host, window=window)


def load(trace_dir: str, chips: int) -> Optional[Trace]:
    """The newest `.xplane.pb` under `trace_dir`, reduced; None if none."""
    import jax

    files = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True), key=os.path.getmtime)
    if not files:
        return None
    return from_profile(jax.profiler.ProfileData.from_file(files[-1]), chips)


def union(events: Iterable[Event], lo: float, hi: float) -> list:
    """Merged [start, end) intervals of the events, clipped to [lo, hi)."""
    spans = sorted((max(e.start, lo), min(e.end, hi)) for e in events)
    out = []
    for s, t in spans:
        if t <= s:
            continue
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], t)
        else:
            out.append([s, t])
    return out


def busy_ns(trace: Trace, chip: int = 0) -> float:
    """Nanoseconds of the window in which an operation ran on the chip."""
    lo, hi = trace.window
    return sum(t - s for s, t in union(trace.ops(chip), lo, hi))


def idle_gaps(trace: Trace, chip: int = 0) -> list:
    """[start, end) intervals of the window with no operation on the chip."""
    lo, hi = trace.window
    gaps, at = [], lo
    for s, t in union(trace.ops(chip), lo, hi):
        if s > at:
            gaps.append((at, s))
        at = max(at, t)
    if hi > at:
        gaps.append((at, hi))
    return gaps


def _host_activity(trace: Trace, names: set) -> list:
    return sorted(
        (e for e in trace.host if e.name != WINDOW and (
            e.name.startswith("bench:") or e.name in names)),
        key=lambda e: e.start,
    )


def short_name(name: str, limit: int = 100) -> str:
    """An HLO op's event name without layouts, comments and `%`, cut to
    `limit` characters: `fusion.88 = f32[20474704] fusion(f32[10000] ...`."""
    name = re.sub(r"\{[^{}]*\}|/\*.*?\*/|%", "", name)
    return re.sub(r"\s+", " ", name).strip()[:limit]


def module_name(name: str) -> str:
    """`jit_stage_fn(8072233835495416132)` -> `jit_stage_fn`."""
    return re.sub(r"\(\d+\)$", "", name)


def self_seconds(trace: Trace, chip: int = 0) -> dict:
    """Per operation (`<program>/<op>`), the device time of the window it
    spent itself, not in the operations nested in it (a `while` holds its
    body's ops on the same line)."""
    lo, hi = trace.window
    ops = sorted(trace.ops(chip), key=lambda e: (e.start, -e.end))
    mods = sorted(trace.modules(chip), key=lambda e: e.start)
    starts = [m.start for m in mods]
    own = [min(e.end, hi) - max(e.start, lo) for e in ops]
    stack: list = []
    for i, e in enumerate(ops):
        while stack and ops[stack[-1]].end <= e.start:
            stack.pop()
        if stack:
            own[stack[-1]] -= own[i]
        stack.append(i)
    out: dict = {}
    for e, t in zip(ops, own):
        k = bisect.bisect_right(starts, e.start) - 1
        mod = (module_name(mods[k].name) + "/"
               if k >= 0 and mods[k].end >= e.start else "")
        key = mod + short_name(e.name)
        out[key] = out.get(key, 0.0) + t * 1e-9
    return out


def breakdown(trace: Trace, span_names: Iterable[str] = (), chip: int = 0,
              top: int = 10) -> dict:
    """The device operations that took most time of their own in the window
    (`self_seconds`), and the idle time by what the host was doing (the
    innermost host span covering each stretch of idle time; `other` where
    none does), each as [[name, seconds], ...] with at most `top` entries."""
    spans = _host_activity(trace, set(span_names))
    per_host: dict = {}
    for gs, ge in idle_gaps(trace, chip):
        # cut the gap at every span boundary inside it, and give each piece
        # to the innermost (latest-starting) span that covers it
        cuts = sorted({gs, ge} | {p for e in spans for p in (e.start, e.end)
                                  if gs < p < ge})
        for a, b in zip(cuts, cuts[1:]):
            mid = 0.5 * (a + b)
            cover = [e for e in spans if e.start <= mid < e.end]
            name = max(cover, key=lambda e: e.start).name if cover else "other"
            per_host[name] = per_host.get(name, 0.0) + (b - a) * 1e-9

    def ranked(d):
        return [[k, v] for k, v in
                sorted(d.items(), key=lambda kv: -kv[1])[:top]]

    return {"device_ops": ranked(self_seconds(trace, chip)),
            "idle_gaps": ranked(per_host)}
