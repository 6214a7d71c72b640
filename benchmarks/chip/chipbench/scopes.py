"""The program's own layer names, read from a profiler trace and from the
program's tracer.

Device side: each op's event metadata in the trace (XSpace) carries
`tf_op`, the op-name path the program gave the op
(`jit(stage_fn)/while/body/.../oracle.gather/L16/jit(_take)/gather:`), and
`program_id`, the number in its module's event name
(`jit_stage_fn(8072233835495416132)`).  `jax.profiler.ProfileData` exposes
only the event stats, so `tf_ops` decodes the metadata from the file
itself, keyed by (program id, op name), and `scope_seconds` joins it to the
window's op events through their module's program id.

Host side: `span_seconds` sums the program's `telemetry.span`s by name from
the tracer the harness installs at the start of a traced run.

The decoder reads the protobuf wire format of the few fields it needs:
`XSpace.planes` = 1; `XPlane` name = 2, event_metadata = 4 and
stat_metadata = 5 (maps: key = 1, value = 2); `XEventMetadata` name = 2,
stats = 5; `XStatMetadata` name = 2; `XStat` metadata_id = 1,
uint64_value = 3, int64_value = 4, str_value = 5, ref_value = 7.
"""
from __future__ import annotations

import bisect
import glob
import os
import re
from typing import Optional

from chipbench import trace as tr
from chipbench.harness import CACHE

__all__ = ["TRACE_DIR", "decode", "tf_ops", "scope_seconds", "per_solve",
           "span_seconds"]

# where a traced run writes its profile (harness.Context.trace_dir)
TRACE_DIR = os.path.join(CACHE, "trace")
_PROGRAM = re.compile(r"\((\d+)\)$")


# -- the wire format ---------------------------------------------------------


def _varint(buf: bytes, i: int) -> tuple:
    out = shift = 0
    while True:
        b = buf[i]
        i += 1
        out |= (b & 0x7F) << shift
        if b < 0x80:
            return out, i
        shift += 7


def _fields(buf: bytes):
    """(field number, value) of a message: an int for varints, bytes for
    length-delimited fields; fixed-width fields are skipped."""
    i, n = 0, len(buf)
    while i < n:
        key, i = _varint(buf, i)
        field, wire = key >> 3, key & 7
        if wire == 0:
            value, i = _varint(buf, i)
        elif wire == 2:
            size, i = _varint(buf, i)
            value, i = buf[i:i + size], i + size
        elif wire == 1:
            i += 8
            continue
        elif wire == 5:
            i += 4
            continue
        else:
            raise ValueError(f"unsupported wire type {wire}")
        yield field, value


def _map_values(entries: list) -> dict:
    """A protobuf map's entries (key = 1, value = 2) as {key: value bytes}."""
    out = {}
    for entry in entries:
        key, value = 0, b""
        for f, v in _fields(entry):
            if f == 1:
                key = v
            elif f == 2:
                value = v
        out[key] = value
    return out


def _name(msg: bytes) -> str:
    for f, v in _fields(msg):
        if f == 2:
            return v.decode("utf-8", "replace")
    return ""


def _device_plane(plane: bytes, chips: Optional[int]) -> Optional[tuple]:
    name, events, stats = "", [], []
    for f, v in _fields(plane):
        if f == 2:
            name = v.decode("utf-8", "replace")
        elif f == 4:
            events.append(v)
        elif f == 5:
            stats.append(v)
    m = tr._DEVICE.match(name)
    if not m or (chips is not None and int(m.group(1)) >= chips):
        return None
    return events, stats


def decode(data: bytes, chips: Optional[int] = None) -> dict:
    """{(program_id, op name): tf_op} over the device planes of a
    serialized XSpace (chips 0..chips-1, or all)."""
    out = {}
    for f, plane in _fields(data):
        if f != 1:
            continue
        found = _device_plane(plane, chips)
        if found is None:
            continue
        events, stats = found
        stat_names = {k: _name(v) for k, v in _map_values(stats).items()}
        for meta in _map_values(events).values():
            name, values = "", {}
            for g, v in _fields(meta):
                if g == 2:
                    name = v.decode("utf-8", "replace")
                elif g == 5:
                    values.update(_stat(v, stat_names))
            if "program_id" in values and "tf_op" in values:
                out[(int(values["program_id"]), name)] = str(values["tf_op"])
    return out


def _stat(msg: bytes, stat_names: dict) -> dict:
    """{name: value} of one XStat whose name is `tf_op` or `program_id`."""
    key, value = None, None
    for f, v in _fields(msg):
        if f == 1:
            key = stat_names.get(v)
        elif f in (3, 4):
            value = v if f == 3 or v < 1 << 63 else v - (1 << 64)
        elif f == 5:
            value = v.decode("utf-8", "replace")
        elif f == 7:
            value = stat_names.get(v)
    return {key: value} if key in ("tf_op", "program_id") else {}


def tf_ops(trace_dir: Optional[str] = None, chips: Optional[int] = None) -> dict:
    """`decode` of the newest `.xplane.pb` under `trace_dir` (the file
    `trace.load` reads); {} where there is none."""
    files = sorted(glob.glob(os.path.join(trace_dir or TRACE_DIR, "**",
                                          "*.xplane.pb"), recursive=True),
                   key=os.path.getmtime)
    if not files:
        return {}
    with open(files[-1], "rb") as f:
        return decode(f.read(), chips)


# -- readings ----------------------------------------------------------------


def _own(trace: tr.Trace, chip: int) -> list:
    """(op event, its own ns in the window, its module event or None) for
    every op of the window, as `trace.self_seconds` counts own time: a
    `while` less the ops nested in it."""
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
    out = []
    for e, t in zip(ops, own):
        k = bisect.bisect_right(starts, e.start) - 1
        out.append((e, t, mods[k] if k >= 0 and mods[k].end >= e.start else None))
    return out


def _in_scope(path: str, scope: str) -> bool:
    return re.search(rf"(^|/){re.escape(scope)}(/|:|$)", path) is not None


def scope_seconds(trace: tr.Trace, paths: dict, scope: str,
                  chips: int = 1) -> float:
    """Device seconds of the window spent by ops whose `tf_op` path holds
    `scope` as a whole component, own time, averaged over the chips."""
    total = 0.0
    for chip in range(chips):
        for e, t, mod in _own(trace, chip):
            m = _PROGRAM.search(mod.name) if mod is not None else None
            path = paths.get((int(m.group(1)), e.name)) if m else None
            if path is not None and _in_scope(path, scope):
                total += t
    return total * 1e-9 / chips


def per_solve(rec: dict, scope: str) -> Optional[float]:
    """`scope_seconds` over the window's cold solves; None outside a traced
    solve run or where no op carries the scope."""
    run, t = rec["run"], rec["trace"]
    if t is None or run.get("driver") != "solve" or not run.get("solves"):
        return None
    paths = tf_ops(chips=rec["chips"])
    seconds = scope_seconds(t, paths, scope, rec["chips"]) if paths else 0.0
    return seconds / run["solves"] if seconds > 0 else None


def span_seconds(rec: dict, name: str) -> Optional[float]:
    """Host seconds of the program's spans called `name` in a solve run
    (the tracer the harness installs in a traced run); None if none."""
    if rec["run"].get("driver") != "solve":
        return None
    from repro import telemetry

    durs = [e["dur"] for e in telemetry.get_tracer().events()
            if e["name"] == name]
    return sum(durs) * 1e-6 if durs else None
