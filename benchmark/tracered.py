"""From a profiler trace to device busy/idle time, module and operation
time, and idle gaps attributed to what the host was doing.

``load_xplane`` turns the ``.xplane.pb`` JAX's profiler wrote into plain
lists; everything after that works on those lists, so the reduction is
checked on a small recorded trace kept beside this file
(``testdata/small_trace.json``).
"""
from __future__ import annotations

import glob
import os

MARK_OPEN = "bench_window_open"
MARK_CLOSE = "bench_window_close"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"


def find_xplane(log_dir: str) -> str:
    found = sorted(
        glob.glob(os.path.join(log_dir, "plugins", "profile", "*", "*.xplane.pb"))
    )
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return found[-1]


def load_xplane(path: str, keep_host=(MARK_OPEN, MARK_CLOSE)) -> dict:
    """Device planes whole; of host planes only the named marker events
    (host planes hold every traced call of every thread)."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    planes = []
    for plane in data.planes:
        device = plane.name.startswith("/device:")
        lines = []
        for line in plane.lines:
            events = [
                (ev.name, int(ev.start_ns), int(ev.duration_ns))
                for ev in line.events
                if device or ev.name in keep_host
            ]
            if events:
                lines.append({"name": line.name, "events": events})
        if lines:
            planes.append({"name": plane.name, "lines": lines})
    return {"planes": planes}


def _union(intervals: list) -> list:
    """Sorted, merged [start, end) intervals."""
    out: list = []
    for start, end in sorted(intervals):
        if out and start <= out[-1][1]:
            if end > out[-1][1]:
                out[-1][1] = end
        else:
            out.append([start, end])
    return out


def _clip(intervals: list, lo: int, hi: int) -> list:
    return [
        (max(s, lo), min(e, hi)) for s, e in intervals if e > lo and s < hi
    ]


def window_of(trace: dict):
    """(open_ns, close_ns) of the harness's markers on the trace clock,
    or None when the trace lacks them."""
    opened = closed = None
    for plane in trace["planes"]:
        if plane["name"].startswith("/device:"):
            continue
        for line in plane["lines"]:
            for name, start, dur in line["events"]:
                if name == MARK_OPEN:
                    opened = start + dur
                elif name == MARK_CLOSE:
                    closed = start
    if opened is None or closed is None or closed <= opened:
        return None
    return opened, closed


def device_planes(trace: dict) -> list:
    return [
        p for p in trace["planes"]
        if p["name"].startswith("/device:") and "TPU" in p["name"].upper()
    ] or [p for p in trace["planes"] if p["name"].startswith("/device:")]


def _line(plane: dict, name: str):
    for line in plane["lines"]:
        if line["name"] == name:
            return line
    return None


def reduce_trace(trace: dict, host_spans=(), window=None) -> dict:
    """``host_spans``: (name, start_ns, end_ns) on the trace clock.
    Returns seconds: ``window_s``, ``busy_s`` (union of device operation
    intervals, averaged over the device planes), ``modules`` and ``ops``
    ({name: [count, seconds]}, each event cut at the window's edges),
    ``idle_gaps`` ({host span: seconds})."""
    if window is None:
        window = window_of(trace)
    planes = device_planes(trace)
    if window is None:
        starts = [
            ev[1] for p in planes for ln in p["lines"] for ev in ln["events"]
        ]
        ends = [
            ev[1] + ev[2]
            for p in planes for ln in p["lines"] for ev in ln["events"]
        ]
        if not starts:
            return {"window_s": 0.0, "busy_s": 0.0, "modules": {}, "ops": {},
                    "idle_gaps": {}, "planes": 0}
        window = (min(starts), max(ends))
    lo, hi = window
    busy_ns = []
    modules: dict = {}
    ops: dict = {}
    gaps: dict = {}

    def tally(table: dict, events) -> list:
        """Count and seconds by name, each event cut at the window's
        edges; returns the events' intervals."""
        intervals = []
        for name, start, dur in events:
            if start + dur <= lo or start >= hi:
                continue
            intervals.append((start, start + dur))
            cell = table.setdefault(name, [0, 0.0])
            cell[0] += 1
            cell[1] += (min(start + dur, hi) - max(start, lo)) / 1e9
        return intervals

    by_name: dict = {}
    for name, s, e in host_spans:
        by_name.setdefault(name, []).append((s, e))
    merged_spans = {k: _union(v) for k, v in by_name.items()}
    for plane in planes:
        ops_line = _line(plane, OPS_LINE)
        intervals = []
        for line in [ops_line] if ops_line else plane["lines"]:
            intervals += tally(ops, line["events"])
        merged = _union(_clip(intervals, lo, hi))
        busy_ns.append(sum(e - s for s, e in merged))
        mod_line = _line(plane, MODULES_LINE)
        if mod_line:
            tally(modules, mod_line["events"])
        # idle gaps of this device, by what the host was doing
        cursor = lo
        idle = []
        for s, e in merged:
            if s > cursor:
                idle.append((cursor, s))
            cursor = max(cursor, e)
        if cursor < hi:
            idle.append((cursor, hi))
        for gs, ge in idle:
            best, best_cover = "unattributed", 0
            for name, spans in merged_spans.items():
                cover = sum(e - s for s, e in _clip(spans, gs, ge))
                if cover > best_cover:
                    best, best_cover = name, cover
            gaps[best] = gaps.get(best, 0.0) + (ge - gs) / 1e9
    n = max(1, len(planes))
    return {
        "window_s": (hi - lo) / 1e9,
        "busy_s": sum(busy_ns) / n / 1e9,
        "modules": modules,
        "ops": ops,
        "idle_gaps": {k: v / n for k, v in gaps.items()},
        "planes": len(planes),
    }


def short_name(name: str) -> str:
    """An operation's name as the trace gives it, without the HLO text
    that follows it."""
    return name.split(" = ", 1)[0].strip()[:80]


def top(table: dict, k: int = 10) -> list:
    """[[name, seconds], ...] of the k largest, names shortened (two
    modules' operations of one name count together)."""
    merged: dict = {}
    for name, v in table.items():
        secs = v[1] if isinstance(v, (list, tuple)) else v
        short = short_name(name)
        merged[short] = merged.get(short, 0.0) + secs
    rows = sorted(([n, s] for n, s in merged.items()), key=lambda r: -r[1])
    return rows[:k]


def module_seconds(reduced: dict, prefix: str):
    """(count, seconds) of the modules whose name starts with ``prefix``."""
    count, total = 0, 0.0
    for name, (n, secs) in reduced["modules"].items():
        if name.startswith(prefix):
            count += n
            total += secs
    return count, total


def outline(trace: dict) -> list:
    """Planes, lines and their busiest event names, to look at a trace
    by hand before trusting a reduction of it."""
    out = []
    for plane in trace["planes"]:
        lines = []
        for line in plane["lines"]:
            names: dict = {}
            for name, _start, dur in line["events"]:
                cell = names.setdefault(name, [0, 0.0])
                cell[0] += 1
                cell[1] += dur / 1e9
            lines.append(
                {"line": line["name"], "events": len(line["events"]),
                 "top": top(names, 8)}
            )
        out.append({"plane": plane["name"], "lines": lines})
    return out


def excerpt(trace: dict, start_ns: int, length_ns: int) -> dict:
    """A small piece of a trace, names shortened, to keep as a recorded
    test input: device events that start inside the piece, and the
    harness's markers moved to its edges."""
    lo, hi = start_ns, start_ns + length_ns
    planes = []
    for plane in device_planes(trace):
        lines = []
        for line in plane["lines"]:
            events = [
                [short_name(n), s - lo, d]
                for n, s, d in line["events"] if lo <= s < hi
            ]
            if events:
                lines.append({"name": line["name"], "events": events})
        planes.append({"name": plane["name"], "lines": lines})
    planes.append({
        "name": "/host:CPU",
        "lines": [{"name": "python3", "events": [
            [MARK_OPEN, 0, 0], [MARK_CLOSE, length_ns, 0],
        ]}],
    })
    return {"planes": planes}
