"""From a profiler trace (xplane) to a few numbers, the same way in every PR.

``load_xplane`` turns the profiler's file into a plain structure (kept small:
the device's op lines, and of the host's threads only the spans of the
benchmark, ``chipbench/...``, and of the program, ``tpuft::...``):

    {"planes": [{"name": "/device:TPU:0",
                 "lines": [{"name": "XLA Ops",
                            "events": [[name, start_ns, duration_ns, module, kind], ...]}]}]}

A device op's ``name`` is the HLO instruction's name and first result shape
("fusion.348 bf16[8192,4096]"), cut from the instruction text the profiler
gives; ``module`` is the jitted program running at the time (the device's
"XLA Modules" line: "jit_fused"); ``kind`` is "kernel" for a Pallas/Mosaic
call (``custom_call_target="tpu_custom_call"``), "container" for a ``while``
or ``conditional`` whose time is its children's, else "op".

``reduce`` works on that structure alone, so it is checked against a small
recorded trace kept beside this file (tests/small_trace.json) without a chip:

- window: from the end of the first ``chipbench/fetch`` span to the end of
  the last one (the harness opens and closes its window with a fetch);
- busy: per device the UNION of its op intervals inside the window, so that
  overlapping ops are not counted twice; the mean over the devices;
- ops: seconds by op name, a mean over the devices; containers are left out
  (their children are listed), kernels are also summed apart;
- gaps: the device's idle intervals, each attributed to the innermost host
  span open at its middle, seconds by span name, a mean over the devices.
"""

from __future__ import annotations

import re
from pathlib import Path
from typing import Any, Dict, List, Optional, Sequence, Tuple

DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
KERNEL_MARK = 'custom_call_target="tpu_custom_call"'
SHAPE = re.compile(r"[a-z]+[0-9]*\[[0-9,]*\]")
CONTAINER = re.compile(r"^(while|conditional|call)(\.|$)")
HOST_SPAN = re.compile(r"^(chipbench/|tpuft::)")
FETCH = "chipbench/fetch"

Event = Sequence[Any]  # [name, start_ns, duration_ns, module, kind]


def short_name(text: str) -> str:
    """"%fusion.348 = bf16[8192,4096]{1,0:T(8,128)} fusion(...)" ->
    "fusion.348 bf16[8192,4096]"."""
    head, _, rest = text.partition(" = ")
    shape = SHAPE.search(rest)
    name = head.strip().lstrip("%")
    return f"{name} {shape.group(0)}" if shape else name


def kind_of(text: str) -> str:
    if KERNEL_MARK in text:
        return "kernel"
    return "container" if CONTAINER.match(text.lstrip("%")) else "op"


def load_xplane(path: Path) -> Dict[str, Any]:
    import bisect

    from jax.profiler import ProfileData

    data = ProfileData.from_file(str(path))
    planes = []
    for plane in data.planes:
        device = bool(DEVICE_PLANE.match(plane.name))
        if not device and not plane.name.startswith("/host:"):
            continue
        modules: List[Tuple[float, float, str]] = []
        if device:
            for line in plane.lines:
                if line.name == MODULES_LINE:
                    modules = sorted(
                        (float(e.start_ns), float(e.start_ns + e.duration_ns),
                         e.name.split("(")[0])
                        for e in line.events
                    )
        starts = [m[0] for m in modules]
        lines = []
        for line in plane.lines:
            if device and line.name != OPS_LINE:
                continue
            events = []
            for e in line.events:
                if not device:
                    if HOST_SPAN.match(e.name):
                        events.append([e.name, float(e.start_ns), float(e.duration_ns), "", "span"])
                    continue
                at = bisect.bisect_right(starts, float(e.start_ns)) - 1
                module = modules[at][2] if at >= 0 and e.start_ns < modules[at][1] else ""
                events.append([
                    short_name(e.name), float(e.start_ns), float(e.duration_ns),
                    module, kind_of(e.name),
                ])
            if events:
                lines.append({"name": line.name, "events": events})
        if lines:
            planes.append({"name": plane.name, "lines": lines})
    return {"planes": planes}


def sample(space: Dict[str, Any], per_line: int = 400) -> Dict[str, Any]:
    """The first events of every line: small enough to keep or to look at."""
    return {"planes": [
        {"name": p["name"], "lines": [
            {"name": l["name"], "n_events": len(l["events"]), "events": l["events"][:per_line]}
            for l in p["lines"]
        ]} for p in space["planes"]
    ]}


def union_seconds(intervals: List[Tuple[float, float]]) -> Tuple[float, List[Tuple[float, float]]]:
    """Total length of the union of [start, end) intervals, and the merged
    intervals in order."""
    merged: List[Tuple[float, float]] = []
    for start, end in sorted(intervals):
        if merged and start <= merged[-1][1]:
            merged[-1] = (merged[-1][0], max(merged[-1][1], end))
        else:
            merged.append((start, end))
    return sum(e - s for s, e in merged), merged


def _host_spans(space: Dict[str, Any]) -> List[Tuple[str, float, float]]:
    spans = []
    for plane in space["planes"]:
        if plane["name"].startswith("/host:"):
            for line in plane["lines"]:
                for name, start, dur, *_ in line["events"]:
                    spans.append((name, start, start + dur))
    return spans


def _window(space: Dict[str, Any], spans) -> Optional[Tuple[float, float]]:
    fetches = sorted(end for name, _, end in spans if name == FETCH)
    if len(fetches) >= 2:
        return fetches[0], fetches[-1]
    # No harness spans in the trace: the extent of the device's ops.
    starts, ends = [], []
    for plane in space["planes"]:
        if DEVICE_PLANE.match(plane["name"]):
            for line in plane["lines"]:
                if line["name"] == OPS_LINE:
                    starts += [e[1] for e in line["events"]]
                    ends += [e[1] + e[2] for e in line["events"]]
    return (min(starts), max(ends)) if starts else None


def _owner(spans, at: float) -> str:
    """The innermost (latest started) host span open at time ``at``."""
    best, best_start = "unattributed", -1.0
    for name, start, end in spans:
        if start <= at < end and start >= best_start and name != "chipbench/step":
            best, best_start = name, start
    if best == "unattributed":
        for name, start, end in spans:
            if start <= at < end:
                return name
    return best


def reduce(space: Dict[str, Any]) -> Optional[Dict[str, Any]]:
    """busy_s, window_s, ops, gaps, per-program and per-kernel seconds; None
    when the trace holds no device op."""
    spans = _host_spans(space)
    window = _window(space, spans)
    if window is None:
        return None
    lo, hi = window
    devices = []
    for plane in space["planes"]:
        if not DEVICE_PLANE.match(plane["name"]):
            continue
        events: List[Event] = []
        for line in plane["lines"]:
            if line["name"] == OPS_LINE:
                events += line["events"]
        clipped = [
            (max(lo, e[1]), min(hi, e[1] + e[2]), e[0], e[3], e[4])
            for e in events if e[1] + e[2] > lo and e[1] < hi
        ]
        if clipped:
            devices.append(clipped)
    if not devices:
        return None
    n = len(devices)
    busy = 0.0
    ops: Dict[str, float] = {}
    kernels: Dict[str, Dict[str, float]] = {}
    modules: Dict[str, float] = {}
    gaps: Dict[str, float] = {}
    for clipped in devices:
        total, merged = union_seconds([(s, e) for s, e, *_ in clipped])
        busy += total
        for s, e, name, module, kind in clipped:
            if kind == "container":
                continue
            ops[name] = ops.get(name, 0.0) + (e - s)
            modules[module] = modules.get(module, 0.0) + (e - s)
            if kind == "kernel":
                slot = kernels.setdefault(module, {})
                slot[name] = slot.get(name, 0.0) + (e - s)
        edges = [lo] + [t for pair in merged for t in pair] + [hi]
        for start, end in zip(edges[0::2], edges[1::2]):
            if end > start:
                owner = _owner(spans, (start + end) / 2)
                gaps[owner] = gaps.get(owner, 0.0) + (end - start)
    ns = 1e-9 / n

    def top(table: Dict[str, float], k: Optional[int] = None) -> List[List[Any]]:
        rows = sorted(table.items(), key=lambda kv: -kv[1])
        return [[name, seconds * ns] for name, seconds in (rows[:k] if k else rows)]

    return {
        "devices": n,
        "window_s": (hi - lo) * 1e-9,
        "busy_s": busy * ns,
        "ops": top(ops),
        "gaps": top(gaps),
        # Seconds of leaf ops by jitted program, and of Pallas kernels by
        # program and name.
        "modules": top(modules),
        "kernels": {module: top(table) for module, table in kernels.items()},
    }
