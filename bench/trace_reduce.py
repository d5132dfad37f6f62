"""Reduce a profiler trace (``.xplane.pb``) to the device numbers.

* busy: the union of the intervals in which an operation ran on a
  device (the ``XLA Ops`` line of each ``/device:TPU:<i>`` plane; the
  ``XLA Modules`` line where a plane has no op line), clipped to the
  window and averaged over the devices;
* the window: the host span ``bench.window`` that the harness wraps
  around its measured window (the whole trace where it is missing);
* the idle share: 1 - busy / window;
* the top device operations by summed time;
* the idle gaps, each cut into pieces by the innermost ``bench.*`` host
  span that was open at that moment (``outside`` where none was), and
  summed by span name.

``reduce_file`` takes a path; ``reduce_events`` takes plain
``(name, start_ns, end_ns)`` tuples, which is what the tests build.
"""

from __future__ import annotations

import re
from pathlib import Path
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

__all__ = ["reduce_file", "reduce_events", "union", "find_trace"]

Event = Tuple[str, float, float]  # name, start_ns, end_ns

_DEVICE = re.compile(r"^/device:TPU:\d+$")
_OP_LINES = ("XLA Ops",)
_MODULE_LINES = ("XLA Modules",)
WINDOW_SPAN = "bench.window"
SPAN_PREFIX = "bench."


def find_trace(directory) -> Optional[Path]:
    """The newest ``.xplane.pb`` under a profiler output directory."""
    found = sorted(Path(directory).rglob("*.xplane.pb"),
                   key=lambda p: p.stat().st_mtime)
    return found[-1] if found else None


def union(intervals: Iterable[Tuple[float, float]]) -> List[Tuple[float, float]]:
    """Merge intervals into disjoint, sorted ones."""
    out: List[List[float]] = []
    for a, b in sorted(intervals):
        if b <= a:
            continue
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [(a, b) for a, b in out]


def _clip(iv, lo, hi):
    return [(max(a, lo), min(b, hi)) for a, b in iv if min(b, hi) > max(a, lo)]


def _span_timeline(spans: Sequence[Event]) -> List[Tuple[float, float, str]]:
    """Flatten nested host spans into pieces labelled by the innermost
    open span."""
    edges = []
    for name, a, b in spans:
        edges.append((a, 1, b, name))
        edges.append((b, 0, a, name))
    edges.sort(key=lambda e: (e[0], e[1]))
    stack: List[Tuple[str, float]] = []
    out = []
    last = None
    for t, is_start, _, name in edges:
        if last is not None and stack and t > last:
            out.append((last, t, stack[-1][0]))
        if is_start:
            stack.append((name, t))
        else:
            for k in range(len(stack) - 1, -1, -1):
                if stack[k][0] == name:
                    del stack[k]
                    break
        last = t
    return out


def _label_gaps(gaps, timeline) -> Dict[str, float]:
    """Seconds of idle time by the innermost host span open during it."""
    out: Dict[str, float] = {}
    j = 0
    for a, b in gaps:
        covered = 0.0
        while j < len(timeline) and timeline[j][1] <= a:
            j += 1
        k = j
        while k < len(timeline) and timeline[k][0] < b:
            lo, hi = max(a, timeline[k][0]), min(b, timeline[k][1])
            if hi > lo:
                out[timeline[k][2]] = out.get(timeline[k][2], 0.0) + (hi - lo)
                covered += hi - lo
            k += 1
        rest = (b - a) - covered
        if rest > 0:
            out["outside"] = out.get("outside", 0.0) + rest
    return {k: v / 1e9 for k, v in out.items()}


def reduce_events(devices: Dict[str, List[Event]], spans: List[Event],
                  top: int = 10) -> dict:
    """``devices`` maps a device name to its op events; ``spans`` are the
    host spans. Times in nanoseconds, results in seconds."""
    win = [s for s in spans if s[0] == WINDOW_SPAN]
    if win:
        lo, hi = win[0][1], win[0][2]
    else:
        every = [e for evs in devices.values() for e in evs] + list(spans)
        lo = min(e[1] for e in every)
        hi = max(e[2] for e in every)
    busy_each, ops = [], {}
    gaps_each = []
    for evs in devices.values():
        iv = union(_clip([(a, b) for _, a, b in evs], lo, hi))
        busy_each.append(sum(b - a for a, b in iv))
        gaps, t = [], lo
        for a, b in iv:
            if a > t:
                gaps.append((t, a))
            t = b
        if hi > t:
            gaps.append((t, hi))
        gaps_each.append(gaps)
        for name, a, b in evs:
            a, b = max(a, lo), min(b, hi)
            if b > a:
                ops[name] = ops.get(name, 0.0) + (b - a)
    window_s = (hi - lo) / 1e9
    busy_s = (sum(busy_each) / len(busy_each) / 1e9) if busy_each else 0.0
    timeline = _span_timeline([s for s in spans if s[0] != WINDOW_SPAN])
    idle: Dict[str, float] = {}
    for gaps in gaps_each:
        for k, v in _label_gaps(gaps, timeline).items():
            idle[k] = idle.get(k, 0.0) + v / len(gaps_each)
    return {
        "window_s": window_s,
        "busy_s": busy_s,
        "idle_share": (1.0 - busy_s / window_s) if window_s > 0 else None,
        "devices": len(devices),
        "device_ops": sorted(([k, v / 1e9] for k, v in ops.items()),
                             key=lambda kv: -kv[1])[:top],
        "idle_gaps": sorted(([k, v] for k, v in idle.items()),
                            key=lambda kv: -kv[1])[:top],
    }


def op_name(text: str) -> str:
    """``%fusion.3 = f32[..] fusion(..)`` -> ``fusion.3``: the HLO
    instruction's own name, without its operands."""
    return text.split(" = ", 1)[0].lstrip("%").strip()


def load_events(path) -> Tuple[Dict[str, List[Event]], List[Event]]:
    """Device op events and ``bench.*`` host spans of one trace file."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(str(path))
    devices: Dict[str, List[Event]] = {}
    spans: List[Event] = []
    for plane in data.planes:
        if _DEVICE.match(plane.name):
            lines = {line.name: line for line in plane.lines}
            pick = next((lines[n] for n in _OP_LINES if n in lines), None)
            if pick is None:
                pick = next((lines[n] for n in _MODULE_LINES if n in lines),
                            None)
            if pick is not None:
                devices[plane.name] = [(op_name(e.name), e.start_ns,
                                        e.end_ns) for e in pick.events]
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                spans += [(e.name, e.start_ns, e.end_ns) for e in line.events
                          if e.name.startswith(SPAN_PREFIX)]
    return devices, spans


def reduce_file(path, top: int = 10) -> dict:
    devices, spans = load_events(path)
    if not devices:
        raise ValueError(f"no TPU device plane with ops in {path}")
    return reduce_events(devices, spans, top=top)
