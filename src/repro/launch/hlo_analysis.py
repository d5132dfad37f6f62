"""Post-compile HLO analysis: collective bytes, loop-weighted.

``cost_analysis()`` has no collective term, so the roofline's third term is
derived here by parsing the optimized HLO (``compiled.as_text()``):
every ``all-reduce / all-gather / reduce-scatter / all-to-all /
collective-permute`` (sync or ``-start`` async form) contributes its result
bytes.

Loop weighting: scan-over-layers (and the recurrent time scans) lower to
``while`` ops whose bodies execute ``trip_count`` times, but appear once in
the text. We recover trip counts from each while's condition computation
(the ``compare(induction, constant)`` pattern) and propagate weights from
ENTRY through nested whiles, so a collective inside the layer scan counts
``num_groups`` times and one inside a mamba time-scan counts ``seq_len``
times. Unresolvable conditions get weight 1 and are reported in
``unresolved`` (EXPERIMENTS.md flags any cell where that happens).
"""

from __future__ import annotations

import dataclasses
import re
from collections import defaultdict
from typing import Dict, List, Optional, Tuple

def cost_analysis_dict(compiled) -> Dict[str, float]:
    """``compiled.cost_analysis()`` as a plain dict (empty when the
    backend reports nothing), so callers can do ``ca.get("flops", 0.0)``.
    JAX 0.9 returns one flat properties dict for the executable."""
    ca = compiled.cost_analysis()
    return {} if ca is None else dict(ca)


_DTYPE_BYTES = {
    "pred": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2, "s32": 4, "u32": 4,
    "s64": 8, "u64": 8, "f8e4m3fn": 1, "f8e5m2": 1, "bf16": 2, "f16": 2,
    "f32": 4, "f64": 8, "c64": 8, "c128": 16,
}

_COLL_RE = re.compile(
    r"\b(all-reduce|all-gather|reduce-scatter|all-to-all|collective-permute)"
    r"(-start)?\(")
_SHAPE_RE = re.compile(r"(\w+)\[([\d,]*)\]")
_WHILE_RE = re.compile(
    r"while\(.*?\), condition=([%\w\.\-_]+), body=([%\w\.\-_]+)")
_COMP_HDR = re.compile(r"^(?:ENTRY\s+)?(%?[\w\.\-_]+)\s*(?:\(.*)?\{\s*$")


def _shape_bytes(result_part: str) -> int:
    total = 0
    for dt, dims in _SHAPE_RE.findall(result_part):
        if dt not in _DTYPE_BYTES:
            continue
        n = 1
        if dims:
            for d in dims.split(","):
                n *= int(d)
        total += n * _DTYPE_BYTES[dt]
    return total


def split_computations(hlo: str) -> Dict[str, List[str]]:
    """computation name -> its lines. Entry computation key: '__entry__'."""
    comps: Dict[str, List[str]] = {}
    cur: Optional[str] = None
    for line in hlo.splitlines():
        if not line.startswith(" ") and line.rstrip().endswith("{"):
            m = _COMP_HDR.match(line.strip())
            if m:
                name = m.group(1).lstrip("%")
                if line.lstrip().startswith("ENTRY"):
                    name = "__entry__:" + name
                cur = name
                comps[cur] = []
                continue
        if cur is not None:
            if line.startswith("}"):
                cur = None
            else:
                comps[cur].append(line)
    return comps


def _trip_count(cond_lines: List[str]) -> Optional[int]:
    """Recover the while trip count from its condition computation."""
    consts: Dict[str, int] = {}
    compare_ops: List[Tuple[str, str, str]] = []
    for ln in cond_lines:
        m = re.search(r"(%[\w\.\-_]+) = s32\[\] constant\((\d+)\)", ln)
        if m:
            consts[m.group(1)] = int(m.group(2))
        m = re.search(
            r"compare\((%[\w\.\-_]+), (%[\w\.\-_]+)\), direction=(\w+)", ln)
        if m:
            compare_ops.append((m.group(1), m.group(2), m.group(3)))
    for a, b, direction in compare_ops:
        if direction == "LT" and b in consts:
            return consts[b]
        if direction == "GT" and a in consts:
            return consts[a]
    if len(consts) == 1:
        return next(iter(consts.values()))
    return None


@dataclasses.dataclass
class CollectiveReport:
    total_bytes: float
    by_kind: Dict[str, float]
    op_count: int
    unresolved_loops: int

    def as_dict(self):
        return {"total_bytes": self.total_bytes, "by_kind": dict(self.by_kind),
                "op_count": self.op_count,
                "unresolved_loops": self.unresolved_loops}


def collective_bytes(hlo: str) -> CollectiveReport:
    comps = split_computations(hlo)
    # resolve entry name
    entry = next((k for k in comps if k.startswith("__entry__:")), None)
    if entry is None and comps:
        entry = next(iter(comps))

    # computation -> list of (body_comp, trip or None)
    calls: Dict[str, List[Tuple[str, Optional[int]]]] = defaultdict(list)
    for name, lines in comps.items():
        for ln in lines:
            for m in _WHILE_RE.finditer(ln):
                cond = m.group(1).lstrip("%")
                body = m.group(2).lstrip("%")
                trip = _trip_count(comps.get(cond, []))
                calls[name].append((body, trip))

    # propagate weights from entry through nested whiles
    weights: Dict[str, float] = defaultdict(float)
    unresolved = 0
    stack = [(entry, 1.0)]
    seen_guard = 0
    while stack:
        name, w = stack.pop()
        if name is None or seen_guard > 10000:
            break
        seen_guard += 1
        weights[name] += w
        for body, trip in calls.get(name, ()):
            if trip is None:
                unresolved += 1
                trip_eff = 1
            else:
                trip_eff = trip
            stack.append((body, w * trip_eff))

    by_kind: Dict[str, float] = defaultdict(float)
    op_count = 0
    for name, lines in comps.items():
        w = weights.get(name, 0.0)
        if w <= 0:
            continue
        for ln in lines:
            m = _COLL_RE.search(ln)
            if not m or "=" not in ln:
                continue  # (-done forms don't match the regex: no '(' after)
            result_part = ln.split("=", 1)[1].split(m.group(1))[0]
            nbytes = _shape_bytes(result_part)
            if m.group(2):  # async -start: result tuple = (input, output)
                nbytes /= 2
            by_kind[m.group(1)] += nbytes * w
            op_count += 1
    total = float(sum(by_kind.values()))
    return CollectiveReport(total, dict(by_kind), op_count, unresolved)


def loop_weighted_flops(hlo: str, raw_flops: float) -> Dict[str, float]:
    """Report the while-loop structure so flop correction is transparent:
    returns {comp_name_weight: trip} for every resolved loop."""
    comps = split_computations(hlo)
    out = {}
    for name, lines in comps.items():
        for ln in lines:
            for m in _WHILE_RE.finditer(ln):
                cond = m.group(1).lstrip("%")
                trip = _trip_count(comps.get(cond, []))
                out[m.group(2).lstrip("%")] = trip if trip is not None else -1
    return out
