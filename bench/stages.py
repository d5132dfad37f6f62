"""Device time by stage of the render, and the host's time by the
program's own chunk phases, from a profiler trace.

The program names each stage of the level scan with a
``jax.named_scope`` (``ask.query``, ``ask.fill``, ``ask.dwell``,
``ask.compact``, ``ask.subdivide``), which reaches every device
operation's ``tf_op`` (``bench.xplane``), and each phase of a chunk
with a host span ``repro.<phase>`` tagged ``chunk=<index>``. On top of
``bench.trace_reduce``'s numbers, which it leaves as they are, this
module reduces a trace to:

* ``stages``: seconds per device by stage. Each busy instant goes to
  the innermost operation running then (the latest to start), and
  from it to the innermost ``ask.*`` scope of its ``op_name``. An
  operation with no scope of its own, such as a loop body the compiler
  wrote (a scatter expanded into a loop), takes the stage of the
  operation it runs inside; ``unscoped`` takes the rest. So a loop and
  the operations of its body are not counted twice, and the stages sum
  to ``busy_s``;
* ``stages_each``: the same for each device;
* ``idle_gaps``: the idle time by the innermost ``repro.*`` span open
  during it, else by the innermost ``bench.*`` span, else ``outside``
  (``idle_gaps_bench`` keeps ``trace_reduce``'s, by ``bench.*`` alone);
* ``phases``: seconds a chunk of each ``repro.*`` phase in the window,
  over the ``chunks`` that were dispatched in it.

    python3 -m bench.stages --workload <cell> --seed <n> --seconds <s>

runs a cell as ``bench.run --trace 1`` does, with this reduction, and
prints the result line (every metric of the cell, end to end too) with
the stage and phase numbers, and the per-layer numbers they give.
"""

from __future__ import annotations

import time

_T0 = time.monotonic()

import argparse  # noqa: E402
import dataclasses  # noqa: E402
import json  # noqa: E402
import re  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402
from typing import Dict, List, Optional, Sequence, Tuple  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

from bench import trace_reduce, xplane  # noqa: E402

__all__ = ["stage_of", "stage_seconds", "program_idle_gaps", "phases",
           "reduce_file"]

PROGRAM_PREFIX = "repro."
_SCOPE = re.compile(r"ask\.([a-z_]+)")
Op = Tuple[str, float, float, str]  # name, start_ns, end_ns, tf_op
Span = Tuple[str, float, float, Optional[int]]  # name, start, end, chunk


def stage_of(tf_op: str) -> str:
    """The innermost ``ask.<stage>`` scope of an op_name path
    (``vmap(ask.subdivide)/while/body/ask.query/add:`` -> ``query``),
    ``unscoped`` where it names none."""
    found = _SCOPE.findall(tf_op or "")
    return found[-1] if found else "unscoped"


def stage_seconds(ops: Sequence[Op], lo: float, hi: float) -> Dict[str, float]:
    """Seconds of one device's busy time in [lo, hi] by stage: each
    instant goes to the innermost op covering it, an op without a scope
    to the stage of the op it runs inside."""
    evs = sorted(((max(a, lo), min(b, hi), stage_of(tf))
                  for _, a, b, tf in ops if min(b, hi) > max(a, lo)),
                 key=lambda e: (e[0], -e[1]))
    out: Dict[str, float] = {}

    def give(label: str, ns: float) -> None:
        if ns > 0:
            out[label] = out.get(label, 0.0) + ns

    stack: List[Tuple[float, str]] = []  # (end, stage), innermost last
    t = lo
    for a, b, label in evs:
        while stack and stack[-1][0] <= a:
            end, top = stack.pop()
            if end > t:
                give(top, end - t)
                t = end
        if stack:
            give(stack[-1][1], a - t)
            if label == "unscoped":
                label = stack[-1][1]
        t = max(t, a)
        stack.append((b, label))
    while stack:
        end, top = stack.pop()
        if end > t:
            give(top, end - t)
            t = end
    return {k: v / 1e9 for k, v in out.items()}


def _minus(pieces, cover):
    """``pieces`` (start, end, label) with the disjoint sorted intervals
    ``cover`` cut out."""
    out = []
    for a, b, label in pieces:
        for c, d in cover:
            if d <= a or c >= b:
                continue
            if c > a:
                out.append((a, c, label))
            a = max(a, d)
            if a >= b:
                break
        if b > a:
            out.append((a, b, label))
    return out


def program_idle_gaps(ops: Dict[str, Sequence[Op]], spans: Sequence[Span],
                      lo: float, hi: float) -> Dict[str, float]:
    """Idle seconds (mean over devices) by the innermost ``repro.*``
    span open, else the innermost ``bench.*`` one, else ``outside``."""
    prog = [(n, a, b) for n, a, b, _ in spans
            if n.startswith(PROGRAM_PREFIX)]
    harness = [(n, a, b) for n, a, b, _ in spans
               if n.startswith(trace_reduce.SPAN_PREFIX)
               and n != trace_reduce.WINDOW_SPAN]
    first = trace_reduce._span_timeline(prog)
    rest = _minus(trace_reduce._span_timeline(harness),
                  trace_reduce.union((a, b) for a, b, _ in first))
    timeline = sorted(first + rest)
    idle: Dict[str, float] = {}
    for evs in ops.values():
        busy = trace_reduce.union(
            trace_reduce._clip([(a, b) for _, a, b, _ in evs], lo, hi))
        gaps, t = [], lo
        for a, b in busy:
            if a > t:
                gaps.append((t, a))
            t = b
        if hi > t:
            gaps.append((t, hi))
        for k, v in trace_reduce._label_gaps(gaps, timeline).items():
            idle[k] = idle.get(k, 0.0) + v / len(ops)
    return idle


def phases(spans: Sequence[Span], lo: float,
           hi: float) -> Tuple[Dict[str, float], int]:
    """Seconds a chunk of each ``repro.<phase>`` span starting in the
    window, over the chunks whose ``repro.dispatch`` started there."""
    total: Dict[str, float] = {}
    chunks = set()
    for name, a, b, chunk in spans:
        if not name.startswith(PROGRAM_PREFIX) or not lo <= a < hi:
            continue
        phase = name[len(PROGRAM_PREFIX):]
        total[phase] = total.get(phase, 0.0) + (b - a) / 1e9
        if phase == "dispatch":
            chunks.add(chunk)
    n = len(chunks)
    return ({k: v / n for k, v in total.items()} if n else {}), n


def load(path) -> Tuple[Dict[str, List[Op]], List[Span]]:
    """Device ops with their ``tf_op``, and the ``bench.*`` and
    ``repro.*`` host spans with their ``chunk`` argument."""
    from jax.profiler import ProfileData

    paths = xplane.op_paths(path, line=trace_reduce._OP_LINES[0])
    ops: Dict[str, List[Op]] = {}
    spans: List[Span] = []
    for plane in ProfileData.from_file(str(path)).planes:
        if trace_reduce._DEVICE.match(plane.name) and plane.name in paths:
            lines = {line.name: line for line in plane.lines}
            evs = list(lines[trace_reduce._OP_LINES[0]].events)
            tags = paths[plane.name]
            if len(evs) != len(tags) or any(
                    e.name != n for e, (n, _) in zip(evs, tags)):
                raise ValueError(f"{path}: the ops of {plane.name} do not "
                                 "line up with their metadata")
            ops[plane.name] = [(trace_reduce.op_name(e.name), e.start_ns,
                                e.end_ns, tf) for e, (_, tf) in
                               zip(evs, tags)]
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith((trace_reduce.SPAN_PREFIX,
                                          PROGRAM_PREFIX)):
                        chunk = dict(e.stats).get("chunk")
                        spans.append((e.name, e.start_ns, e.end_ns,
                                      None if chunk is None
                                      else int(chunk)))
    return ops, spans


def reduce_file(path, top: int = 10) -> dict:
    """``trace_reduce.reduce_file(path)`` with the stage and phase keys
    above added, and ``idle_gaps`` by the program's spans."""
    out = trace_reduce.reduce_file(path, top=top)
    ops, spans = load(path)
    win = [s for s in spans if s[0] == trace_reduce.WINDOW_SPAN]
    if win:
        lo, hi = win[0][1], win[0][2]
    else:
        every = [(a, b) for evs in ops.values() for _, a, b, _ in evs]
        every += [(a, b) for _, a, b, _ in spans]
        lo, hi = min(a for a, _ in every), max(b for _, b in every)
    each = {name: stage_seconds(evs, lo, hi) for name, evs in ops.items()}
    mean: Dict[str, float] = {}
    for per in each.values():
        for k, v in per.items():
            mean[k] = mean.get(k, 0.0) + v / len(each)
    per_chunk, chunks = phases(spans, lo, hi)
    out.update(
        idle_gaps_bench=out["idle_gaps"],
        idle_gaps=sorted(([k, v] for k, v in program_idle_gaps(
            ops, spans, lo, hi).items()), key=lambda kv: -kv[1])[:top],
        stages=mean, stages_each=each, phases=per_chunk, chunks=chunks)
    return out


# the host phases the program spends outside the device wait
HOST_PHASES = ("plan", "dispatch", "stats", "copy", "retry", "observe")


def derived(line: dict, reduced: dict) -> Dict[str, Optional[float]]:
    """The per-layer numbers the stages and phases give: each stage's
    share of ``device_ms_per_frame``, and the host milliseconds a chunk
    outside the wait. None where the program has no such scope or
    span."""
    metrics = {k: v["value"] for k, v in line["metrics"].items()}
    busy, stages = reduced["busy_s"], reduced["stages"]
    per_frame = metrics.get("device_ms_per_frame")

    def share(*names):
        if per_frame is None or not busy or not all(n in stages
                                                    for n in names):
            return None
        return per_frame * sum(stages[n] for n in names) / busy

    ph = reduced["phases"]
    return {"dwell_ms_per_frame": share("dwell"),
            "query_ms_per_frame": share("query"),
            "fill_ms_per_frame": share("fill"),
            "worklist_ms_per_frame": share("compact", "subdivide"),
            "unscoped_ms_per_frame": share("unscoped"),
            "host_ms_per_chunk.stream": (
                1e3 * sum(ph.get(p, 0.0) for p in HOST_PHASES)
                if ph else None)}


def main(argv=None) -> int:
    from bench import run, spec

    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args(argv)
    cell = spec.load_cell(args.workload, ROOT)
    devices, why = run._chips(cell)
    if devices is None:
        return run._fail(why)
    run._compile_cache(ROOT)
    # a traced run reads the per-layer metrics; read the end-to-end too
    cell = dataclasses.replace(cell,
                               per_layer=cell.per_layer + cell.end_to_end)
    reduced: dict = {}

    def reduce(path):
        reduced.update(reduce_file(path))
        return reduced

    line = run.run_cell(cell, seed=args.seed, seconds=args.seconds,
                        trace=True, devices=devices, t0=_T0, reduce=reduce)
    line["stages"] = {k: reduced[k] for k in (
        "stages", "stages_each", "phases", "chunks", "idle_gaps_bench")}
    line["derived"] = derived(line, reduced)
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
