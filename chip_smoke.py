"""Smoke run of the served render path on a TPU: one chip, or four.

    python chip_smoke.py             # one chip: frame stream, then tiles
    python chip_smoke.py --chips 4   # the sharded frames mesh vs one device

Drives ``RenderService`` and ``TileService`` through the calls a user
makes, at the size a user renders, and checks every canvas pixel for
pixel against the exhaustive render of its window on the same chip.

* Phase 1 (frames): a 16-frame Mandelbrot zoom at n = 4096 (the paper's
  g = 4, r = 2, B = 32, max_dwell = 512), streamed through
  ``RenderService`` with the pooled and then the per-frame scan engine.
* Phase 2 (tiles): a pan/zoom viewport trace of 256 x 256 tiles (the
  OpenStreetMap tile size ``TileAddress`` follows) through
  ``TileService``, then the same trace again, served from the cache.
* ``--chips 4``: 32 frames, 8 per device, through a four-device
  ``frames`` mesh with both engines, compared bit for bit with the same
  frames rendered on a one-device mesh. Only this phase runs.

Without a TPU the script exits nonzero before any work. Any failed
check exits nonzero too, and then the last line is not printed. On
success the last line of stdout is one JSON object naming the device.
The times printed are those of one cold run, compilation included:
smoke, not a benchmark.
"""

from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

import numpy as np

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

from repro.launch.mesh import make_frames_mesh  # noqa: E402
from repro.launch.render_service import RenderService, zoom_bounds  # noqa: E402
from repro.launch.tiles import TileService  # noqa: E402
from repro.workloads import FrameProblem, exhaustive  # noqa: E402

ENGINES = ("ask_pooled", "ask_scan")
SEAHORSE = (-0.7436447860, 0.1318252536)  # zoom target of zoom_bounds()

# Pixels of phase 1 (all 16 frames, per engine) allowed to differ from
# the exhaustive render. Mariani-Silver fills a region from its sampled
# border; a lone pixel whose float32 orbit escapes inside a region whose
# border never does is filled wrongly. The CPU rehearsal of these exact
# frames counted 5 per engine, one each in frames 1, 6, 7, 9 and 15,
# every one inside a 64 x 64 region whose border is all max_dwell; the
# chip takes no looser limit.
FRAME_SAMPLING_MISMATCHES = 5


def log(*parts) -> None:
    print(*parts, flush=True)


_COMPILE_EVENTS = ("/jax/core/compile/jaxpr_trace_duration",
                   "/jax/core/compile/jaxpr_to_mlir_module_duration",
                   "/jax/core/compile/backend_compile_duration")
_compile_s = 0.0


def _on_duration_event(event, duration, **_):
    global _compile_s
    if event in _COMPILE_EVENTS:
        _compile_s += duration


def compile_seconds() -> float:
    """Seconds JAX has spent tracing, lowering and compiling since
    ``main`` started listening to its monitoring events (so one cold run
    gives both the compile and the wall time; 0 when not listening)."""
    return _compile_s


class Timer:
    """Wall and compile seconds accumulated over the ``with`` blocks."""

    def __init__(self):
        self.wall_s = 0.0
        self.compile_s = 0.0

    def __enter__(self):
        self._t0, self._c0 = time.perf_counter(), compile_seconds()
        return self

    def __exit__(self, *exc):
        self.wall_s += time.perf_counter() - self._t0
        self.compile_s += compile_seconds() - self._c0

    def as_dict(self) -> dict:
        return {"compile_s": self.compile_s, "wall_s": self.wall_s}


def reference(problem, bounds) -> np.ndarray:
    """Exhaustive render of one window: every pixel iterated, no
    subdivision. It takes the window as float32 runtime data, as the
    service does, so both map each pixel to the same plane point."""
    canvas, _ = exhaustive(problem.n, max_dwell=problem.max_dwell,
                           bounds=bounds, policy=problem.policy,
                           workload=problem.workload)
    return np.asarray(canvas)


def _render_stats(rs) -> dict:
    return {"frames": rs.frames, "chunks": rs.chunks,
            "dispatches": rs.dispatches,
            "dispatches_per_chunk": rs.dispatches_per_chunk,
            "overflow_dropped": rs.overflow_dropped, "retries": rs.retries,
            "ring_rows": rs.ring_rows, "program_traces": rs.program_traces}


def _serving_failures(label, stats) -> list:
    out = []
    if stats["overflow_dropped"] != 0:
        out.append(f"{label}: overflow_dropped={stats['overflow_dropped']}")
    if stats["dispatches_per_chunk"] != 1.0:
        out.append(f"{label}: dispatches_per_chunk="
                   f"{stats['dispatches_per_chunk']}")
    return out


def phase_frames(*, n=4096, B=32, max_dwell=512, frames=16, chunk=8,
                 max_mismatched=0):
    """Stream one zoom through both engines on a one-device mesh; every
    canvas must equal the exhaustive render of its window, but for at
    most ``max_mismatched`` pixels per engine. Returns (report dict,
    failure list)."""
    prob = FrameProblem(n=n, g=4, r=2, B=B, max_dwell=max_dwell)
    bounds = list(zoom_bounds(frames))
    with Timer() as ref_timer:
        refs = [reference(prob, b) for b in bounds]
    report = {"reference": ref_timer.as_dict()}
    failures = []
    mesh = make_frames_mesh(1)
    for engine in ENGINES:
        svc = RenderService(prob, mesh=mesh, engine=engine,
                            chunk_frames=chunk, pipeline_depth=2,
                            feedback=True)
        with Timer() as timer:
            canvases, rs = svc.render(bounds)
        stats = _render_stats(rs)
        stats["mismatched_pixels"] = int(sum(
            np.count_nonzero(canvases[i] != refs[i]) for i in range(frames)))
        stats.update(timer.as_dict())
        report[engine] = stats
        failures += _serving_failures(f"frames/{engine}", stats)
        if stats["mismatched_pixels"] > max_mismatched:
            failures.append(f"frames/{engine}: "
                            f"{stats['mismatched_pixels']} pixels differ "
                            "from the exhaustive render (limit "
                            f"{max_mismatched})")
    return report, failures


def tile_trace(ref_bounds, depth):
    """A pan over the whole reference window at ``depth`` (tile-wide
    viewports offset by half a tile, so each touches 2 x 2 tiles and
    neighbours share two), then two zoom steps onto the seahorse valley
    at depth + 1 and depth + 2. Misses: 4**depth tiles from the pan,
    up to 8 from the zoom."""
    re0, im0, re1, im1 = (float(x) for x in ref_bounds)
    tw = (re1 - re0) / 2 ** depth
    th = (im1 - im0) / 2 ** depth
    views = []
    for row in range(2 ** (depth - 1)):
        cols = range(2 ** depth - 1)
        if row % 2:
            cols = reversed(cols)  # serpentine, like a user panning
        for col in cols:
            x = re0 + (col + 0.5) * tw
            y = im0 + (2 * row + 0.5) * th
            views.append((x, y, x + tw, y + th))
    cx, cy = SEAHORSE
    for k in (1, 2):
        w, h = tw / 2 ** k, th / 2 ** k
        views.append((cx - w / 2, cy - h / 2, cx + w / 2, cy + h / 2))
    return views


def phase_tiles(*, n=256, B=16, max_dwell=512, depth=3, chunk=8):
    """Serve the pan/zoom trace twice through ``TileService``: the first
    pass renders every tile once (misses), the second is served from the
    cache (hits). Every tile must equal the exhaustive render of its
    ``TileAddress.bounds()``. Returns (report dict, failure list)."""
    prob = FrameProblem(n=n, g=4, r=2, B=B, max_dwell=max_dwell)
    svc = RenderService(prob, mesh=make_frames_mesh(1), chunk_frames=chunk,
                        pipeline_depth=2, feedback=True)
    tiles = TileService(svc)
    views = tile_trace(prob.bounds, depth)
    refs = {}
    ref_timer = Timer()
    report = {"views_per_pass": len(views)}
    failures = []
    for name in ("first_pass", "replay"):
        hits = misses = dispatches = retries = mismatched = 0
        timer = Timer()
        for v in views:
            with timer:
                r = tiles.serve(v)
            hits += r.hits
            misses += r.misses
            dispatches += r.dispatches
            retries += sum(c.retries for c in r.chunks)
            for a in r.addresses:
                if a not in refs:
                    with ref_timer:
                        refs[a] = reference(prob, a.bounds(prob.bounds))
                mismatched += int(np.count_nonzero(r.tiles[a] != refs[a]))
        report[name] = {"hits": hits, "misses": misses,
                        "dispatches": dispatches, "retries": retries,
                        "mismatched_pixels": mismatched, **timer.as_dict()}
        if mismatched:
            failures.append(f"tiles/{name}: {mismatched} pixels differ "
                            "from the exhaustive render")
    report["distinct_tiles"] = len(refs)
    report["reference"] = ref_timer.as_dict()
    first, replay = report["first_pass"], report["replay"]
    if first["misses"] != len(refs):
        failures.append(f"tiles: first pass rendered {first['misses']} "
                        f"tiles for {len(refs)} distinct addresses")
    if replay["misses"] or replay["dispatches"]:
        failures.append(f"tiles: replay missed {replay['misses']} tiles "
                        f"in {replay['dispatches']} dispatches")
    if replay["hits"] != first["hits"] + first["misses"]:
        failures.append(f"tiles: replay served {replay['hits']} hits for "
                        f"{first['hits'] + first['misses']} requests")
    return report, failures


def phase_mesh(*, devices=4, n=4096, B=32, max_dwell=512,
               frames_per_device=8):
    """The same zoom through a ``devices``-wide frames mesh and through a
    one-device mesh, both engines; the canvases must be bit-identical
    and every chunk one dispatch. Returns (report dict, failure list)."""
    prob = FrameProblem(n=n, g=4, r=2, B=B, max_dwell=max_dwell)
    bounds = list(zoom_bounds(devices * frames_per_device))
    report = {}
    failures = []
    for engine in ENGINES:
        canvases = {}
        for ndev in (1, devices):
            svc = RenderService(prob, mesh=make_frames_mesh(ndev),
                                engine=engine,
                                chunk_frames=ndev * frames_per_device,
                                pipeline_depth=2, feedback=True)
            with Timer() as timer:
                canvases[ndev], rs = svc.render(bounds)
            stats = {**_render_stats(rs), **timer.as_dict()}
            report[f"{engine}/{ndev}dev"] = stats
            failures += _serving_failures(f"mesh/{engine}/{ndev}dev", stats)
        differ = int(np.count_nonzero(canvases[1] != canvases[devices]))
        report[f"{engine}/differing_pixels"] = differ
        if differ:
            failures.append(f"mesh/{engine}: {differ} pixels differ between "
                            f"1 and {devices} devices")
    return report, failures


def _print_report(phase, report):
    for key, val in report.items():
        if isinstance(val, dict):
            val = " ".join(f"{k}={v}" for k, v in val.items())
        log(f"{phase} {key}: {val}")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: only the sharded frames-mesh phase")
    args = ap.parse_args(argv)

    import jax
    import jax.monitoring

    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"chip_smoke: no TPU (JAX platform {devices[0].platform!r})",
              file=sys.stderr)
        return 2
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} but JAX sees "
              f"{len(devices)} device(s)", file=sys.stderr)
        return 2

    from repro.kernels.policy import Backend
    from repro.launch.compile_cache import enable_compile_cache

    log(f"device_kind={devices[0].device_kind} count={len(devices)}")
    log(f"compile cache: {enable_compile_cache()}")
    policy = FrameProblem(n=256).policy
    log(f"kernel policy: {policy} "
        f"(interpret={policy.resolve_interpret()})")
    if policy.backend is not Backend.JNP or policy.resolve_interpret():
        print("chip_smoke: the default policy must be the compiled jnp "
              "lowering", file=sys.stderr)
        return 1
    log("timings below: one cold run, compilation included -- "
        "smoke, not a benchmark")
    jax.monitoring.register_event_duration_secs_listener(_on_duration_event)

    if args.chips == 4:
        phases = [("mesh", lambda: phase_mesh(devices=4))]
        log("mesh: n=4096 g=4 r=2 B=32 max_dwell=512, 32 frames, "
            "8 per device, 64 MiB int32 per frame")
    else:
        phases = [("frames", lambda: phase_frames(
                      max_mismatched=FRAME_SAMPLING_MISMATCHES)),
                  ("tiles", phase_tiles)]
        log("frames: n=4096 g=4 r=2 B=32 max_dwell=512, 16 frames, "
            "chunk 8, 64 MiB int32 per frame")
        log("tiles: 256x256 tiles, g=4 r=2 B=16 max_dwell=512, "
            "depth-3 pan + 2 zoom steps, then a replay")
    failures = []
    for name, run in phases:
        report, bad = run()
        _print_report(name, report)
        failures += bad
    stats = devices[0].memory_stats() or {}
    log(f"peak_bytes_in_use={stats.get('peak_bytes_in_use', 'not reported')}")
    if failures:
        for f in failures:
            print(f"chip_smoke: FAILED {f}", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": devices[0].platform, "kind": devices[0].device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
