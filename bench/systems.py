"""Drive the system under test the way its users call it.

A configuration's ``system`` names one of the deployments below; each
builds the program's own service from the configuration's sizes, runs
its warm-up, then its measured window, and hands back a ``Run``: what
the metric readers (``bench/metrics/``) and the check (``bench.check``)
read. From the program this module takes only the service entry points
(``RenderService.stream_chunks``, ``TileService.serve``), their stats
and their counters.

In a traced run every call into a layer is wrapped in a host span
(``jax.profiler.TraceAnnotation``) named ``bench.<call>``, so the trace
reduction can say what the host was doing in each idle gap on the
device.
"""

from __future__ import annotations

import contextlib
import dataclasses
import time
from typing import Any, Callable, Dict, List, Optional

import numpy as np

from bench import generator
from bench.check import Answer

__all__ = ["Run", "Spans", "SYSTEMS", "now"]

now = time.perf_counter


class Spans:
    """Host spans of a traced run; no-ops otherwise."""

    def __init__(self, on: bool):
        self.on = bool(on)

    def __call__(self, name: str):
        if not self.on:
            return contextlib.nullcontext()
        import jax

        return jax.profiler.TraceAnnotation(f"bench.{name}")


class CompileCounter:
    """Counts JAX's compile events (tracing, compiling, and loading from
    the persistent cache) through its monitoring hooks."""

    EVENTS = ("/jax/core/compile/jaxpr_trace_duration",
              "/jax/core/compile/backend_compile_duration",
              "/jax/compilation_cache/cache_retrieval_time_sec")

    def __init__(self):
        import jax.monitoring

        self.count = 0
        self.events: Dict[str, list] = {}
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, duration, **_):
        if event.startswith("/jax/core/compile/") or event.startswith(
                "/jax/compilation_cache/"):
            seen = self.events.setdefault(event.rsplit("/", 1)[1], [0, 0.0])
            seen[0] += 1
            seen[1] += float(duration)
        if event in self.EVENTS:
            self.count += 1

    def by_event(self) -> Dict[str, list]:
        """``{event: [count, seconds]}`` so far."""
        return {k: list(v) for k, v in self.events.items()}


@dataclasses.dataclass
class Run:
    """Everything one run measured, for the metric readers."""

    system: str
    setup_s: float = 0.0
    window_s: float = 0.0  # window start to the last completion
    frames: int = 0  # frames completed in the window
    latencies_ms: List[float] = dataclasses.field(default_factory=list)
    lateness_ms: List[float] = dataclasses.field(default_factory=list)
    chunks: List[dict] = dataclasses.field(default_factory=list)
    hits: int = 0
    misses: int = 0
    compiles_in_window: int = 0
    answers: List[Answer] = dataclasses.field(default_factory=list)
    trace: Optional[dict] = None  # bench.trace_reduce.reduce(...) result
    reference_s_per_answer: Optional[float] = None
    notes: Dict[str, Any] = dataclasses.field(default_factory=dict)


def _chunk_record(result, engine: str, devices: int) -> dict:
    """What a chunk or miss batch tells the readers: its host timing
    (``ChunkStats``) and the level scan's counts (``ASKStats``)."""
    st = result.stats
    live = (sum(sum(int(c) for c in counts) for counts in st.region_counts)
            + sum(int(x) for x in st.frame_leaf_counts))
    return {"frames": int(result.chunk.frames),
            "dispatch_s": float(result.chunk.dispatch_s),
            "fetch_s": float(result.chunk.fetch_s),
            "retries": int(result.chunk.retries),
            "caps": [int(c) for c in st.olt_caps],
            "live": int(live), "engine": engine, "devices": devices}


def _problem(config: dict):
    from repro.workloads import FrameProblem

    kw = {}
    if config.get("window") is not None:
        kw["bounds"] = tuple(float(x) for x in config["window"])
    return FrameProblem(n=int(config["n"]), g=int(config["g"]),
                        r=int(config["r"]), B=int(config["B"]),
                        max_dwell=int(config["max_dwell"]),
                        workload=config["workload"], **kw)


def _pattern(traffic: dict, want: str) -> None:
    if traffic.get("pattern") != want:
        raise ValueError(f"this system serves {want!r} traffic, the mix "
                         f"is {traffic.get('pattern')!r}")


def _service(config: dict, chips: int):
    from repro.launch.mesh import make_frames_mesh
    from repro.launch.render_service import RenderService

    return RenderService(_problem(config), mesh=make_frames_mesh(chips),
                         engine=config["engine"],
                         feedback=bool(config["feedback"]),
                         pipeline_depth=int(config["pipeline_depth"]))


class FrameStream:
    """A zoom video streamed closed loop through
    ``RenderService.stream_chunks``: the stream refills itself as each
    chunk is consumed, and each chunk's canvases are fetched to numpy."""

    name = "frame_stream"

    def __init__(self, config: dict, traffic: dict, chips: int, seed: int,
                 spans: Spans):
        _pattern(traffic, "frame_stream")
        self.config, self.spans, self.chips = config, spans, chips
        self.svc = _service(config, chips)
        self.plan = generator.frame_plan(config, traffic, seed,
                                         chunk=self.svc.chunk_frames)

    def _consume(self, windows, run: Optional[Run], on_chunk=None):
        it = self.svc.stream_chunks(windows)
        done = []
        while True:
            with self.spans("stream_next"):
                result = next(it, None)
            if result is None:
                return done
            with self.spans("fetch"):
                host = np.asarray(result.canvases)
            done.append(host)
            if run is not None:
                run.chunks.append(_chunk_record(
                    result, self.config["engine"], self.chips))
            if on_chunk is not None:
                on_chunk(host)

    def warmup(self) -> None:
        self._consume(self.plan.windows[:self.plan.warmup], None)

    def window(self, seconds: float, run: Run) -> None:
        plan, chunk = self.plan, self.plan.chunk
        offered: List[int] = []
        t0 = now()

        def source():
            for i in range(plan.warmup, len(plan.windows)):
                # whole chunks only: a part chunk is a new program
                if (i - plan.warmup) % chunk == 0 and now() - t0 >= seconds:
                    return
                offered.append(i)
                yield plan.windows[i]

        canvases: List[np.ndarray] = []

        def on_chunk(host):
            canvases.extend(host)
            run.frames += host.shape[0]
            run.window_s = now() - t0

        with self.spans("window"):
            self._consume(source(), run, on_chunk)
        for j, i in enumerate(offered):
            run.answers.append(Answer(plan.windows[i],
                                      canvases[j] if j < len(canvases)
                                      else None))

    def close(self) -> None:
        self.svc = None


class TileServer:
    """A slippy-map tile server: viewports served open loop through
    ``TileService.serve``, one at a time as they fall due."""

    name = "tile_server"

    def __init__(self, config: dict, traffic: dict, chips: int, seed: int,
                 spans: Spans, seconds: float):
        from repro.launch.tiles import TileService
        from repro.workloads.options import TileOptions

        _pattern(traffic, "viewport_sessions")
        self.config, self.spans = config, spans
        self.svc = _service(config, chips)
        self.tiles = TileService(self.svc, options=TileOptions(
            max_bytes=int(config["cache_bytes"])))
        self.plan = generator.viewport_plan(config, traffic, seed, seconds)
        if spans.on:
            self._wrap_calls()

    def _wrap_calls(self) -> None:
        """Host spans around the tile path's calls into the render
        service: ``dispatch_planned`` and each handle's ``finalize``."""
        spans, svc = self.spans, self.svc
        inner = svc.dispatch_planned

        def dispatch_planned(*a, **kw):
            with spans("dispatch_planned"):
                handle = inner(*a, **kw)
            fin = handle.finalize

            def finalize():
                with spans("finalize"):
                    return fin()

            handle.finalize = finalize
            return handle

        svc.dispatch_planned = dispatch_planned

    def _serve(self, req):
        with self.spans("serve"):
            return self.tiles.serve(generator.viewport_window(self.config,
                                                              req))

    def prime(self) -> None:
        """Compile every chunk program the window can ask for.

        The feedback path sizes each miss batch's ring from the
        estimator's quantised prediction, pads the batch to a power of
        two, and retries an overflowing batch at doubled capacities. So
        for each class the estimator can predict, and each batch size, a
        batch of the configuration's dense tiles (every region of every
        level live) is served through ``dispatch_planned`` by a service
        whose estimator predicts that class: its retries climb the
        whole doubling chain. The programs land in the program's own
        cache, which the served service shares."""
        from repro.core.feedback import OccupancyEstimator
        from repro.launch.render_service import RenderService

        prob = self.svc.problem_for("")
        wl = prob.workload
        classes = set()
        lo, hi = float(wl.prior_band[2]), float(wl.prior_band[0])
        for i in range(101):
            est = OccupancyEstimator()
            est.observe_value(0.0, lo + (hi - lo) * i / 100, workload=wl)
            classes.add(est.predict_quantized(0.0, workload=wl))
        dense = [generator.tile_window(self.config, tuple(t))
                 for t in self.config["dense_tiles"]]
        counts = range(1, self.svc.chunk_frames + 1)
        for p in sorted(classes):
            est = OccupancyEstimator()
            for b in range(2 * 32):  # every depth bucket, 0 to 32 levels
                est.observe_value(b / 2, p, workload=wl)
            svc = RenderService(prob, mesh=self.svc.mesh,
                                engine=self.config["engine"], feedback=est,
                                adapt=False,
                                pipeline_depth=self.svc.pipeline_depth)
            for k in counts:
                svc.dispatch_planned([dense[i % len(dense)]
                                      for i in range(k)]).finalize()

    def warmup(self) -> None:
        self.prime()
        for req in self.plan.warmup:
            self._serve(req)

    def window(self, seconds: float, run: Run,
               grace_s: float = 60.0) -> None:
        t0 = now()
        with self.spans("window"):
            for req in self.plan.window:
                due = t0 + req.due
                wait = due - now()
                if wait > 0:
                    time.sleep(wait)
                start = now()
                if start - t0 > seconds + grace_s:
                    resp = None  # never served: every tile unanswered
                else:
                    resp = self._serve(req)
                done = now()
                self._record(req, resp, due, start, done, run)
                run.window_s = done - t0

    def _record(self, req, resp, due, start, done, run: Run) -> None:
        got = {}
        if resp is not None:
            got = {(a.depth, a.iy, a.ix): c for a, c in resp.tiles.items()}
            run.hits += resp.hits
            run.misses += resp.misses
            for c in resp.chunks:
                run.chunks.append({"frames": int(c.frames),
                                   "dispatch_s": float(c.dispatch_s),
                                   "fetch_s": float(c.fetch_s),
                                   "retries": int(c.retries)})
        # a viewport never served counts with the wait until it was given up
        run.latencies_ms.append((done - due) * 1e3)
        run.lateness_ms.append((start - due) * 1e3)
        for tile in generator.viewport_tiles(req):
            run.answers.append(Answer(generator.tile_window(self.config,
                                                            tile),
                                      got.get(tile)))

    def close(self) -> None:
        self.tiles = self.svc = None


SYSTEMS: Dict[str, Callable] = {
    "frame_stream": lambda cfg, trf, chips, seed, spans, seconds:
        FrameStream(cfg, trf, chips, seed, spans),
    "tile_server": TileServer,
}
