"""Sharded frame-rendering service: stream arbitrarily long zoom
sequences through the single-dispatch sharded ASK engine, with the host
I/O of chunk k overlapped against the device compute of chunk k+1.

A zoom trajectory can be millions of frames -- far more than one batch
should hold -- so the service chunks the stream into fixed-size,
device-divisible batches and pushes each chunk through the sharded scan
pipeline (``core.ask.dispatch_ask_scan_sharded``, or ``core.pooled.
dispatch_ask_pooled_sharded`` for ``engine="ask_pooled"``):

  * chunk size is a multiple of the mesh device count, so every device
    owns ``chunk/devices`` frames and the GSPMD partition is collective-free;
  * the ragged tail chunk is padded back up to the SAME chunk width
    (``pad_to=chunk_frames``), so every chunk -- tail included -- hits the
    one compiled program in the jitted-pipeline cache
    (``core.ask._PIPELINE_CACHE``): one XLA dispatch per chunk, zero
    retracing for the life of the service;
  * padded frames are masked out of canvases and stats, so
    the streamed output is bit-identical to rendering each frame alone;
  * with ``pipeline_depth >= 2`` (the default is 2: double buffering) the
    service exploits JAX *async dispatch*: up to ``pipeline_depth``
    chunks are in flight at once, so while the host blocks on
    ``finalize()`` of chunk k -- and while the consumer of the stream
    converts, encodes, or writes chunk k -- the devices are already
    computing chunks k+1..k+depth-1. ``ChunkStats`` records per-chunk
    enqueue/fetch times; a pipelined run's ``wall_s`` measured against a
    synchronous run's ``busy_s`` (its serial per-chunk cost) quantifies
    the overlap. ``pipeline_depth=1`` restores the fully synchronous
    PR-2 behaviour (dispatch, block, yield, repeat);

  * with ``feedback=`` set, the service closes the occupancy loop
    (planner-aware chunking): each chunk's ring capacities are re-planned
    from a ``core.feedback.OccupancyEstimator`` BEFORE dispatch -- the
    zoom-depth prior on the cold-start chunk, the EWMA of the previous
    chunks' measured ``region_counts`` afterwards -- and a boundary-aware
    chunker cuts a chunk early when the predicted capacity class jumps,
    so a trajectory's deep tail gets its own (hotter) compiled program
    instead of inflating every frame's ring. Predictions are quantized
    onto the estimator's ``p_quantum`` grid and dispatch widths are
    power-of-two bucketed (``_pad_width``), so the compiled-program
    cache stays keyed on (chunk width, capacity signature) with both
    factors bounded for the life of the service.
    Frames that still overflow are retried (``planner.retry_until_fit``)
    before the chunk is yielded: ``overflow_dropped == 0`` holds chunk
    by chunk, and the measured counts that come back -- retries
    included -- are what the estimator folds in.

``python -m repro.launch.render_service --frames 64 --n 256`` runs a
self-timed trajectory end to end and prints both pipelined and
synchronous wall times (``--feedback`` switches on the closed loop).
"""

from __future__ import annotations

import argparse
import collections
import contextlib
import dataclasses
import itertools
import json
import os
import time
from pathlib import Path
from typing import Any, Iterable, Iterator, Mapping, NamedTuple, Tuple, Union

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.ask import ASKStats, dispatch_ask_scan_sharded, scan_capacities
from repro.core.feedback import OccupancyEstimator
from repro.core.olt import next_pow2
from repro.core.planner import (escalate_capacities, retry_until_fit,
                                worst_case_capacities, zoom_depth)
from repro.core.pooled import (dispatch_ask_pooled_sharded,
                               pooled_retry_capacities, shard_capacities)
from repro.launch.mesh import make_frames_mesh

# frames each device renders per dispatch when the caller doesn't pin a
# chunk size; bigger amortises dispatch overhead, smaller bounds latency
DEFAULT_FRAMES_PER_DEVICE = 4

# dispatched-but-not-finalised chunks the pipelined stream keeps in
# flight: 2 == classic double buffering (compute k+1 behind fetch of k)
DEFAULT_PIPELINE_DEPTH = 2

# a chunk's phases in the order it meets them: each is a profiler span
# ``repro.<phase>`` and a ``ChunkStats.<phase>_s`` field
PHASES = ("plan", "dispatch", "wait", "stats", "copy", "retry", "observe")

__all__ = ["RenderService", "RenderStats", "ChunkStats", "ChunkResult",
           "PlannedDispatch", "zoom_bounds", "DEFAULT_FRAMES_PER_DEVICE",
           "DEFAULT_PIPELINE_DEPTH", "PHASES"]


class _WallClock:
    """Default timing source: monotonic wall time. The service reads
    time ONLY through its clock, so the deterministic test harness
    (``tests/fakes.py``) can substitute a virtual clock and assert on
    exact schedules instead of sleeping."""

    @staticmethod
    def now() -> float:
        return time.perf_counter()


_WALL = _WallClock()


class _Phase:
    """One phase of a chunk's life: the profiler span ``repro.<name>``
    tagged ``chunk=<ChunkStats.index>`` (so every span of a chunk shares
    one id), timed by the service clock into ``seconds``. The span costs
    something only while a profiler session records."""

    __slots__ = ("_clock", "_span", "_t0", "seconds")

    def __init__(self, clock, name: str, chunk: int):
        self._clock = clock
        self._span = jax.profiler.TraceAnnotation(f"repro.{name}",
                                                  chunk=chunk)
        self.seconds = 0.0

    def __enter__(self) -> "_Phase":
        self._span.__enter__()
        self._t0 = self._clock.now()
        return self

    def __exit__(self, *exc) -> None:
        self.seconds = self._clock.now() - self._t0
        self._span.__exit__(*exc)


@dataclasses.dataclass
class ChunkStats:
    """Per-chunk timing of the streamed pipeline.

    ``dispatch_s`` is the time to *enqueue* the chunk's XLA call (JAX
    async dispatch returns before the devices finish); ``fetch_s`` is the
    time the host then spent blocked in ``finalize()`` materialising the
    chunk. In the synchronous path ``fetch_s`` absorbs the chunk's whole
    device compute; in the pipelined path chunk k+1's compute runs
    behind the fetch/host processing of chunk k, so its own ``fetch_s``
    shrinks by the hidden amount -- comparing a pipelined run's
    ``RenderStats.wall_s`` against a synchronous run's ``busy_s`` (the
    sum of per-chunk compute + host-copy costs) measures the overlap.

    The ``*_s`` phase fields split the chunk's host time; each phase is
    also a profiler span ``repro.<phase>`` (``_Phase``). ``plan_s``: the
    chunker (or ``dispatch_planned``) pulling and sizing the chunk;
    ``wait_s``: blocked until the chunk's device work ends; ``stats_s``:
    reading the scan's counters back into ``ASKStats``; ``copy_s``: the
    canvases' device-to-host copy (feedback path); ``retry_s``: the
    overflow re-dispatch loop (0 unless a frame overflowed);
    ``observe_s``: folding the counts into the estimator. ``fetch_s``
    spans ``wait_s + stats_s + copy_s + retry_s``.

    ``shard_leaf_counts`` and ``shard_frames`` split the chunk over the
    mesh's devices as the dispatch assigned it: device d holds frames
    ``[d * S, (d + 1) * S)`` of the padded batch (frame-major, ``S`` =
    padded width over devices). Each is one entry a device, counting the
    live leaf regions (``ASKStats.frame_leaf_counts``) and the live
    frames there; padding frames count on neither.
    """

    index: int
    frames: int
    dispatch_s: float
    fetch_s: float
    in_flight: int  # chunks already enqueued when this one was finalised
    # feedback (planner-aware) serving only:
    p_subdiv: float | None = None  # quantized planning P that sized the chunk
    p_source: str = ""  # "prior" | "measured" | "mixed" (cold start = prior)
    retries: int = 0  # frame re-dispatches after overflow
    ring_rows: int = 0  # OLT-ring rows allocated, retry dispatches included
    workload: str = ""  # mixed-workload serving: problem key of this chunk
    # multi-tenant front-door batches (launch.frontdoor): the tenant id
    # of each frame of this chunk, in frame order; () for single-tenant
    # streams. ``tenant_frames()`` aggregates the attribution.
    tenants: tuple = ()
    # tile serving (launch.tiles): how the viewport that produced this
    # chunk split between the dwell cache and fresh rendering. The
    # chunk's frames are the MISSES; hits never reach a dispatch.
    cache_hits: int = 0
    cache_misses: int = 0
    cache_bytes: int = 0  # bytes resident in the tile cache afterwards
    plan_s: float = 0.0
    wait_s: float = 0.0
    stats_s: float = 0.0
    copy_s: float = 0.0
    retry_s: float = 0.0
    observe_s: float = 0.0
    shard_leaf_counts: tuple = ()
    shard_frames: tuple = ()

    @property
    def busy_s(self) -> float:
        return self.dispatch_s + self.fetch_s

    def tenant_frames(self) -> dict:
        """Per-tenant frame attribution of this chunk ({tenant: frame
        count}; empty for single-tenant streams)."""
        out: dict = {}
        for t in self.tenants:
            out[t] = out.get(t, 0) + 1
        return out


@dataclasses.dataclass
class ChunkResult:
    """One finalised chunk: canvases [f, n, n], engine stats, timing."""

    canvases: Any
    stats: Any  # core.ask.ASKStats for this chunk's dispatch
    chunk: ChunkStats


class _Enqueued(NamedTuple):
    """A dispatched chunk awaiting its finalize. ``depths``, ``p`` and
    ``caps`` are None on the uniform (non-feedback) path."""

    index: int
    key: str
    bounds: list
    depths: Any
    p: Any
    caps: Any
    src: str
    handle: Any  # the engine's dispatch handle (wait() / finalize())
    dispatch_s: float
    plan_s: float


class PlannedDispatch:
    """Handle of one in-flight ``RenderService.dispatch_planned`` batch.

    The batch-ingestion seam of the multi-tenant front door
    (``launch.frontdoor``): the batch is already enqueued on the
    devices when this handle exists; ``finalize()`` blocks, runs the
    service's overflow-retry loop to zero drops (feedback path), feeds
    the estimator, and returns the same ``ChunkResult`` the streaming
    path yields -- with ``ChunkStats.tenants`` carrying the per-frame
    tenant attribution. ``finalize()`` is one-shot.
    """

    def __init__(self, service, item, tenants, tenant_feedback):
        self._service = service
        self._item = item  # an _Enqueued
        self._tenants = tuple(tenants)
        self._tenant_feedback = bool(tenant_feedback)
        self._done = False

    @property
    def frames(self) -> int:
        return len(self._item.bounds)

    @property
    def workload(self) -> str:
        return self._item.key

    @property
    def tenants(self) -> tuple:
        return self._tenants

    def finalize(self) -> ChunkResult:
        """Block until the batch is materialised (overflow retried to
        zero drops on the feedback path) and demuxable."""
        if self._done:
            raise RuntimeError("PlannedDispatch.finalize() is one-shot")
        self._done = True
        svc = self._service
        if svc.estimator is not None:
            return svc._finalize_feedback(
                self._item, in_flight=1, tenants=self._tenants,
                tenant_feedback=self._tenant_feedback)
        return svc._finalize_uniform(self._item, in_flight=1,
                                     tenants=self._tenants)


@dataclasses.dataclass
class RenderStats:
    """Aggregate accounting across a streamed trajectory."""

    frames: int = 0
    chunks: int = 0
    dispatches: int = 0  # XLA dispatches issued (target: one per chunk)
    leaf_count: int = 0
    overflow_dropped: int = 0
    wall_s: float = 0.0
    pipeline_depth: int = 1
    dispatch_s: float = 0.0  # total time spent enqueueing chunks
    fetch_s: float = 0.0  # total time blocked materialising chunks
    host_copy_s: float = 0.0  # render() only: device->numpy conversion
    chunk_stats: tuple = ()  # ChunkStats per chunk, stream order
    # traced signatures of the chunk program AFTER the stream (None when
    # jax doesn't expose the jit cache). Uniform serving: 1 == every
    # chunk, ragged tail included, reused ONE compiled program; 2+ means
    # the pad_to plumbing regressed and the tail retraced. Feedback
    # serving: the sum across capacity signatures, whose regression
    # target is ``plan_signatures`` (each signature traced exactly once).
    program_traces: int | None = None
    # feedback serving only: frame re-dispatches after overflow, total
    # OLT-ring rows allocated (retries included), and how many distinct
    # capacity signatures (compiled chunk programs) the stream requested
    retries: int = 0
    ring_rows: int = 0
    plan_signatures: int | None = None

    @property
    def dispatches_per_chunk(self) -> float:
        return self.dispatches / self.chunks if self.chunks else 0.0

    @property
    def busy_s(self) -> float:
        """Sum of per-chunk (enqueue + fetch + host copy/sink) costs. For
        a synchronous run (pipeline_depth=1) this is the serial cost of
        the trajectory -- the baseline a pipelined run's ``wall_s`` is
        measured against: wall(pipelined) < busy(sync) is the overlap."""
        return self.dispatch_s + self.fetch_s + self.host_copy_s


def zoom_bounds(
    frames: int,
    *,
    center: Tuple[float, float] = (-0.7436447860, 0.1318252536),
    width0: float = 3.0,
    zoom_per_frame: float = 1.05,
) -> Iterator[Tuple[float, float, float, float]]:
    """Exponential zoom trajectory: yields (re0, im0, re1, im1) per frame,
    shrinking the window by ``zoom_per_frame`` each step around ``center``
    (default: a classic seahorse-valley deep-zoom target)."""
    cr, ci = center
    half = width0 / 2.0
    for _ in range(frames):
        yield (cr - half, ci - half, cr + half, ci + half)
        half /= zoom_per_frame


class RenderService:
    """Chunked sharded serving of a workload frame stream.

    ``problem`` is a ``workloads.FrameProblem`` (any registered
    workload), or -- mixed-workload serving -- a mapping {key:
    FrameProblem} whose problems share one canvas size; stream items
    are then ``(key, bounds)`` pairs instead of bare bounds tuples.
    ``mesh`` defaults to a 1-D mesh over every visible device
    (``launch.mesh.make_frames_mesh``); ``chunk_frames`` is rounded up to
    a multiple of the device count; ``pipeline_depth`` bounds how many
    chunks may be in flight at once (1 = synchronous, 2 = double
    buffering, the default). Engine kwargs (``capacities``,
    ``safety_factor``, ...) pass through to the scan engine unchanged.

    ``feedback`` (True or a ``core.feedback.OccupancyEstimator``) turns
    on closed-loop planner-aware chunking: every chunk's ring
    capacities come from the estimator's (quantized) prediction at the
    chunk's zoom depths -- the WORKLOAD's zoom-depth prior while the
    estimator is cold, the previous chunks' measured occupancy
    afterwards -- the chunker splits a chunk early when the predicted
    capacity class (or the workload) jumps, overflowing frames are
    retried at doubled capacities before the chunk is yielded, and the
    finished chunk's measured ``region_counts`` are folded back into
    the estimator under the chunk's workload namespace (so a mixed
    mandelbrot+julia stream never plans one workload from the other's
    measurements). Mixed-workload serving requires the feedback path
    (it IS the planner-aware chunker). ``adapt=False`` keeps the same
    chunking/retry machinery but never feeds measurements back -- the
    prior-only baseline the feedback benchmark rows compare against.
    With ``pipeline_depth >= 2`` the feedback lags by the chunks in
    flight: chunk k is planned from the chunks finalised before it was
    enqueued, which is what keeps the re-plan loop compatible with the
    async overlap.

    ``feedback_state`` (a JSON path) persists the estimator across
    service restarts: an existing file is loaded at construction (so
    the first chunk plans from the previous process's measurements
    instead of the cold prior), and ``render()`` saves back on
    completion (``save_feedback_state()`` for streaming callers).

    ``engine="ask_pooled"`` serves every chunk through the cross-frame
    pooled worklists (``core.pooled``): each device shard pools ITS
    frames into ONE shared ring sized from their summed per-frame
    occupancies. On the feedback path the chunker then cuts only on
    workload switches (heterogeneous frames are the point of pooling --
    a capacity-class jump stays inside the chunk, see
    ``_feedback_chunks``), the retry loop re-pools the overflowing frames
    (``pooled.pooled_retry_capacities``), and ``ChunkStats.ring_rows``
    counts ``n_dev x 2 x max(caps)`` per dispatch -- the pooled
    allocation the feedback benchmark compares against the per-frame
    path's ``pad x 2 x max(caps)``. The constructor resolves the engine
    once, into the dispatch function, the chunker, the chunk's
    capacities, the retry rule and the rings a dispatch allocates.
    """

    def __init__(self, problem, *, mesh=None, chunk_frames: int | None = None,
                 pipeline_depth: int = DEFAULT_PIPELINE_DEPTH,
                 feedback: OccupancyEstimator | bool | None = None,
                 adapt: bool = True,
                 feedback_state: Union[str, Path, None] = None,
                 policy=None,
                 engine: str = "ask_scan",
                 clock=None,
                 **engine_kw):
        if engine not in ("ask_scan", "ask_pooled"):
            raise ValueError(
                f"service engine must be 'ask_scan' or 'ask_pooled', got "
                f"{engine!r} (the tuned tier is a policy= concern)")
        self.engine = engine
        if "pad_to" in engine_kw:
            raise ValueError(
                "pad_to is owned by the service (pinned to chunk_frames so "
                "every chunk reuses one compiled program); set chunk_frames "
                "instead")
        if pipeline_depth < 1:
            raise ValueError(f"pipeline_depth must be >= 1, got {pipeline_depth}")
        if isinstance(problem, Mapping):
            if not problem:
                raise ValueError("problem mapping must not be empty")
            self._problems = {str(k): p for k, p in problem.items()}
            self._mixed = True
            self.problem = None  # no single canonical problem in mixed mode
        else:
            self._problems = {"": problem}
            self._mixed = False
            self.problem = problem
        if policy is not None:
            # one KernelPolicy for every tenant: the service owns kernel
            # routing the same way it owns pad_to / chunking
            from repro.kernels.policy import KernelPolicy
            pol = KernelPolicy.coerce(policy)
            self._problems = {k: dataclasses.replace(p, policy=pol)
                              for k, p in self._problems.items()}
            if not self._mixed:
                self.problem = self._problems[""]
        sizes = {p.n for p in self._problems.values()}
        if len(sizes) != 1:
            raise ValueError(
                f"mixed-workload problems must share one canvas size n, "
                f"got {sorted(sizes)}")
        self._n = sizes.pop()
        dtypes = {np.dtype(getattr(getattr(p, "workload", None), "dtype",
                                   np.int32))
                  for p in self._problems.values()}
        if len(dtypes) != 1:
            raise ValueError(
                "mixed-workload problems must share one canvas dtype "
                f"(render() stacks chunks into one array), got "
                f"{sorted(d.name for d in dtypes)}")
        self._dtype = dtypes.pop()
        self.mesh = make_frames_mesh() if mesh is None else mesh
        n_dev = int(self.mesh.devices.size)
        want = (n_dev * DEFAULT_FRAMES_PER_DEVICE if chunk_frames is None
                else int(chunk_frames))
        if want < 1:
            raise ValueError(f"chunk_frames must be >= 1, got {want}")
        self.chunk_frames = -(-want // n_dev) * n_dev  # round up to multiple
        self.pipeline_depth = int(pipeline_depth)
        self._state_path = (None if feedback_state is None
                            else Path(feedback_state))
        if self._state_path is not None and not feedback:
            feedback = True  # a state path IS a request for the closed loop
        if feedback:
            clash = {"capacities", "p_subdiv"} & engine_kw.keys()
            if clash:
                raise ValueError(
                    f"{sorted(clash)} conflict with feedback=: the service "
                    "re-plans each chunk's capacities from the estimator; "
                    "tune safety_factor / the OccupancyEstimator instead")
            if (self._state_path is not None
                    and isinstance(feedback, OccupancyEstimator)):
                raise ValueError(
                    "pass feedback_state= OR a prebuilt OccupancyEstimator, "
                    "not both -- restoring the file would discard the "
                    "estimator you handed in")
            if self._state_path is not None and self._state_path.exists():
                self.estimator = OccupancyEstimator.restore(
                    json.loads(self._state_path.read_text()))
            else:
                self.estimator = (feedback
                                  if isinstance(feedback, OccupancyEstimator)
                                  else OccupancyEstimator())
            self._ref_widths = {}
            for key, prob in self._problems.items():
                bounds = getattr(prob, "bounds", None)
                if bounds is None:
                    raise ValueError(
                        "feedback= needs problem.bounds to anchor zoom depth")
                self._ref_widths[key] = float(bounds[2]) - float(bounds[0])
        else:
            if self._mixed:
                raise ValueError(
                    "mixed-workload serving needs feedback= (the planner-"
                    "aware chunker is what routes each frame to its "
                    "workload's compiled program and prior)")
            if not adapt:
                raise ValueError(
                    "adapt=False is the prior-only FEEDBACK baseline (same "
                    "chunking/retry machinery, no estimator updates) -- it "
                    "needs feedback= set; without it the service runs the "
                    "uniform path and the flag would be silently ignored")
            self.estimator = None
            self._ref_widths = None
        self.adapt = bool(adapt)
        self.engine_kw = engine_kw
        # all service timing goes through the clock so the deterministic
        # harness (tests/fakes.py VirtualClock) can replace wall time
        self._clock = _WALL if clock is None else clock
        self._caps_cache: dict = {}  # (problem key, quantized P) -> capacities
        self._used_sigs: set = set()  # (problem key, pad width, caps) dispatched
        self._programs: dict = {}  # id -> each jitted program dispatched
        self._planned_index = 0  # ChunkStats.index of dispatch_planned batches
        if engine == "ask_pooled":
            self._engine_dispatch = dispatch_ask_pooled_sharded
            self._chunk_class = lambda key, p: key  # cut on workload only
            self._chunk_caps = self._pooled_caps_for
            self._retry_rule = self._pooled_retry
            self._rings = lambda f: n_dev  # one shared ring a device
        else:
            self._engine_dispatch = dispatch_ask_scan_sharded
            self._chunk_class = lambda key, p: (key, p)
            self._chunk_caps = lambda key, ps: self._caps_for(key, max(ps))
            self._retry_rule = self._frame_retry
            self._rings = self._pad_width  # one ring a frame, padding incl.

    # -- dispatch plumbing --------------------------------------------------

    def _dispatch(self, chunk, caps=None, key: str = ""):
        """Enqueue one chunk; returns (the engine's dispatch handle,
        enqueue seconds).

        ``caps`` (feedback path) overrides the engine kwargs' sizing with
        a per-chunk capacity vector and pads to the pow2-bucketed width
        (``_pad_width``); the uniform path keeps the width pinned to
        ``chunk_frames``. ``key`` selects the problem in mixed-workload
        mode. Either way compiled programs are keyed on (problem, chunk
        width, capacity signature) and nothing retraces across chunks
        that share a signature.
        """
        kw = dict(self.engine_kw)
        pad = self.chunk_frames
        if caps is not None:
            kw["capacities"] = caps
            pad = self._pad_width(len(chunk))
            self._used_sigs.add((key, pad, tuple(caps)))
        t0 = self._clock.now()
        d = self._engine_dispatch(self._problems[key],
                                  jnp.asarray(chunk, jnp.float32),
                                  mesh=self.mesh, pad_to=pad, **kw)
        self._programs[id(d.program)] = d.program
        return d, self._clock.now() - t0

    def _phase(self, name: str, chunk: int) -> _Phase:
        return _Phase(self._clock, name, chunk)

    def _shards(self, frame_leaf_counts, pad: int):
        """``(shard_leaf_counts, shard_frames)`` of a dispatch of
        ``len(frame_leaf_counts)`` live frames padded to ``pad``:
        frame-major, ``pad // devices`` frames a device."""
        n_dev = int(self.mesh.devices.size)
        per = pad // n_dev
        shards = [frame_leaf_counts[d * per:(d + 1) * per]
                  for d in range(n_dev)]
        return (tuple(int(sum(s)) for s in shards),
                tuple(len(s) for s in shards))

    def _enqueue(self, index: int, key: str, bounds, plan_s: float, *,
                 depths=None, p=None, caps=None, src: str = "") -> _Enqueued:
        """Dispatch one planned chunk inside its ``repro.dispatch``
        span; ``dispatch_s`` is ``_dispatch``'s own enqueue time."""
        with self._phase("dispatch", index):
            d, secs = self._dispatch(bounds, caps=caps, key=key)
        return _Enqueued(index, key, bounds, depths, p, caps, src, d, secs,
                         plan_s)

    def _finalize_uniform(self, item: _Enqueued, in_flight: int,
                          tenants=()) -> ChunkResult:
        """Block on one in-flight uniform-path chunk: wait for its
        device work, then read its counters back (the canvases stay on
        the device)."""
        i = item.index
        t0 = self._clock.now()
        with self._phase("wait", i) as wait:
            item.handle.wait()
        with self._phase("stats", i) as stats:
            canvases, st = item.handle.finalize()
            f = len(item.bounds)
            if int(canvases.shape[0]) != f:  # a padded tail, on the device
                canvases = canvases[:f]
        fetch_s = self._clock.now() - t0
        leaves, frames = self._shards(st.frame_leaf_counts,
                                      self.chunk_frames)
        return ChunkResult(canvases, st, ChunkStats(
            index=i, frames=len(item.bounds), dispatch_s=item.dispatch_s,
            fetch_s=fetch_s, in_flight=in_flight, workload=item.key,
            tenants=tuple(tenants), plan_s=item.plan_s,
            wait_s=wait.seconds, stats_s=stats.seconds,
            shard_leaf_counts=leaves, shard_frames=frames))

    def _pad_width(self, f: int) -> int:
        """Padding width of a feedback-path dispatch: the next power-of-
        two multiple of the device count, capped at ``chunk_frames``.

        Early-split chunks and small retry batches would waste most of a
        full-width dispatch's ring (padding frames trace real compute),
        but letting every length be its own width would trace a program
        per length; power-of-two bucketing bounds the widths at
        O(log(chunk_frames / devices)) -- so the compiled-program cache
        stays keyed on (chunk width, capacity signature) with both
        factors small, the discipline the uniform path pins with its
        single width.
        """
        n_dev = int(self.mesh.devices.size)
        w = n_dev
        while w < f:
            w *= 2
        return min(w, self.chunk_frames)

    # -- feedback (planner-aware) serving -----------------------------------

    def _split_item(self, item) -> Tuple[str, Any]:
        """One stream item -> (problem key, bounds). Single-problem
        streams carry bare bounds tuples; mixed-workload streams carry
        (key, bounds) pairs."""
        if not self._mixed:
            return "", item
        key, bounds = item
        key = str(key)
        if key not in self._problems:
            raise KeyError(
                f"stream item names unknown problem {key!r}; serving "
                f"{sorted(self._problems)}")
        return key, bounds

    def _depth(self, key: str, bounds) -> float:
        return zoom_depth(float(bounds[2]) - float(bounds[0]),
                          ref_width=self._ref_widths[key],
                          r=self._problems[key].r)

    def _caps_for(self, key: str, p: float):
        """Capacity vector for one (problem, quantized planning P)
        (memoised: the p_quantum grid keeps this cache -- and the
        compiled-program signature set -- small for the life of the
        service)."""
        ck = (key, round(float(p), 6))
        caps = self._caps_cache.get(ck)
        if caps is None:
            prob = self._problems[key]
            caps = scan_capacities(
                prob.n, prob.g, prob.r, prob.B, p_subdiv=ck[1],
                safety_factor=self.engine_kw.get("safety_factor", 2.0))
            self._caps_cache[ck] = caps
        return caps

    def _feedback_chunks(self, it: Iterator):
        """Boundary-aware chunker: yields (key, bounds, ``_enqueue``
        kwargs: depths, p, caps, src) with every frame of a chunk in ONE
        problem and ONE chunk class (``_chunk_class``); a class jump cuts
        the chunk early. The per-frame engine's class is the problem and
        the predicted P, so deep-tail frames get their own (hotter)
        program instead of inflating the whole chunk's ring. The pooled engine's class is
        the problem alone: heterogeneous frames are the POINT of pooling
        -- one shared ring sized from their summed occupancies -- so a
        P jump stays inside the chunk. Either way every dispatch stays
        single-workload; ``caps`` is ``_chunk_caps`` of the members' P,
        and ``p`` the hottest member's. Lazy: predictions are made as
        frames are pulled, so re-planning naturally picks up whatever
        the estimator has observed by then.
        """
        est = self.estimator
        buf: list = []
        depths: list = []
        ps: list = []
        sources: list = []
        cls = key_open = None  # class and problem key of the open chunk

        def flush():
            src = (sources[0] if len(set(sources)) == 1 else "mixed")
            return key_open, list(buf), dict(
                depths=list(depths), p=max(ps),
                caps=self._chunk_caps(key_open, ps), src=src)

        for item in it:
            key, b = self._split_item(item)
            wl = self._problems[key].workload
            d = self._depth(key, b)
            p = est.predict_quantized(d, workload=wl)
            if buf and self._chunk_class(key, p) != cls:
                yield flush()
                buf, depths, ps, sources = [], [], [], []
                # the estimator may have observed the flushed chunk while
                # this generator was suspended in that yield: re-predict
                # the held-over frame so the new chunk's class and
                # provenance both reflect the post-observation state
                p = est.predict_quantized(d, workload=wl)
            cls, key_open = self._chunk_class(key, p), key
            buf.append(b)
            depths.append(d)
            ps.append(p)
            sources.append("measured"
                           if est.measured(d, workload=wl) is not None
                           else "prior")
            if len(buf) == self.chunk_frames:
                yield flush()
                buf, depths, ps, sources = [], [], [], []
                cls = None
        if buf:
            yield flush()

    def _pooled_caps_for(self, key: str, ps):
        """Shared per-shard ring capacities for one pooled chunk: the
        members' expected occupancies are summed per shard (frame-major
        assignment, live frames only), maxed across shards so every
        shard runs the one compiled program (``core.pooled.
        shard_capacities``), then rounded up to powers of two (clamped
        at the shard worst case) -- so the capacity-signature set stays
        bounded even though every chunk carries its own P mix."""
        prob = self._problems[key]
        S = self._pad_width(len(ps)) // int(self.mesh.devices.size)
        sf = self.engine_kw.get("safety_factor", 2.0)
        caps = shard_capacities(prob, ps, S, safety_factor=sf)
        worst = worst_case_capacities(prob)
        return tuple(min(next_pow2(c), S * w) for c, w in zip(caps, worst))

    def _frame_retry(self, key, bounds, caps, tag, failed, chains, ran):
        """Per-frame engine's retry rule: double, clamped at the worst
        case (``planner.escalate_capacities``)."""
        worst = worst_case_capacities(self._problems[key])
        return escalate_capacities(caps, worst, failed), tag

    def _pooled_retry(self, key, bounds, caps, retry, failed, chains, ran):
        """Pooled engine's retry rule (``pooled.pooled_retry_capacities``):
        pools of pow2-bucketed width, and the failed frames' own
        estimated P for the first retry's sizing."""
        prob = self._problems[key]
        n_dev = int(self.mesh.devices.size)
        ps = [float(self.estimator.predict_quantized(
                  self._depth(key, bounds[j]), workload=prob.workload))
              for j in failed]
        return pooled_retry_capacities(
            prob, caps, chains, retry=retry,
            ran_per_shard=self._pad_width(ran) // n_dev,
            next_per_shard=self._pad_width(len(failed)) // n_dev,
            frame_ps=ps), retry + 1

    def _resolve_overflow(self, key, bounds, caps, canv, st, *, index: int):
        """Retry overflowing frames until every frame fits
        (``planner.retry_until_fit`` with the engine's retry rule), then
        merge canvases/stats. ``canv`` holds the chunk's canvases on the
        host. Returns (canvases np, merged ASKStats, frame re-dispatch
        count, ring rows of every dispatch, retry seconds); the retry
        loop runs in the chunk's ``repro.retry`` phase, opened only when
        a frame overflowed.

        The merged stats' ``olt_caps`` are the last dispatch's
        capacities (the escalated vector when retries happened), so
        ``ASKStats.ring_rows`` follows the ring the chunk's frames
        finished in; the per-dispatch total incl. padding lives in
        ``ChunkStats.ring_rows``."""
        def dispatch(idx, c):
            d, _ = self._dispatch([bounds[j] for j in idx], caps=c, key=key)
            return d.finalize()

        def rule(*args):
            return self._retry_rule(key, bounds, *args)

        f = len(bounds)
        retry = self._phase("retry", index) if any(st.frame_overflow) else None
        with retry or contextlib.nullcontext():
            done = retry_until_fit(dispatch, [(tuple(caps), range(f), 0)],
                                   rule, frames=f, rings=self._rings,
                                   done=(canv, st))
        chains = done.chains
        merged = ASKStats(
            levels=max((len(c) for c, _ in chains), default=0),
            kernel_launches=sum(s.kernel_launches for s in done.stats),
            region_counts=tuple(c for c, _ in chains),
            leaf_count=sum(leaf for _, leaf in chains),
            overflow_dropped=0,  # the loop only exits once every frame fits
            wall_s=sum(s.wall_s for s in done.stats),
            olt_caps=done.caps,  # == caps when nothing retried
            frame_overflow=(0,) * f,
            frame_leaf_counts=tuple(leaf for _, leaf in chains),
        )
        retry_s = retry.seconds if retry else 0.0
        return done.states, merged, done.retries, done.ring_rows, retry_s

    def _finalize_feedback(self, item, in_flight: int, tenants=(),
                           tenant_feedback: bool = False) -> ChunkResult:
        """Block on one in-flight feedback chunk: finalize, retry any
        overflow, fold the measured counts into the estimator (under
        the chunk's workload namespace -- and, for multi-tenant batches
        with ``tenant_feedback``, additionally under each frame's
        tenant namespace so per-tenant plans refine independently)."""
        i, key, bounds, depths, caps = (item.index, item.key, item.bounds,
                                        item.depths, item.caps)
        t0 = self._clock.now()
        with self._phase("wait", i) as wait:
            item.handle.wait()
        with self._phase("stats", i) as stats:
            canvases, st = item.handle.finalize()
        with self._phase("copy", i) as copy:
            # the program's own output: the copy needs only this chunk's
            # program to have ended; a padded tail is cut off here
            canv = np.asarray(canvases)[:len(bounds)]
        canv, merged, retries, ring, retry_s = self._resolve_overflow(
            key, bounds, caps, canv, st, index=i)
        fetch_s = self._clock.now() - t0  # retry dispatches included
        prob = self._problems[key]
        with self._phase("observe", i) as observe:
            if self.adapt:
                self.estimator.observe_stats(depths, merged, g=prob.g,
                                             r=prob.r, workload=prob.workload)
                if tenant_feedback and tenants:
                    chains = merged.frame_chains()
                    by_tenant: dict = {}
                    for j, t in enumerate(tenants):
                        by_tenant.setdefault(t, []).append(j)
                    for t, idxs in by_tenant.items():
                        self.estimator.observe_frames(
                            [depths[j] for j in idxs],
                            [chains[j] for j in idxs],
                            g=prob.g, r=prob.r, workload=prob.workload,
                            tenant=t)
        leaves, frames = self._shards(merged.frame_leaf_counts,
                                      self._pad_width(len(bounds)))
        return ChunkResult(canv, merged, ChunkStats(
            index=i, frames=len(bounds), dispatch_s=item.dispatch_s,
            fetch_s=fetch_s, in_flight=in_flight, p_subdiv=item.p,
            p_source=item.src, retries=retries,
            ring_rows=ring, workload=key, tenants=tuple(tenants),
            plan_s=item.plan_s, wait_s=wait.seconds, stats_s=stats.seconds,
            copy_s=copy.seconds, retry_s=retry_s, observe_s=observe.seconds,
            shard_leaf_counts=leaves, shard_frames=frames))

    # -- multi-tenant front-door seam ---------------------------------------

    def workload_keys(self) -> Tuple[str, ...]:
        """The problem keys this service can dispatch ("" for a single-
        problem service). The front door validates request workloads
        against this set at admission time."""
        return tuple(sorted(self._problems))

    @property
    def n(self) -> int:
        """Shared canvas size of every problem this service serves."""
        return self._n

    def problem_for(self, key: str = ""):
        """The ``FrameProblem`` serving ``key`` ("" for a single-problem
        service). The tile service's progressive path (``launch.tiles``)
        dispatches split scans (``core.progressive``) against it
        directly, bypassing the uniform chunker."""
        key = str(key)
        if key not in self._problems:
            raise KeyError(
                f"unknown problem {key!r}; serving {sorted(self._problems)}")
        return self._problems[key]

    def dispatch_planned(self, bounds, *, key: str = "", tenants=(),
                         tenant_feedback: bool = False) -> PlannedDispatch:
        """Batch-ingestion seam: enqueue ONE explicitly coalesced batch.

        This is how the multi-tenant front door (``launch.frontdoor``)
        feeds shared batches through the service's planning, dispatch,
        retry, and feedback machinery without going through the
        streaming chunker: ``bounds`` is a list of frame bounds (all in
        problem ``key``, at most ``chunk_frames`` of them -- the front
        door owns coalescing, the service owns planning and padding),
        ``tenants`` optionally attributes each frame to a tenant id
        (same length as ``bounds``; lands in ``ChunkStats.tenants``).

        On the feedback path the batch's ring capacities come from the
        estimator exactly as the streaming chunker's would -- sized for
        the HOTTEST member, since a coalesced batch deliberately mixes
        tenants' capacity classes -- and ``finalize()`` retries overflow
        to zero drops and folds the measured counts back in (per-tenant
        namespaces too when ``tenant_feedback`` is set). Without
        feedback the batch runs the uniform path (engine kwargs sizing,
        no retry), mirroring the uniform stream. Returns immediately
        with a ``PlannedDispatch`` (JAX async dispatch): the caller
        overlaps its own admission/demux work with device compute and
        calls ``finalize()`` when it needs the frames.
        """
        key = str(key)
        if key not in self._problems:
            raise KeyError(
                f"dispatch_planned names unknown problem {key!r}; serving "
                f"{sorted(self._problems)}")
        bounds = [tuple(float(x) for x in b) for b in bounds]
        if not bounds:
            raise ValueError("dispatch_planned needs at least one frame")
        if len(bounds) > self.chunk_frames:
            raise ValueError(
                f"batch of {len(bounds)} frames exceeds chunk_frames="
                f"{self.chunk_frames}; the front door must cut batches at "
                "the service's chunk width")
        tenants = tuple(str(t) for t in tenants)
        if tenants and len(tenants) != len(bounds):
            raise ValueError(
                f"got {len(tenants)} tenants for {len(bounds)} frames")
        index = self._planned_index
        self._planned_index += 1
        if self.estimator is None:
            if self._mixed:
                raise ValueError(
                    "mixed-workload dispatch_planned needs feedback= "
                    "(same contract as the streaming chunker)")
            item = self._enqueue(index, key, bounds, 0.0)
            return PlannedDispatch(self, item, tenants, tenant_feedback)
        with self._phase("plan", index) as plan:
            est = self.estimator
            wl = self._problems[key].workload
            depths = [self._depth(key, b) for b in bounds]
            t_of = (lambda j: tenants[j]) if (tenant_feedback and tenants) \
                else (lambda j: None)
            ps = [est.predict_quantized(d, workload=wl, tenant=t_of(j))
                  for j, d in enumerate(depths)]
            sources = {"measured"
                       if est.measured(d, workload=wl,
                                       tenant=t_of(j)) is not None
                       else "prior"
                       for j, d in enumerate(depths)}
            src = sources.pop() if len(sources) == 1 else "mixed"
            caps = self._chunk_caps(key, ps)
        item = self._enqueue(index, key, bounds, plan.seconds, depths=depths,
                             p=max(ps), caps=caps, src=src)
        return PlannedDispatch(self, item, tenants, tenant_feedback)

    def _pipelined(self, pull, finalize) -> Iterator[ChunkResult]:
        """Keep up to ``pipeline_depth`` chunks in flight. ``pull()``
        plans the next chunk inside its ``repro.plan`` span -> ``(key,
        bounds, _enqueue kwargs)``, or None once the stream ends;
        ``finalize(item, in_flight)`` blocks on the oldest chunk (younger
        ones compute behind it). The queue is refilled AFTER each
        finalize -- which folds the feedback path's counts into the
        estimator -- and BEFORE the yield, so the next chunk is planned
        from the freshest finalised state while the devices stay busy
        behind the consumer. ``pipeline_depth == 1`` is synchronous: at
        most one chunk in flight, the next planned AND dispatched only
        after the consumer returns."""
        pending: collections.deque = collections.deque()
        index = 0

        def enqueue() -> bool:
            nonlocal index
            with self._phase("plan", index) as plan:
                item = pull()
            if item is None:
                return False
            key, bounds, kw = item
            pending.append(self._enqueue(index, key, bounds, plan.seconds,
                                         **kw))
            index += 1
            return True

        if self.pipeline_depth == 1:
            while enqueue():
                yield finalize(pending.popleft(), in_flight=1)
            return
        while len(pending) < self.pipeline_depth and enqueue():
            pass
        while pending:
            in_flight = len(pending)
            # ``item`` keeps the finalised chunk's device buffers alive
            # until the next finalize: on a four-chip v5e host, freeing
            # them before the next enqueue lowered peak memory by one
            # chunk's canvases but added ~50 ms to that enqueue
            item = pending.popleft()
            result = finalize(item, in_flight)
            enqueue()
            yield result

    def stream_chunks(self, bounds_iter: Iterable) -> Iterator[ChunkResult]:
        """Yield ``ChunkResult`` per chunk, f <= chunk_frames frames each.

        Lazy: pulls ``chunk_frames`` bounds at a time, so the input can be
        an unbounded generator (a million-frame trajectory never
        materialises host-side). With ``pipeline_depth >= 2`` up to that
        many chunks are enqueued ahead of the one being finalised, and
        the queue is refilled BEFORE each yield -- so the devices compute
        chunk k+1 while the consumer of the stream is still busy with
        chunk k. Chunk order (and therefore frame order) is preserved.

        With ``feedback=`` set -- the closed loop: re-plan, dispatch,
        retry, observe, refill -- the stream re-plans each chunk's
        capacities from the estimator state before dispatch (see
        ``_feedback_chunks``); chunks may then be SHORTER than
        ``chunk_frames`` where the chunk class jumps.
        """
        if self.estimator is not None:
            chunks = self._feedback_chunks(iter(bounds_iter))
            yield from self._pipelined(lambda: next(chunks, None),
                                       self._finalize_feedback)
            return
        it = iter(bounds_iter)

        def pull():
            chunk = list(itertools.islice(it, self.chunk_frames))
            return ("", chunk, {}) if chunk else None

        yield from self._pipelined(pull, self._finalize_uniform)

    def stream(self, bounds_iter: Iterable):
        """Yield (canvases [f, n, n], ASKStats) per chunk (the PR-2
        interface; ``stream_chunks`` adds per-chunk pipeline timing)."""
        for r in self.stream_chunks(bounds_iter):
            yield r.canvases, r.stats

    def program_traces(self) -> int | None:
        """Traced signatures of the chunk program(s) this service has
        dispatched so far (None when jax doesn't expose the jit cache).

        Summed over the jitted programs the dispatch handles carried
        (the exact objects every chunk dispatches through), so it is a
        real regression signal: pinning ``pad_to`` to the chunk width
        must keep this at 1 no matter how ragged the trajectory tail is.
        On the feedback path its regression target is ``RenderStats.
        plan_signatures`` -- each signature compiled once, every chunk
        sharing a signature reusing that program.
        """
        total = 0
        for fn in self._programs.values():
            size = getattr(fn, "_cache_size", None)
            if not callable(size):
                return None
            total += int(size())
        return total

    def render(self, bounds_seq: Iterable, *, sink=None):
        """Render a whole (finite) trajectory.

        Returns (canvases np [F, n, n], RenderStats). For streams too big
        to stack host-side, iterate ``stream_chunks`` directly. The
        device->numpy conversion of chunk k happens while chunk k+1 is in
        flight (``pipeline_depth >= 2``), which is exactly the host-I/O /
        device-compute overlap the pipelined service exists for.

        ``sink(canvases_np, stats)``, if given, is called once per chunk
        -- the place for the serving-side host I/O (encode frames, write
        to disk/network). Its cost is counted in ``host_copy_s`` and,
        like the numpy conversion, overlaps the next chunk's device
        compute whenever it releases the GIL (compression, file/socket
        writes, and numpy copies largely do).
        """
        out = []
        rs = RenderStats(pipeline_depth=self.pipeline_depth)
        chunk_stats = []
        t0 = self._clock.now()
        for r in self.stream_chunks(bounds_seq):
            tc = self._clock.now()
            host = np.asarray(r.canvases)
            out.append(host)
            if sink is not None:
                sink(host, r.stats)
            rs.host_copy_s += self._clock.now() - tc
            rs.frames += int(r.canvases.shape[0])
            rs.chunks += 1
            rs.dispatches += r.stats.kernel_launches
            rs.leaf_count += r.stats.leaf_count
            rs.overflow_dropped += r.stats.overflow_dropped
            rs.dispatch_s += r.chunk.dispatch_s
            rs.fetch_s += r.chunk.fetch_s
            rs.retries += r.chunk.retries
            rs.ring_rows += r.chunk.ring_rows
            chunk_stats.append(r.chunk)
        rs.wall_s = self._clock.now() - t0
        rs.chunk_stats = tuple(chunk_stats)
        rs.program_traces = self.program_traces()
        if self.estimator is not None:
            rs.plan_signatures = len(self._used_sigs)
        if self._state_path is not None:
            self.save_feedback_state()
        n = self._n
        stacked = (np.concatenate(out, axis=0) if out
                   else np.zeros((0, n, n), self._dtype))
        return stacked, rs

    def save_feedback_state(self, path: Union[str, Path, None] = None) -> Path:
        """Write the estimator snapshot as JSON (``feedback_state`` path
        unless overridden). ``render()`` calls this automatically when
        the service was constructed with ``feedback_state=``; streaming
        callers (``stream_chunks``) invoke it at their own cadence."""
        if self.estimator is None:
            raise ValueError("no estimator to save -- service runs the "
                             "uniform path (feedback= not set)")
        target = self._state_path if path is None else Path(path)
        if target is None:
            raise ValueError("no feedback_state path configured; pass path=")
        target.parent.mkdir(parents=True, exist_ok=True)
        # atomic replace: a crash mid-save (the exact restart scenario
        # feedback_state exists for) must never leave truncated JSON
        # behind for the next construction to choke on
        tmp = target.with_name(target.name + ".tmp")
        tmp.write_text(json.dumps(self.estimator.snapshot()))
        os.replace(tmp, target)
        return target


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--n", type=int, default=256)
    ap.add_argument("--frames", type=int, default=64)
    ap.add_argument("--chunk", type=int, default=None)
    ap.add_argument("--devices", type=int, default=None,
                    help="mesh size (default: all visible devices)")
    ap.add_argument("--max-dwell", type=int, default=128)
    ap.add_argument("--zoom", type=float, default=1.05)
    ap.add_argument("--safety-factor", type=float, default=2.0)
    ap.add_argument("--pipeline-depth", type=int,
                    default=DEFAULT_PIPELINE_DEPTH,
                    help="chunks in flight at once (1 = synchronous)")
    ap.add_argument("--feedback", action="store_true",
                    help="closed-loop occupancy feedback: re-plan each "
                         "chunk's ring from measured region_counts")
    ap.add_argument("--engine", choices=("ask_scan", "ask_pooled"),
                    default="ask_scan",
                    help="ask_pooled: ONE shared cross-frame ring per "
                         "device shard (core.pooled)")
    args = ap.parse_args(argv)

    from repro.launch.compile_cache import enable_compile_cache
    from repro.mandelbrot import MandelbrotProblem

    enable_compile_cache()
    prob = MandelbrotProblem(n=args.n, g=4, r=2, B=16,
                             max_dwell=args.max_dwell)
    mesh = make_frames_mesh(args.devices)
    svc = RenderService(prob, mesh=mesh, chunk_frames=args.chunk,
                        pipeline_depth=args.pipeline_depth,
                        feedback=args.feedback, engine=args.engine,
                        safety_factor=args.safety_factor)
    bounds = zoom_bounds(args.frames, zoom_per_frame=args.zoom)

    # warm the jitted sharded pipeline, then stream the trajectory
    next(svc.stream(zoom_bounds(svc.chunk_frames)))
    _, rs = svc.render(bounds)
    print(f"devices={mesh.devices.size} chunk={svc.chunk_frames} "
          f"depth={svc.pipeline_depth} frames={rs.frames} chunks={rs.chunks} "
          f"dispatches_per_chunk={rs.dispatches_per_chunk:.1f} "
          f"program_traces={rs.program_traces}")
    print(f"wall={rs.wall_s * 1e3:.1f} ms  "
          f"{rs.wall_s * 1e3 / max(rs.frames, 1):.2f} ms/frame  "
          f"busy={rs.busy_s * 1e3:.1f} ms  "
          f"fetch={rs.fetch_s * 1e3:.1f} ms  "
          f"overflow_dropped={rs.overflow_dropped}")
    if args.feedback:
        print(f"feedback: retries={rs.retries} ring_rows={rs.ring_rows} "
              f"plan_signatures={rs.plan_signatures} "
              f"sources={[c.p_source for c in rs.chunk_stats]}")
    chunks = rs.chunk_stats or (ChunkStats(0, 0, 0.0, 0.0, 0),)
    means = {ph: 1e3 * sum(getattr(c, f"{ph}_s") for c in chunks)
             / len(chunks) for ph in PHASES}
    print("mean ms/chunk: "
          + "  ".join(f"{ph}={ms:.2f}" for ph, ms in means.items()))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
