"""Deterministic concurrency harness: virtual clock, scripted engine,
scripted service.

The serving stack's concurrency properties -- pipeline overlap, bounded
in-flight queues, fairness, deadline shedding, backpressure -- used to
be tested with wall-clock sleeps, which is both slow and flaky on
CPU-starved CI hosts. This module replaces real time with a virtual
timeline:

* :class:`VirtualClock` -- the injectable clock (``RenderService`` and
  ``FrontDoor`` read time ONLY through ``clock.now()``). Time advances
  exactly when a fake says it does, so schedule assertions are exact
  equalities, not tolerance bands.
* :class:`FakeDevice` -- a serial device timeline: dispatches queue up
  back-to-back (one accelerator), ``finalize`` blocks (advances the
  clock) until the dispatch's scripted completion time. This is the
  async-dispatch model JAX gives the service: enqueue returns
  immediately, materialisation blocks.
* :class:`FakeEngine` -- drop-in for ``RenderService._dispatch``
  (instance-attribute patch): every chunk costs a scripted device time,
  returns plausible canvases/ASKStats, and records its enqueue/ready
  times so tests assert the REAL service's pipeline schedule on the
  virtual timeline. ``FakeEngine.attach(svc, ...)`` wires clock +
  engine in one call.
* :class:`FakeService` -- a scripted ``RenderService`` stand-in exposing
  exactly the front-door seam (``workload_keys / chunk_frames / n /
  dispatch_planned``), with per-batch latency models, injectable
  dispatch failures, scripted retry/overflow counts, and canvases that
  encode each frame's identity (``canvas[0, 0] == bounds[0]``) so demux
  tests can prove which frame went to which tenant.

Nothing in here sleeps; nothing reads wall time.
"""

import dataclasses

import numpy as np

from repro.launch.render_service import ChunkResult, ChunkStats


class VirtualClock:
    """A manually-advanced clock with the service clock protocol
    (``now() -> float``). Fakes advance it to model device compute and
    host I/O; tests advance it to model the passage of deadline time."""

    def __init__(self, start: float = 0.0):
        self._t = float(start)

    def now(self) -> float:
        return self._t

    def advance(self, dt: float) -> float:
        if dt < 0:
            raise ValueError(f"time only moves forward, got advance({dt})")
        self._t += dt
        return self._t

    def advance_to(self, t: float) -> float:
        """Advance to absolute time ``t`` (no-op when already past)."""
        if t > self._t:
            self._t = t
        return self._t


class FakeDevice:
    """One serial accelerator timeline on a virtual clock.

    ``enqueue(compute_s)`` models async dispatch: the work starts when
    the device frees up (not when the host calls), costs ``compute_s``
    of device time, and the call returns its absolute completion time
    immediately. ``wait_until(ready_at)`` models materialisation: the
    host blocks -- the clock advances -- until the work is done.
    """

    def __init__(self, clock: VirtualClock):
        self.clock = clock
        self.free_at = clock.now()

    def enqueue(self, compute_s: float) -> float:
        start = max(self.free_at, self.clock.now())
        self.free_at = start + float(compute_s)
        return self.free_at

    def wait_until(self, ready_at: float) -> None:
        self.clock.advance_to(ready_at)


@dataclasses.dataclass
class FakeStats:
    """Minimal ASKStats stand-in (the fields the serving layers read),
    shaped so ``frame_chains()`` yields one no-information chain per
    frame -- the estimator skips such chains, exactly like a real chunk
    whose frames never subdivided."""

    kernel_launches: int = 1
    leaf_count: int = 0
    overflow_dropped: int = 0
    wall_s: float = 0.0
    levels: int = 1
    region_counts: tuple = ()
    frame_overflow: tuple = ()
    frame_leaf_counts: tuple = ()
    dwell_rows: tuple = ()

    def frame_chains(self) -> tuple:
        return tuple(zip(self.region_counts, self.frame_leaf_counts))


def _fake_stats(f: int, *, launches: int = 1) -> FakeStats:
    return FakeStats(
        kernel_launches=launches, leaf_count=f,
        region_counts=((1,),) * f, frame_overflow=(0,) * f,
        frame_leaf_counts=(1,) * f)


@dataclasses.dataclass
class DispatchRecord:
    """One scripted dispatch, as the fakes saw it."""

    index: int
    key: str
    frames: int
    enqueued_at: float
    ready_at: float
    finalized_at: float = -1.0
    bounds: tuple = ()
    tenants: tuple = ()


class _FakeEngineHandle:
    """The engine-dispatch handle ``RenderService`` finalises:
    ``wait()`` blocks on the device timeline; ``finalize()`` waits too,
    then returns ``(canvases, stats)``."""

    def __init__(self, engine, record, canvases, stats):
        self._engine = engine
        self._record = record
        self._canvases = canvases
        self._stats = stats

    def wait(self):
        self._engine.device.wait_until(self._record.ready_at)

    def finalize(self):
        self.wait()
        self._record.finalized_at = self._engine.clock.now()
        return self._canvases, self._stats


class FakeEngine:
    """Scripted stand-in for ``RenderService._dispatch``.

    Attach with :meth:`attach` (or assign ``svc._dispatch = engine``
    after constructing the service with ``clock=engine.clock``): the
    REAL service then runs its real chunker / pipeline / retry logic
    while every dispatch costs exactly ``compute_s(frames)`` of virtual
    device time. ``records`` holds one :class:`DispatchRecord` per
    dispatch, in enqueue order -- the material for exact-schedule
    overlap assertions. ``enqueue_s`` is the host time each dispatch
    call takes; ``overflow`` maps a dispatch's index to the rows each of
    its frames dropped (``ASKStats.frame_overflow``), which sends those
    frames through the service's retry loop.
    """

    def __init__(self, *, n: int, compute_s=1.0, clock=None,
                 dtype=np.int32, enqueue_s: float = 0.0, overflow=None):
        self.clock = clock if clock is not None else VirtualClock()
        self.device = FakeDevice(self.clock)
        self.n = int(n)
        self.dtype = dtype
        self._compute_s = (compute_s if callable(compute_s)
                          else (lambda f: float(compute_s)))
        self._enqueue_s = float(enqueue_s)
        self._overflow = dict(overflow or {})
        self.records = []

    @classmethod
    def attach(cls, service, *, compute_s=1.0, **kw):
        """Wire a fresh engine into ``service``: the service's clock is
        replaced by the engine's virtual clock and its ``_dispatch`` by
        the scripted one. Returns the engine."""
        eng = cls(n=service.n, compute_s=compute_s,
                  dtype=service._dtype, **kw)
        service._clock = eng.clock
        service._dispatch = eng
        return eng

    def __call__(self, chunk, caps=None, key=""):
        f = len(chunk)
        t0 = self.clock.now()
        ready = self.device.enqueue(self._compute_s(f))
        rec = DispatchRecord(
            index=len(self.records), key=str(key), frames=f,
            enqueued_at=t0, ready_at=ready,
            bounds=tuple(tuple(float(x) for x in b) for b in chunk))
        self.records.append(rec)
        canvases = np.zeros((f, self.n, self.n), self.dtype)
        # encode frame identity so demux/order tests can see who is who
        for j, b in enumerate(rec.bounds):
            canvases[j, 0, 0] = np.asarray(b[0]).astype(self.dtype)
        stats = _fake_stats(f)
        if rec.index in self._overflow:
            stats.frame_overflow = tuple(self._overflow[rec.index])
        self.clock.advance(self._enqueue_s)
        handle = _FakeEngineHandle(self, rec, canvases, stats)
        return handle, self.clock.now() - t0


class FakePlanned:
    """The ``PlannedDispatch`` surface the front door drives: one-shot
    ``finalize()`` blocking on the scripted device timeline."""

    def __init__(self, service, record, fail=None, retries=0,
                 overflow_dropped=0, launches=1):
        self._service = service
        self._record = record
        self._fail = fail
        self._retries = int(retries)
        self._overflow = int(overflow_dropped)
        self._launches = int(launches)
        self._done = False

    @property
    def frames(self) -> int:
        return self._record.frames

    @property
    def workload(self) -> str:
        return self._record.key

    @property
    def tenants(self) -> tuple:
        return self._record.tenants

    def finalize(self) -> ChunkResult:
        if self._done:
            raise RuntimeError("FakePlanned.finalize() is one-shot")
        self._done = True
        svc = self._service
        svc.device.wait_until(self._record.ready_at)
        self._record.finalized_at = svc._clock.now()
        if self._fail is not None:
            raise self._fail
        f = self._record.frames
        canvases = np.zeros((f, svc.n, svc.n), np.float64)
        for j, b in enumerate(self._record.bounds):
            canvases[j, 0, 0] = b[0]
        st = _fake_stats(f, launches=self._launches)
        st.overflow_dropped = self._overflow
        return ChunkResult(canvases, st, ChunkStats(
            index=self._record.index, frames=f,
            dispatch_s=0.0,
            fetch_s=self._record.finalized_at - self._record.enqueued_at,
            in_flight=1, retries=self._retries, workload=self._record.key,
            tenants=self._record.tenants))


class FakeService:
    """Scripted ``RenderService`` stand-in exposing exactly the front-
    door seam.

    Latency model: a batch of ``f`` frames costs ``overhead_s + f *
    per_frame_s`` of serial device time (the same affine shape the
    front door's deadline model assumes, so deadline-width tests can
    predict schedules exactly). ``fail`` injects dispatch failures --
    either a set of batch indices (dispatch order) or a callable
    ``(index, key, bounds, tenants) -> Exception | None``. ``script``
    maps batch index to per-batch stat overrides
    (``{"retries": 2, "overflow_dropped": 1, "launches": 3}``). Every
    batch is recorded in ``batches`` (a :class:`DispatchRecord` list).
    """

    def __init__(self, *, keys=("",), chunk_frames: int = 8, n: int = 1,
                 clock=None, overhead_s: float = 0.0,
                 per_frame_s: float = 1.0, fail=None, script=None):
        self._clock = clock if clock is not None else VirtualClock()
        self.device = FakeDevice(self._clock)
        self._keys = tuple(str(k) for k in keys)
        self.chunk_frames = int(chunk_frames)
        self.n = int(n)
        self.overhead_s = float(overhead_s)
        self.per_frame_s = float(per_frame_s)
        if fail is None:
            self._fail = lambda *a: None
        elif callable(fail):
            self._fail = fail
        else:
            bad = frozenset(fail)
            self._fail = (lambda index, key, bounds, tenants:
                          RuntimeError(f"injected dispatch failure on "
                                       f"batch {index}")
                          if index in bad else None)
        self._script = dict(script or {})
        self.batches = []

    def workload_keys(self) -> tuple:
        return tuple(sorted(self._keys))

    def dispatch_planned(self, bounds, *, key: str = "", tenants=(),
                         tenant_feedback: bool = False):
        del tenant_feedback  # accepted for surface parity; no estimator
        key = str(key)
        if key not in self._keys:
            raise KeyError(f"unknown problem {key!r}")
        bounds = [tuple(float(x) for x in b) for b in bounds]
        if not bounds:
            raise ValueError("dispatch_planned needs at least one frame")
        if len(bounds) > self.chunk_frames:
            raise ValueError(
                f"batch of {len(bounds)} frames exceeds chunk_frames="
                f"{self.chunk_frames}")
        tenants = tuple(str(t) for t in tenants)
        if tenants and len(tenants) != len(bounds):
            raise ValueError(
                f"got {len(tenants)} tenants for {len(bounds)} frames")
        index = len(self.batches)
        cost = self.overhead_s + len(bounds) * self.per_frame_s
        t0 = self._clock.now()
        ready = self.device.enqueue(cost)
        rec = DispatchRecord(
            index=index, key=key, frames=len(bounds), enqueued_at=t0,
            ready_at=ready, bounds=tuple(bounds), tenants=tenants)
        self.batches.append(rec)
        over = self._script.get(index, {})
        return FakePlanned(
            self, rec, fail=self._fail(index, key, bounds, tenants),
            retries=over.get("retries", 0),
            overflow_dropped=over.get("overflow_dropped", 0),
            launches=over.get("launches", 1))
