"""Mariani-Silver subdivision for any registered workload (paper Sec. 6,
generalised per Sec. 7's "the machinery is workload-agnostic" argument).

``FrameProblem`` implements the ``ASKProblem`` adapter for ONE workload
(a ``WorkloadSpec`` or registry name), so the same object runs under all
the drivers the paper compares:

  Ex   -- ``exhaustive`` below                   (one flat kernel)
  DP   -- ``repro.core.dp_emul.run_dp``          (one dispatch per tree node)
  ASK  -- ``repro.core.ask.run_ask`` / ``run_ask_fused``  (one per level)
  scan -- ``repro.core.ask.run_ask_scan``        (one per run / batch)

Per level, ``level_step`` performs:
  Q (perimeter query)            kernels/perimeter_query.py
  T (fill homogeneous regions)   kernels/region_fill.py
  subdivide flags                for the driver's OLT step
and ``leaf_step`` performs the last-level application work A
(kernels/region_dwell.py). Q, T and A run in the ``jax.named_scope``s
``ask.query``, ``ask.fill`` and ``ask.dwell`` (the drivers add
``ask.compact`` and ``ask.subdivide``), which name every operation of a
stage in the compiled program and in a profile. The workload spec
rides into every kernel as a static argument, so one kernel body serves
all escape-time workloads bit-identically to its jnp oracle; grid
workloads route through the jnp path (see ``kernels.ops``).

``MandelbrotProblem`` is a back-compat alias: a ``FrameProblem`` whose
default workload is the registry's ``mandelbrot`` spec is the exact
pre-refactor object (same fields, same compute, same hash/equality
semantics for the jitted-pipeline caches).

The fill-OLT compaction inside level_step uses jnp.nonzero(size=...) --
shape-static, so the whole step stays jittable; padding rows duplicate the
first live row (see region_fill.py for why duplicates, not masks).
"""

from __future__ import annotations

import dataclasses
import time
from typing import Tuple, Union

import jax
import jax.numpy as jnp

from repro.kernels import ops, ref
from repro.kernels.policy import KernelPolicy
from repro.workloads.registry import get_workload
from repro.workloads.spec import WorkloadSpec

__all__ = ["FrameProblem", "MandelbrotProblem", "exhaustive", "solve",
           "solve_batch", "dispatch_batch"]


@dataclasses.dataclass(frozen=True)
class FrameProblem:
    """ASKProblem adapter for Mariani-Silver subdivision of one workload.

    ``workload`` accepts a registry name or a ``WorkloadSpec`` and is
    resolved to the canonical spec instance at construction; ``bounds``
    defaults to the workload's own window (``spec.default_bounds``), so
    ``FrameProblem(n=256, workload="julia")`` is a fully-specified
    problem. The dataclass stays frozen and hashable -- it is the
    compile-cache key of the scan engines (``core.ask._PIPELINE_CACHE``),
    and since the resolved ``policy`` participates in equality/hash, two
    problems that route kernels differently never share a compiled
    pipeline.

    Kernel routing: ``policy`` (a ``kernels.policy.KernelPolicy`` or a
    backend name) is the canonical knob; the legacy ``backend`` string
    field remains as constructor sugar -- at construction the two are
    reconciled (``policy`` wins when both are given; ``backend`` is
    rewritten to the resolved policy's backend so the pair can never
    disagree).
    """

    n: int
    g: int = 2
    r: int = 2
    B: int = 32
    max_dwell: int = 512
    bounds: Union[Tuple[float, float, float, float], None] = None
    scheme: str = "sbr"  # "sbr" | "mbr"  (paper Sec. 4.3)
    tile: int = 256  # MBR tile side
    backend: str = "jnp"  # "jnp" | "pallas" | "tuned" (sugar for policy)
    workload: Union[str, WorkloadSpec] = "mandelbrot"
    policy: Union[KernelPolicy, str, None] = None

    def __post_init__(self):
        spec = get_workload(self.workload)
        object.__setattr__(self, "workload", spec)
        if self.policy is None:
            pol = KernelPolicy(backend=self.backend)
        else:
            pol = KernelPolicy.coerce(self.policy)
        object.__setattr__(self, "policy", pol)
        object.__setattr__(self, "backend", pol.backend.value)
        bounds = spec.default_bounds if self.bounds is None else self.bounds
        object.__setattr__(self, "bounds",
                           tuple(float(b) for b in bounds))
        if self.n % self.g:
            raise ValueError("n must be divisible by g")
        side = self.n // self.g
        while side > self.B:
            if side % self.r:
                raise ValueError(
                    f"subdivision chain broken: side {side} not divisible by r={self.r}")
            side //= self.r

    # -- ASKProblem protocol ------------------------------------------------

    def init_state(self) -> jax.Array:
        return jnp.zeros((self.n, self.n), dtype=self.workload.dtype)

    def root_coords(self) -> jax.Array:
        g = self.g
        cy, cx = jnp.meshgrid(jnp.arange(g), jnp.arange(g), indexing="ij")
        return jnp.stack([cy.ravel(), cx.ravel()], axis=-1).astype(jnp.int32)

    def region_side(self, level: int) -> int:
        return self.n // (self.g * self.r ** level)

    def level_step(self, state: jax.Array, coords: jax.Array,
                   valid: jax.Array, *, level: int,
                   bounds=None) -> Tuple[jax.Array, jax.Array]:
        bounds = self.bounds if bounds is None else bounds
        side = self.region_side(level)
        with jax.named_scope("ask.query"):
            homog, common = ops.perimeter_query(
                coords, side=side, n=self.n, bounds=bounds,
                max_dwell=self.max_dwell, policy=self.policy,
                workload=self.workload)
            homog = jnp.logical_and(homog, valid)

        with jax.named_scope("ask.fill"):
            # compact fill-OLT; pad with duplicates of the first live row
            cap = coords.shape[0]
            (idx,) = jnp.nonzero(homog, size=cap, fill_value=0)
            count = jnp.sum(homog.astype(jnp.int32))
            live = jnp.arange(cap) < count
            idx = jnp.where(live, idx, idx[0])
            fill_coords = coords[idx]
            fill_vals = common[idx]
            nonempty = (count > 0).astype(jnp.int32).reshape((1,))
            state = ops.region_fill(
                state, fill_coords, fill_vals, nonempty, side=side, n=self.n,
                scheme=self.scheme, tile=self.tile, policy=self.policy)

        subdivide = jnp.logical_and(valid, jnp.logical_not(homog))
        return state, subdivide

    def leaf_step(self, state: jax.Array, coords: jax.Array,
                  valid: jax.Array, *, level: int, bounds=None) -> jax.Array:
        bounds = self.bounds if bounds is None else bounds
        side = self.region_side(level)
        with jax.named_scope("ask.dwell"):
            # duplicate-pad the invalid tail (idempotent recompute)
            cap = coords.shape[0]
            count = jnp.sum(valid.astype(jnp.int32))
            idx = jnp.where(jnp.arange(cap) < count, jnp.arange(cap), 0)
            coords = coords[idx]
            nonempty = (count > 0).astype(jnp.int32).reshape((1,))
            return ops.region_dwell(
                state, coords, nonempty, side=side, n=self.n, bounds=bounds,
                max_dwell=self.max_dwell, scheme=self.scheme, tile=self.tile,
                policy=self.policy, workload=self.workload)

    def preview_step(self, state: jax.Array, coords: jax.Array,
                     valid: jax.Array, *, level: int,
                     bounds=None) -> jax.Array:
        """Cheap coarse paint of the still-live set (``core.progressive``).

        Every live region -- homogeneous or not -- is constant-filled
        with its perimeter's common value: one border query per region,
        NO per-pixel interior dwell (that is ``leaf_step``'s full-cost
        job). The result is a full-coverage preview canvas; the scan
        state itself is never painted with it, so the refinement half
        stays bit-identical to the unsplit program.
        """
        bounds = self.bounds if bounds is None else bounds
        side = self.region_side(level)
        with jax.named_scope("ask.query"):
            _, common = ops.perimeter_query(
                coords, side=side, n=self.n, bounds=bounds,
                max_dwell=self.max_dwell, policy=self.policy,
                workload=self.workload)
        with jax.named_scope("ask.fill"):
            # live rows are the ring's contiguous prefix; duplicate-pad
            # the tail
            cap = coords.shape[0]
            count = jnp.sum(valid.astype(jnp.int32))
            idx = jnp.where(jnp.arange(cap) < count, jnp.arange(cap), 0)
            nonempty = (count > 0).astype(jnp.int32).reshape((1,))
            return ops.region_fill(
                state, coords[idx], common[idx], nonempty, side=side,
                n=self.n, scheme=self.scheme, tile=self.tile,
                policy=self.policy)

    # -- dynamic-parameter protocol (batched frame serving) -----------------
    # ``extra`` is a traced [4] bounds array: one plane window per frame
    # in the vmapped ask_scan pipeline. The kernels route to the
    # traced-bounds jnp path automatically (ops._bounds_traced).

    def level_step_dyn(self, state, coords, valid, *, level: int, extra):
        return self.level_step(state, coords, valid, level=level,
                               bounds=extra)

    def leaf_step_dyn(self, state, coords, valid, *, level: int, extra):
        return self.leaf_step(state, coords, valid, level=level,
                              bounds=extra)

    def preview_step_dyn(self, state, coords, valid, *, level: int, extra):
        return self.preview_step(state, coords, valid, level=level,
                                 bounds=extra)

    # -- pooled protocol (cross-frame worklists, core.pooled) ---------------
    # ``rows`` is a frame-tagged [N, 3] = (frame, cy, cx) worklist pooled
    # across the whole batch; ``state`` is the tall [F*n, n] canvas and
    # ``bounds_all`` the [F, 4] per-frame windows. The math per row is the
    # traced-bounds path of level_step evaluated in the row's OWN frame
    # window (ops.pooled_bounds), so each frame's subsequence stays
    # bit-identical to its private per-frame scan.

    def pooled_level_step(self, state: jax.Array, rows: jax.Array,
                          valid: jax.Array, *, level: int,
                          bounds_all) -> Tuple[jax.Array, jax.Array]:
        side = self.region_side(level)
        with jax.named_scope("ask.query"):
            homog, common = ops.perimeter_query(
                rows[:, 1:], side=side, n=self.n,
                bounds=ops.pooled_bounds(bounds_all, rows),
                max_dwell=self.max_dwell, policy=self.policy,
                workload=self.workload)
            homog = jnp.logical_and(homog, valid)

        with jax.named_scope("ask.fill"):
            # compact fill-OLT; pad with duplicates of the first live row
            cap = rows.shape[0]
            (idx,) = jnp.nonzero(homog, size=cap, fill_value=0)
            count = jnp.sum(homog.astype(jnp.int32))
            live = jnp.arange(cap) < count
            idx = jnp.where(live, idx, idx[0])
            nonempty = (count > 0).astype(jnp.int32).reshape((1,))
            state = ops.region_fill_pooled(
                state, rows[idx], common[idx], nonempty, side=side,
                n=self.n, policy=self.policy)

        subdivide = jnp.logical_and(valid, jnp.logical_not(homog))
        return state, subdivide

    def pooled_leaf_step(self, state: jax.Array, rows: jax.Array,
                         valid: jax.Array, *, level: int,
                         bounds_all) -> Tuple[jax.Array, jax.Array]:
        """A over the live leaf rows (``valid``, a prefix of the ring):
        ``(state, rows A ran)``."""
        side = self.region_side(level)
        with jax.named_scope("ask.dwell"):
            count = jnp.sum(valid.astype(jnp.int32))
            return ops.region_dwell_pooled(
                state, rows, count, side=side, n=self.n,
                bounds_all=bounds_all, max_dwell=self.max_dwell,
                policy=self.policy, workload=self.workload)


# back-compat: the paper's case study is the default-workload FrameProblem
MandelbrotProblem = FrameProblem


def exhaustive(n: int, *, max_dwell: int = 512, bounds=None,
               block=(256, 256), backend=None, policy=None,
               workload: Union[str, WorkloadSpec, None] = None):
    """Ex: the flat one-kernel baseline (paper Sec. 6.1, implementation 1).

    One flat kernel over the whole n x n domain; W_E = n^2 * A. With
    ``workload=None`` this is the seed Mandelbrot kernel; otherwise the
    workload's point function runs inside the same kernel body.
    ``policy`` is a ``KernelPolicy`` (or backend name); the legacy
    ``backend=`` string kwarg still works via the deprecation shim.
    """
    from repro.core.ask import ASKStats
    from repro.kernels.policy import resolve_policy

    spec = None if workload is None else get_workload(workload)
    if bounds is None:
        bounds = ref.DEFAULT_BOUNDS if spec is None else spec.default_bounds
    # resolve the legacy backend= here, ONCE, so the DeprecationWarning
    # points at the caller's backend= usage (stacklevel: resolve_policy ->
    # exhaustive -> caller) instead of at ops.mandelbrot's internals --
    # and so the shim never warns twice for one user call
    pol = resolve_policy(backend, policy, stacklevel=3)
    t0 = time.perf_counter()
    canvas = ops.mandelbrot(
        n, bounds=tuple(bounds), max_dwell=max_dwell, block=block,
        policy=pol, workload=spec)
    canvas = jax.block_until_ready(canvas)
    stats = ASKStats(levels=0, kernel_launches=1,
                     wall_s=time.perf_counter() - t0)
    return canvas, stats


def solve(problem: FrameProblem, method: str = "ask", **kw):
    """Convenience dispatcher:
    method in {ex, ask, ask_fused, ask_scan, ask_tuned, ask_pooled, dp}.

    ``ask_tuned`` is the autotuned rung of the engine ladder: the same
    scan pipeline as ``ask_scan``, with every kernel dispatch routed
    through the tuned tier (``kernels.autotune`` winners / heuristics,
    see ``kernels.policy.KernelPolicy``). Bit-identical to ``ask_scan``
    for every registered workload -- the tuned tier only re-schedules
    (block shape, escape-loop unroll), it never changes the math.
    """
    if method == "ex":
        return exhaustive(problem.n, max_dwell=problem.max_dwell,
                          bounds=problem.bounds, policy=problem.policy,
                          workload=problem.workload)
    if method == "ask":
        from repro.core.ask import run_ask
        return run_ask(problem, **kw)
    if method == "ask_fused":
        from repro.core.ask import run_ask_fused
        return run_ask_fused(problem, **kw)
    if method == "ask_scan":
        from repro.core.ask import run_ask_scan
        return run_ask_scan(problem, **kw)
    if method == "ask_tuned":
        from repro.core.ask import run_ask_scan
        tuned = dataclasses.replace(
            problem, policy=problem.policy.with_backend("tuned"))
        return run_ask_scan(tuned, **kw)
    if method == "ask_pooled":
        from repro.core.pooled import run_ask_pooled
        return run_ask_pooled(problem, **kw)
    if method == "dp":
        from repro.core.dp_emul import run_dp
        return run_dp(problem, **kw)
    raise ValueError(f"unknown method {method!r}")


def _bounds_array(bounds_batch) -> jax.Array:
    bounds_arr = jnp.asarray(bounds_batch, jnp.float32)
    if bounds_arr.ndim != 2 or bounds_arr.shape[1] != 4:
        raise ValueError(f"bounds_batch must be [F, 4], got {bounds_arr.shape}")
    return bounds_arr


def solve_batch(problem: FrameProblem, bounds_batch, *, options=None,
                mesh=None, plan=None, **kw):
    """Batched frame serving: render F frames in ONE XLA dispatch.

    ``options`` (an ``EngineOptions`` -- re-exported from
    ``repro.workloads`` -- or an engine name) is the canonical way to
    configure this call: engine selection (``engine="ask_tuned"`` routes
    every kernel through the autotuned tier; ``engine="ask_pooled"``
    pools all frames' regions into ONE cross-frame worklist per level
    whose shared ring is sized from the summed per-frame occupancies --
    see ``core.pooled`` -- with ``plan=True`` routing through
    ``planner.solve_pooled``), batching (``mesh`` /
    ``pad_to``), planning (``plan`` / ``observed`` / ``num_buckets``),
    capacity sizing, kernel routing (``policy``), and planner expert
    knobs (``extra``) in one frozen object. The flat keyword arguments
    below remain supported for backward compatibility but are
    **deprecated** -- they are folded into an ``EngineOptions`` via
    ``EngineOptions.from_kwargs``; mixing ``options=`` with any legacy
    kwarg raises ``ValueError``.

    ``bounds_batch`` is [F, 4] (re0, im0, re1, im1) per frame -- a zoom
    sequence or F tenants' viewports, all of the problem's ONE workload
    (mixed-workload streams are served by ``launch.render_service.
    RenderService`` over several problems). The scan engine is vmapped
    over the frame axis (see ``core.ask.run_ask_scan_batch``): per-level
    ring capacities -- sized from the cost model's expected occupancy
    E_l = g^2 (r^2 P)^l over the tau = log_r(n/(gB)) subdivision levels
    (``cost_model.expected_level_counts`` / ``tau_levels``) -- are shared
    across frames, overflow accounting is summed (and broken out per
    frame in ``ASKStats.frame_overflow``). The dwell compute runs the
    traced-bounds jnp path (identical math, so each frame is
    bit-identical to a single-frame ``run_ask`` at those bounds).

    ``mesh`` (a 1-D ``jax.sharding.Mesh``, see ``launch.mesh.
    make_frames_mesh``) shards the frame axis across its devices
    (``core.ask.run_ask_scan_sharded``): still one dispatch, frame counts
    that don't divide the device count are padded and masked, and each
    frame stays bit-identical to the unsharded batch. For streaming more
    frames than fit one batch, see ``launch.render_service``.

    ``plan`` switches to the occupancy-aware capacity planner
    (``core.planner``) for heterogeneous batches -- deep-zoom frames get
    a hotter effective P (hence a bigger ring) than wide frames, and any
    frame that still overflows is re-planned automatically. The per-frame
    P prior comes from the workload's own band (``WorkloadSpec.
    prior_band``), so a julia batch and a mandelbrot batch plan from
    their own falloffs. Pass an int (the bucket count K), True (default
    K), or a prebuilt ``planner.CapacityPlan``. With ``observed=`` (a
    ``core.feedback.OccupancyEstimator``) the plan blends MEASURED
    occupancy from previous runs -- keyed per workload -- into the
    per-frame P instead of relying on the zoom-depth prior alone
    (``planner.plan_frames``). The planned path returns (canvases
    [F, n, n] numpy, ``planner.PlanReport``) -- whose ``frame_p_subdiv``
    / ``frame_p_source`` record the P that actually sized each frame and
    where it came from -- and issues one compiled program per bucket
    instead of one overall; the uniform path returns (canvases
    [F, n, n], ASKStats).
    """
    from repro.workloads.options import EngineOptions

    if options is not None:
        if mesh is not None or plan is not None or kw:
            legacy = [k for k, v in (("mesh", mesh), ("plan", plan))
                      if v is not None] + sorted(kw)
            raise ValueError(
                f"pass options= OR the legacy kwargs {legacy}, not both")
        opts = EngineOptions.coerce(options)
        problem = opts.apply_to(problem)
        mesh, plan, kw = opts.mesh, opts.plan, opts.engine_kwargs()
        engine = opts.engine
    else:
        engine = "ask_scan"  # the legacy flat-kwarg path predates engines
    bounds_arr = _bounds_array(bounds_batch)
    planned = plan is not None and plan is not False
    # ``block_until_ready`` is an ENGINE kwarg: the planned paths block
    # by construction (they read stats back to drive the retry loop), so
    # it must not leak into plan_frames / plan_pooled through **kw
    block = kw.pop("block_until_ready", None)
    if not planned:
        # observed= without plan=: thread the estimator into the engine
        # sizing exactly as RenderService's feedback chunker does --
        # per-frame P into the pooled shared ring, the hottest member's
        # P into the uniform scan -- instead of crashing in the engine
        # entry point (which takes no estimator)
        observed = kw.pop("observed", None)
        quantize = kw.pop("quantize", None)
        if quantize and observed is None:
            raise ValueError(
                "quantize=True needs observed=: the p_quantum grid lives "
                "on the OccupancyEstimator")
        if observed is not None:
            clash = {"capacities", "p_subdiv", "frame_ps"} & kw.keys()
            if clash:
                raise ValueError(
                    f"{sorted(clash)} conflict with observed=: the "
                    "estimator sizes the ring -- drop them or drop "
                    "observed=")
            from repro.core import planner as planner_lib
            ps = planner_lib.observed_frame_ps(
                problem, bounds_arr, observed, quantize=bool(quantize),
                ref_width=kw.pop("ref_width", None),
                tenant=kw.pop("tenant", None))
            if engine == "ask_pooled":
                kw["frame_ps"] = list(ps)
            else:
                kw["p_subdiv"] = max(ps)
        if block is not None:
            kw["block_until_ready"] = block
    if engine == "ask_pooled":
        if planned:
            from repro.core import planner as planner_lib
            engine_only = ({"capacities", "p_subdiv", "pad_to",
                            "num_buckets"} & kw.keys())
            if engine_only:
                raise ValueError(
                    f"{sorted(engine_only)} do not apply to the pooled "
                    "planner -- it sizes ONE shared ring from the summed "
                    "per-frame occupancies (tune safety_factor / observed "
                    "/ quantize / band knobs instead)")
            plan_obj = (plan if isinstance(plan, planner_lib.CapacityPlan)
                        else None)
            if plan_obj is None and not isinstance(plan, bool):
                raise ValueError(
                    "plan=<bucket count> does not apply to ask_pooled -- "
                    "the pooled worklist IS one shared bucket; pass "
                    "plan=True or a pooled CapacityPlan")
            return planner_lib.solve_pooled(problem, bounds_arr,
                                            plan=plan_obj, mesh=mesh, **kw)
        from repro.core.pooled import (run_ask_pooled_batch,
                                       run_ask_pooled_sharded)
        if mesh is None:
            return run_ask_pooled_batch(problem, bounds_arr, **kw)
        return run_ask_pooled_sharded(problem, bounds_arr, mesh=mesh, **kw)
    if planned:
        from repro.core import planner as planner_lib
        engine_only = {"capacities", "p_subdiv", "pad_to"} & kw.keys()
        if engine_only:
            raise ValueError(
                f"{sorted(engine_only)} belong to the uniform path; the "
                "planner sizes capacities itself -- tune num_buckets / "
                "safety_factor / p_deep / slope / p_min / ref_width instead")
        plan_obj = plan if isinstance(plan, planner_lib.CapacityPlan) else None
        if plan_obj is None and not isinstance(plan, bool):
            kw.setdefault("num_buckets", int(plan))
        return planner_lib.solve_planned(problem, bounds_arr, plan=plan_obj,
                                         mesh=mesh, **kw)
    from repro.core.ask import run_ask_scan_batch, run_ask_scan_sharded
    if mesh is None:
        return run_ask_scan_batch(problem, bounds_arr, **kw)
    return run_ask_scan_sharded(problem, bounds_arr, mesh=mesh, **kw)


def dispatch_batch(problem: FrameProblem, bounds_batch, *, mesh=None,
                   options=None, **kw):
    """Enqueue one sharded frame batch WITHOUT blocking (async serving).

    The non-blocking half of ``solve_batch(..., mesh=...)``: returns a
    ``core.ask.ShardedDispatch`` handle as soon as the XLA call is
    enqueued; ``.finalize()`` yields the same (canvases, ASKStats) (the
    pooled engine's ``core.pooled.PooledDispatch`` keeps the padded
    tail on its canvases). The pipelined render service
    (``launch.render_service``) uses this to overlap the host copy of
    chunk k with the device compute of chunk k+1. ``options`` (an ``EngineOptions`` carrying the mesh) is the
    canonical configuration spelling, as in ``solve_batch``.
    """
    from repro.core.ask import dispatch_ask_scan_sharded
    from repro.workloads.options import EngineOptions

    if options is not None:
        if mesh is not None or kw:
            raise ValueError(
                "pass options= OR the legacy mesh=/engine kwargs, not both")
        opts = EngineOptions.coerce(options)
        problem = opts.apply_to(problem)
        mesh, kw = opts.mesh, opts.engine_kwargs()
        engine = opts.engine
    else:
        engine = "ask_scan"
    if mesh is None:
        raise ValueError(
            "dispatch_batch needs a mesh (mesh= or options.mesh)")
    if engine == "ask_pooled":
        from repro.core.pooled import dispatch_ask_pooled_sharded
        return dispatch_ask_pooled_sharded(
            problem, _bounds_array(bounds_batch), mesh=mesh, **kw)
    return dispatch_ask_scan_sharded(problem, _bounds_array(bounds_batch),
                                     mesh=mesh, **kw)
