"""Lambda-reduction benchmark: the five engines side by side.

The paper's central claim is that ASK beats Dynamic Parallelism because it
pays a smaller per-launch overhead lambda. This suite makes the claim
measurable across the whole engine ladder:

  ex         one flat kernel, no subdivision         (1 dispatch, no OLT)
  dp         one dispatch per subdivision-tree node  (lambda paid per node)
  ask        one dispatch per level + host sync      (lambda paid per level)
  ask_fused  one dispatch, worst-case OLT buffers    (lambda paid once,
                                                      memory worst-case)
  ask_scan   one dispatch, bounded OLT ring          (lambda paid once,
                                                      memory ~expected)
  ask_tuned  ask_scan with autotuned kernel routing  (same dispatches,
                                                      tuned schedules)

  ask_pooled one dispatch for a WHOLE batch, one     (lambda paid once per
             cross-frame pooled worklist per level    batch, ring ~ summed
                                                      expected occupancy)

The ``tuned_tier`` suite additionally emits a machine-readable
``BENCH_6.json`` (dispatches / ring rows / wall times / tuned-vs-jnp
speedup per registry workload) and the ``pooled_tier`` suite a
``BENCH_7.json`` (pooled vs per-frame-planned ring rows on a
heterogeneous batch), and the ``tile_service`` suite a ``BENCH_9.json``
(content-addressed dwell-cache hit rate and dispatch savings on an
overlapping pan/zoom stream); CI's ``compare_bench`` gate diffs all
three against the checked-in baselines.

Rows (``name,case,value``):
  ask_scan_launches_<m>      kernel dispatch count
  ask_scan_olt_peak_rows_<m> peak live OLT rows resident at once
  ask_scan_olt_total_rows_<m> total OLT rows allocated across the program
  ask_scan_wall_ms_<m>       best-of-3 wall time (CPU/jnp backend)
  ask_scan_identical_<m>     canvas identical to run_ask (1/0)
plus ``ask_scan_batch_*`` rows for the vmapped multi-frame front-end.

Peak-rows accounting: ask re-uses one bucket per level (peak = largest
parent+child pair); fused keeps every per-level worst-case buffer inside
one program (peak = sum); scan keeps exactly two ring buffers (peak =
2 x max level capacity).
"""

from __future__ import annotations

import dataclasses
import json
import time

import numpy as np

from repro.core.ask import run_ask
from repro.mandelbrot import MandelbrotProblem, solve, solve_batch

DWELL = 128

METHODS = ("ex", "dp", "ask", "ask_fused", "ask_scan", "ask_tuned")


def _best_time(fn, reps=3):
    ts = []
    for _ in range(reps):
        t0 = time.perf_counter()
        fn()
        ts.append(time.perf_counter() - t0)
    return min(ts)


def _peak_rows(method: str, stats, r: int) -> int:
    caps = list(getattr(stats, "olt_caps", ()) or ())
    if method == "ex" or not caps:
        return 0
    if method == "dp":
        return 1  # one 1-row OLT per dispatch
    if method == "ask":
        # serial kernels: parent bucket + the transient child write-OLT
        # (run_ask sizes it next_pow2(cap * r^2) before the next level's
        # bucket shrinks it back to next_pow2(count))
        from repro.core.olt import next_pow2
        if len(caps) == 1:
            return caps[0]
        return max(c + next_pow2(c * r * r) for c in caps[:-1])
    if method == "ask_fused":
        return sum(caps)  # all per-level buffers live in one program
    if method in ("ask_scan", "ask_tuned"):
        return 2 * max(caps)  # the double-buffered ring
    return sum(caps)


def engines(writer, n=256, g=4, r=2, B=16):
    prob = MandelbrotProblem(n=n, g=g, r=r, B=B, max_dwell=DWELL,
                             backend="jnp")
    reference, _ = run_ask(prob)
    reference = np.asarray(reference)
    case = f"n={n}"
    for method in METHODS:
        solve(prob, method)  # warm the jit caches
        canvas, stats = solve(prob, method)
        wall = _best_time(lambda m=method: solve(prob, m))
        launches = stats.kernel_launches if method != "ex" else 1
        writer(f"ask_scan_launches_{method}", case, launches)
        writer(f"ask_scan_olt_peak_rows_{method}", case,
               _peak_rows(method, stats, r) if method != "ex" else 0)
        writer(f"ask_scan_olt_total_rows_{method}", case,
               sum(getattr(stats, "olt_caps", ()) or ()) if method != "ex"
               else 0)
        writer(f"ask_scan_wall_ms_{method}", case, wall * 1e3)
        writer(f"ask_scan_identical_{method}", case,
               int(np.array_equal(np.asarray(canvas), reference)))


def batch_serving(writer, n=256, frames=8):
    """The serving front-end: F frames of a zoom sequence, one dispatch."""
    prob = MandelbrotProblem(n=n, g=4, r=2, B=16, max_dwell=DWELL,
                             backend="jnp")
    re0, im0, re1, im1 = prob.bounds
    zooms = np.linspace(0.0, 0.6, frames)
    bounds = [(re0 + z * (re1 - re0) * 0.4, im0 + z * (im1 - im0) * 0.4,
               re1 - z * (re1 - re0) * 0.4, im1 - z * (im1 - im0) * 0.4)
              for z in zooms]
    solve_batch(prob, bounds)  # warm
    t = _best_time(lambda: solve_batch(prob, bounds))
    _, stats = solve_batch(prob, bounds)
    writer("ask_scan_batch_frames", f"n={n}", frames)
    writer("ask_scan_batch_launches", f"n={n}", stats.kernel_launches)
    writer("ask_scan_batch_wall_ms", f"n={n}", t * 1e3)
    writer("ask_scan_batch_ms_per_frame", f"n={n}", t * 1e3 / frames)
    writer("ask_scan_batch_overflow", f"n={n}", stats.overflow_dropped)

    # single-frame loop as the serving baseline (same engine, F dispatches)
    def loop():
        for b in bounds:
            solve(dataclasses.replace(prob, bounds=tuple(b)), "ask_scan")

    loop()  # warm (each distinct bounds tuple retraces once)
    writer("ask_scan_unbatched_wall_ms", f"n={n}", _best_time(loop) * 1e3)


def sharded_serving(writer, n=128, frames=16, chunk=8):
    """The sharded row: a 1-device mesh vs one over every visible device.

    Both mesh sizes stream the SAME chunked zoom trajectory through
    ``launch.render_service``; rows record wall time per mesh, dispatches
    per chunk (the acceptance target: exactly 1), and whether the sharded
    canvases are bit-identical to the 1-device render. Runs in this
    process: on a TPU host the mesh is the host's chips (a child process
    could not reach a chip this one holds); on CPU, set
    ``XLA_FLAGS=--xla_force_host_platform_device_count=8`` before the run
    to shard over 8 host devices.
    """
    import jax

    from repro.launch.mesh import make_frames_mesh
    from repro.launch.render_service import RenderService, zoom_bounds

    devices = len(jax.devices())
    prob = MandelbrotProblem(n=n, g=4, r=2, B=16, max_dwell=DWELL)
    res = {}
    canvases = {}
    for ndev in sorted({1, devices}):
        svc = RenderService(prob, mesh=make_frames_mesh(ndev),
                            chunk_frames=chunk, safety_factor=1e9)
        for _ in svc.stream(zoom_bounds(svc.chunk_frames)):
            pass  # warm the jitted sharded pipeline
        best = None
        for _ in range(2):
            c, rs = svc.render(zoom_bounds(frames))
            best = rs if best is None or rs.wall_s < best.wall_s else best
        canvases[ndev] = c
        res[ndev] = best
    case = f"n={n} f={frames}"  # no commas: rows stay 3-column CSV
    writer("ask_scan_sharded_frames", case, frames)
    writer("ask_scan_sharded_devices", case, devices)
    for ndev, rs in res.items():
        writer(f"ask_scan_sharded_wall_ms_{ndev}dev", case, rs.wall_s * 1e3)
    writer("ask_scan_sharded_dispatches_per_chunk", case,
           res[devices].dispatches_per_chunk)
    writer("ask_scan_sharded_program_traces", case,
           res[devices].program_traces)
    writer("ask_scan_sharded_identical", case,
           int(np.array_equal(canvases[1], canvases[devices])))


def planner_batch(writer, n=512, dwell=256, n_sparse=8, n_dense=4):
    """Heterogeneous-zoom acceptance rows: the occupancy-aware capacity
    planner (core/planner.py) against uniform safety_factor=2.0 sizing on
    a batch mixing zoomed-out (sparse) and deep-zoom (dense) frames.

    Rows record, per sizing policy: total OLT-ring memory (rows and
    bytes), regions overflow-dropped, and warm wall time. The planner
    must report overflow_dropped == 0 (retrying internally if a bucket
    runs hot) with strictly less total ring memory than the uniform
    baseline -- which, sized for the P=0.7 average, both over-allocates
    the sparse majority AND drops regions on the dense frames.
    """
    from repro.core.ask import scan_capacities
    from repro.core.planner import plan_capacities

    prob = MandelbrotProblem(n=n, g=4, r=2, B=16, max_dwell=dwell,
                             backend="jnp")

    def window(cx, cy, w):
        return (cx - w / 2, cy - w / 2, cx + w / 2, cy + w / 2)

    widths = np.geomspace(16.0, 4.0, n_sparse)
    sparse = [window(-0.5, 0.0, float(w)) for w in widths]
    dense = [window(-0.7436447860, 0.1318252536, 3.0 / 2 ** k)
             for k in np.linspace(4, 12, n_dense)]
    bounds = sparse + dense
    F = len(bounds)
    case = f"n={n} f={F}"

    plan = plan_capacities(prob, bounds, num_buckets=4)
    # the warm call (compiles every bucket program) already yields the
    # canvases + report; only the timing reps re-execute
    planned_canv, rep = solve_batch(prob, bounds, plan=plan)
    t_plan = _best_time(lambda: solve_batch(prob, bounds, plan=plan), reps=2)

    _, st_uni = solve_batch(prob, bounds, safety_factor=2.0)  # warm
    t_uni = _best_time(lambda: solve_batch(prob, bounds, safety_factor=2.0),
                       reps=2)
    uni_caps = scan_capacities(n, 4, 2, 16, safety_factor=2.0)
    uni_rows = F * 2 * max(uni_caps)

    exact, _ = solve_batch(prob, bounds, safety_factor=1e9)

    writer("ask_scan_planner_frames", case, F)
    writer("ask_scan_planner_buckets", case, len(plan.buckets))
    writer("ask_scan_planner_dispatches", case, rep.dispatches)
    writer("ask_scan_planner_retries", case, rep.retries)
    writer("ask_scan_planner_overflow", case, rep.overflow_dropped)
    writer("ask_scan_planner_ring_rows", case, rep.ring_rows)
    writer("ask_scan_planner_ring_bytes", case, rep.ring_bytes)
    writer("ask_scan_planner_wall_ms", case, t_plan * 1e3)
    writer("ask_scan_uniform2x_overflow", case, st_uni.overflow_dropped)
    writer("ask_scan_uniform2x_ring_rows", case, uni_rows)
    writer("ask_scan_uniform2x_ring_bytes", case, uni_rows * 8)
    writer("ask_scan_uniform2x_wall_ms", case, t_uni * 1e3)
    writer("ask_scan_planner_ring_vs_uniform", case,
           rep.ring_rows / uni_rows if uni_rows else 0.0)
    writer("ask_scan_planner_identical", case,
           int(np.array_equal(planned_canv, np.asarray(exact))))


def pipelined_serving(writer, n=256, dwell=128, frames=64, chunk=8,
                      sink_ms=40.0):
    """Async-pipeline acceptance rows: RenderService pipeline_depth=2 vs
    the synchronous path on a >= 8-chunk trajectory with a blocking
    per-chunk host-I/O sink (a sleep: models encoding/writing a chunk to
    disk or network without competing for the CPU cores XLA computes
    on). The pipelined wall time must land measurably below the sync
    path's summed per-chunk (compute + host-copy) cost, rs.busy_s.
    """
    from repro.launch.mesh import make_frames_mesh
    from repro.launch.render_service import RenderService, zoom_bounds

    prob = MandelbrotProblem(n=n, g=4, r=2, B=16, max_dwell=dwell)
    mesh = make_frames_mesh(1)

    def sink(canvases, stats):
        time.sleep(sink_ms / 1e3)

    res = {}
    canvases = {}
    for depth in (1, 2):
        svc = RenderService(prob, mesh=mesh, chunk_frames=chunk,
                            pipeline_depth=depth, safety_factor=2.0)
        for _ in svc.stream(zoom_bounds(svc.chunk_frames)):
            pass  # warm the chunk program
        best = None
        for _ in range(2):
            c, rs = svc.render(zoom_bounds(frames), sink=sink)
            best = rs if best is None or rs.wall_s < best.wall_s else best
        canvases[depth] = c
        res["sync" if depth == 1 else "pipelined"] = best
    sync, pipe = res["sync"], res["pipelined"]
    case = f"n={n} f={frames} chunk={chunk}"
    writer("render_pipeline_chunks", case, pipe.chunks)
    writer("render_pipeline_sink_ms", case, sink_ms)
    writer("render_sync_busy_ms", case, sync.busy_s * 1e3)
    writer("render_sync_wall_ms", case, sync.wall_s * 1e3)
    writer("render_sync_fetch_ms", case, sync.fetch_s * 1e3)
    writer("render_pipelined_wall_ms", case, pipe.wall_s * 1e3)
    writer("render_pipelined_fetch_ms", case, pipe.fetch_s * 1e3)
    writer("render_overlap_saved_ms", case,
           (sync.busy_s - pipe.wall_s) * 1e3)
    writer("render_pipelined_speedup", case,
           sync.busy_s / pipe.wall_s if pipe.wall_s else 0.0)
    writer("render_pipelined_identical", case,
           int(np.array_equal(canvases[1], canvases[2])))


def feedback_serving(writer, n=256, dwell=64, frames=48, chunk=4,
                     zoom=1.02, width0=6.0, safety_factor=1.1):
    """Closed-loop occupancy feedback acceptance rows: the feedback-
    driven render service (``RenderService(feedback=True)``) against the
    prior-only baseline (same chunking/retry machinery, ``adapt=False``)
    on a boundary-skimming zoom -- a trajectory that hugs the seahorse-
    valley boundary while still zoomed OUT, where the real subdivision
    density runs hotter than the zoom-depth prior.

    Rows record, per policy: total OLT-ring rows allocated (retry
    dispatches included), regions overflow-dropped (both must be 0 --
    the in-service retry guarantees it), frame retries, and dispatches.
    The feedback plan must reach 0 drops with FEWER ring rows and FEWER
    retries than the prior plan, and its cold-start chunk 0 must
    reproduce the prior plan exactly (same quantized P, "prior" source).
    """
    from repro.core.planner import ROW_BYTES
    from repro.launch.mesh import make_frames_mesh
    from repro.launch.render_service import RenderService, zoom_bounds

    prob = MandelbrotProblem(n=n, g=4, r=2, B=16, max_dwell=dwell,
                             backend="jnp")
    mesh = make_frames_mesh(1)
    center = (-0.7436447860, 0.1318252536)  # seahorse valley

    def traj():
        return zoom_bounds(frames, center=center, width0=width0,
                           zoom_per_frame=zoom)

    case = f"n={n} f={frames} chunk={chunk}"
    ref, _ = RenderService(prob, mesh=mesh, chunk_frames=chunk,
                           safety_factor=1e9).render(traj())

    results = {}
    for adapt, key in ((False, "prior"), (True, "feedback")):
        svc = RenderService(prob, mesh=mesh, chunk_frames=chunk,
                            feedback=True, adapt=adapt,
                            safety_factor=safety_factor)
        canv, rs = svc.render(traj())
        results[key] = rs
        writer(f"ask_scan_{key}_ring_rows", case, rs.ring_rows)
        writer(f"ask_scan_{key}_ring_bytes", case, rs.ring_rows * ROW_BYTES)
        writer(f"ask_scan_{key}_overflow", case, rs.overflow_dropped)
        writer(f"ask_scan_{key}_retries", case, rs.retries)
        writer(f"ask_scan_{key}_dispatches", case, rs.dispatches)
        writer(f"ask_scan_{key}_chunks", case, rs.chunks)
        writer(f"ask_scan_{key}_plan_signatures", case, rs.plan_signatures)
        writer(f"ask_scan_{key}_wall_ms", case, rs.wall_s * 1e3)
        writer(f"ask_scan_{key}_identical", case,
               int(np.array_equal(canv, ref)))

    prior, fb = results["prior"], results["feedback"]
    writer("ask_scan_feedback_ring_vs_prior", case,
           fb.ring_rows / prior.ring_rows if prior.ring_rows else 0.0)
    writer("ask_scan_feedback_cold_start_matches_prior", case,
           int(fb.chunk_stats[0].p_subdiv == prior.chunk_stats[0].p_subdiv
               and fb.chunk_stats[0].p_source == "prior"))
    writer("ask_scan_feedback_measured_chunks", case,
           sum(1 for c in fb.chunk_stats if c.p_source == "measured"))


def workload_serving(writer, n=256, dwell=64, frames=24, chunk=4,
                     zoom=1.05, safety_factor=1.15):
    """Beyond-Mandelbrot scenario rows: the planned batch path and the
    prior/feedback serving loop on a julia zoom and a burning-ship zoom
    (each toward a boundary target of its own set), so the BENCH
    trajectories cover more than one workload.

    Per workload, rows record: the planned heterogeneous batch
    (buckets/dispatches/ring rows/0 drops, bit-identical to the exact
    batch) and the closed-loop serving comparison (prior-only vs
    feedback ring rows and retries -- both 0-drop, feedback planning
    from each workload's OWN measured occupancy). The priors come from
    the per-workload bands on the ``WorkloadSpec``, not the Mandelbrot
    constants.
    """
    from repro.core.planner import ROW_BYTES
    from repro.launch.mesh import make_frames_mesh
    from repro.launch.render_service import RenderService, zoom_bounds
    from repro.workloads import FrameProblem

    # (workload, zoom target on its boundary, starting width)
    targets = (("julia", (0.0, 0.0), 3.2),
               ("burning_ship", (-1.7548, -0.0281), 4.0))
    mesh = make_frames_mesh(1)
    for wl, center, width0 in targets:
        prob = FrameProblem(n=n, g=4, r=2, B=16, max_dwell=dwell,
                            backend="jnp", workload=wl)
        case = f"wl={wl} n={n} f={frames}"

        def traj():
            return zoom_bounds(frames, center=center, width0=width0,
                               zoom_per_frame=zoom)

        # planned batch: wide establishing shots + the deep tail, one
        # compiled program per capacity bucket, each frame's P from the
        # workload's own zoom-depth prior
        batch = list(zoom_bounds(8, center=center, width0=width0 * 8,
                                 zoom_per_frame=2.0))
        canv, rep = solve_batch(prob, batch, plan=3)
        exact, _ = solve_batch(prob, batch, safety_factor=1e9)
        writer("ask_scan_wl_planned_buckets", case, len(rep.plan.buckets))
        writer("ask_scan_wl_planned_dispatches", case, rep.dispatches)
        writer("ask_scan_wl_planned_overflow", case, rep.overflow_dropped)
        writer("ask_scan_wl_planned_ring_rows", case, rep.ring_rows)
        writer("ask_scan_wl_planned_identical", case,
               int(np.array_equal(canv, np.asarray(exact))))

        # closed-loop serving: prior-only baseline vs feedback
        ref, _ = RenderService(prob, mesh=mesh, chunk_frames=chunk,
                               safety_factor=1e9).render(traj())
        results = {}
        for adapt, key in ((False, "prior"), (True, "feedback")):
            svc = RenderService(prob, mesh=mesh, chunk_frames=chunk,
                                feedback=True, adapt=adapt,
                                safety_factor=safety_factor)
            canv, rs = svc.render(traj())
            results[key] = rs
            writer(f"ask_scan_wl_{key}_ring_rows", case, rs.ring_rows)
            writer(f"ask_scan_wl_{key}_ring_bytes", case,
                   rs.ring_rows * ROW_BYTES)
            writer(f"ask_scan_wl_{key}_overflow", case, rs.overflow_dropped)
            writer(f"ask_scan_wl_{key}_retries", case, rs.retries)
            writer(f"ask_scan_wl_{key}_dispatches", case, rs.dispatches)
            writer(f"ask_scan_wl_{key}_identical", case,
                   int(np.array_equal(canv, ref)))
        prior, fb = results["prior"], results["feedback"]
        writer("ask_scan_wl_feedback_ring_vs_prior", case,
               fb.ring_rows / prior.ring_rows if prior.ring_rows else 0.0)
        writer("ask_scan_wl_feedback_measured_chunks", case,
               sum(1 for c in fb.chunk_stats if c.p_source == "measured"))


def tuned_tier(writer, n=256, dwell=64, bench_json=None):
    """The autotuned rung vs the plain scan engine, per registry workload.

    For every registered workload (the four escape-time sets AND the
    generated ``ssd_synth`` field) renders the 256^2 default viewport with
    ``ask_scan`` (jnp routing) and ``ask_tuned`` (autotune heuristics /
    cache), asserting the tuned canvas is bit-identical, and records
    dispatch count, ring rows, best-of-3 wall times, and the tuned-vs-jnp
    speedup. With ``bench_json`` the same numbers are written as the
    machine-readable ``BENCH_6.json`` CI's ``compare_bench`` gate diffs.
    """
    from repro.workloads import FrameProblem, available, solve

    payload = {"version": 1,
               "config": {"n": n, "max_dwell": dwell, "g": 4, "r": 2,
                          "B": 16},
               "workloads": {}}
    for wl in available():
        prob = FrameProblem(n=n, g=4, r=2, B=16, max_dwell=dwell,
                            backend="jnp", workload=wl)
        case = f"wl={wl} n={n}"
        base, base_stats = solve(prob, "ask_scan", safety_factor=1e9)
        tuned, stats = solve(prob, "ask_tuned", safety_factor=1e9)
        wall_jnp = _best_time(lambda: solve(prob, "ask_scan",
                                            safety_factor=1e9))
        wall_tuned = _best_time(lambda: solve(prob, "ask_tuned",
                                              safety_factor=1e9))
        identical = int(np.array_equal(np.asarray(base), np.asarray(tuned)))
        speedup = wall_jnp / wall_tuned if wall_tuned > 0 else 0.0
        ring_rows = stats.ring_rows
        writer("ask_tuned_dispatches", case, stats.kernel_launches)
        writer("ask_tuned_ring_rows", case, ring_rows)
        writer("ask_tuned_wall_ms_jnp", case, wall_jnp * 1e3)
        writer("ask_tuned_wall_ms_tuned", case, wall_tuned * 1e3)
        writer("ask_tuned_speedup", case, speedup)
        writer("ask_tuned_identical", case, identical)
        payload["workloads"][wl] = {
            "dispatches": int(stats.kernel_launches),
            "ring_rows": int(ring_rows),
            "wall_ms_jnp": round(wall_jnp * 1e3, 3),
            "wall_ms_tuned": round(wall_tuned * 1e3, 3),
            "speedup": round(speedup, 4),
            "identical": identical,
        }
    if bench_json:
        with open(bench_json, "w") as fh:
            json.dump(payload, fh, indent=2, sort_keys=True)
            fh.write("\n")
    return payload


def pooled_tier(writer, n=512, dwell=128, n_sparse=12, n_dense=4,
                bench_json=None):
    """Cross-frame pooled worklists vs the per-frame capacity plan.

    The same heterogeneous zoom batch as ``planner_batch`` (a sparse
    zoomed-out majority plus a deep seahorse-valley tail), solved two
    ways: the bucketed per-frame plan (``plan=4``, each bucket's ring
    sized for its WORST member) and the ``ask_pooled`` engine (one
    compacted cross-frame worklist per level, the ring sized from the
    SUM of per-frame expected occupancies). Pooling must land strictly
    below the per-frame plan's total ring rows -- averaging over a
    heterogeneous batch beats per-bucket maxima -- in ONE dispatch with
    zero overflow-drops and a bit-identical canvas. With ``bench_json``
    the numbers are written as the machine-readable ``BENCH_7.json``
    that CI's ``compare_bench`` gate diffs (the pooled config is the
    SAME in smoke and full mode so the checked-in baseline's exact
    ring-row / dispatch budgets stay comparable).
    """
    from repro.workloads import EngineOptions

    prob = MandelbrotProblem(n=n, g=4, r=2, B=16, max_dwell=dwell,
                             backend="jnp")

    def window(cx, cy, w):
        return (cx - w / 2, cy - w / 2, cx + w / 2, cy + w / 2)

    widths = np.geomspace(16.0, 4.0, n_sparse)
    sparse = [window(-0.5, 0.0, float(w)) for w in widths]
    dense = [window(-0.7436447860, 0.1318252536, 3.0 / 2 ** k)
             for k in np.linspace(4, 12, n_dense)]
    bounds = sparse + dense
    F = len(bounds)
    case = f"n={n} f={F}"

    planned_canv, base_rep = solve_batch(prob, bounds, plan=4)  # warm
    t_plan = _best_time(lambda: solve_batch(prob, bounds, plan=4), reps=2)

    opts = EngineOptions(engine="ask_pooled", plan=True)
    pooled_canv, pool_rep = solve_batch(prob, bounds, options=opts)  # warm
    t_pool = _best_time(lambda: solve_batch(prob, bounds, options=opts),
                        reps=2)

    identical = int(np.array_equal(np.asarray(planned_canv),
                                   np.asarray(pooled_canv)))
    below = int(pool_rep.ring_rows < base_rep.ring_rows)
    speedup = t_plan / t_pool if t_pool > 0 else 0.0

    writer("ask_pooled_frames", case, F)
    writer("ask_pooled_dispatches", case, pool_rep.dispatches)
    writer("ask_pooled_overflow", case, pool_rep.overflow_dropped)
    writer("ask_pooled_ring_rows", case, pool_rep.ring_rows)
    writer("ask_pooled_planned_ring_rows", case, base_rep.ring_rows)
    writer("ask_pooled_ring_vs_planned", case,
           pool_rep.ring_rows / base_rep.ring_rows
           if base_rep.ring_rows else 0.0)
    writer("ask_pooled_below_planned", case, below)
    writer("ask_pooled_wall_ms_planned", case, t_plan * 1e3)
    writer("ask_pooled_wall_ms_pooled", case, t_pool * 1e3)
    writer("ask_pooled_speedup", case, speedup)
    writer("ask_pooled_identical", case, identical)

    payload = {"version": 1,
               "config": {"n": n, "max_dwell": dwell, "g": 4, "r": 2,
                          "B": 16, "n_sparse": n_sparse,
                          "n_dense": n_dense},
               "workloads": {"mixed_mandelbrot": {
                   "identical": identical,
                   "dispatches": int(pool_rep.dispatches),
                   "ring_rows": int(pool_rep.ring_rows),
                   "planned_ring_rows": int(base_rep.ring_rows),
                   "overflow": int(pool_rep.overflow_dropped),
                   "below_planned": below,
                   "wall_ms_planned": round(t_plan * 1e3, 3),
                   "wall_ms_pooled": round(t_pool * 1e3, 3),
                   "speedup": round(speedup, 4),
               }}}
    if bench_json:
        with open(bench_json, "w") as fh:
            json.dump(payload, fh, indent=2, sort_keys=True)
            fh.write("\n")
    return payload


def pooled_tuned_tier(writer, n=256, dwell=64, frames=6, bench_json=None):
    """The banded pooled Pallas tier (ISSUE 10): ask_pooled jnp vs tuned.

    Renders a zoom ladder of ``frames`` windows per escape-time workload
    through the pooled engine twice: once with the all-jnp policy and
    once with ``EngineOptions(engine="ask_pooled", policy="tuned")`` --
    the rung that now routes the banded ``region_fill_pooled`` /
    ``region_dwell_pooled`` kernels and the blocked cross-frame
    compaction through the autotune ladder instead of the pre-ISSUE-10
    jnp pin. Bit-identity and zero overflow are hard gate invariants;
    wall times and the tuned-vs-jnp speedup are soft (the tuned tier
    must never lose more than the gate's collapse floor). With
    ``bench_json`` the numbers are written as the machine-readable
    ``BENCH_10.json`` that CI's ``compare_bench`` gate diffs (config
    identical in smoke and full mode, like ``pooled_tier``).
    """
    from repro.workloads import EngineOptions, FrameProblem

    payload = {"version": 1,
               "config": {"n": n, "max_dwell": dwell, "g": 4, "r": 2,
                          "B": 16, "frames": frames},
               "workloads": {}}
    opts_jnp = EngineOptions(engine="ask_pooled", plan=True)
    opts_tuned = EngineOptions(engine="ask_pooled", plan=True,
                               policy="tuned")
    for wl in ("mandelbrot", "julia"):
        prob = FrameProblem(n=n, g=4, r=2, B=16, max_dwell=dwell,
                            backend="jnp", workload=wl)
        case = f"wl={wl} n={n} f={frames}"
        b = np.asarray(prob.bounds, np.float64)
        c = (b[:2] + b[2:]) / 2.0
        w0 = b[2] - b[0]
        bounds = []
        for k in range(frames):
            w = w0 / (1.35 ** k)
            bounds.append((c[0] - w / 2, c[1] - w / 2,
                           c[0] + w / 2, c[1] + w / 2))

        base_canv, base_rep = solve_batch(prob, bounds, options=opts_jnp)
        tuned_canv, rep = solve_batch(prob, bounds, options=opts_tuned)
        t_jnp = _best_time(
            lambda: solve_batch(prob, bounds, options=opts_jnp), reps=2)
        t_tuned = _best_time(
            lambda: solve_batch(prob, bounds, options=opts_tuned), reps=2)
        identical = int(np.array_equal(np.asarray(base_canv),
                                       np.asarray(tuned_canv)))
        speedup = t_jnp / t_tuned if t_tuned > 0 else 0.0
        writer("ask_pooled_tuned_dispatches", case, rep.dispatches)
        writer("ask_pooled_tuned_overflow", case, rep.overflow_dropped)
        writer("ask_pooled_tuned_ring_rows", case, rep.ring_rows)
        writer("ask_pooled_tuned_wall_ms_jnp", case, t_jnp * 1e3)
        writer("ask_pooled_tuned_wall_ms_tuned", case, t_tuned * 1e3)
        writer("ask_pooled_tuned_speedup", case, speedup)
        writer("ask_pooled_tuned_identical", case, identical)
        payload["workloads"][wl] = {
            "identical": identical,
            "overflow": int(rep.overflow_dropped),
            "dispatches": int(rep.dispatches),
            "ring_rows": int(rep.ring_rows),
            "wall_ms_jnp": round(t_jnp * 1e3, 3),
            "wall_ms_tuned": round(t_tuned * 1e3, 3),
            "speedup": round(speedup, 4),
        }
    if bench_json:
        with open(bench_json, "w") as fh:
            json.dump(payload, fh, indent=2, sort_keys=True)
            fh.write("\n")
    return payload


def tile_service(writer, n=256, dwell=64, chunk=8, bench_json=None):
    """Content-addressed tile cache over the planned front door.

    Replays an overlapping pan/zoom viewport stream twice through
    ``launch.tiles.TileService`` on a feedback ``RenderService``: a
    half-viewport pan across the cardioid, a half-overlap zoom sequence
    one depth down, then a full replay (the interactive steady state --
    most of what a viewer looks at was rendered before). Records the
    cache hit rate, the ``dispatch_planned`` batches actually issued vs
    the uncached baseline (every requested tile re-rendered, coalesced
    the same way), wall times for both, and bit-identity of every
    served tile against a fresh exact ``solve_batch`` render. With
    ``bench_json`` the numbers are written as the machine-readable
    ``BENCH_9.json`` CI's ``compare_bench`` gate diffs (``identical``
    and ``fewer_dispatches`` hard, ``hit_rate`` a hard floor,
    ``dispatches`` a monotone budget, wall times soft; the config is
    the SAME in smoke and full mode so the checked-in baseline's exact
    hit-rate / dispatch budgets stay comparable).
    """
    from repro.launch.frontdoor import FrontDoorStats
    from repro.launch.render_service import RenderService
    from repro.launch.tiles import TileOptions, TileService
    from repro.workloads import FrameProblem

    prob = FrameProblem(n=n, g=4, r=2, B=16, max_dwell=dwell,
                        backend="jnp", workload="mandelbrot")
    svc = RenderService(prob, chunk_frames=chunk, feedback=True)

    # half-overlap pan at one depth + half-overlap zoom one depth down,
    # then the full replay: a deterministic overlapping stream
    pan = [(-1.0 + 0.25 * i, -0.25, -0.5 + 0.25 * i, 0.25)
           for i in range(6)]
    zoom = [(-0.85 + 0.125 * i, -0.125, -0.6 + 0.125 * i, 0.125)
            for i in range(3)]
    views = (pan + zoom) * 2
    case = f"n={n} views={len(views)}"

    def stream(tiles):
        hits = misses = dispatches = retries = 0
        served = {}
        for v in views:
            r = tiles.serve(v)
            hits += r.hits
            misses += r.misses
            dispatches += r.dispatches
            retries += sum(c.retries for c in r.chunks)
            served.update(r.tiles)
        return hits, misses, dispatches, retries, served

    fd = FrontDoorStats()
    cached_tiles = TileService(svc, stats_sink=fd)
    hits, misses, dispatches, retries, served = stream(cached_tiles)
    hit_rate = hits / (hits + misses) if hits + misses else 0.0

    # uncached baseline: a zero-byte cache misses every lookup, so the
    # same stream re-renders every requested tile (same coalescing)
    def uncached():
        return TileService(svc, options=TileOptions(max_bytes=0))

    base_dispatches = stream(uncached())[2]
    t_uncached = _best_time(lambda: stream(uncached()), reps=2)
    t_cached = _best_time(lambda: stream(TileService(svc)), reps=2)
    speedup = t_uncached / t_cached if t_cached > 0 else 0.0

    # bit-identity: every unique tile served (cached or fresh) equals an
    # exact one-shot render of its reconstructed window
    ref = tuple(float(x) for x in prob.bounds)
    addrs = list(served)
    exact, _ = solve_batch(prob, [a.bounds(ref) for a in addrs],
                           p_subdiv=1.0)
    exact = np.asarray(exact)
    identical = int(all(np.array_equal(served[a], exact[j])
                        for j, a in enumerate(addrs)))
    fewer = int(dispatches < base_dispatches)

    writer("ask_tiles_frames_requested", case, hits + misses)
    writer("ask_tiles_tiles_unique", case, len(addrs))
    writer("ask_tiles_hit_rate", case, round(hit_rate, 4))
    writer("ask_tiles_dispatches", case, dispatches)
    writer("ask_tiles_baseline_dispatches", case, base_dispatches)
    writer("ask_tiles_fewer_dispatches", case, fewer)
    writer("ask_tiles_retries", case, retries)
    writer("ask_tiles_cache_bytes", case, cached_tiles.cache.resident_bytes)
    writer("ask_tiles_wall_ms_cached", case, t_cached * 1e3)
    writer("ask_tiles_wall_ms_uncached", case, t_uncached * 1e3)
    writer("ask_tiles_speedup", case, speedup)
    writer("ask_tiles_identical", case, identical)

    assert fd.tile_hits == hits and fd.tile_misses == misses

    payload = {"version": 1,
               "config": {"n": n, "max_dwell": dwell, "g": 4, "r": 2,
                          "B": 16, "chunk": chunk, "views": len(views)},
               "workloads": {"pan_zoom_mandelbrot": {
                   "identical": identical,
                   "hit_rate": round(hit_rate, 4),
                   "dispatches": int(dispatches),
                   "baseline_dispatches": int(base_dispatches),
                   "fewer_dispatches": fewer,
                   "frames_requested": int(hits + misses),
                   "tiles_unique": len(addrs),
                   "cache_bytes": int(cached_tiles.cache.resident_bytes),
                   "wall_ms_cached": round(t_cached * 1e3, 3),
                   "wall_ms_uncached": round(t_uncached * 1e3, 3),
                   "speedup": round(speedup, 4),
               }}}
    if bench_json:
        with open(bench_json, "w") as fh:
            json.dump(payload, fh, indent=2, sort_keys=True)
            fh.write("\n")
    return payload


def run(writer, full=False, bench_json=None, bench_json_pooled=None,
        bench_json_tiles=None, bench_json_pooled_tuned=None):
    if full:
        engines(writer, n=1024, g=4, r=2, B=32)
        batch_serving(writer, n=512, frames=16)
        sharded_serving(writer, n=256, frames=64, chunk=16)
        planner_batch(writer, n=512, dwell=256, n_sparse=12, n_dense=6)
        pipelined_serving(writer, n=256, dwell=128, frames=128, chunk=8)
        feedback_serving(writer, n=256, dwell=128, frames=96, chunk=8)
        workload_serving(writer, n=512, dwell=128, frames=48, chunk=8)
        tuned_tier(writer, n=256, dwell=128, bench_json=bench_json)
        pooled_tier(writer, bench_json=bench_json_pooled)
        tile_service(writer, bench_json=bench_json_tiles)
        pooled_tuned_tier(writer, bench_json=bench_json_pooled_tuned)
    else:  # CI smoke: small n, dp recursion stays cheap
        engines(writer, n=256, g=4, r=2, B=16)
        batch_serving(writer, n=128, frames=4)
        sharded_serving(writer, n=128, frames=16, chunk=8)
        planner_batch(writer, n=512, dwell=128, n_sparse=8, n_dense=4)
        pipelined_serving(writer, n=256, dwell=128, frames=64, chunk=8)
        feedback_serving(writer, n=256, dwell=64, frames=48, chunk=4)
        workload_serving(writer, n=256, dwell=64, frames=24, chunk=4)
        tuned_tier(writer, n=256, dwell=64, bench_json=bench_json)
        pooled_tier(writer, bench_json=bench_json_pooled)
        # the tile config is kept identical to full mode (see pooled_tier)
        tile_service(writer, bench_json=bench_json_tiles)
        pooled_tuned_tier(writer, bench_json=bench_json_pooled_tuned)
