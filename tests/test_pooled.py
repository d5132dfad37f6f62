"""Tests for the pooled cross-frame engine (core/pooled.py): bit-identity
with the per-frame scan engine across the registry, summed-occupancy ring
sizing, per-frame overflow attribution + retry, the planner integration
(plan_pooled / solve_pooled), EngineOptions routing, sharded dead-frame
padding, and pooled render-service chunking."""

import types

import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import pooled
from repro.core.ask import run_ask_scan_batch
from repro.core.planner import (BucketPlan, CapacityPlan, plan_frames,
                                plan_pooled, retry_until_fit, solve_pooled,
                                worst_case_capacities)
from repro.launch.mesh import make_frames_mesh
from repro.mandelbrot import MandelbrotProblem

# the registry golden config (tests/test_golden.py): the acceptance bar
# is bit-identity at exactly this rendering
GOLDEN_N = 256
GOLDEN_DWELL = 128


def _prob(n=128, dwell=32, **kw):
    return MandelbrotProblem(n=n, g=4, r=2, B=16, max_dwell=dwell,
                             backend="jnp", **kw)


def _mixed_bounds(n_sparse=4, n_dense=2):
    """A heterogeneous batch: zoomed-out sparse majority + deep seahorse
    tail (the regime pooling exists for)."""
    def window(cx, cy, w):
        return (cx - w / 2, cy - w / 2, cx + w / 2, cy + w / 2)

    sparse = [window(-0.5, 0.0, float(w))
              for w in np.geomspace(16.0, 4.0, n_sparse)]
    dense = [window(-0.7436447860, 0.1318252536, 3.0 / 2 ** k)
             for k in np.linspace(4, 10, n_dense)]
    return sparse + dense


# ---------------------------------------------------------------------------
# bit-identity with the per-frame scan engine
# ---------------------------------------------------------------------------

def test_pooled_identical_to_scan_every_registry_workload():
    """The ISSUE acceptance bar: ask_pooled bit-identical to ask_scan on
    every registered workload at the 256^2 golden config -- the pooled
    worklist, the frame-tagged subdivision, and the tall-canvas scatter
    may never change a pixel."""
    from repro.workloads import FrameProblem, available, solve

    for wl in available():
        prob = FrameProblem(n=GOLDEN_N, g=4, r=2, B=16,
                            max_dwell=GOLDEN_DWELL, backend="jnp",
                            workload=wl)
        ref, st_ref = solve(prob, "ask_scan", safety_factor=1e9)
        got, st = solve(prob, "ask_pooled", safety_factor=1e9)
        np.testing.assert_array_equal(np.asarray(got), np.asarray(ref),
                                      err_msg=f"ask_pooled[{wl}]")
        assert st.kernel_launches == 1
        assert st.overflow_dropped == 0
        assert st.leaf_count == st_ref.leaf_count
        assert st.region_counts == st_ref.region_counts


def test_pooled_batch_identical_on_heterogeneous_batch():
    """A mixed sparse+dense batch through ONE pooled worklist: canvases
    and the per-frame stats breakdown match the vmapped per-frame
    engine frame for frame."""
    prob = _prob()
    bounds = np.asarray(_mixed_bounds(), np.float32)
    ref, st_ref = run_ask_scan_batch(prob, jnp.asarray(bounds),
                                     safety_factor=1e9)
    got, st = pooled.run_ask_pooled_batch(prob, bounds, safety_factor=1e9)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(ref))
    assert st.kernel_launches == 1
    assert st.frame_overflow == (0,) * len(bounds)
    assert st.region_counts == st_ref.region_counts
    assert st.frame_leaf_counts == st_ref.frame_leaf_counts
    # the ring is ONE shared allocation for the whole batch
    assert st.ring_rows == 2 * max(st.olt_caps)


def test_pooled_zero_level_config():
    """n == g*B: the scan has zero subdivision levels -- the pooled
    pipeline must still render (roots ARE the leaves)."""
    prob = _prob(n=64, dwell=16)
    bounds = np.asarray([prob.bounds, (-2.0, -2.0, 2.0, 2.0)], np.float32)
    ref, _ = run_ask_scan_batch(prob, jnp.asarray(bounds), safety_factor=1e9)
    got, st = pooled.run_ask_pooled_batch(prob, bounds, safety_factor=1e9)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(ref))
    assert st.overflow_dropped == 0


def test_pooled_live_mask_zeroes_dead_frames():
    """Dead frames (sharded padding) contribute zero rows, zero stats,
    zero canvas -- and leave the live frames bit-identical."""
    prob = _prob()
    bounds = np.asarray(_mixed_bounds(2, 1), np.float32)
    live = [True, False, True]
    got, st = pooled.run_ask_pooled_batch(prob, bounds, live=live,
                                          safety_factor=1e9)
    ref, _ = run_ask_scan_batch(prob, jnp.asarray(bounds), safety_factor=1e9)
    got = np.asarray(got)
    np.testing.assert_array_equal(got[0], np.asarray(ref)[0])
    np.testing.assert_array_equal(got[2], np.asarray(ref)[2])
    assert not got[1].any()
    assert st.frame_leaf_counts[1] == 0 and st.frame_overflow[1] == 0


# ---------------------------------------------------------------------------
# summed-occupancy capacity sizing
# ---------------------------------------------------------------------------

def test_pooled_capacities_sum_and_clamp():
    from repro.core.cost_model import expected_level_counts, num_levels

    prob = _prob()
    n, g, r, B = prob.n, prob.g, prob.r, prob.B
    levels = num_levels(n, g, r, B)
    ps = (0.3, 0.9, 0.5)
    caps = pooled.pooled_capacities(prob, ps, safety_factor=1.5)
    assert len(caps) == levels + 1
    exp = [expected_level_counts(n, g, r, B, P=p) for p in ps]
    for lv, cap in enumerate(caps):
        total = sum(e[lv] for e in exp)
        worst = len(ps) * (g * r ** lv) ** 2
        assert cap == max(1, min(int(np.ceil(total * 1.5)), worst))
    # safety >= 1 admits every live root: level 0 saturates at F g^2
    assert caps[0] == len(ps) * g * g
    # the sum grows with the pool; the clamp caps it at F x worst
    more = pooled.pooled_capacities(prob, ps + ps, safety_factor=1.5)
    assert all(b >= a for a, b in zip(caps, more))
    huge = pooled.pooled_capacities(prob, (1.0,) * 4, safety_factor=1e9)
    assert huge == tuple(4 * (g * r ** lv) ** 2 for lv in range(levels + 1))
    # an empty pool carries nothing but still shapes a valid ring
    assert pooled.pooled_capacities(prob, ()) == (1,) * (levels + 1)


def test_pooled_capacity_resolution_and_validation():
    prob = _prob()
    levels = len(worst_case_capacities(prob)) - 1
    # int -> uniform per-level caps
    caps = pooled._resolve_pooled_capacities(prob, 3, 64, None, 0.7, 2.0)
    assert caps == (64,) * (levels + 1)
    with pytest.raises(ValueError, match="not both"):
        pooled._resolve_pooled_capacities(prob, 3, (8,) * (levels + 1),
                                          (0.5, 0.5, 0.5), 0.7, 2.0)
    with pytest.raises(ValueError, match="capacities"):
        pooled._resolve_pooled_capacities(prob, 3, (8,), None, 0.7, 2.0)
    with pytest.raises(ValueError, match="frame_ps"):
        pooled._resolve_pooled_capacities(prob, 3, None, (0.5,), 0.7, 2.0)
    with pytest.raises(ValueError, match="pooled extras"):
        pooled.run_ask_pooled_batch(prob, np.zeros((3, 2), np.float32))


def test_escalate_pooled_capacities():
    """Later retries of the one pooled rule double each level."""
    prob = MandelbrotProblem(n=256, g=4, r=2, B=16, max_dwell=64)
    assert worst_case_capacities(prob) == (16, 64, 256)

    def later(caps, ran, nxt):
        return pooled.pooled_retry_capacities(
            prob, caps, [((16, 64), 256)], retry=1, ran_per_shard=ran,
            next_per_shard=nxt)

    # doubling, clamped at the S-frame pooled worst case
    assert later((4, 10, 40), 1, 1) == (8, 20, 80)
    assert later((10, 60, 250), 1, 1) == (16, 64, 256)
    # reaching the ceiling with frames still dropping is a bug, not a
    # sizing problem
    with pytest.raises(RuntimeError, match="worst-case"):
        later((16, 64, 256), 1, 1)
    # a bigger pool raises the ceiling
    assert later((16, 64, 256), 2, 2) == (32, 128, 512)
    # THE shrinking-pool regression: a frame that overflowed while
    # SHARING a 3-frame ring is not at its OWN worst case even when the
    # shared caps exceed it -- no raise, and the retry caps clamp DOWN
    # to the 1-frame ceiling (the pool shrank with them)
    assert later((32, 128, 512), 3, 1) == (16, 64, 256)
    with pytest.raises(RuntimeError, match="worst-case"):
        later((48, 192, 768), 3, 1)
    # the same inputs on a FIRST retry size from the measured chain
    # instead: 2 x (16, 64, 256), clamped at the 1-frame ceiling
    assert pooled.pooled_retry_capacities(
        prob, (4, 10, 40), [((16, 64), 256)], retry=0, ran_per_shard=1,
        next_per_shard=1) == (16, 64, 256)


# ---------------------------------------------------------------------------
# planner integration: plan_pooled / solve_pooled
# ---------------------------------------------------------------------------

def test_plan_pooled_undercuts_per_frame_plan():
    """The tentpole memory claim, at the BENCH_7 configuration (planning
    is pure cost model -- nothing renders): on the sparse-majority mixed
    batch the pooled plan's ring (2 x max summed caps, TOTAL) lands
    strictly below the per-frame bucketed plan's sum of per-member
    maxima."""
    prob = _prob(n=512, dwell=128)
    bounds = _mixed_bounds(12, 4)
    per_frame = plan_frames(prob, bounds, num_buckets=4)
    plan = plan_pooled(prob, bounds)
    assert plan.pooled and len(plan.buckets) == 1
    bucket = plan.buckets[0]
    assert bucket.pooled and bucket.frames == tuple(range(len(bounds)))
    assert bucket.p_subdiv == max(e.p_subdiv for e in plan.estimates)
    assert plan.ring_rows == 2 * max(bucket.capacities)
    assert plan.ring_rows < per_frame.ring_rows, \
        (plan.ring_rows, per_frame.ring_rows)


def test_solve_pooled_executes_plan_with_zero_drops():
    prob = _prob(n=256, dwell=64)
    bounds = _mixed_bounds(6, 3)
    exact, _ = run_ask_scan_batch(
        prob, jnp.asarray(np.asarray(bounds, np.float32)),
        safety_factor=1e9)
    canv, rep = solve_pooled(prob, np.asarray(bounds, np.float32))
    np.testing.assert_array_equal(np.asarray(canv), np.asarray(exact))
    assert rep.overflow_dropped == 0
    assert rep.frames == len(bounds)
    assert rep.frame_p_source == ("prior",) * len(bounds)
    if rep.retries == 0:
        assert rep.dispatches == 1
        assert rep.ring_rows == 2 * max(rep.plan.buckets[0].capacities)


def test_solve_pooled_retry_converges_from_hostile_caps():
    """A hand-built pooled plan with starved capacities: frames overflow,
    the shared pool escalates (doubling, clamped at the pool's worst
    case) until every frame fits -- zero final drops, bit-identical."""
    prob = _prob()
    bounds = np.asarray(_mixed_bounds(2, 2), np.float32)
    F = len(bounds)
    levels = len(worst_case_capacities(prob)) - 1
    tiny = tuple(min(8 * 4 ** lv, w) for lv, w in
                 enumerate(worst_case_capacities(prob)))[:levels + 1]
    plan = CapacityPlan(
        buckets=(BucketPlan(frames=tuple(range(F)), p_subdiv=0.7,
                            capacities=tiny, pooled=True),),
        estimates=(), safety_factor=1.0, pooled=True)
    exact, _ = run_ask_scan_batch(prob, jnp.asarray(bounds),
                                  safety_factor=1e9)
    canv, rep = solve_pooled(prob, bounds, plan=plan)
    np.testing.assert_array_equal(np.asarray(canv), np.asarray(exact))
    assert rep.overflow_dropped == 0
    assert rep.retries > 0 and rep.dispatches > 1
    assert rep.retried_frames  # the overflowing frames were recorded
    # ring accounting covered every dispatch, retries included
    assert rep.ring_rows >= rep.dispatches * 2 * max(tiny)


def test_solve_pooled_plan_validation():
    prob = _prob()
    bounds = np.asarray(_mixed_bounds(2, 1), np.float32)
    flat = plan_frames(prob, bounds, num_buckets=2)
    with pytest.raises(ValueError, match="pooled plan"):
        solve_pooled(prob, bounds, plan=flat)
    short = plan_pooled(prob, bounds[:2])
    with pytest.raises(ValueError, match="covers 2 frames"):
        solve_pooled(prob, bounds, plan=short)
    good = plan_pooled(prob, bounds)
    with pytest.raises(ValueError, match="ignored"):
        solve_pooled(prob, bounds, plan=good, quantize=True)


# ---------------------------------------------------------------------------
# EngineOptions routing through solve_batch / dispatch_batch
# ---------------------------------------------------------------------------

def test_solve_batch_routes_pooled_engine():
    from repro.workloads import EngineOptions
    from repro.mandelbrot import solve_batch

    prob = _prob()
    bounds = _mixed_bounds(3, 1)
    exact, _ = solve_batch(prob, bounds, safety_factor=1e9)

    canv, st = solve_batch(prob, bounds,
                           options=EngineOptions(engine="ask_pooled",
                                                 safety_factor=1e9))
    np.testing.assert_array_equal(np.asarray(canv), np.asarray(exact))
    assert st.kernel_launches == 1

    canv2, rep = solve_batch(prob, bounds,
                             options=EngineOptions(engine="ask_pooled",
                                                   plan=True))
    np.testing.assert_array_equal(np.asarray(canv2), np.asarray(exact))
    assert rep.overflow_dropped == 0 and rep.plan.pooled

    # the sharded front under options= (1-device mesh in-process)
    canv3, st3 = solve_batch(
        prob, bounds, options=EngineOptions(engine="ask_pooled",
                                            mesh=make_frames_mesh(1),
                                            safety_factor=1e9))
    np.testing.assert_array_equal(np.asarray(canv3), np.asarray(exact))
    assert st3.kernel_launches == 1


def test_solve_batch_pooled_rejects_bad_knobs():
    from repro.workloads import EngineOptions
    from repro.mandelbrot import solve_batch

    prob = _prob()
    bounds = _mixed_bounds(2, 1)
    with pytest.raises(ValueError, match="ask_pooled"):
        solve_batch(prob, bounds,
                    options=EngineOptions(engine="ask_pooled", plan=2))
    with pytest.raises(ValueError, match="occupancies"):
        solve_batch(prob, bounds,
                    options=EngineOptions(engine="ask_pooled", plan=True,
                                          capacities=(8, 8, 8)))
    with pytest.raises(ValueError, match="engine must be one of"):
        EngineOptions(engine="ask_warp")


def test_dispatch_batch_routes_pooled_engine():
    from repro.workloads import EngineOptions, dispatch_batch

    prob = _prob()
    bounds = np.asarray(_mixed_bounds(2, 1), np.float32)
    d = dispatch_batch(prob, bounds,
                       options=EngineOptions(engine="ask_pooled",
                                             mesh=make_frames_mesh(1),
                                             safety_factor=1e9))
    assert isinstance(d, pooled.PooledDispatch)
    canv, st = d.finalize()
    ref, _ = run_ask_scan_batch(prob, jnp.asarray(bounds), safety_factor=1e9)
    np.testing.assert_array_equal(np.asarray(canv), np.asarray(ref))
    assert st.overflow_dropped == 0


def test_sharded_pooled_ragged_padding_single_device():
    """pad_to > F on a 1-device mesh: dead padding frames are masked out
    of canvases and stats, and the result is bit-identical to the
    unsharded pool."""
    prob = _prob()
    bounds = np.asarray(_mixed_bounds(2, 1), np.float32)  # F=3, pad to 4
    ref, st_ref = pooled.run_ask_pooled_batch(prob, bounds,
                                              safety_factor=1e9)
    got, st = pooled.run_ask_pooled_sharded(
        prob, bounds, mesh=make_frames_mesh(1), pad_to=4,
        safety_factor=1e9)
    got = np.asarray(got)
    assert got.shape[0] == 3
    np.testing.assert_array_equal(got, np.asarray(ref))
    assert st.frame_leaf_counts == st_ref.frame_leaf_counts
    assert st.region_counts == st_ref.region_counts
    assert st.overflow_dropped == 0


def test_sharded_pooled_finalize_queues_no_device_work():
    """A full-width chunk's ``finalize()`` hands back the program's own
    frame-major canvases, so nothing is queued behind the chunk still in
    flight and the host copy can start as soon as this chunk ends."""
    prob = _prob()
    bounds = np.asarray(_mixed_bounds(2, 1), np.float32)
    mesh = make_frames_mesh(1)
    d = pooled.dispatch_ask_pooled_sharded(prob, bounds, mesh=mesh,
                                           safety_factor=1e9)
    nxt = pooled.dispatch_ask_pooled_sharded(prob, bounds[::-1], mesh=mesh,
                                             safety_factor=1e9)
    canv, st = d.finalize()
    assert canv is d.states
    assert canv.shape == (3, prob.n, prob.n)
    ref, st_ref = run_ask_scan_batch(prob, jnp.asarray(bounds),
                                     safety_factor=1e9)
    np.testing.assert_array_equal(np.asarray(canv), np.asarray(ref))
    assert st.region_counts == st_ref.region_counts
    assert st.frame_leaf_counts == st_ref.frame_leaf_counts
    canv2, _ = nxt.finalize()
    np.testing.assert_array_equal(np.asarray(canv2),
                                  np.asarray(ref)[::-1])


def test_sharded_pooled_finalize_leaves_padded_tail_to_caller():
    """A padded chunk's ``finalize()`` also returns the program's own
    output: the true frames first, then zero canvases for the dead
    padding frames, with stats for the true frames alone."""
    prob = _prob()
    bounds = np.asarray(_mixed_bounds(2, 1), np.float32)  # F=3, pad to 4
    d = pooled.dispatch_ask_pooled_sharded(prob, bounds,
                                           mesh=make_frames_mesh(1),
                                           pad_to=4, safety_factor=1e9)
    canv, st = d.finalize()
    assert canv is d.states and d.frames == 3
    host = np.asarray(canv)
    assert host.shape == (4, prob.n, prob.n)
    assert not host[3].any()
    ref, st_ref = pooled.run_ask_pooled_batch(prob, bounds,
                                              safety_factor=1e9)
    np.testing.assert_array_equal(host[:3], np.asarray(ref))
    assert st.frame_leaf_counts == st_ref.frame_leaf_counts
    assert len(st.region_counts) == 3


# ---------------------------------------------------------------------------
# pooled render-service chunking
# ---------------------------------------------------------------------------

def test_service_rejects_unknown_engine():
    from repro.launch.render_service import RenderService

    with pytest.raises(ValueError, match="policy"):
        RenderService(_prob(), engine="ask_tuned")


def test_pooled_service_uniform_stream_identical():
    from repro.launch.render_service import RenderService, zoom_bounds

    prob = _prob(dwell=34)  # dwell unique to this test's program caches
    bounds = list(zoom_bounds(10))
    kw = dict(mesh=make_frames_mesh(1), chunk_frames=4, safety_factor=1e9)
    ref, _ = RenderService(prob, **kw).render(bounds)
    canv, rs = RenderService(prob, engine="ask_pooled", **kw).render(bounds)
    np.testing.assert_array_equal(canv, ref)
    assert rs.chunks == 3 and rs.dispatches_per_chunk == 1.0
    assert rs.overflow_dropped == 0
    assert rs.program_traces in (None, 1), rs.program_traces


def test_pooled_service_uniform_stream_keeps_canvases_on_device():
    """The uniform stream makes no host copy: full-width and padded
    chunks alike hand back device arrays of the chunk's true frames."""
    import jax

    from repro.launch.render_service import RenderService, zoom_bounds

    prob = _prob(dwell=35)
    svc = RenderService(prob, engine="ask_pooled", mesh=make_frames_mesh(1),
                        chunk_frames=4, safety_factor=1e9)
    chunks = list(svc.stream_chunks(zoom_bounds(10)))
    assert [c.chunk.frames for c in chunks] == [4, 4, 2]
    for c in chunks:
        assert isinstance(c.canvases, jax.Array)
        assert c.canvases.shape == (c.chunk.frames, prob.n, prob.n)
        assert c.chunk.copy_s == 0.0


def test_pooled_chunker_keeps_class_jumps_inside_chunks():
    """The pooled feedback chunker cuts ONLY on workload switches or a
    full chunk: a capacity-class jump that splits the per-frame chunker
    stays pooled -- heterogeneous frames are the point."""
    from repro.launch.render_service import RenderService

    prob = _prob(dwell=38)
    wide = (-8.5, -8.0, 7.5, 8.0)  # sparse
    deep = (-0.7486447860, 0.1268252536, -0.7386447860, 0.1368252536)
    bounds = [wide] * 3 + [deep] * 5
    kw = dict(mesh=make_frames_mesh(1), chunk_frames=4, feedback=True,
              adapt=False, safety_factor=2.0)
    per_frame = RenderService(prob, **kw)
    assert [c.chunk.frames
            for c in per_frame.stream_chunks(bounds)] == [3, 4, 1]
    svc = RenderService(prob, engine="ask_pooled", **kw)
    chunks = list(svc.stream_chunks(bounds))
    assert [c.chunk.frames for c in chunks] == [4, 4]
    assert all(c.stats.overflow_dropped == 0 for c in chunks)
    # bit-identity against the uniform worst-case service
    ref, _ = RenderService(prob, mesh=make_frames_mesh(1), chunk_frames=4,
                           safety_factor=1e9).render(bounds)
    got = np.concatenate([np.asarray(c.canvases) for c in chunks])
    np.testing.assert_array_equal(got, ref)


def test_pooled_service_feedback_retry_converges():
    from repro.launch.render_service import RenderService, zoom_bounds

    prob = _prob(dwell=42)
    skim = list(zoom_bounds(8, center=(-0.7436447860, 0.1318252536),
                            width0=6.0, zoom_per_frame=1.02))
    svc = RenderService(prob, engine="ask_pooled", mesh=make_frames_mesh(1),
                        chunk_frames=4, feedback=True, safety_factor=0.4)
    canv, rs = svc.render(skim)
    assert rs.overflow_dropped == 0
    assert rs.retries > 0 and rs.dispatches > rs.chunks
    ref, _ = RenderService(prob, mesh=make_frames_mesh(1), chunk_frames=4,
                           safety_factor=1e9).render(skim)
    np.testing.assert_array_equal(canv, ref)


def test_pooled_service_mixed_workloads_identical():
    """Mixed mandelbrot+julia serving through the pooled engine: chunks
    cut at workload switches, each pool sized from its own workload's
    predictions, canvases bit-identical to the per-frame feedback
    service on the same stream."""
    from repro.launch.render_service import RenderService
    from repro.workloads import FrameProblem

    probs = {
        "m": FrameProblem(n=128, g=4, r=2, B=16, max_dwell=46,
                          backend="jnp", workload="mandelbrot"),
        "j": FrameProblem(n=128, g=4, r=2, B=16, max_dwell=46,
                          backend="jnp", workload="julia"),
    }
    items = ([("m", probs["m"].bounds)] * 3 + [("j", probs["j"].bounds)] * 3
             + [("m", probs["m"].bounds)] * 2)
    kw = dict(mesh=make_frames_mesh(1), chunk_frames=4, feedback=True,
              safety_factor=1.5)
    ref, _ = RenderService(dict(probs), **kw).render(items)
    canv, rs = RenderService(dict(probs), engine="ask_pooled", **kw
                             ).render(items)
    np.testing.assert_array_equal(canv, ref)
    assert rs.overflow_dropped == 0
    assert [c.workload for c in rs.chunk_stats] == ["m", "j", "m"]
    assert rs.program_traces == rs.plan_signatures


def test_pooled_stats_flat_single_frame_shape():
    """solve(..., "ask_pooled") returns the single-frame stats shape of
    run_ask_scan (flat region_counts, no per-frame tuples)."""
    from repro.workloads import solve

    prob = _prob()
    _, st = solve(prob, "ask_pooled", safety_factor=1e9)
    _, st_scan = solve(prob, "ask_scan", safety_factor=1e9)
    assert st.region_counts == st_scan.region_counts
    assert st.frame_overflow == () and st.frame_leaf_counts == ()
    assert st.leaf_count == st_scan.leaf_count


def test_pooled_pipeline_cache_reuses_programs():
    prob = _prob()
    caps = pooled._resolve_pooled_capacities(prob, 2, None, None, 0.7, 2.0)
    fn1 = pooled._jitted_pooled(prob, caps, 2)
    fn2 = pooled._jitted_pooled(prob, caps, 2)
    assert fn1 is fn2
    fn3 = pooled._jitted_pooled(prob, caps, 3)
    assert fn3 is not fn1
    assert pooled._jitted_pooled(prob, caps, 2) is fn1


def test_solve_pooled_sharded_single_device_with_retries():
    """solve_pooled under a mesh: the initial dispatch sizes each
    shard's ring from its OWN members' P (the frame_ps path -- per-shard
    sums, elementwise-maxed), retries re-pool at explicit escalated
    caps, and the result stays bit-identical with zero drops."""
    prob = _prob()
    bounds = np.asarray(_mixed_bounds(2, 2), np.float32)
    F = len(bounds)
    exact, _ = run_ask_scan_batch(prob, jnp.asarray(bounds),
                                  safety_factor=1e9)
    mesh = make_frames_mesh(1)
    canv, rep = solve_pooled(prob, bounds, mesh=mesh)
    np.testing.assert_array_equal(np.asarray(canv), np.asarray(exact))
    assert rep.overflow_dropped == 0

    # sharded initial dispatch sizes from the members' own P at the
    # plan's safety factor (NOT the whole-batch summed caps, which would
    # over-allocate n_dev-fold): starve it to force the explicit-caps
    # retry branch
    levels = len(worst_case_capacities(prob)) - 1
    tiny = (8,) * (levels + 1)
    plan = CapacityPlan(
        buckets=(BucketPlan(frames=tuple(range(F)), p_subdiv=0.7,
                            capacities=tiny, pooled=True),),
        estimates=(), safety_factor=0.05, pooled=True)
    canv2, rep2 = solve_pooled(prob, bounds, plan=plan, mesh=mesh)
    np.testing.assert_array_equal(np.asarray(canv2), np.asarray(exact))
    assert rep2.retries > 0 and rep2.overflow_dropped == 0

    # frame_ps validation on the sharded front
    with pytest.raises(ValueError, match="frame_ps covers"):
        pooled.dispatch_ask_pooled_sharded(prob, bounds, mesh=mesh,
                                           frame_ps=(0.5,))
    with pytest.raises(ValueError, match="pooled extras"):
        pooled.dispatch_ask_pooled_sharded(prob, bounds[:, :2], mesh=mesh)


def test_pooled_cache_evicts_fifo():
    prob = _prob()
    caps = pooled._resolve_pooled_capacities(prob, 2, None, None, 0.7, 2.0)
    saved = dict(pooled._POOLED_CACHE)
    try:
        pooled._POOLED_CACHE.clear()
        for i in range(pooled._POOLED_CACHE_MAX):
            pooled._POOLED_CACHE[("dummy", i)] = None
        pooled._jitted_pooled(prob, caps, 2)
        assert len(pooled._POOLED_CACHE) == pooled._POOLED_CACHE_MAX
        assert ("dummy", 0) not in pooled._POOLED_CACHE  # oldest evicted
    finally:
        pooled._POOLED_CACHE.clear()
        pooled._POOLED_CACHE.update(saved)


# ---------------------------------------------------------------------------
# failed-frame retry sizing (the re-pool-the-whole-chunk bugfix)
# ---------------------------------------------------------------------------

class TestFailedPoolRetry:
    """A shared ring that undersizes for SOME frames must not be
    escalated by doubling the whole chunk's pool: the retry ring is
    sized from the overflowing frames' own measured contribution."""

    @staticmethod
    def _mixed_batch():
        prob = MandelbrotProblem(n=256, g=4, r=2, B=16, max_dwell=64)

        def win(cx, cy, w):
            return (cx - w / 2, cy - w / 2, cx + w / 2, cy + w / 2)

        dense = [win(-0.745, 0.11, 0.05), win(-0.16, 1.035, 0.04)]
        sparse = [win(-0.2, 0.0, 0.02), win(-0.25, 0.0, 0.015)]
        return prob, np.asarray(dense + sparse, dtype=np.float32)

    def test_mixed_dense_sparse_retry_counts_dispatches(self):
        """Level 0 sized for everyone, deeper levels for the sparse
        frames only: exactly the dense frames retry, in ONE extra
        dispatch, and the result is bit-identical with zero drops."""
        import dataclasses as dc

        from repro.core.planner import plan_pooled, solve_pooled

        prob, bounds = self._mixed_batch()
        base = plan_pooled(prob, bounds, safety_factor=1.0)
        caps = (64, 40, 160)  # 64 = F * g**2: level 0 always fits
        plan = dc.replace(base, buckets=(
            dc.replace(base.buckets[0], capacities=caps),))
        states, rep = solve_pooled(prob, bounds, plan=plan)
        assert rep.retried_frames == (0, 1)  # the dense frames, ONLY
        assert rep.dispatches == 2  # initial + one measured-size retry
        assert rep.overflow_dropped == 0
        ref, ref_st = run_ask_scan_batch(prob, bounds, p_subdiv=1.0)
        assert np.array_equal(np.asarray(states), np.asarray(ref))
        # the blunt whole-pool doubling would have undersized the leaf
        # level for the dense frames' TRUE need and burned a THIRD
        # dispatch; the measured sizing covered it in one
        blunt = pooled.pooled_retry_capacities(
            prob, caps, [], retry=1, ran_per_shard=4, next_per_shard=2)
        true_leaf = ref_st.frame_leaf_counts[0] + ref_st.frame_leaf_counts[1]
        assert blunt[-1] < true_leaf
        retry_caps = rep.bucket_stats[1].olt_caps
        assert retry_caps[-1] >= true_leaf

    def test_failed_pool_capacities_sizes_from_failed_frames_only(self):
        prob = MandelbrotProblem(n=256, g=4, r=2, B=16, max_dwell=64)
        caps = pooled.pooled_retry_capacities(
            prob, (1, 1, 1), [((16, 44), 148), ((16, 64), 252)], retry=0,
            ran_per_shard=2, next_per_shard=2)
        # 2x the measured contribution, clamped at the retry pool's own
        # worst case -- independent of how big the failed pool was
        worst = [(4 * 2 ** lv) ** 2 for lv in range(3)]
        assert caps == tuple(min(2 * m, 2 * w) for m, w in
                             zip((32, 108, 400), worst))

    def test_failed_pool_capacities_impossibility_guard(self):
        prob = MandelbrotProblem(n=64, g=4, r=2, B=8, max_dwell=16)
        worst = [(4 * 2 ** lv) ** 2 for lv in range(2)]
        full = tuple(2 * w for w in worst)  # covered 2 frames' worst case
        with pytest.raises(RuntimeError, match="worst-case"):
            pooled.pooled_retry_capacities(
                prob, full, [((16,), 1), ((16,), 1)], retry=0,
                ran_per_shard=2, next_per_shard=2)


# ---------------------------------------------------------------------------
# the one retry loop (planner.retry_until_fit) on a scripted dispatch
# ---------------------------------------------------------------------------

class TestRetryLoop:
    """``retry_until_fit`` driven by a scripted dispatch: no program
    runs. Frame ``i`` fits once ``caps[0] >= need[i]``; its canvas row
    records the capacity it fit at."""

    @staticmethod
    def _script(need):
        calls = []

        def dispatch(idx, caps):
            calls.append((list(idx), tuple(caps)))
            over = tuple(int(need[i] > caps[0]) for i in idx)
            states = np.asarray([[i, caps[0]] for i in idx], np.int32)
            return states, types.SimpleNamespace(
                frame_overflow=over, olt_caps=tuple(caps),
                region_counts=tuple((i,) for i in idx),
                frame_leaf_counts=tuple(10 * i for i in idx))

        return dispatch, calls

    @staticmethod
    def _double(caps, tag, failed, chains, ran):
        return tuple(2 * c for c in caps), tag + 1

    def test_frames_merge_back_in_input_order(self):
        dispatch, calls = self._script([1, 5, 2, 7, 3])
        done = retry_until_fit(dispatch, [((4,), range(5), 0)], self._double,
                               frames=5, rings=lambda f: f)
        assert calls == [([0, 1, 2, 3, 4], (4,)), ([1, 3], (8,))]
        assert done.states.tolist() == [[0, 4], [1, 8], [2, 4], [3, 8],
                                        [4, 4]]
        assert done.chains == tuple(((i,), 10 * i) for i in range(5))
        assert done.tags == (0, 1, 0, 1, 0)
        assert done.retried_frames == (1, 3)
        assert (done.dispatches, done.retries) == (2, 2)
        assert done.ring_rows == 5 * 2 * 4 + 2 * 2 * 8

    def test_two_failed_groups_with_one_target_share_one_dispatch(self):
        dispatch, calls = self._script([3, 1, 5, 1])

        def to_eight(caps, tag, failed, chains, ran):
            return (8,), tag

        done = retry_until_fit(
            dispatch, [((3,), [2, 3], "b"), ((2,), [0, 1], "a")], to_eight,
            frames=4, rings=lambda f: 1)
        # ascending ring width; frame 0 waits at (8,) for frame 2
        assert calls == [([0, 1], (2,)), ([2, 3], (3,)), ([0, 2], (8,))]
        assert done.states[:, 1].tolist() == [8, 2, 8, 3]
        # frame 2 joined the pending group, so it carries that group's tag
        assert done.tags == ("a", "a", "a", "b")
        assert done.retries == 2

    def test_returned_caps_are_the_largest_any_frame_ran_at(self):
        dispatch, calls = self._script([1, 12])
        first = dispatch([0, 1], (2,))
        calls.clear()
        done = retry_until_fit(dispatch, [((2,), [0, 1], 0)], self._double,
                               frames=2, rings=lambda f: 1, done=first)
        # the first group's result was handed in, not dispatched again
        assert calls == [([1], (4,)), ([1], (8,)), ([1], (16,))]
        assert done.caps == (16,)
        assert done.caps == max([(2,)] + [c for _, c in calls])
        assert done.dispatches == 4 and done.retries == 3

    def test_max_dispatches_raises(self):
        dispatch, calls = self._script([100])
        with pytest.raises(RuntimeError, match="max_dispatches=3"):
            retry_until_fit(dispatch, [((1,), [0], 0)], self._double,
                            frames=1, rings=lambda f: 1, max_dispatches=3)
        assert len(calls) == 3
