"""Tests for the async double-buffered render service: bit-identity of
the pipelined stream, the bounded in-flight queue, per-chunk stats, the
measured compute / host-I/O overlap, and the closed-loop occupancy
feedback path (planner-aware chunking)."""

import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.ask import run_ask_scan_batch
from repro.core.feedback import OccupancyEstimator
from repro.launch.mesh import make_frames_mesh
from repro.launch.render_service import (DEFAULT_PIPELINE_DEPTH,
                                         RenderService, zoom_bounds)
from repro.mandelbrot import MandelbrotProblem


def _prob(n=128, dwell=48):
    # dwell 48 is unique to this module: the jitted chunk program (and
    # the program_traces counter) is cached per problem config, and
    # test_ask_scan traces other batch widths on the dwell-32 config in
    # the same pytest process
    return MandelbrotProblem(n=n, g=4, r=2, B=16, max_dwell=dwell,
                             backend="jnp")


def _svc(prob, **kw):
    kw.setdefault("mesh", make_frames_mesh(1))
    kw.setdefault("chunk_frames", 4)
    kw.setdefault("safety_factor", 1e9)
    return RenderService(prob, **kw)


def test_default_depth_is_double_buffered():
    assert DEFAULT_PIPELINE_DEPTH == 2
    svc = _svc(_prob())
    assert svc.pipeline_depth == 2
    with pytest.raises(ValueError):
        _svc(_prob(), pipeline_depth=0)


def test_pipelined_bit_identical_to_sync_and_reference():
    """19 frames / chunk 4 / depth 3: frame order preserved, every chunk
    one dispatch, canvases bit-identical to both the synchronous service
    and one unsharded batch over all frames."""
    prob = _prob()
    bounds = list(zoom_bounds(19))
    ref, st_ref = run_ask_scan_batch(
        prob, jnp.asarray(np.asarray(bounds, np.float32)), safety_factor=1e9)

    sync, rs_sync = _svc(prob, pipeline_depth=1).render(bounds)
    pipe, rs_pipe = _svc(prob, pipeline_depth=3).render(bounds)

    np.testing.assert_array_equal(pipe, np.asarray(ref))
    np.testing.assert_array_equal(pipe, sync)
    for rs in (rs_sync, rs_pipe):
        assert rs.frames == 19 and rs.chunks == 5
        assert rs.dispatches_per_chunk == 1.0
        assert rs.program_traces in (None, 1), rs.program_traces
        assert rs.leaf_count == st_ref.leaf_count
        assert rs.overflow_dropped == 0
    assert rs_pipe.pipeline_depth == 3 and rs_sync.pipeline_depth == 1


def test_in_flight_queue_is_bounded():
    """The pipelined stream may never hold more than pipeline_depth
    dispatches in flight, and actually reaches the bound when the
    trajectory is long enough."""
    prob = _prob()
    for depth in (1, 2, 3):
        svc = _svc(prob, pipeline_depth=depth)
        chunks = list(svc.stream_chunks(zoom_bounds(20)))
        inflight = [c.chunk.in_flight for c in chunks]
        assert max(inflight) <= depth
        assert max(inflight) == min(depth, len(chunks))
        assert [c.chunk.index for c in chunks] == list(range(len(chunks)))


def test_chunk_stats_timing_fields():
    prob = _prob()
    svc = _svc(prob, pipeline_depth=2)
    canv, rs = svc.render(zoom_bounds(12))
    assert canv.shape == (12, 128, 128)
    assert len(rs.chunk_stats) == rs.chunks == 3
    for c in rs.chunk_stats:
        assert c.dispatch_s >= 0 and c.fetch_s >= 0
        assert c.busy_s == pytest.approx(c.dispatch_s + c.fetch_s)
    assert rs.dispatch_s == pytest.approx(
        sum(c.dispatch_s for c in rs.chunk_stats))
    assert rs.fetch_s == pytest.approx(
        sum(c.fetch_s for c in rs.chunk_stats))
    assert rs.busy_s <= rs.wall_s + 0.05  # host phases can't exceed wall


def test_sink_runs_per_chunk_and_is_timed():
    prob = _prob()
    svc = _svc(prob, pipeline_depth=2)
    seen = []

    def sink(canvases, stats):
        seen.append((canvases.shape[0], stats.kernel_launches))

    canv, rs = svc.render(zoom_bounds(10), sink=sink)
    assert [f for f, _ in seen] == [4, 4, 2]
    assert all(k == 1 for _, k in seen)
    assert rs.host_copy_s >= 0


def test_pipeline_overlaps_io_latency():
    """The ISSUE acceptance property: for a >= 8-chunk trajectory with a
    blocking per-chunk host I/O stage, the pipelined wall time is
    measurably below the synchronous path's summed per-chunk (compute +
    host-copy) cost -- the device computes chunk k+1 while the host
    writes chunk k.

    Runs the REAL service pipeline on the deterministic harness
    (``tests.fakes``): device compute and sink I/O cost virtual time
    only, so the classic pipeline law is asserted as an exact equality
    -- saved == (chunks - 1) * min(compute, io) -- instead of the
    tolerance band the old wall-clock-sleep version needed (which was
    flaky on CPU-starved CI hosts).
    """
    from fakes import FakeEngine

    compute_s, sink_s = 1.0, 0.5
    frames = 32  # chunk 4 -> 8 chunks

    results = {}
    for depth in (1, 2):
        svc = _svc(_prob(), pipeline_depth=depth)
        eng = FakeEngine.attach(svc, compute_s=compute_s)

        def sink(canvases, stats, _eng=eng):
            _eng.clock.advance(sink_s)  # an I/O wait, in virtual time

        canv, rs = svc.render(zoom_bounds(frames), sink=sink)
        results[depth] = (canv, rs, eng)

    sync_canv, sync_rs, _ = results[1]
    pipe_canv, pipe_rs, eng = results[2]
    np.testing.assert_array_equal(pipe_canv, sync_canv)
    assert sync_rs.chunks == pipe_rs.chunks == 8
    # sync serial cost == its wall (nothing overlaps at depth 1)
    assert sync_rs.busy_s == pytest.approx(sync_rs.wall_s)
    assert sync_rs.wall_s == pytest.approx(8 * (compute_s + sink_s))
    # pipelined: chunk k+1's device compute hides behind chunk k's sink
    saved = sync_rs.busy_s - pipe_rs.wall_s
    assert saved == pytest.approx((sync_rs.chunks - 1)
                                  * min(compute_s, sink_s))
    # the schedule itself: every pipelined chunk after the first was
    # enqueued BEFORE the previous chunk was consumed (true overlap),
    # and the device timeline stayed fully serial
    recs = eng.records
    assert len(recs) == 8
    for prev, cur in zip(recs, recs[1:]):
        assert cur.enqueued_at < prev.finalized_at
        assert cur.ready_at == prev.ready_at + compute_s


# ---------------------------------------------------------------------------
# closed-loop occupancy feedback (planner-aware chunking)
# ---------------------------------------------------------------------------
# A boundary-skimming zoom: the window hugs the seahorse-valley boundary
# while still zoomed OUT, so the real subdivision density runs HOTTER
# than the zoom-depth prior -- the regime the feedback loop exists for.
_SKIM_CENTER = (-0.7436447860, 0.1318252536)


def _skim_bounds(frames=32):
    return zoom_bounds(frames, center=_SKIM_CENTER, width0=6.0,
                       zoom_per_frame=1.02)


def _fb_svc(prob, **kw):
    kw.setdefault("mesh", make_frames_mesh(1))
    kw.setdefault("chunk_frames", 4)
    kw.setdefault("feedback", True)
    kw.setdefault("safety_factor", 1.1)
    return RenderService(prob, **kw)


def test_feedback_acceptance_on_boundary_skimming_trajectory():
    """The ISSUE acceptance property at test scale: on a boundary-
    skimming zoom the feedback-driven plan reaches overflow_dropped == 0
    with FEWER total ring rows and FEWER retry dispatches than the
    zoom-depth-prior plan, chunk 0 (cold start) reproduces the prior
    plan exactly, and every canvas stays bit-identical."""
    prob = _prob(dwell=40)  # dwell unique to this module's feedback tests
    ref, _ = _svc(prob).render(_skim_bounds())

    runs = {}
    for adapt in (False, True):
        svc = _fb_svc(prob, adapt=adapt)
        canv, rs = svc.render(_skim_bounds())
        np.testing.assert_array_equal(canv, ref)
        assert rs.overflow_dropped == 0
        assert rs.frames == 32
        runs[adapt] = rs

    prior, fb = runs[False], runs[True]
    assert fb.retries < prior.retries, (fb.retries, prior.retries)
    assert fb.ring_rows < prior.ring_rows, (fb.ring_rows, prior.ring_rows)
    assert fb.dispatches < prior.dispatches
    # chunk 0 is cold on both sides: same planning P, same prior source
    assert fb.chunk_stats[0].p_subdiv == prior.chunk_stats[0].p_subdiv
    assert fb.chunk_stats[0].p_source == prior.chunk_stats[0].p_source == "prior"
    # ... and the later chunks really switched to the measured signal
    assert any(c.p_source == "measured" for c in fb.chunk_stats)
    assert all(c.p_source == "prior" for c in prior.chunk_stats)


def test_feedback_pipelined_matches_sync_and_bounds_queue():
    """The closed loop composes with async double buffering: same
    canvases at depth 1 and 3, in-flight never exceeds the depth, and
    the estimator still converges (later chunks plan from measurement).
    """
    prob = _prob(dwell=44)
    results = {}
    for depth in (1, 3):
        svc = _fb_svc(prob, pipeline_depth=depth)
        chunks = list(svc.stream_chunks(_skim_bounds(24)))
        assert max(c.chunk.in_flight for c in chunks) <= depth
        results[depth] = (np.concatenate([np.asarray(c.canvases)
                                          for c in chunks]), chunks)
    sync_c, sync_chunks = results[1]
    pipe_c, pipe_chunks = results[3]
    np.testing.assert_array_equal(pipe_c, sync_c)
    for chunks in (sync_chunks, pipe_chunks):
        assert sum(c.chunk.frames for c in chunks) == 24
        assert any(c.chunk.p_source == "measured" for c in chunks)
        assert all(c.stats.overflow_dropped == 0 for c in chunks)


def test_pooled_feedback_pipelined_matches_sync_and_pool():
    """The pooled feedback stream at depth 2, where each chunk's copy
    runs while the next chunk computes, gives the canvases of depth 1
    and of one pooled batch over every frame, padded tail included
    (11 frames in chunks of 4, 4 and 3). ``fetch_s`` still splits into
    its phases: on the wall clock the phases are disjoint pieces of it,
    and what they leave out is the service's own bookkeeping."""
    from repro.core.pooled import run_ask_pooled_batch

    prob = _prob(dwell=45)
    bounds = list(_skim_bounds(11))
    results = {}
    for depth in (1, 2):
        svc = _fb_svc(prob, engine="ask_pooled", pipeline_depth=depth)
        chunks = list(svc.stream_chunks(bounds))
        assert [c.chunk.frames for c in chunks] == [4, 4, 3]
        assert max(c.chunk.in_flight for c in chunks) == depth
        for c in chunks:
            assert isinstance(c.canvases, np.ndarray)
            phases = (c.chunk.wait_s + c.chunk.stats_s + c.chunk.copy_s
                      + c.chunk.retry_s)
            assert phases <= c.chunk.fetch_s
            assert c.chunk.fetch_s == pytest.approx(phases, abs=0.05)
            assert c.stats.overflow_dropped == 0
        results[depth] = np.concatenate([c.canvases for c in chunks])
    ref, _ = run_ask_pooled_batch(prob, np.asarray(bounds, np.float32),
                                  safety_factor=1e9)
    np.testing.assert_array_equal(results[2], results[1])
    np.testing.assert_array_equal(results[2], np.asarray(ref))


def test_feedback_splits_chunk_on_capacity_class_jump():
    """Boundary-aware chunking: a stream whose density jumps mid-chunk
    is cut at the jump -- the cold prefix keeps its small ring and the
    deep tail gets its own hotter program -- and the compiled-program
    count stays pinned to the (width, signature) pairs actually used."""
    prob = _prob(dwell=52)  # dedicated config: clean trace counting
    wide = (-0.5 - 8.0, 0.0 - 8.0, -0.5 + 8.0, 0.0 + 8.0)  # sparse
    deep = (_SKIM_CENTER[0] - 0.005, _SKIM_CENTER[1] - 0.005,
            _SKIM_CENTER[0] + 0.005, _SKIM_CENTER[1] + 0.005)  # saturated
    bounds = [wide] * 3 + [deep] * 5
    svc = _fb_svc(prob, adapt=False)  # prior-driven classes: deterministic
    chunks = list(svc.stream_chunks(bounds))
    # [wide x3] cut early at the class jump, then [deep x4], [deep x1]
    assert [c.chunk.frames for c in chunks] == [3, 4, 1]
    ps = [c.chunk.p_subdiv for c in chunks]
    assert ps[0] < ps[1] and ps[1] == ps[2]
    rs_sigs = {(svc._pad_width(c.chunk.frames)) for c in chunks}
    assert rs_sigs <= {1, 2, 4}  # power-of-two width bucketing
    assert svc.program_traces() == len(svc._used_sigs)
    # bit-identity against the uniform worst-case service
    ref, _ = _svc(prob).render(bounds)
    got = np.concatenate([np.asarray(c.canvases) for c in chunks])
    np.testing.assert_array_equal(got, ref)


def test_feedback_retry_converges_with_zero_drops():
    """A deliberately hostile safety factor: chunks overflow, the
    in-service retry doubles capacities until every frame fits, and the
    yielded chunks still report overflow_dropped == 0 bit-identically."""
    prob = _prob(dwell=60)
    svc = _fb_svc(prob, safety_factor=0.4)
    canv, rs = svc.render(_skim_bounds(8))
    assert rs.overflow_dropped == 0
    assert rs.retries > 0
    assert rs.dispatches > rs.chunks  # the retries really dispatched
    ref, _ = _svc(prob).render(_skim_bounds(8))
    np.testing.assert_array_equal(canv, ref)


def test_feedback_estimator_state_carries_across_renders():
    """The estimator is service state: a second trajectory over the same
    depths plans from measurement starting at chunk 0 -- the cold-start
    retry tax is paid once per estimator, not once per render call."""
    prob = _prob(dwell=36)
    est = OccupancyEstimator()
    svc = _fb_svc(prob, feedback=est)
    _, rs1 = svc.render(_skim_bounds(8))
    assert rs1.chunk_stats[0].p_source == "prior"
    _, rs2 = svc.render(_skim_bounds(8))
    assert rs2.chunk_stats[0].p_source == "measured"
    assert est.chunks_observed == rs1.chunks + rs2.chunks


def test_feedback_rejects_conflicting_engine_kwargs():
    prob = _prob()
    with pytest.raises(ValueError, match="feedback"):
        _fb_svc(prob, capacities=(8, 8, 8))
    with pytest.raises(ValueError, match="feedback"):
        _fb_svc(prob, p_subdiv=0.8)
    with pytest.raises(ValueError, match="feedback"):
        _svc(prob, adapt=False)  # prior-only baseline needs feedback= set


# ---------------------------------------------------------------------------
# estimator persistence across service restarts (feedback_state=)
# ---------------------------------------------------------------------------

def test_feedback_state_survives_service_restart(tmp_path):
    """The ROADMAP persistence item: a service constructed with
    ``feedback_state=path`` saves its estimator on render() and a NEW
    service (a restarted process, as far as the estimator can tell)
    restored from that file plans its FIRST chunk from measurement --
    reproducing the warm service's plan, not the cold prior -- with
    canvases still bit-identical."""
    prob = _prob(dwell=56)  # dwell unique to this test's trace caches
    path = tmp_path / "estimator.json"

    svc1 = _fb_svc(prob, feedback_state=path)
    canv1, rs1 = svc1.render(_skim_bounds(8))
    assert rs1.chunk_stats[0].p_source == "prior"  # genuinely cold
    assert path.exists()  # render() auto-saved
    saved = path.read_bytes()  # state after exactly one trajectory

    # warm reference: what the SAME (unrestarted) service plans next
    canv_warm, rs_warm = svc1.render(_skim_bounds(8))
    assert rs_warm.chunk_stats[0].p_source == "measured"

    # the restarted service: fresh object, restored from the state the
    # warm reference planned from (render() above re-saved, so put the
    # post-first-render snapshot back first)
    path.write_bytes(saved)
    svc2 = _fb_svc(prob, feedback_state=path)
    canv2, rs2 = svc2.render(_skim_bounds(8))
    assert rs2.chunk_stats[0].p_source == "measured"  # warm from disk
    # the restarted run reproduces the warm plan chunk for chunk
    assert [c.p_subdiv for c in rs2.chunk_stats] == \
        [c.p_subdiv for c in rs_warm.chunk_stats]
    assert rs2.retries == rs_warm.retries
    np.testing.assert_array_equal(canv2, canv_warm)
    assert rs2.overflow_dropped == 0

    # conflicting construction fails loudly
    with pytest.raises(ValueError, match="not both"):
        _fb_svc(prob, feedback=OccupancyEstimator(), feedback_state=path)


def test_save_feedback_state_requires_estimator(tmp_path):
    svc = _svc(_prob())
    with pytest.raises(ValueError, match="estimator"):
        svc.save_feedback_state(tmp_path / "x.json")


def test_feedback_observation_uses_own_chunks_workload_when_pipelined():
    """Workload-switch boundaries with chunks in flight (the satellite
    bugfix this pins): when the stream is already PLANNING workload B's
    chunk while workload A's dispatch is still finalizing, A's measured
    counts must be filed under A's namespace -- the estimator observes
    each finalized chunk BEFORE the loop refills the queue, so an
    interleaved two-workload stream may never cross-pollinate bands."""
    from repro.workloads import FrameProblem

    probs = {
        "m": FrameProblem(n=128, g=4, r=2, B=16, max_dwell=62,
                          backend="jnp", workload="mandelbrot"),
        "j": FrameProblem(n=128, g=4, r=2, B=16, max_dwell=62,
                          backend="jnp", workload="julia"),
    }
    est = OccupancyEstimator()
    observed = []  # workload names, in observation order
    orig = est.observe_stats

    def spy(depths, stats, **kw):
        wl = kw.get("workload")
        observed.append(getattr(wl, "name", wl))
        return orig(depths, stats, **kw)

    est.observe_stats = spy
    svc = RenderService(dict(probs), mesh=make_frames_mesh(1),
                        chunk_frames=4, pipeline_depth=2, feedback=est,
                        safety_factor=2.0)
    # alternate every frame: EVERY chunk boundary is a workload switch,
    # and depth 2 keeps the previous workload's dispatch in flight while
    # the next one's chunk is being planned
    items = [("m", probs["m"].bounds), ("j", probs["j"].bounds)] * 3
    chunks = list(svc.stream_chunks(items))
    assert max(c.chunk.in_flight for c in chunks) == 2  # really pipelined
    expected = [probs[c.chunk.workload].workload.name for c in chunks]
    assert observed == expected == ["mandelbrot", "julia"] * 3
    # and the measurements landed in their own namespaces
    assert {"mandelbrot", "julia"} <= set(est.workloads_observed())


# ---------------------------------------------------------------------------
# chunk phases (``ChunkStats.*_s``, the ``repro.*`` profiler spans)
# ---------------------------------------------------------------------------

def test_feedback_chunk_phases_split_fetch_on_the_virtual_clock():
    """On the deterministic harness: ``fetch_s`` is exactly wait + stats
    + copy + retry, ``dispatch_s`` is still the engine's own enqueue
    time, the wait is the device time the chunk had left, and only the
    chunk whose frame overflowed spends time in the retry loop."""
    from fakes import FakeEngine

    svc = _fb_svc(_prob(dwell=61), pipeline_depth=2, safety_factor=0.4,
                  engine="ask_pooled")
    # dispatch 1 is chunk 1's first: its second frame drops rows
    eng = FakeEngine.attach(svc, compute_s=1.0, enqueue_s=0.25,
                            overflow={1: (0, 3, 0, 0)})
    chunks = [r.chunk for r in svc.stream_chunks(zoom_bounds(12))]
    assert [c.index for c in chunks] == [0, 1, 2]
    for c in chunks:
        assert c.fetch_s == pytest.approx(
            c.wait_s + c.stats_s + c.copy_s + c.retry_s)
        assert c.dispatch_s == pytest.approx(0.25)
        assert (c.retry_s > 0) == (c.retries > 0) == (c.index == 1)
    # chunk 0 runs on the device over [0, 1] and is finalised from 0.5,
    # after chunk 1's enqueue; chunk 1 runs over [1, 2] and is finalised
    # from 1.25, after chunk 2's enqueue; chunk 2's device work ([2, 3])
    # ended while chunk 1 retried
    assert [c.wait_s for c in chunks] == pytest.approx([0.5, 0.75, 0.0])
    # the retry queues behind chunk 2 on the serial device: it waits for
    # chunk 2's second and its own
    assert chunks[1].retry_s == pytest.approx(2.0)
    assert [r.frames for r in eng.records] == [4, 4, 4, 1]


def test_uniform_chunk_phases_split_fetch_on_the_virtual_clock():
    """The uniform path reaches the same phases, less those it has no
    work for: its canvases stay on the device and nothing retries."""
    from fakes import FakeEngine

    svc = _svc(_prob(), pipeline_depth=1)
    FakeEngine.attach(svc, compute_s=1.0, enqueue_s=0.25)
    chunks = [r.chunk for r in svc.stream_chunks(zoom_bounds(8))]
    assert len(chunks) == 2
    for c in chunks:
        assert c.dispatch_s == pytest.approx(0.25)
        # synchronous: the device time left after the enqueue returned
        assert c.wait_s == pytest.approx(0.75)
        assert c.fetch_s == pytest.approx(c.wait_s + c.stats_s)
        assert c.copy_s == c.retry_s == c.observe_s == 0.0


def test_chunk_phases_are_profiler_spans(tmp_path):
    """Each phase of a real (CPU) chunk is a ``repro.<phase>`` host span
    tagged with its chunk's index."""
    import jax
    from jax.profiler import ProfileData

    svc = _fb_svc(_prob(dwell=62), pipeline_depth=2)
    next(svc.stream_chunks(zoom_bounds(4)))  # compile outside the trace
    jax.profiler.start_trace(str(tmp_path))
    chunks = [r.chunk for r in svc.stream_chunks(zoom_bounds(8))]
    jax.profiler.stop_trace()
    path = next(tmp_path.rglob("*.xplane.pb"))
    spans = {}
    for plane in ProfileData.from_file(str(path)).planes:
        for line in plane.lines:
            for e in line.events:
                if e.name.startswith("repro."):
                    spans.setdefault(e.name, set()).add(
                        dict(e.stats).get("chunk"))
    assert {"repro.plan", "repro.dispatch", "repro.wait", "repro.stats",
            "repro.copy", "repro.observe"} <= set(spans)
    assert {c.index for c in chunks} <= spans["repro.wait"]
    assert all(c.wait_s >= 0 and c.copy_s >= 0 for c in chunks)
