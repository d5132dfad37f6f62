"""Pooled per-level worklists: ONE cross-frame OLT ring for a whole batch.

The batched scan engine (``core.ask.run_ask_scan_batch``) vmaps the level
pipeline over frames, so every frame carries its OWN double-buffered ring
sized for the batch's hottest member: F frames pay ``F x 2 x max_l cap_l``
rows even when most of them are sparse. The capacity planner (PR 4)
recovers part of that by bucketing frames into capacity classes, but
within a bucket the per-frame maximum still rules.

This module pools instead: per level, the live regions of ALL frames are
carried in ONE compacted worklist of frame-tagged rows ``(frame, cy,
cx)`` (``olt.subdivide_olt_tagged``), and the shared ring is provisioned
from the *sum* of the per-frame expected occupancies

    cap_l = ceil(safety * sum_f E_l(P_f)),   E_l(P) = g^2 (r^2 P)^l

clamped at the pooled worst case ``F (g r^l)^2`` (``pooled_capacities``).
On a heterogeneous batch -- a few dense deep-zoom frames amid a sparse
majority -- the sum is far below ``F x`` the dense frames' capacity, which
is exactly the memory the per-frame sizing wastes.

Bit-identity with the per-frame engine is by construction:

* the pooled worklist is kept in stable frame-major order (roots are
  enumerated frame-major; ``subdivide_olt_tagged`` inserts children via
  the same stable prefix-sum compaction as ``subdivide_olt``), so each
  frame's subsequence of the pooled worklist IS the worklist its private
  scan would have carried;
* the level kernels evaluate each row against its OWN frame's plane
  window (``ops.pooled_bounds`` gathers per-row bounds; the elementwise
  math and f32 op order match the traced-bounds batched path exactly);
* region writes land on a tall ``[F*n, n]`` canvas at row offset
  ``frame * n`` -- disjoint across frames, so one scatter per level
  serves every frame (``ops.region_fill_pooled`` /
  ``ops.region_dwell_pooled``).

Overflow accounting stays per frame: each level attributes its dropped
insertions to the frames that owned them (the insertion layout is
contiguous from slot 0, so the drop split is exact), and
``ASKStats.frame_overflow`` keys the same retry machinery as the
per-frame engines (``planner.solve_pooled``, the render service).

``run_ask_pooled_sharded`` spreads the pooled pipeline over a 1-D frame
mesh: frames are assigned frame-major (device d owns frames ``d*S ..
(d+1)*S - 1``), each shard pools ITS frames into one ring, and dead
padding frames (``live=False``) contribute zero occupancy to the sizing
and zero rows at runtime.
"""

from __future__ import annotations

import dataclasses
import math
import time
from typing import Any, Sequence, Tuple, Union

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import olt as olt_lib
from repro.core.ask import ASKStats, _frames_axis, _per_frame_counts
from repro.core.cost_model import expected_level_counts, num_levels
from repro.core.planner import worst_case_capacities
from repro.kernels import ops as ops_lib

__all__ = ["PooledDispatch", "pooled_capacities", "shard_capacities",
           "pooled_retry_capacities",
           "run_ask_pooled", "run_ask_pooled_batch",
           "run_ask_pooled_sharded", "dispatch_ask_pooled_sharded"]


def pooled_capacities(problem, frame_ps: Sequence[float], *,
                      safety_factor: float = 2.0) -> Tuple[int, ...]:
    """Shared per-level ring capacities for a pooled frame batch.

    One capacity per level 0..tau, each the SUM of the member frames'
    expected occupancies E_l = g^2 (r^2 P_f)^l (every addend pre-clamped
    at its own per-frame worst case, as ``scan_capacities`` does) times
    ``safety_factor``, clamped at the pooled worst case F (g r^l)^2.
    With safety_factor >= 1 level 0 is exactly F g^2: every live root is
    admitted. An empty ``frame_ps`` yields the all-ones floor (a pool of
    zero frames carries nothing).
    """
    n, g, r, B = problem.n, problem.g, problem.r, problem.B
    levels = num_levels(n, g, r, B)
    F = len(frame_ps)
    totals = [0.0] * (levels + 1)
    for p in frame_ps:
        for lv, e in enumerate(expected_level_counts(n, g, r, B, P=float(p))):
            totals[lv] += e
    caps = []
    for lv in range(levels + 1):
        worst = (g * r ** lv) ** 2
        caps.append(max(1, min(int(math.ceil(totals[lv] * safety_factor)),
                               F * worst)))
    return tuple(caps)


def shard_capacities(problem, frame_ps: Sequence[float], per_shard: int, *,
                     safety_factor: float = 2.0) -> Tuple[int, ...]:
    """Shared ring capacities for a frame-major sharded pool: per level
    the max over shards (``per_shard`` frames each, a ragged last one
    included) of the shard's ``pooled_capacities``. Every shard runs the
    one compiled program, so the ring fits the fullest shard."""
    caps = None
    for d0 in range(0, len(frame_ps), per_shard):
        c = pooled_capacities(problem, frame_ps[d0:d0 + per_shard],
                              safety_factor=safety_factor)
        caps = c if caps is None else tuple(map(max, caps, c))
    return caps


def pooled_retry_capacities(problem, caps, failed_chains, *, retry: int,
                            ran_per_shard: int, next_per_shard: int,
                            frame_ps=None,
                            safety_factor: float = 2.0) -> Tuple[int, ...]:
    """THE pooled overflow-retry rule: per-shard shared capacities to
    re-pool the frames that overflowed a pool at ``caps``.

    ``failed_chains`` are those frames' measured ``(region_counts,
    leaf_count)`` in the dispatch that overflowed; a shard held
    ``ran_per_shard`` frames there and holds ``next_per_shard`` in the
    retry; ``retry`` counts the retries the frames already had.

    First retry (``retry == 0``): re-pooling at the WHOLE previous
    pool's doubled capacities would size a ring for frames that already
    fit, so the ring comes from the failed frames alone -- per level,
    twice their measured live rows (doubling covers the children the
    drops truncated) or their own pooled estimate from ``frame_ps``,
    whichever is larger. Later retries double each level. Both clamp at
    the retry pool's worst case ``next_per_shard * (g r^l)^2``, possibly
    below ``caps``: the pool shrank with them.

    The impossibility check uses the pool that RAN: a frame that
    overflowed while sharing a ring only proves the SHARED ring was
    short, but a pool already at the worst case of its own
    ``ran_per_shard`` frames cannot overflow, so reaching it with frames
    still dropping is a bug, not a sizing problem (RuntimeError, as
    ``planner.escalate_capacities``)."""
    worst = worst_case_capacities(problem)
    hi_ran = tuple(max(1, int(ran_per_shard)) * w for w in worst)
    if tuple(min(c, h) for c, h in zip(caps, hi_ran)) == hi_ran:
        raise RuntimeError(
            f"{len(failed_chains)} frames overflow at pooled worst-case "
            f"capacities {tuple(caps)}")
    hi = tuple(max(1, int(next_per_shard)) * w for w in worst)
    if retry:
        return tuple(min(2 * c, h) for c, h in zip(caps, hi))
    est = (pooled_capacities(problem, frame_ps, safety_factor=safety_factor)
           if frame_ps else None)
    levels = len(worst) - 1
    out = []
    for lv, h in enumerate(hi):
        if lv == levels:
            meas = sum(int(leaf) for _, leaf in failed_chains)
        else:
            meas = sum(int(rc[lv]) for rc, _ in failed_chains if lv < len(rc))
        need = 2 * meas if est is None else max(2 * meas, est[lv])
        out.append(max(1, min(need, h)))
    return tuple(out)


def _resolve_pooled_capacities(problem, frames: int, capacities, frame_ps,
                               p_subdiv, safety_factor) -> Tuple[int, ...]:
    levels = num_levels(problem.n, problem.g, problem.r, problem.B)
    if capacities is not None:
        if frame_ps is not None:
            raise ValueError("pass capacities= OR frame_ps=, not both")
        if isinstance(capacities, int):
            return (max(1, capacities),) * (levels + 1)
        caps = tuple(max(1, int(c)) for c in capacities)
        if len(caps) != levels + 1:
            raise ValueError(
                f"need {levels + 1} capacities (levels 0..{levels}), "
                f"got {len(caps)}")
        return caps
    if frame_ps is None:
        ps: Tuple[float, ...] = (float(p_subdiv),) * frames
    else:
        ps = tuple(float(p) for p in frame_ps)
        if len(ps) != frames:
            raise ValueError(
                f"frame_ps covers {len(ps)} frames, batch has {frames}")
    return pooled_capacities(problem, ps, safety_factor=safety_factor)


def _build_pooled_pipeline(problem, caps: Sequence[int], frames: int):
    """One XLA program rendering ``frames`` frames through ONE shared
    OLT ring of frame-tagged rows.

    Returns ``pipeline(bounds_all [F, 4], live [F] bool) -> (states
    [F, n, n], entering [levels, F], leaf_f [F], frame_dropped [F])``.
    The problem must implement ``pooled_level_step`` /
    ``pooled_leaf_step`` (``workloads.FrameProblem`` does).
    """
    g, r = problem.g, problem.r
    n = problem.n
    levels = len(caps) - 1
    ring_width = max(caps)
    F = frames
    R = r * r
    pol = getattr(problem, "policy", None)

    def ranks_of(flags):
        """Policy-routed exclusive-scan compaction. The pooled worklist
        is F times the per-frame one, so above the single-block cap the
        tuned tier's blocked schedule applies (ops.compact_ranks pads
        ragged lengths); problems without a kernel policy keep the plain
        jnp scan. Every lowering is exact integer math -> identical."""
        with jax.named_scope("ask.compact"):
            if pol is None:
                return olt_lib.compact_ranks(flags)
            return ops_lib.compact_ranks(flags, policy=pol)

    def frame_sum(rows, weights):
        """Segment-sum ``weights`` by the rows' frame tags -> [F] int32.
        mode="drop" discards out-of-range tags (zero-padded dead rows
        always carry weight 0 anyway)."""
        return jnp.zeros((F,), jnp.int32).at[rows[:, 0]].add(
            weights.astype(jnp.int32), mode="drop")

    def pipeline(bounds_all, live):
        state = jnp.zeros((F * n, n), dtype=problem.workload.dtype)
        # everything below that no inner stage claims is worklist
        # bookkeeping: roots, the level scan's control, children, ring
        # reads and writes, per-frame counts
        with jax.named_scope("ask.subdivide"):
            state, entering, leaf_f, frame_dropped = level_scan(
                state, bounds_all, live)
        return state.reshape(F, n, n), entering, leaf_f, frame_dropped

    def level_scan(state, bounds_all, live):
        # frame-major root worklist: frame f's g^2 roots, in root order,
        # before frame f+1's -- the order every per-frame scan would use
        roots = problem.root_coords()  # [g*g, 2]
        roots_n = roots.shape[0]
        frame_ids = jnp.repeat(jnp.arange(F, dtype=jnp.int32), roots_n)
        rows0 = jnp.concatenate(
            [frame_ids[:, None], jnp.tile(roots, (F, 1))], axis=1)
        flags0 = live[rows0[:, 0]]
        ranks0, count0 = ranks_of(flags0)
        with jax.named_scope("ask.compact"):
            rows_c, _ = olt_lib.compact_gather(
                rows0, flags0, caps[0], ranks_count=(ranks0, count0))
        root_drop = jnp.logical_and(flags0, ranks0 >= caps[0])
        frame_dropped = frame_sum(rows0, root_drop)
        count = jnp.minimum(count0, jnp.int32(caps[0]))
        ring = olt_lib.ring_init(rows_c, caps[0], ring_width)
        parity = jnp.int32(0)

        def make_branch(lv):
            cap_in, cap_out = caps[lv], caps[lv + 1]

            def branch(carry):
                state, ring, parity, count, frame_dropped = carry
                rows = olt_lib.ring_read(ring, parity, cap_in)
                valid = jnp.arange(cap_in) < count
                state, flags = problem.pooled_level_step(
                    state, rows, valid, level=lv, bounds_all=bounds_all)
                flags = jnp.logical_and(flags, valid)
                ranks, kcount = ranks_of(flags)
                children, child_count = olt_lib.subdivide_olt_tagged(
                    rows, flags, r=r, capacity=cap_out,
                    ranks_count=(ranks, kcount))
                # per-frame drop attribution: the flagged parent at rank
                # k owns slots [k*R, (k+1)*R), so insertion is contiguous
                # from slot 0 and each parent's dropped-children count is
                # exactly R - clip(cap_out - k*R, 0, R)
                inserted = jnp.clip(cap_out - ranks * R, 0, R)
                row_drops = jnp.where(flags, R - inserted, 0)
                frame_dropped = frame_dropped + frame_sum(rows, row_drops)
                count = jnp.minimum(child_count, cap_out)
                ring = olt_lib.ring_write(ring, parity, children)
                return state, ring, jnp.int32(1) - parity, count, frame_dropped

            return branch

        branches = [make_branch(lv) for lv in range(levels)]

        def scan_body(carry, lv):
            # per-frame live counts entering this level, read off the
            # front buffer (rows beyond count are zeros; valid masks them)
            front = olt_lib.ring_read(carry[1], carry[2], ring_width)
            entering = frame_sum(front, jnp.arange(ring_width) < carry[3])
            carry = jax.lax.switch(lv, branches, carry)
            return carry, entering

        carry = (state, ring, parity, count, frame_dropped)
        if levels > 0:
            carry, entering = jax.lax.scan(
                scan_body, carry, jnp.arange(levels, dtype=jnp.int32))
        else:
            entering = jnp.zeros((0, F), jnp.int32)
        state, ring, parity, count, frame_dropped = carry

        cap_leaf = caps[levels]
        rows = olt_lib.ring_read(ring, parity, cap_leaf)
        valid = jnp.arange(cap_leaf) < count
        leaf_f = frame_sum(rows, valid)
        state = problem.pooled_leaf_step(state, rows, valid, level=levels,
                                         bounds_all=bounds_all)
        return state, entering, leaf_f, frame_dropped

    return pipeline


# Compiled-pipeline cache, mirroring core.ask._PIPELINE_CACHE: keyed on
# (problem, caps, frames-per-program, mesh); the frozen problem (policy
# included) hashes, unhashable problems just rebuild. Bounded FIFO.
_POOLED_CACHE: dict = {}
_POOLED_CACHE_MAX = 128


def _jitted_pooled(problem, caps: Tuple[int, ...], frames: int, mesh=None):
    """Build (or fetch) the jitted pooled pipeline.

    ``mesh`` maps the pipeline over the SHARD axis: inputs become
    ``[n_dev, S, ...]`` with ``frames = S`` frames pooled per shard, so
    each device runs its own pool with zero collectives (the lax.switch
    level index stays unbatched). The canvases come out frame-major,
    ``[n_dev * S, n, n]``, as each device's ``[S, n, n]`` block; the
    per-frame counters keep their leading shard axis.
    """
    try:
        key = (problem, caps, frames, mesh)
        cached = _POOLED_CACHE.get(key)
        if cached is not None:
            return cached
    except TypeError:  # unhashable problem: no caching
        key = None
    pipeline = _build_pooled_pipeline(problem, caps, frames)
    if mesh is None:
        fn = jax.jit(pipeline)
    else:
        from jax.sharding import PartitionSpec

        def one_pool(bounds_all, live):  # this device's [1, S, ...] block
            states, *counters = pipeline(bounds_all[0], live[0])
            return (states, *(x[None] for x in counters))

        # shard_map, not a vmap for GSPMD to partition: each device runs
        # the one-pool program on its own block (GSPMD partitioning of
        # the vmapped pool crashed the TPU compiler at n >= 1024 on a
        # 2x2 v5e mesh)
        shards = PartitionSpec(_frames_axis(mesh))
        fn = jax.jit(jax.shard_map(one_pool, mesh=mesh,
                                   in_specs=(shards, shards),
                                   out_specs=shards, check_vma=False))
    if key is not None:
        if len(_POOLED_CACHE) >= _POOLED_CACHE_MAX:
            _POOLED_CACHE.pop(next(iter(_POOLED_CACHE)))
        _POOLED_CACHE[key] = fn
    return fn


def _pooled_stats(caps, entering_fl, leaf_f, frame_dropped, wall_s) -> ASKStats:
    """Assemble per-frame ASKStats from pooled pipeline outputs.
    ``entering_fl`` is host-side [F, levels]."""
    per_frame = _per_frame_counts(entering_fl)
    leaf_host = [int(c) for c in leaf_f]
    drop_host = [int(d) for d in frame_dropped]
    return ASKStats(
        levels=max((len(c) for c in per_frame), default=0),
        kernel_launches=1,  # the whole pooled batch is one dispatch
        region_counts=per_frame,
        leaf_count=sum(leaf_host),
        overflow_dropped=sum(drop_host),
        wall_s=wall_s,
        olt_caps=tuple(caps),  # SHARED ring: ring_rows == the pool total
        frame_overflow=tuple(drop_host),
        frame_leaf_counts=tuple(leaf_host),
    )


def run_ask_pooled_batch(
    problem,
    extras: Any,
    *,
    capacities: Union[None, int, Sequence[int]] = None,
    frame_ps: Union[Sequence[float], None] = None,
    p_subdiv: float = 0.7,
    safety_factor: float = 2.0,
    live=None,
    block_until_ready: bool = True,
) -> Tuple[Any, ASKStats]:
    """Render F frames through ONE pooled cross-frame worklist.

    ``extras`` is the [F, 4] per-frame bounds array (the pooled kernels
    gather each row's plane window by its frame tag, so bounds-shaped
    extras are required). Ring sizing: ``capacities`` (explicit shared
    per-level caps) > ``frame_ps`` (per-frame subdivision probabilities,
    summed by ``pooled_capacities``) > uniform ``p_subdiv`` for every
    frame. ``live`` masks frames out of the pool entirely (sharded
    padding); dead frames return zero canvases and zero stats.

    Returns (states [F, n, n], stats) with the same per-frame ASKStats
    breakdown as ``run_ask_scan_batch`` -- but ``stats.ring_rows``
    (2 x max caps) is now the whole batch's ring, not a per-frame cost.
    Bit-identical to the per-frame engine whenever nothing overflows.
    """
    bounds_all = jnp.asarray(extras, jnp.float32)
    if bounds_all.ndim != 2 or bounds_all.shape[1] != 4:
        raise ValueError(
            f"pooled extras must be [F, 4] bounds, got {bounds_all.shape}")
    F = int(bounds_all.shape[0])
    caps = _resolve_pooled_capacities(problem, F, capacities, frame_ps,
                                      p_subdiv, safety_factor)
    fn = _jitted_pooled(problem, caps, F)
    live_arr = (jnp.ones((F,), bool) if live is None
                else jnp.asarray(live, bool))

    t0 = time.perf_counter()
    states, entering, leaf_f, frame_dropped = fn(bounds_all, live_arr)
    if block_until_ready:
        states = jax.block_until_ready(states)
    stats = _pooled_stats(caps, jax.device_get(entering).T,
                          jax.device_get(leaf_f),
                          jax.device_get(frame_dropped),
                          time.perf_counter() - t0)
    return states, stats


def run_ask_pooled(
    problem,
    *,
    capacities: Union[None, int, Sequence[int]] = None,
    p_subdiv: float = 0.7,
    safety_factor: float = 2.0,
    block_until_ready: bool = True,
) -> Tuple[Any, ASKStats]:
    """Single-frame front of the pooled engine (the F=1 pool), with the
    flat single-frame stats shape of ``run_ask_scan`` -- the engine-
    ladder rung ``solve(problem, "ask_pooled")`` dispatches to."""
    bounds = jnp.asarray(problem.bounds, jnp.float32)[None, :]
    states, stats = run_ask_pooled_batch(
        problem, bounds, capacities=capacities, p_subdiv=p_subdiv,
        safety_factor=safety_factor, block_until_ready=block_until_ready)
    stats = dataclasses.replace(stats, region_counts=stats.region_counts[0],
                                frame_overflow=(), frame_leaf_counts=())
    return states[0], stats


# ---------------------------------------------------------------------------
# sharded pooled dispatch: one pool per device shard
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class PooledDispatch:
    """An in-flight sharded pooled batch (see ``core.ask.ShardedDispatch``
    for the async-dispatch contract). Frames are assigned frame-major
    (device d owns frames d*S .. (d+1)*S - 1): ``states`` is the
    program's own [F_pad, n, n] canvas output, already in input order;
    the counters carry a leading shard axis (entering [n_dev, levels,
    S], leaf/dropped [n_dev, S]). ``caps`` is the PER-SHARD shared ring
    sizing.

    ``finalize()`` queues no device work: it returns ``states`` as they
    are, padded tail included (rows ``frames`` on are dead frames), and
    reads the small counters back. So the host's copy of a chunk's
    canvases starts when the chunk's program ends, while the next one
    runs; callers that want the true frames slice ``[:frames]`` where
    the canvases land (host or device)."""

    states: Any
    entering: Any
    leaf_f: Any
    frame_dropped: Any
    frames: int  # true F before padding
    caps: Tuple[int, ...]
    n_dev: int
    t0: float
    program: Any  # the jitted program it was enqueued on (host-side)

    def wait(self) -> None:
        """Block until the batch's device work has ended."""
        jax.block_until_ready(self.states)

    def finalize(self, *, block_until_ready: bool = True) -> Tuple[Any, ASKStats]:
        """Block, then read the counters back: ``(states, stats)``, the
        states as the program left them ([F_pad, n, n]), the stats of
        the ``frames`` true frames."""
        if block_until_ready:
            self.wait()
        F = self.frames
        entering = jax.device_get(self.entering)  # [n_dev, levels, S]
        entering = np.moveaxis(entering, 1, 2).reshape(
            -1, entering.shape[1])[:F]
        leaf_f = jax.device_get(self.leaf_f).reshape(-1)[:F]
        dropped = jax.device_get(self.frame_dropped).reshape(-1)[:F]
        stats = _pooled_stats(self.caps, entering, leaf_f, dropped,
                              time.perf_counter() - self.t0)
        return self.states, stats


def dispatch_ask_pooled_sharded(
    problem,
    extras: Any,
    *,
    mesh,
    capacities: Union[None, int, Sequence[int]] = None,
    frame_ps: Union[Sequence[float], None] = None,
    p_subdiv: float = 0.7,
    safety_factor: float = 2.0,
    pad_to: Union[int, None] = None,
) -> PooledDispatch:
    """Enqueue one sharded pooled batch WITHOUT blocking.

    Frames are padded up to a multiple of the device count (``pad_to``
    overrides the multiple, as in the per-frame engine) with DEAD frames
    -- ``live=False`` rows that contribute zero occupancy and zero rows
    -- then assigned frame-major: device d pools frames ``d*S .. (d+1)*S
    - 1`` into one shared ring. Every shard runs the same compiled
    program, so the ring sizing is shared too: per level, the MAX over
    shards of that shard's pooled capacity (live frames only). With
    ``frame_ps`` each shard's sum uses its members' own P; uniform
    ``p_subdiv`` sizes a full shard of S frames (keeping the compiled
    signature independent of the ragged tail). Explicit ``capacities``
    are PER-SHARD shared caps, taken as given.
    """
    bounds_all = jnp.asarray(extras, jnp.float32)
    if bounds_all.ndim != 2 or bounds_all.shape[1] != 4:
        raise ValueError(
            f"pooled extras must be [F, 4] bounds, got {bounds_all.shape}")
    F = int(bounds_all.shape[0])
    n_dev = int(mesh.devices.size)
    multiple = n_dev if pad_to is None else int(pad_to)
    if multiple % n_dev:
        raise ValueError(
            f"pad_to={multiple} must be a multiple of the mesh device "
            f"count {n_dev}")
    pad = (-F) % multiple
    F_pad = F + pad
    S = F_pad // n_dev
    if pad:
        fill = jnp.broadcast_to(bounds_all[:1], (pad, 4))
        bounds_all = jnp.concatenate([bounds_all, fill], axis=0)
    live = jnp.arange(F_pad) < F

    if capacities is not None:
        caps = _resolve_pooled_capacities(problem, S, capacities, None,
                                          p_subdiv, safety_factor)
    elif frame_ps is not None:
        ps = [float(p) for p in frame_ps]
        if len(ps) != F:
            raise ValueError(
                f"frame_ps covers {len(ps)} frames, batch has {F}")
        caps = shard_capacities(problem, ps, S, safety_factor=safety_factor)
    else:
        caps = pooled_capacities(problem, (float(p_subdiv),) * S,
                                 safety_factor=safety_factor)

    fn = _jitted_pooled(problem, caps, S, mesh=mesh)
    t0 = time.perf_counter()
    states, entering, leaf_f, frame_dropped = fn(
        bounds_all.reshape(n_dev, S, 4), live.reshape(n_dev, S))
    return PooledDispatch(states=states, entering=entering, leaf_f=leaf_f,
                          frame_dropped=frame_dropped, frames=F,
                          caps=tuple(caps), n_dev=n_dev, t0=t0, program=fn)


def run_ask_pooled_sharded(
    problem,
    extras: Any,
    *,
    mesh,
    capacities: Union[None, int, Sequence[int]] = None,
    frame_ps: Union[Sequence[float], None] = None,
    p_subdiv: float = 0.7,
    safety_factor: float = 2.0,
    pad_to: Union[int, None] = None,
    block_until_ready: bool = True,
) -> Tuple[Any, ASKStats]:
    """Synchronous wrapper over ``dispatch_ask_pooled_sharded`` +
    ``PooledDispatch.finalize`` (one pool per device shard; total ring
    across the mesh is ``n_dev * stats.ring_rows``), with the padded
    tail trimmed: states [F, n, n]."""
    d = dispatch_ask_pooled_sharded(
        problem, extras, mesh=mesh, capacities=capacities,
        frame_ps=frame_ps, p_subdiv=p_subdiv, safety_factor=safety_factor,
        pad_to=pad_to)
    states, stats = d.finalize(block_until_ready=block_until_ready)
    if int(states.shape[0]) != d.frames:
        states = states[:d.frames]
    return states, stats
