"""Adaptive Serial Kernels (ASK) -- paper Sec. 5, adapted to TPU/XLA.

ASK replaces Dynamic Parallelism's recursive kernel tree with a *serial*
sequence of flat kernels, one per subdivision level, the active-region set
carried between launches in a compact OLT (see ``core/olt.py``).

Three execution modes (DESIGN.md Sec. 2), trading dispatches for memory:

``run_ask``        -- the paper-faithful mode: one host-driven kernel launch
                      per level (tau+1 dispatches, one host<->device sync per
                      level to learn the next grid size). XLA needs static
                      shapes, so the live region count is padded to the next
                      power of two ("bucketing"); at most O(log n) distinct
                      shapes are ever compiled and the jit cache amortises
                      them across levels and frames. OLT memory: the live
                      bucket only -- O(next_pow2(max live count)).

``run_ask_fused``  -- beyond-paper: because ASK is *iterative*, the entire
                      level pipeline can be unrolled into ONE jitted XLA
                      program (static per-level capacities, masked tails),
                      removing even the per-level launch+sync overhead.
                      DP's data-dependent recursion tree cannot be compiled
                      this way -- this is the structural advantage the
                      paper's cost model prices as a smaller lambda. The
                      price is memory: per-level buffers are the *worst
                      case* (g r^l)^2, and all tau+1 of them live inside one
                      program -- the exact blow-up DP-consolidation
                      compilers (arXiv 1606.08150, 2201.02789) hit.

``run_ask_scan``   -- the serving engine: ONE dispatch like the fused mode,
                      but the live OLT is carried through a ``lax.scan``
                      over levels in a bounded double-buffered ring
                      (``olt.ring_*``). Per-level capacities come from the
                      cost model's *expected* occupancy E_l = g^2 (r^2 P)^l
                      times a safety factor (``cost_model.
                      expected_level_counts``), so memory is O(2 x
                      max expected live set) -- strictly below the fused
                      worst case from level 2 on. Regions beyond capacity
                      are dropped and counted in ``ASKStats.
                      overflow_dropped``. The default sizing (P=0.7,
                      safety 2x) covers the paper's benchmark config but
                      is NOT a guarantee -- near-boundary windows run
                      hotter than the constant-P model; callers needing
                      bit-exactness must check ``overflow_dropped == 0``
                      and retry with a larger ``safety_factor`` (or
                      worst-case ``capacities``) when it isn't.
                      Because level kernels are shape-specialised, the scan
                      body dispatches through ``lax.switch`` -- the scan
                      index is unbatched under ``vmap``, which is what
                      makes the batched frame-serving front-end
                      (``mandelbrot.solve_batch``) a single XLA program
                      over a whole stack of frames.

``run_ask_scan_sharded`` spreads the *frame* axis of the batched scan
pipeline over a 1-D device mesh (``jax.sharding.NamedSharding``): per-level
ring capacities are shared across frames and the ``lax.switch`` level index
is unbatched, so only the canvas / OLT-ring carries partition -- each device
renders its slice of the frame batch with zero cross-device collectives and
the result is bit-identical to the unsharded batch. Frame counts that don't
divide the device count are padded (repeating frame 0) and the padded
frames are masked out of the leaf/overflow sums.

A problem plugs in via the ``ASKProblem`` protocol; the Mandelbrot /
Mariani-Silver instantiation lives in ``repro/mandelbrot``.
"""

from __future__ import annotations

import dataclasses
import math
import time
from typing import Any, Protocol, Sequence, Tuple, Union

import jax
import jax.numpy as jnp

from repro.core import olt as olt_lib
from repro.core.cost_model import expected_level_counts, num_levels

__all__ = ["ASKProblem", "ASKStats", "ShardedDispatch", "run_ask",
           "run_ask_fused", "run_ask_scan", "run_ask_scan_batch",
           "run_ask_scan_sharded", "dispatch_ask_scan_sharded",
           "pad_frames", "scan_capacities"]


class ASKProblem(Protocol):
    """Adapter for an SSD workload driven by subdivision.

    Regions at level ``l`` live on a ``(g * r**l)``-per-side grid and are
    identified by int32 coords (cy, cx) -- see ``core/olt.py``.

    Optional extension for batched serving (``run_ask_scan_batch``):
    ``level_step_dyn(state, coords, valid, *, level, extra)`` and
    ``leaf_step_dyn(...)`` -- the same kernels but parameterised by a
    traced per-frame pytree ``extra`` (the vmap axis), e.g. the complex-
    plane bounds of each frame in a zoom sequence.
    """

    n: int
    g: int
    r: int
    B: int

    def init_state(self) -> Any:
        """Initial output state (e.g. the n x n canvas)."""

    def root_coords(self) -> jax.Array:
        """[g*g, 2] level-0 region coordinates."""

    def level_step(self, state: Any, coords: jax.Array, valid: jax.Array,
                   level: int) -> Tuple[Any, jax.Array]:
        """Exploration kernel for one level: performs the query Q on each
        valid region, applies terminal work T to homogeneous ones, and
        returns (new_state, subdivide_flags[bool])."""

    def leaf_step(self, state: Any, coords: jax.Array, valid: jax.Array,
                  level: int) -> Any:
        """Last-level application work A on each remaining region."""

    def region_side(self, level: int) -> int:
        """Pixel side of a level-``level`` region: n // (g * r**level)."""


@dataclasses.dataclass
class ASKStats:
    """Per-run accounting (feeds the cost-model validation benchmarks)."""

    levels: int = 0
    kernel_launches: int = 0  # host->device dispatches (ASK: one per level)
    region_counts: tuple = ()  # live regions entering each level
    leaf_count: int = 0
    # host seconds from the dispatch to the stats. On the synchronous
    # paths that is the render; on the async paths (``ShardedDispatch``,
    # ``PooledDispatch``) it runs from enqueue to ``finalize()``, so it
    # includes queueing behind earlier chunks and is no device time. The
    # device time of each stage is in a profile, under the ``ask.*``
    # scopes; the host's wait is ``ChunkStats.wait_s``.
    wall_s: float = 0.0
    overflow_dropped: int = 0  # fused/scan modes: regions beyond capacity
    olt_caps: tuple = ()  # OLT rows allocated per level (incl. leaf level)
    # batched/sharded engines only: per-true-frame breakdowns of the two
    # sums above, in input frame order. ``frame_overflow`` is what the
    # capacity planner's retry path keys on (core/planner.py): a frame
    # whose entry is nonzero gets re-planned into a larger bucket.
    frame_overflow: tuple = ()
    frame_leaf_counts: tuple = ()
    # pooled engine only: the leaf ring rows A ran, one entry a pool
    # (device shard). A runs block by block up to the live leaf count
    # (``ops.region_dwell_pooled``), so over ``olt_caps[-1]`` this is
    # the share of the leaf ring A computed.
    dwell_rows: tuple = ()

    @property
    def ring_rows(self) -> int:
        """Live OLT rows resident per frame in the scan engines' double-
        buffered ring: two buffers of the widest level slice."""
        return 2 * max(self.olt_caps) if self.olt_caps else 0

    def frame_chains(self) -> tuple:
        """Per-frame ``(region_counts, leaf_count)`` observation chains.

        The raw material of the measured-occupancy feedback loop
        (``core.feedback``): consecutive entries of a chain are parent /
        child counts whose ratio is the measured per-level subdivision
        rate. Batched/sharded stats yield one chain per true frame (in
        input order); single-frame stats yield one chain.
        """
        if self.frame_leaf_counts:
            return tuple(zip(self.region_counts, self.frame_leaf_counts))
        return ((self.region_counts, self.leaf_count),)


def _num_levels(n: int, g: int, r: int, B: int) -> int:
    """Number of exploration levels (shared definition: cost_model)."""
    return num_levels(n, g, r, B)


def run_ask(problem: ASKProblem, *, block_until_ready: bool = True) -> Tuple[Any, ASKStats]:
    """Paper-faithful ASK: serial kernels, bucketed dynamic grids."""
    n, g, r, B = problem.n, problem.g, problem.r, problem.B
    t0 = time.perf_counter()
    state = problem.init_state()
    coords = problem.root_coords()
    count = g * g
    stats = ASKStats()
    counts = []
    caps_used = []

    levels = _num_levels(n, g, r, B)
    level_fn = jax.jit(problem.level_step, static_argnames=("level",))
    leaf_fn = jax.jit(problem.leaf_step, static_argnames=("level",))

    for level in range(levels):
        if count == 0:
            break
        cap = olt_lib.next_pow2(count)
        coords_p, valid = olt_lib.pad_olt(coords, count, cap)
        counts.append(count)
        caps_used.append(cap)
        state, flags = level_fn(state, coords_p, valid, level=level)
        stats.kernel_launches += 1
        # write-OLT: every flagged region inserts r*r children (Sec. 5.3.2)
        child_cap = olt_lib.next_pow2(cap * r * r)
        coords, child_count = olt_lib.subdivide_olt(
            coords_p, jnp.logical_and(flags, valid), r=r, capacity=child_cap)
        count = int(child_count)  # host sync == the serial-kernel boundary
        stats.levels += 1

    if count > 0:
        cap = olt_lib.next_pow2(count)
        coords_p, valid = olt_lib.pad_olt(coords, count, cap)
        state = leaf_fn(state, coords_p, valid, level=stats.levels)
        stats.kernel_launches += 1
        stats.leaf_count = count
        caps_used.append(cap)

    if block_until_ready:
        state = jax.block_until_ready(state)
    stats.region_counts = tuple(counts)
    stats.olt_caps = tuple(caps_used)
    stats.wall_s = time.perf_counter() - t0
    return state, stats


def run_ask_fused(
    problem: ASKProblem,
    *,
    capacity_factor: float = 1.0,
    block_until_ready: bool = True,
) -> Tuple[Any, ASKStats]:
    """Beyond-paper fused ASK: one XLA program for the whole pipeline.

    Per-level OLT capacities are static worst cases scaled by
    ``capacity_factor`` (<= 1.0 keeps the exhaustive bound; the worst case
    at level l is the full region grid (g*r**l)^2). Regions beyond capacity
    are dropped and counted -- with the default factor nothing can drop.
    """
    n, g, r, B = problem.n, problem.g, problem.r, problem.B
    levels = _num_levels(n, g, r, B)
    caps = []
    for lv in range(levels + 1):
        worst = (g * r ** lv) ** 2
        caps.append(max(1, olt_lib.next_pow2(int(worst * capacity_factor))))

    def pipeline(state):
        coords = problem.root_coords()
        count = jnp.int32(g * g)
        dropped = jnp.int32(0)
        for level in range(levels):
            cap = caps[level]
            coords_p, _ = olt_lib.pad_olt(coords, 0, cap)  # shape only
            coords_p = coords_p.at[: min(coords.shape[0], cap)].set(coords[:cap])
            valid = jnp.arange(cap) < count
            state, flags = problem.level_step(state, coords_p, valid, level=level)
            flags = jnp.logical_and(flags, valid)
            child_cap = caps[level + 1]
            coords, child_count = olt_lib.subdivide_olt(
                coords_p, flags, r=r, capacity=child_cap)
            dropped = dropped + jnp.maximum(child_count - child_cap, 0)
            count = jnp.minimum(child_count, child_cap)
        valid = jnp.arange(caps[levels]) < count
        state = problem.leaf_step(state, coords, valid, level=levels)
        return state, count, dropped

    t0 = time.perf_counter()
    state, leaf_count, dropped = jax.jit(pipeline)(problem.init_state())
    if block_until_ready:
        state = jax.block_until_ready(state)
    stats = ASKStats(
        levels=levels,
        kernel_launches=1,  # the whole pipeline is one dispatch
        leaf_count=int(leaf_count),
        overflow_dropped=int(dropped),
        wall_s=time.perf_counter() - t0,
        olt_caps=tuple(caps),
    )
    return state, stats


# ---------------------------------------------------------------------------
# run_ask_scan: single-dispatch streaming engine over a bounded OLT ring
# ---------------------------------------------------------------------------

def scan_capacities(
    n: int, g: int, r: int, B: int,
    *, p_subdiv: float = 0.7, safety_factor: float = 2.0,
) -> Tuple[int, ...]:
    """Per-level ring-slice capacities for ``run_ask_scan``.

    Expected occupancy from the cost model (E_l = g^2 (r^2 p)^l, paper
    Sec. 4.2.1 assumption ii -- ``cost_model.expected_level_counts``)
    times a safety factor, clamped to the exhaustive worst case (g r^l)^2.
    Level 0 is always exactly g^2 (every root is live). One capacity per
    level 0..tau, where tau = floor(log_r(n / (g B))) is the paper's
    subdivision depth (``cost_model.tau_levels`` / ``num_levels``).

    ``p_subdiv`` is the constant per-level subdivision probability P that
    also parameterises the paper's work model W_SSD^M (Eq. 20,
    ``cost_model.w_ssd_mandelbrot``): the same P that predicts the work
    reduction predicts the live-OLT footprint. The default P=0.7 matches
    the paper's Mandelbrot benchmark window; deep-zoom windows hug the
    set boundary and run effectively hotter -- ``core.planner`` sizes P
    per frame from zoom depth instead of using this one constant.
    """
    expected = expected_level_counts(n, g, r, B, P=p_subdiv)
    caps = []
    for lv, e in enumerate(expected):
        worst = (g * r ** lv) ** 2
        caps.append(max(1, min(int(math.ceil(e * safety_factor)), worst)))
    return tuple(caps)


def _resolve_capacities(problem: ASKProblem, capacities, p_subdiv,
                        safety_factor) -> Tuple[int, ...]:
    n, g, r, B = problem.n, problem.g, problem.r, problem.B
    levels = _num_levels(n, g, r, B)
    if capacities is None:
        caps = scan_capacities(n, g, r, B, p_subdiv=p_subdiv,
                               safety_factor=safety_factor)
    elif isinstance(capacities, int):
        caps = (max(1, capacities),) * (levels + 1)
    else:
        caps = tuple(max(1, int(c)) for c in capacities)
        if len(caps) != levels + 1:
            raise ValueError(
                f"need {levels + 1} capacities (levels 0..{levels}), "
                f"got {len(caps)}")
    return caps


def _build_scan_pipeline(problem: ASKProblem, caps: Sequence[int]):
    """One XLA program: lax.scan over levels, lax.switch to the
    shape-specialised level kernel, live OLT in a double-buffered ring.

    Returns ``pipeline(state, extra=None) -> (state, entering [levels],
    leaf_count, dropped)``. When ``extra`` is not None the problem must
    provide ``level_step_dyn`` / ``leaf_step_dyn`` taking the traced pytree
    (e.g. per-frame complex-plane bounds) -- that is the ``vmap`` axis of
    the batched front-end.
    """
    g, r = problem.g, problem.r
    levels = len(caps) - 1
    ring_width = max(caps)
    roots_n = g * g

    def pipeline(state, extra=None):
        def level_at(lv, state, coords, valid):
            if extra is None:
                return problem.level_step(state, coords, valid, level=lv)
            return problem.level_step_dyn(state, coords, valid, level=lv,
                                          extra=extra)

        def leaf_at(lv, state, coords, valid):
            if extra is None:
                return problem.leaf_step(state, coords, valid, level=lv)
            return problem.leaf_step_dyn(state, coords, valid, level=lv,
                                         extra=extra)

        # everything below that no inner stage claims is worklist
        # bookkeeping: the level scan's control, children (with their
        # compaction, ``ask.compact``), ring reads and writes
        with jax.named_scope("ask.subdivide"):
            roots = problem.root_coords()
            ring = olt_lib.ring_init(roots, roots_n, ring_width)
            parity = jnp.int32(0)
            count = jnp.int32(min(roots_n, caps[0]))
            dropped = jnp.int32(max(roots_n - caps[0], 0))

            def make_branch(lv):
                cap_in, cap_out = caps[lv], caps[lv + 1]

                def branch(carry):
                    state, ring, parity, count, dropped = carry
                    coords = olt_lib.ring_read(ring, parity, cap_in)
                    valid = jnp.arange(cap_in) < count
                    state, flags = level_at(lv, state, coords, valid)
                    flags = jnp.logical_and(flags, valid)
                    children, child_count = olt_lib.subdivide_olt(
                        coords, flags, r=r, capacity=cap_out)
                    dropped = dropped + jnp.maximum(child_count - cap_out, 0)
                    count = jnp.minimum(child_count, cap_out)
                    ring = olt_lib.ring_write(ring, parity, children)
                    return state, ring, jnp.int32(1) - parity, count, dropped

                return branch

            branches = [make_branch(lv) for lv in range(levels)]

            def scan_body(carry, lv):
                entering = carry[3]  # live count entering this level
                carry = jax.lax.switch(lv, branches, carry)
                return carry, entering

            carry = (state, ring, parity, count, dropped)
            if levels > 0:
                carry, entering = jax.lax.scan(
                    scan_body, carry, jnp.arange(levels, dtype=jnp.int32))
            else:
                entering = jnp.zeros((0,), jnp.int32)
            state, ring, parity, count, dropped = carry

            cap_leaf = caps[levels]
            coords = olt_lib.ring_read(ring, parity, cap_leaf)
            valid = jnp.arange(cap_leaf) < count
            state = leaf_at(levels, state, coords, valid)
        return state, entering, count, dropped

    return pipeline


# Jitted-pipeline cache: retracing on every call would reintroduce a
# host-side per-frame overhead -- the very lambda the engine removes.
# Keyed on (problem, caps, batched, mesh) when the problem is hashable
# (the Mandelbrot adapter is a frozen dataclass; Mesh is hashable);
# unhashable problems just rebuild. Bounded FIFO so a long-lived server
# can't grow it unboundedly. The problem's KernelPolicy (frozen, hashes
# with it) is therefore part of the key: the tuned kernel tier
# (kernels.autotune) rides on problem.policy and two problems that route
# kernels differently never share a compiled pipeline -- the tuning
# cache (autotune.TuningCache) is keyed by the same static arguments.
_PIPELINE_CACHE: dict = {}
_PIPELINE_CACHE_MAX = 128


def _jitted_pipeline(problem: ASKProblem, caps: Tuple[int, ...],
                     batched: bool, mesh=None):
    """Build (or fetch) the jitted scan pipeline.

    ``mesh`` (batched only) places the frame axis of the extras / canvas /
    ring carries on the mesh's single axis via ``NamedSharding``; the
    lax.scan level index (and the lax.switch it feeds) is unbatched, hence
    replicated -- every device runs the same per-level branch on its frame
    slice, no collectives.
    """
    try:
        key = (problem, caps, batched, mesh)
        cached = _PIPELINE_CACHE.get(key)
        if cached is not None:
            return cached
    except TypeError:  # unhashable problem: no caching
        key = None
    pipeline = _build_scan_pipeline(problem, caps)
    if batched:
        vm = jax.vmap(lambda extra: pipeline(problem.init_state(), extra))
        if mesh is None:
            fn = jax.jit(vm)
        else:
            from jax.sharding import NamedSharding, PartitionSpec

            frames = NamedSharding(mesh, PartitionSpec(_frames_axis(mesh)))
            fn = jax.jit(vm, in_shardings=frames,
                         out_shardings=(frames, frames, frames, frames))
    else:
        fn = jax.jit(pipeline)
    if key is not None:
        if len(_PIPELINE_CACHE) >= _PIPELINE_CACHE_MAX:
            _PIPELINE_CACHE.pop(next(iter(_PIPELINE_CACHE)))
        _PIPELINE_CACHE[key] = fn
    return fn


def run_ask_scan(
    problem: ASKProblem,
    *,
    capacities: Union[None, int, Sequence[int]] = None,
    p_subdiv: float = 0.7,
    safety_factor: float = 2.0,
    block_until_ready: bool = True,
) -> Tuple[Any, ASKStats]:
    """Single-dispatch streaming ASK: lax.scan over levels, bounded ring.

    The whole tau-level pipeline (tau from ``cost_model.tau_levels``, the
    paper's assumption iii) compiles to ONE XLA program; the live OLT is
    carried through a double-buffered ring whose per-level slices are
    sized from the cost model's expected occupancy E_l = g^2 (r^2 P)^l
    (``scan_capacities``; P = ``p_subdiv`` times ``safety_factor``) -- the
    same P that parameterises W_SSD^M (Eq. 20, ``cost_model.
    w_ssd_mandelbrot``). Ring memory is therefore O(2 x max_l E_l) rows
    (``ASKStats.ring_rows``) instead of the fused engine's worst case.

    ``capacities`` overrides the cost-model sizing: an int is a uniform
    per-level capacity (the overflow tests undersize it deliberately), a
    sequence gives one capacity per level 0..tau. Output is bit-identical
    to ``run_ask`` whenever nothing overflows (``stats.overflow_dropped ==
    0``); dropped regions leave their pixels at the init_state value.
    Rather than hand-tuning ``safety_factor`` when drops appear, see
    ``core.planner`` -- it re-plans overflowing frames automatically.
    """
    caps = _resolve_capacities(problem, capacities, p_subdiv, safety_factor)
    fn = _jitted_pipeline(problem, caps, batched=False)

    t0 = time.perf_counter()
    state, entering, leaf_count, dropped = fn(problem.init_state())
    if block_until_ready:
        state = jax.block_until_ready(state)

    counts = []
    for c in jax.device_get(entering).tolist():  # one transfer, not tau
        if c == 0:
            break
        counts.append(int(c))
    stats = ASKStats(
        levels=len(counts),
        kernel_launches=1,  # the whole level pipeline is one dispatch
        region_counts=tuple(counts),
        leaf_count=int(leaf_count),
        overflow_dropped=int(dropped),
        wall_s=time.perf_counter() - t0,
        olt_caps=tuple(caps),
    )
    return state, stats


def run_ask_scan_batch(
    problem: ASKProblem,
    extras: Any,
    *,
    capacities: Union[None, int, Sequence[int]] = None,
    p_subdiv: float = 0.7,
    safety_factor: float = 2.0,
    block_until_ready: bool = True,
) -> Tuple[Any, ASKStats]:
    """vmap the scan engine over a stack of per-frame parameters.

    ``extras`` is a pytree whose leading axis is the frame axis (for
    Mandelbrot: [F, 4] complex-plane bounds); the problem must implement
    ``level_step_dyn`` / ``leaf_step_dyn``. The whole batch is ONE XLA
    dispatch -- the lax.scan level index stays unbatched, so lax.switch
    executes exactly one shape-specialised branch per level for all
    frames.

    Returns (stacked states [F, ...], stats) where ``stats.region_counts``
    is a tuple of per-frame tuples and leaf/overflow counts are summed.
    """
    caps = _resolve_capacities(problem, capacities, p_subdiv, safety_factor)
    batched = _jitted_pipeline(problem, caps, batched=True)

    t0 = time.perf_counter()
    states, entering, leaf_counts, dropped = batched(extras)
    if block_until_ready:
        states = jax.block_until_ready(states)

    per_frame = _per_frame_counts(jax.device_get(entering))
    leaf_host = [int(c) for c in jax.device_get(leaf_counts)]
    drop_host = [int(d) for d in jax.device_get(dropped)]
    stats = ASKStats(
        levels=max((len(c) for c in per_frame), default=0),  # executed
        kernel_launches=1,  # one dispatch serves the whole frame batch
        region_counts=per_frame,
        leaf_count=sum(leaf_host),
        overflow_dropped=sum(drop_host),
        wall_s=time.perf_counter() - t0,
        olt_caps=tuple(caps),
        frame_overflow=tuple(drop_host),
        frame_leaf_counts=tuple(leaf_host),
    )
    return states, stats


def _per_frame_counts(entering) -> tuple:
    """[F, levels] entering-count matrix -> per-frame region_counts tuples
    (trailing zero levels trimmed, as in the single-frame engine)."""
    per_frame = []
    for row in entering:
        counts = []
        for c in row.tolist():
            if c == 0:
                break
            counts.append(int(c))
        per_frame.append(tuple(counts))
    return tuple(per_frame)


# ---------------------------------------------------------------------------
# run_ask_scan_sharded: the batched engine spread over a device mesh
# ---------------------------------------------------------------------------

def _frame_count(extras) -> int:
    """Size of the leading (frame) axis, validated across all leaves."""
    leaves = jax.tree_util.tree_leaves(extras)
    if not leaves:
        raise ValueError("extras must contain at least one array leaf")
    sizes = {int(leaf.shape[0]) for leaf in leaves}
    if len(sizes) != 1:
        raise ValueError(f"inconsistent frame-axis sizes across extras leaves: {sorted(sizes)}")
    return sizes.pop()


def pad_frames(extras, multiple: int):
    """Pad the frame axis of ``extras`` up to the next multiple of ``multiple``.

    Padding rows repeat frame 0 (valid parameters, so the padded frames
    trace the same compute); callers mask them out of any reduction --
    ``run_ask_scan_sharded`` slices its outputs back to the true frame
    count before summing leaf/overflow stats. Returns (padded, F).
    """
    if multiple < 1:
        raise ValueError(f"multiple must be >= 1, got {multiple}")
    F = _frame_count(extras)
    pad = (-F) % multiple

    def _pad(leaf):
        leaf = jnp.asarray(leaf)
        if pad == 0:
            return leaf
        fill = jnp.broadcast_to(leaf[:1], (pad,) + leaf.shape[1:])
        return jnp.concatenate([leaf, fill], axis=0)

    return jax.tree_util.tree_map(_pad, extras), F


def _frames_axis(mesh) -> str:
    if len(mesh.axis_names) != 1:
        raise ValueError(
            "run_ask_scan_sharded needs a 1-D frames mesh "
            f"(e.g. launch.mesh.make_frames_mesh()), got axes {mesh.axis_names}")
    return mesh.axis_names[0]


@dataclasses.dataclass
class ShardedDispatch:
    """An in-flight sharded batch: enqueued on the devices, not yet
    materialised on the host.

    JAX dispatch is asynchronous -- ``dispatch_ask_scan_sharded`` returns
    as soon as the XLA call is enqueued, holding device arrays here. The
    async render service (``launch.render_service``, ``pipeline_depth >=
    2``) exploits exactly this: it enqueues chunk k+1 and only then calls
    ``finalize()`` on chunk k, so the host-side transfer of k overlaps the
    device compute of k+1. That holds because ``finalize`` queues no
    device work on a full-width batch, here or in the pooled engine's
    ``core.pooled.PooledDispatch``: the canvases are the program's own
    output, ready when k's program ends, not behind k+1. ``finalize``
    blocks, applies the pad-masking, and returns the same ``(states,
    ASKStats)`` the synchronous entry point does.
    """

    states: Any  # padded [F_pad, ...] device arrays
    entering: Any  # [F_pad, levels] live counts entering each level
    leaf_counts: Any  # [F_pad]
    dropped: Any  # [F_pad]
    frames: int  # true F before padding
    multiple: int  # padding multiple the batch was rounded up to
    caps: Tuple[int, ...]
    t0: float  # perf_counter at enqueue (finalize stamps wall_s from it)
    program: Any  # the jitted program it was enqueued on (host-side)

    def wait(self) -> None:
        """Block until the batch's device work has ended."""
        jax.block_until_ready(self.states)

    def finalize(self, *, block_until_ready: bool = True) -> Tuple[Any, ASKStats]:
        """Block on the in-flight program and assemble ``(states, stats)``.

        Idempotent-by-construction is NOT promised: call once per
        dispatch. Stats transfers (``entering``/``leaf``/``dropped``) force
        a device sync regardless of ``block_until_ready``, which only
        gates the explicit wait on the canvases (``wait``).
        """
        states = self.states
        if block_until_ready:
            self.wait()
        F = self.frames
        # per-device stats come back frame-sharded; gather once, then mask
        # the padded tail out of every reduction (divisible batches skip
        # the slice)
        entering = jax.device_get(self.entering)[:F]
        leaf_counts = jax.device_get(self.leaf_counts)[:F]
        dropped = jax.device_get(self.dropped)[:F]
        if F % self.multiple:
            states = jax.tree_util.tree_map(lambda x: x[:F], states)

        per_frame = _per_frame_counts(entering)
        leaf_host = [int(c) for c in leaf_counts]
        drop_host = [int(d) for d in dropped]
        stats = ASKStats(
            levels=max((len(c) for c in per_frame), default=0),
            kernel_launches=1,  # one GSPMD program serves all devices' frames
            region_counts=per_frame,
            leaf_count=sum(leaf_host),
            overflow_dropped=sum(drop_host),
            wall_s=time.perf_counter() - self.t0,
            olt_caps=tuple(self.caps),
            frame_overflow=tuple(drop_host),
            frame_leaf_counts=tuple(leaf_host),
        )
        return states, stats


def dispatch_ask_scan_sharded(
    problem: ASKProblem,
    extras: Any,
    *,
    mesh,
    capacities: Union[None, int, Sequence[int]] = None,
    p_subdiv: float = 0.7,
    safety_factor: float = 2.0,
    pad_to: Union[int, None] = None,
) -> ShardedDispatch:
    """Enqueue one sharded batch WITHOUT blocking on the result.

    The async half of ``run_ask_scan_sharded``: pads, fetches the compiled
    pipeline from the cache, issues the XLA call, and returns a
    ``ShardedDispatch`` handle immediately (JAX async dispatch -- the
    devices compute in the background). Call ``.finalize()`` to collect
    ``(states, ASKStats)``. The pipelined render service keeps a bounded
    queue of these handles in flight.
    """
    caps = _resolve_capacities(problem, capacities, p_subdiv, safety_factor)
    n_dev = int(mesh.devices.size)
    multiple = n_dev if pad_to is None else int(pad_to)
    if multiple % n_dev:
        raise ValueError(
            f"pad_to={multiple} must be a multiple of the mesh device count {n_dev}")
    padded, F = pad_frames(extras, multiple)
    fn = _jitted_pipeline(problem, caps, batched=True, mesh=mesh)

    t0 = time.perf_counter()
    states, entering, leaf_counts, dropped = fn(padded)
    return ShardedDispatch(states=states, entering=entering,
                           leaf_counts=leaf_counts, dropped=dropped,
                           frames=F, multiple=multiple, caps=tuple(caps),
                           t0=t0, program=fn)


def run_ask_scan_sharded(
    problem: ASKProblem,
    extras: Any,
    *,
    mesh,
    capacities: Union[None, int, Sequence[int]] = None,
    p_subdiv: float = 0.7,
    safety_factor: float = 2.0,
    pad_to: Union[int, None] = None,
    block_until_ready: bool = True,
) -> Tuple[Any, ASKStats]:
    """``run_ask_scan_batch`` with the frame axis sharded over ``mesh``.

    ``mesh`` is a 1-D ``jax.sharding.Mesh`` (conventionally axis
    ``"frames"``; see ``launch.mesh.make_frames_mesh``). The frame batch is
    padded up to a multiple of the device count (``pad_to`` overrides the
    padding multiple -- the render service pins it to the chunk size so
    every chunk, ragged tail included, reuses ONE compiled program). Padded
    frames repeat frame 0 and are masked out of the returned canvases and
    the leaf/overflow sums, so results are bit-identical to the unsharded
    batch at any F. Still ONE dispatch: the whole sharded batch is a
    single GSPMD-partitioned XLA program.

    This is the synchronous wrapper over ``dispatch_ask_scan_sharded`` +
    ``ShardedDispatch.finalize``; async callers use those two halves
    directly to overlap host I/O with the next dispatch.
    """
    d = dispatch_ask_scan_sharded(
        problem, extras, mesh=mesh, capacities=capacities,
        p_subdiv=p_subdiv, safety_factor=safety_factor, pad_to=pad_to)
    return d.finalize(block_until_ready=block_until_ready)
