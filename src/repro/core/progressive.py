"""Split ASK scan: a cheap coarse preview early, the exact canvas after.

``run_ask_scan`` compiles the whole tau-level subdivision ladder into
ONE XLA program. The progressive tier splits that program at a
*checkpoint level* k into two jitted halves that share ``core.ask``'s
per-level branch math verbatim:

* the **coarse** half scans levels [0, k) -- homogeneous regions are
  constant-filled exactly as the full program would fill them -- then
  paints every region still live at level k with a cheap per-region
  representative (``FrameProblem.preview_step``: one perimeter query +
  constant fill, NO per-pixel interior dwell), yielding a full-coverage
  preview canvas;
* the **refine** half resumes the scan from the carried OLT ring --
  ``(state, ring, parity, count, dropped)``, the same carry the full
  program threads through ``lax.scan`` -- over levels [k, tau) plus the
  true leaf pass, on the UNPAINTED state. The refined canvas is
  bit-identical to a single-program ``run_ask_scan`` render at the same
  capacities: splitting a scan at an iteration boundary does not change
  a single operation.

The carry stays on device between the halves, so ``refine()`` enqueues
the second program without a host sync (JAX async dispatch). A caller
pipelining tile batches -- ``launch.tiles.TileService`` -- therefore
overlaps the refinement of batch k with the coarse pass of batch k+1,
the pipeline-DP overlap (arXiv 2008.01938) on top of AlSub-style
modular subdivision (arXiv 1809.06047).
"""

from __future__ import annotations

import time
from typing import Any, Sequence, Tuple, Union

import jax
import jax.numpy as jnp

from repro.core import olt as olt_lib
from repro.core.ask import ASKStats, _per_frame_counts, _resolve_capacities

__all__ = ["CoarseDispatch", "RefineDispatch", "checkpoint_for",
           "dispatch_progressive", "dispatch_progressive_batch",
           "run_ask_scan_progressive"]


def checkpoint_for(problem, checkpoint_level: Union[int, None]) -> int:
    """Clamp a requested checkpoint level into [0, tau].

    ``None`` means the default coarse split: after level 1 (the paper's
    level-0/1 preview) when the ladder is that deep, else after
    everything there is.
    """
    from repro.core.cost_model import num_levels

    levels = num_levels(problem.n, problem.g, problem.r, problem.B)
    if checkpoint_level is None:
        return min(1, levels)
    k = int(checkpoint_level)
    if k < 0:
        raise ValueError(f"checkpoint_level must be >= 0, got {k}")
    return min(k, levels)


def _branches(problem, caps: Sequence[int], lo: int, hi: int, extra, r: int):
    """The per-level scan branches for absolute levels [lo, hi) -- the
    same closure body ``core.ask._build_scan_pipeline`` builds, so both
    halves execute identical operations to the full program."""
    out = []
    for lv in range(lo, hi):
        cap_in, cap_out = caps[lv], caps[lv + 1]

        def branch(carry, lv=lv, cap_in=cap_in, cap_out=cap_out):
            state, ring, parity, count, dropped = carry
            coords = olt_lib.ring_read(ring, parity, cap_in)
            valid = jnp.arange(cap_in) < count
            if extra is None:
                state, flags = problem.level_step(state, coords, valid,
                                                  level=lv)
            else:
                state, flags = problem.level_step_dyn(state, coords, valid,
                                                      level=lv, extra=extra)
            flags = jnp.logical_and(flags, valid)
            children, child_count = olt_lib.subdivide_olt(
                coords, flags, r=r, capacity=cap_out)
            dropped = dropped + jnp.maximum(child_count - cap_out, 0)
            count = jnp.minimum(child_count, cap_out)
            ring = olt_lib.ring_write(ring, parity, children)
            return state, ring, jnp.int32(1) - parity, count, dropped

        out.append(branch)
    return out


def _scan_levels(problem, caps, lo, hi, carry, extra):
    """Run absolute levels [lo, hi) from ``carry``; returns (carry,
    entering [hi-lo]) exactly as the full program's scan segment would."""
    branches = _branches(problem, caps, lo, hi, extra, problem.r)

    def scan_body(carry, i):
        entering = carry[3]  # live count entering this level
        carry = jax.lax.switch(i, branches, carry)
        return carry, entering

    if hi > lo:
        # worklist bookkeeping, as in core.ask's scan; the stages inside
        # carry their own scopes
        with jax.named_scope("ask.subdivide"):
            return jax.lax.scan(scan_body, carry,
                                jnp.arange(hi - lo, dtype=jnp.int32))
    return carry, jnp.zeros((0,), jnp.int32)


def _build_split_pipelines(problem, caps: Sequence[int], checkpoint: int):
    """Two pipelines whose composition is ``_build_scan_pipeline``'s one.

    ``coarse(state, extra) -> (preview, carry, entering_a)`` runs levels
    [0, k) and paints the level-k live set for the preview (the carried
    state stays unpainted); ``refine(carry, extra) -> (state,
    entering_b, leaf_count, dropped)`` runs levels [k, tau) + the leaf
    pass.
    """
    g = problem.g
    levels = len(caps) - 1
    k = checkpoint
    ring_width = max(caps)
    roots_n = g * g

    def coarse(state, extra=None):
        roots = problem.root_coords()
        ring = olt_lib.ring_init(roots, roots_n, ring_width)
        carry = (state, ring, jnp.int32(0),
                 jnp.int32(min(roots_n, caps[0])),
                 jnp.int32(max(roots_n - caps[0], 0)))
        carry, entering = _scan_levels(problem, caps, 0, k, carry, extra)
        state, ring, parity, count, dropped = carry
        coords = olt_lib.ring_read(ring, parity, caps[k])
        valid = jnp.arange(caps[k]) < count
        if extra is None and hasattr(problem, "preview_step"):
            preview = problem.preview_step(state, coords, valid, level=k)
        elif extra is not None and hasattr(problem, "preview_step_dyn"):
            preview = problem.preview_step_dyn(state, coords, valid,
                                               level=k, extra=extra)
        else:  # no preview hook: the partially-filled canvas IS the preview
            preview = state
        return preview, (state, ring, parity, count, dropped), entering

    def refine(carry, extra=None):
        carry, entering = _scan_levels(problem, caps, k, levels, carry, extra)
        state, ring, parity, count, dropped = carry
        cap_leaf = caps[levels]
        coords = olt_lib.ring_read(ring, parity, cap_leaf)
        valid = jnp.arange(cap_leaf) < count
        if extra is None:
            state = problem.leaf_step(state, coords, valid, level=levels)
        else:
            state = problem.leaf_step_dyn(state, coords, valid, level=levels,
                                          extra=extra)
        return state, entering, count, dropped

    return coarse, refine


# Same discipline as core.ask._PIPELINE_CACHE: retracing per call would
# reintroduce the host-side overhead the one-dispatch engine removes.
# Keyed on (problem, caps, checkpoint, batched); bounded FIFO.
_SPLIT_CACHE: dict = {}
_SPLIT_CACHE_MAX = 64


def _jitted_split(problem, caps: Tuple[int, ...], checkpoint: int,
                  batched: bool):
    try:
        key = (problem, caps, checkpoint, batched)
        cached = _SPLIT_CACHE.get(key)
        if cached is not None:
            return cached
    except TypeError:  # unhashable problem: no caching
        key = None
    coarse, refine = _build_split_pipelines(problem, caps, checkpoint)
    if batched:
        fns = (jax.jit(jax.vmap(
                   lambda extra: coarse(problem.init_state(), extra))),
               jax.jit(jax.vmap(refine)))
    else:
        fns = (jax.jit(coarse), jax.jit(refine))
    if key is not None:
        if len(_SPLIT_CACHE) >= _SPLIT_CACHE_MAX:
            _SPLIT_CACHE.pop(next(iter(_SPLIT_CACHE)))
        _SPLIT_CACHE[key] = fns
    return fns


class RefineDispatch:
    """The in-flight refine half. ``finalize()`` blocks and returns
    ``(state(s), ASKStats)`` -- the stats stitched across both halves
    (``kernel_launches == 2``: the price of the early preview)."""

    def __init__(self, problem, caps, out, entering_a, frames, t0):
        self._problem = problem
        self._caps = caps
        self._out = out  # (state, entering_b, leaf_count, dropped)
        self._entering_a = entering_a
        self._frames = frames  # None: single-frame
        self._t0 = t0
        self._done = False

    def finalize(self, *, block_until_ready: bool = True):
        if self._done:
            raise RuntimeError("RefineDispatch.finalize() is one-shot")
        self._done = True
        state, entering_b, leaf_count, dropped = self._out
        if block_until_ready:
            state = jax.block_until_ready(state)
        ent_a = jax.device_get(self._entering_a)
        ent_b = jax.device_get(entering_b)
        caps = tuple(self._caps)
        if self._frames is None:
            counts = []
            for c in list(ent_a.tolist()) + list(ent_b.tolist()):
                if c == 0:
                    break
                counts.append(int(c))
            stats = ASKStats(
                levels=len(counts),
                kernel_launches=2,  # coarse + refine
                region_counts=tuple(counts),
                leaf_count=int(leaf_count),
                overflow_dropped=int(dropped),
                wall_s=time.perf_counter() - self._t0,
                olt_caps=caps,
            )
            return state, stats
        import numpy as np

        entering = np.concatenate([np.asarray(ent_a), np.asarray(ent_b)],
                                  axis=1)
        per_frame = _per_frame_counts(entering)
        leaf_host = [int(c) for c in jax.device_get(leaf_count)]
        drop_host = [int(d) for d in jax.device_get(dropped)]
        stats = ASKStats(
            levels=max((len(c) for c in per_frame), default=0),
            kernel_launches=2,
            region_counts=per_frame,
            leaf_count=sum(leaf_host),
            overflow_dropped=sum(drop_host),
            wall_s=time.perf_counter() - self._t0,
            olt_caps=caps,
            frame_overflow=tuple(drop_host),
            frame_leaf_counts=tuple(leaf_host),
        )
        return state, stats


class CoarseDispatch:
    """The in-flight coarse half.

    ``preview()`` blocks only on the preview canvas; ``refine()``
    enqueues the second half on the device-resident carry WITHOUT a host
    sync -- call it before ``preview()`` to overlap the refinement with
    whatever the preview is streamed to.
    """

    def __init__(self, problem, caps, checkpoint, preview, carry,
                 entering, extras, frames, t0):
        self._problem = problem
        self._caps = caps
        self._checkpoint = checkpoint
        self._preview = preview
        self._carry = carry
        self._entering = entering
        self._extras = extras
        self._frames = frames  # None: single-frame
        self._t0 = t0
        self._refined = False

    @property
    def checkpoint(self) -> int:
        return self._checkpoint

    def preview(self, *, block_until_ready: bool = True):
        """The coarse canvas(es): every pixel painted, live regions at
        the checkpoint level carrying their cheap representative."""
        if block_until_ready:
            return jax.block_until_ready(self._preview)
        return self._preview

    def refine(self) -> RefineDispatch:
        """Enqueue the exact-refinement half (one-shot, non-blocking)."""
        if self._refined:
            raise RuntimeError("CoarseDispatch.refine() is one-shot")
        self._refined = True
        _, fn = _jitted_split(self._problem, self._caps, self._checkpoint,
                              batched=self._frames is not None)
        if self._frames is None:
            out = fn(self._carry)
        else:
            out = fn(self._carry, self._extras)
        return RefineDispatch(self._problem, self._caps, out,
                              self._entering, self._frames, self._t0)


def dispatch_progressive(
    problem,
    *,
    checkpoint_level: Union[int, None] = None,
    capacities: Union[None, int, Sequence[int]] = None,
    p_subdiv: float = 0.7,
    safety_factor: float = 2.0,
) -> CoarseDispatch:
    """Enqueue the coarse half of one frame (non-blocking)."""
    caps = _resolve_capacities(problem, capacities, p_subdiv, safety_factor)
    k = checkpoint_for(problem, checkpoint_level)
    coarse, _ = _jitted_split(problem, caps, k, batched=False)
    t0 = time.perf_counter()
    preview, carry, entering = coarse(problem.init_state())
    return CoarseDispatch(problem, caps, k, preview, carry, entering,
                          extras=None, frames=None, t0=t0)


def dispatch_progressive_batch(
    problem,
    extras,
    *,
    checkpoint_level: Union[int, None] = None,
    capacities: Union[None, int, Sequence[int]] = None,
    p_subdiv: float = 0.7,
    safety_factor: float = 2.0,
) -> CoarseDispatch:
    """Enqueue the coarse half of a frame batch (non-blocking).

    ``extras`` is the [F, 4] per-frame bounds array of the vmapped
    engine (``run_ask_scan_batch``); the batch is ONE dispatch per half.
    """
    extras = jnp.asarray(extras)
    frames = int(extras.shape[0])
    caps = _resolve_capacities(problem, capacities, p_subdiv, safety_factor)
    k = checkpoint_for(problem, checkpoint_level)
    coarse, _ = _jitted_split(problem, caps, k, batched=True)
    t0 = time.perf_counter()
    preview, carry, entering = coarse(extras)
    return CoarseDispatch(problem, caps, k, preview, carry, entering,
                          extras=extras, frames=frames, t0=t0)


def run_ask_scan_progressive(
    problem,
    *,
    checkpoint_level: Union[int, None] = None,
    capacities: Union[None, int, Sequence[int]] = None,
    p_subdiv: float = 0.7,
    safety_factor: float = 2.0,
    block_until_ready: bool = True,
) -> Tuple[Any, Any, ASKStats]:
    """Synchronous progressive render: ``(preview, state, stats)``.

    ``state`` is bit-identical to ``run_ask_scan`` at the same
    capacities; ``preview`` is the cheap coarse canvas the split served
    early. ``stats.kernel_launches == 2``.
    """
    d = dispatch_progressive(problem, checkpoint_level=checkpoint_level,
                             capacities=capacities, p_subdiv=p_subdiv,
                             safety_factor=safety_factor)
    r = d.refine()  # enqueue the exact half behind the preview transfer
    preview = d.preview(block_until_ready=block_until_ready)
    state, stats = r.finalize(block_until_ready=block_until_ready)
    return preview, state, stats
