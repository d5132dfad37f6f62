"""Public wrappers routing each kernel to its jnp or Pallas lowering.

Routing is governed by ONE object: :class:`repro.kernels.policy.KernelPolicy`
(``policy=`` on every entry point). Its backend rungs:

  ``jnp``    -- the pure-jnp oracles from ref.py, fused by XLA: the
                default rung, and the one that runs on the TPU (the XLA
                pipelines compile for v5e at the served sizes).
  ``pallas`` -- pl.pallas_call; interpret=True off the TPU, where every
                kernel is validated against its oracle
                (tests/test_kernels.py). Most bodies do not lower for the
                TPU yet at the paper's region sides (their (side, side)
                blocks break the (8, 128) tiling; Mosaic has no cumsum),
                so nothing routes here unless a policy names it.
  ``tuned``  -- per-dispatch choice from the autotune harness
                (``kernels.autotune``): JSON tuning-cache winners when the
                policy names a cache file, the jnp heuristics when cold.
                The choice (impl + block/unroll schedule params) is made
                at trace time from static arguments only.

Schedule-parameter precedence (lowest to highest): explicit kwarg
(``block=``) < tuned choice < ``policy.overrides``.

The legacy per-call ``backend="pallas"|"jnp"`` string kwarg still works via
a deprecation shim (``policy.resolve_policy``) -- pass ``policy=`` instead.

All entry points take/return plain arrays so both ASK and the DP baseline
drive the exact same compute.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.kernels import autotune, ref
from repro.kernels.mandelbrot_dwell import mandelbrot_dwell as _mandelbrot_pallas
from repro.kernels.olt_compact import compact_ranks_blocked, compact_ranks_kernel
from repro.kernels.perimeter_query import perimeter_query as _perimeter_pallas
from repro.kernels.policy import (Backend, DEFAULT_POLICY, KernelPolicy,
                                  resolve_policy)
from repro.kernels.region_dwell import region_dwell as _region_dwell_pallas
from repro.kernels.region_dwell_pooled import (
    region_dwell_pooled as _region_dwell_pooled_pallas)
from repro.kernels.region_fill import region_fill as _region_fill_pallas
from repro.kernels.region_fill_pooled import (
    region_fill_pooled as _region_fill_pooled_pallas)

_OLT_KERNEL_CAP = 1 << 16  # single-VMEM-block bound (see olt_compact.py)


def _grid_workload(workload) -> bool:
    """Grid workloads (per-point value = gather from a generated field)
    always run the jnp oracle path: the field lives in device memory as
    a gathered constant, which the scalar-prefetch Pallas bodies do not
    stage through VMEM. Escape-time workloads (pure arithmetic) flow
    into the Pallas kernel bodies unchanged."""
    return workload is not None and getattr(workload, "kind", "") == "grid"


def _route(pol: KernelPolicy, kernel: str, *, workload=None, **sig):
    """Trace-time routing: -> (impl, schedule-params dict).

    ``sig`` is the kernel's static shape signature (the tuning-cache key
    fields, see ``autotune.cache_key``). Overrides from the policy are
    applied last so they beat both heuristics and cache entries.
    """
    if _grid_workload(workload):
        return "jnp", dict(pol.override_for(kernel))
    if pol.backend is Backend.JNP:
        impl, params = "jnp", {}
    elif pol.backend is Backend.PALLAS:
        impl, params = "pallas", {}
    else:  # Backend.TUNED
        choice = autotune.choose(kernel, workload=workload,
                                 cache=pol.tuning_cache, **sig)
        impl, params = choice.impl, choice.param_dict()
    params.update(pol.override_for(kernel))
    return impl, params


def mandelbrot(n, *, bounds=ref.DEFAULT_BOUNDS, max_dwell=512,
               block=(256, 256), backend=None, policy=None, workload=None):
    """Exhaustive n x n value image (the paper's Ex baseline; named for
    the seed workload, ``workload=`` makes it serve any)."""
    pol = resolve_policy(backend, policy)
    impl, params = _route(pol, "dwell", workload=workload,
                          n=n, max_dwell=max_dwell)
    unroll = int(params.get("unroll", 1))
    if impl == "jnp":
        return ref.mandelbrot_ref(n, bounds, max_dwell, workload=workload,
                                  unroll=unroll)
    blk = tuple(params.get("block", block))
    blk = (min(blk[0], n), min(blk[1], n))
    return _mandelbrot_pallas(n, bounds, max_dwell, blk,
                              pol.resolve_interpret(), workload=workload,
                              unroll=unroll)


def _bounds_traced(bounds) -> bool:
    """Per-frame bounds arrive as a traced [4] array from the batched
    serving path (workloads.solve_batch); static tuples stay jit-static."""
    return isinstance(bounds, jax.Array)


def perimeter_query(coords, *, side, n, bounds=ref.DEFAULT_BOUNDS,
                    max_dwell=512, backend=None, policy=None, workload=None):
    """Border query Q: (homog [N] bool, common [N] int32)."""
    pol = resolve_policy(backend, policy)
    impl, params = _route(pol, "perimeter_query", workload=workload,
                          side=side, n=n, max_dwell=max_dwell)
    unroll = int(params.get("unroll", 1))
    if _bounds_traced(bounds):
        # batched serving: bounds vary per frame, so only the jnp lowering
        # applies -- the tuned tier still contributes its unroll schedule.
        return ref.perimeter_query_dyn(
            coords, side=side, n=n, bounds=bounds, max_dwell=max_dwell,
            workload=workload, unroll=unroll)
    if impl == "jnp":
        return ref.perimeter_query_ref(
            coords, side=side, n=n, bounds=bounds, max_dwell=max_dwell,
            workload=workload, unroll=unroll)
    return _perimeter_pallas(
        coords, side=side, n=n, bounds=bounds, max_dwell=max_dwell,
        interpret=pol.resolve_interpret(), workload=workload, unroll=unroll)


def region_fill(canvas, coords, values, nonempty, *, side, n,
                scheme="sbr", tile=256, backend=None, policy=None):
    """Terminal work T: constant-fill the (duplicate-padded) fill-OLT."""
    pol = resolve_policy(backend, policy)
    impl, params = _route(pol, "region_fill", side=side, n=n)
    # tuned tile choices / policy.overrides must reach the lowering: the
    # MBR block edge comes from the schedule params when present
    tile = int(params.get("tile", tile))
    scheme = params.get("scheme", scheme)
    if impl == "jnp":
        return _fill_blocks(canvas, coords[:, 0], coords[:, 1], values,
                            nonempty, side=side)
    return _region_fill_pallas(
        canvas, coords, values, nonempty, side=side, n=n, scheme=scheme,
        tile=tile, interpret=pol.resolve_interpret())


def region_dwell(canvas, coords, nonempty, *, side, n,
                 bounds=ref.DEFAULT_BOUNDS, max_dwell=512, scheme="sbr",
                 tile=256, backend=None, policy=None, workload=None):
    """Last-level work A: interior values of the (duplicate-padded) leaf-OLT."""
    pol = resolve_policy(backend, policy)
    impl, params = _route(pol, "region_dwell", workload=workload,
                          side=side, n=n, max_dwell=max_dwell)
    unroll = int(params.get("unroll", 1))
    if impl == "jnp" or _bounds_traced(bounds):
        interior = (ref.region_interior_dyn if _bounds_traced(bounds)
                    else ref.region_interior_ref)
        tiles = interior(
            coords, side=side, n=n, bounds=bounds, max_dwell=max_dwell,
            workload=workload, unroll=unroll)
        return _scatter_tiles(canvas, coords[:, 0] * side,
                              coords[:, 1] * side, tiles,
                              nonempty.reshape(()) > 0)
    return _region_dwell_pallas(
        canvas, coords, nonempty, side=side, n=n, bounds=bounds,
        max_dwell=max_dwell, scheme=scheme, tile=tile,
        interpret=pol.resolve_interpret(), workload=workload, unroll=unroll)


def pooled_bounds(bounds_all, rows):
    """Per-row plane windows for a pooled frame-tagged worklist.

    ``bounds_all`` [F, 4] per-frame bounds; ``rows`` [N, 3] = (frame, cy,
    cx). Returns a [4, N, 1, 1] array that unpacks along axis 0 exactly
    like the scalar/[4] bounds the ref-kernel math destructures -- each
    component broadcasts against the per-row coordinate planes, so every
    row is evaluated in its OWN frame's window with the identical
    elementwise f32 op order as the per-frame traced-bounds path."""
    return jnp.moveaxis(bounds_all[rows[:, 0]], -1, 0)[:, :, None, None]


def _scatter_tiles(canvas, y0, x0, tiles, keep):
    """Write [N, side, side] ``tiles`` with their top-left pixels at
    (y0, x0): ONE scatter whose update windows are whole tiles, so the
    index array is [N, 2] rather than one index per pixel (at n = 4096
    the per-pixel form took the TPU compiler ~35 s per call site).
    Where ``keep`` (a bool scalar, or one a row) is false the origin is
    pushed past the canvas, and out-of-range windows are dropped whole.
    Duplicate padding rows rewrite identical values, so the result is
    order-independent."""
    y0 = jnp.where(keep, y0, canvas.shape[0])
    dnums = jax.lax.ScatterDimensionNumbers(
        update_window_dims=(1, 2), inserted_window_dims=(),
        scatter_dims_to_operand_dims=(0, 1))
    return jax.lax.scatter(
        canvas, jnp.stack([y0, x0], axis=-1).astype(jnp.int32),
        tiles.astype(canvas.dtype), dnums,
        mode=jax.lax.GatherScatterMode.FILL_OR_DROP)


def _fill_blocks(canvas, by, bx, values, nonempty, *, side):
    """Constant-fill the side x side blocks at block-grid positions
    (by, bx) with ``values``: the values scatter into a one-cell-per-block
    grid, which then paints the canvas in ONE elementwise select. (As a
    windowed scatter, the TPU compiler lowered the fill to a loop over
    regions that copied the whole canvas twice per region: 25 s of a
    31 s n = 4096, F = 8 pooled chunk on a v5e.) An empty OLT
    (``nonempty == 0``) drops every row; duplicate padding rows rewrite
    identical values."""
    gh, gw = canvas.shape[0] // side, canvas.shape[1] // side
    by = jnp.where(nonempty.reshape(()) > 0, by, gh)
    hit = jnp.zeros((gh, gw), jnp.bool_).at[by, bx].set(True, mode="drop")
    val = jnp.zeros((gh, gw), canvas.dtype).at[by, bx].set(
        values.astype(canvas.dtype), mode="drop")
    blocks = canvas.reshape(gh, side, gw, side)
    return jnp.where(hit[:, None, :, None], val[:, None, :, None],
                     blocks).reshape(canvas.shape)


def _pooled_fill(canvas, rows, values, nonempty, *, side, n):
    """``_fill_blocks`` on the tall pooled canvas [F*n, n]: frame f's
    block rows start at f * (n // side)."""
    return _fill_blocks(canvas, rows[:, 0] * (n // side) + rows[:, 1],
                        rows[:, 2], values, nonempty, side=side)


def _pooled_scatter(canvas, rows, tiles, count, *, side, n):
    """Scatter per-row [side, side] tiles onto the tall pooled canvas
    [F*n, n] at row offset frame*n -- frames are disjoint bands, so ONE
    scatter serves the whole pool, as in the per-frame jnp lowering.
    Rows at or past ``count`` are dropped, one by one: their tiles hold
    nothing, and their origins may repeat a live row's."""
    live = jnp.arange(rows.shape[0]) < count
    return _scatter_tiles(canvas, rows[:, 0] * n + rows[:, 1] * side,
                          rows[:, 2] * side, tiles, live)


# Pixels leaf dwell A evaluates in one block of the pooled block loop: at
# side 32, 2048 ring rows, whose escape-loop planes (3 x 8 MiB) the TPU
# compiler keeps in VMEM. On a v5e the n = 4096, 4-frame chunk program
# ran 0.888 s with 2048-row blocks, 0.905 s with 4096 and 0.909 s with
# 8192 (2.162 s over the whole 65,536-row ring)
DWELL_BLOCK_PIXELS = 1 << 21


def dwell_block_rows(side: int, cap: int) -> int:
    """Ring rows a block of the pooled jnp dwell: the largest power of
    two whose tiles hold at most ``DWELL_BLOCK_PIXELS`` pixels (at least
    one row), clamped to the ring's ``cap`` rows."""
    per = max(1, DWELL_BLOCK_PIXELS // (side * side))
    return max(1, min(1 << (per.bit_length() - 1), cap))


def _pooled_dwell_blocks(canvas, rows, count, *, side, n, bounds_all,
                         max_dwell, workload, unroll):
    """The jnp pooled A over the live rows only: a loop over the
    ``ceil(count / R)`` blocks of ``R = dwell_block_rows(side, cap)``
    rows that hold live rows, each block's tiles written into a
    [cap, side, side] buffer, then one scatter of the live rows. Each
    pixel sees the ops ``region_interior_dyn`` gives it over the whole
    ring, so the canvas is bit-identical. Where ``R`` does not divide
    ``cap``, the last block's slice is clamped to end at ``cap``
    (``dynamic_slice``'s rule) and rewrites rows the block before it
    wrote, with the same values. Returns the canvas and the ring rows A
    ran, ``ceil(count / R) * R`` (int32)."""
    cap = rows.shape[0]
    R = dwell_block_rows(side, cap)
    count = jnp.asarray(count, jnp.int32).reshape(())
    blocks = (count + (R - 1)) // R

    def block(b, tiles):
        start = b * R
        blk = jax.lax.dynamic_slice_in_dim(rows, start, R)
        vals = ref.region_interior_dyn(
            blk[:, 1:], side=side, n=n, bounds=pooled_bounds(bounds_all, blk),
            max_dwell=max_dwell, workload=workload, unroll=unroll)
        return jax.lax.dynamic_update_slice_in_dim(
            tiles, vals.astype(canvas.dtype), start, axis=0)

    tiles = jax.lax.fori_loop(
        0, blocks, block, jnp.zeros((cap, side, side), canvas.dtype))
    return (_pooled_scatter(canvas, rows, tiles, count, side=side, n=n),
            blocks * R)


def region_fill_pooled(canvas, rows, values, nonempty, *, side, n,
                       backend=None, policy=None):
    """Pooled terminal work T: constant-fill frame-tagged regions.

    ``rows`` [N, 3] = (frame, cy, cx), duplicate-padded like the
    per-frame fill-OLT. The fill value is external (no plane math), so
    the frame tag simply folds into the block-row index (jnp) or the
    banded BlockSpec row-block index (Pallas,
    ``kernels.region_fill_pooled``). Both lowerings produce the same
    int32 writes, so the choice is pure schedule."""
    pol = resolve_policy(backend, policy)
    F = canvas.shape[0] // n
    impl, _ = _route(pol, "region_fill_pooled", side=side, n=n, F=F)
    if impl == "jnp":
        return _pooled_fill(canvas, rows, values, nonempty, side=side, n=n)
    return _region_fill_pooled_pallas(
        canvas, rows, values, nonempty, side=side, n=n, F=F,
        interpret=pol.resolve_interpret())


def region_dwell_pooled(canvas, rows, count, *, side, n, bounds_all,
                        max_dwell=512, backend=None, policy=None,
                        workload=None):
    """Pooled last-level work A: interior values of frame-tagged leaves.

    ``rows`` [cap, 3] holds ``count`` (int32 scalar) live rows first;
    what follows them is never written. Each row's interior is evaluated
    in its own frame's window: the jnp lowering broadcasts
    ``pooled_bounds``'s [4, N, 1, 1] components against the per-row
    planes, block by block up to ``count`` (``_pooled_dwell_blocks``);
    the Pallas lowering (``kernels.region_dwell_pooled``) stages the
    [F, 4] windows through scalar prefetch and lands each tile in its
    frame band directly, over every row, the dead ones duplicating row
    0 -- bit-identical per pixel, so the tuned tier picks freely.
    Returns the canvas and the ring rows A ran (int32)."""
    pol = resolve_policy(backend, policy)
    F = canvas.shape[0] // n
    impl, params = _route(pol, "region_dwell_pooled", workload=workload,
                          side=side, n=n, F=F, max_dwell=max_dwell)
    unroll = int(params.get("unroll", 1))
    if impl == "jnp":
        return _pooled_dwell_blocks(
            canvas, rows, count, side=side, n=n, bounds_all=bounds_all,
            max_dwell=max_dwell, workload=workload, unroll=unroll)
    cap = rows.shape[0]
    idx = jnp.where(jnp.arange(cap) < count, jnp.arange(cap), 0)
    nonempty = (count > 0).astype(jnp.int32).reshape((1,))
    return _region_dwell_pooled_pallas(
        canvas, rows[idx], nonempty, bounds_all, side=side, n=n, F=F,
        max_dwell=max_dwell, interpret=pol.resolve_interpret(),
        workload=workload, unroll=unroll), jnp.int32(cap)


def compact_ranks(flags, *, backend=None, policy=None):
    """Exclusive-scan OLT compaction (atomicAdd replacement).
    Returns (ranks [N] int32, count scalar int32)."""
    pol = resolve_policy(backend, policy)
    N = flags.shape[0]
    impl, params = _route(pol, "olt_compact", n=N)
    if impl == "jnp":
        ranks, count = ref.compact_ranks_ref(flags)
        return ranks, count
    block = params.get("block")
    if block is not None and N > int(block):
        # ragged N: zero-pad flags to the block multiple (padding inserts
        # nothing, so the first N exclusive ranks and the grand total are
        # unchanged) and slice the ranks back
        blk = int(block)
        pad = -N % blk
        flags_b = flags if pad == 0 else jnp.concatenate(
            [flags, jnp.zeros((pad,), flags.dtype)])
        ranks, count = compact_ranks_blocked(
            flags_b, block=blk, interpret=pol.resolve_interpret())
        return ranks[:N], count[0]
    if N > _OLT_KERNEL_CAP:
        # too large for one VMEM block and no blocked schedule chosen:
        # XLA's own tiled cumsum is the safe lowering
        ranks, count = ref.compact_ranks_ref(flags)
        return ranks, count
    ranks, count = compact_ranks_kernel(flags, interpret=pol.resolve_interpret())
    return ranks, count[0]


def batched_ranks(flags, *, backend=None, policy=None):
    """Per-column OLT ranks [N, E] (MoE position_in_expert).
    Returns (ranks [N, E] int32, counts [E] int32)."""
    from repro.core.olt import batched_compact_ranks
    pol = resolve_policy(backend, policy)
    impl, _ = _route(pol, "batched_ranks", n=flags.shape[0], e=flags.shape[1])
    if impl == "jnp" or flags.size > _OLT_KERNEL_CAP:
        return batched_compact_ranks(flags)
    from repro.kernels.moe_dispatch import batched_ranks_kernel
    ranks, counts = batched_ranks_kernel(flags, interpret=pol.resolve_interpret())
    return ranks, counts[0]
