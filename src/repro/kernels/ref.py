"""Pure-jnp oracles for every Pallas kernel in this package.

The point-value computation (``dwell_compute``) is THE single definition
shared by oracles and kernels: Pallas kernel bodies import and call it on
values read from refs, so CPU-interpret results are bit-identical to the
oracle (identical op order in f32). It is workload-parametric: the
``workload`` argument (a ``repro.workloads.WorkloadSpec``, or None for the
classic Mandelbrot iteration) supplies the per-point function, so ONE
kernel body serves every registered escape-time workload.

Default (workload=None) semantics follow Adinetz's reference CUDA
implementation (the paper's DP baseline): z0 = c; while dwell < max_dwell
and |z|^2 < 4: z = z^2 + c. Interior points therefore carry dwell ==
max_dwell. The registry's "mandelbrot" spec reuses ``mandelbrot_init`` /
``mandelbrot_step`` below, so the two spellings are the same compute.
"""

from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp

# Complex-plane window used by the paper's benchmark: bottom-left (-1.5, -1),
# top-right (0.5, 1).
DEFAULT_BOUNDS: Tuple[float, float, float, float] = (-1.5, -1.0, 0.5, 1.0)

# The minor dimension of a TPU vector register, and of an array's (8, 128)
# tiling in HBM.
LANES = 128


def map_coords(xs: jax.Array, ys: jax.Array, n: int,
               bounds: Tuple[float, float, float, float] = DEFAULT_BOUNDS):
    """Pixel (x, y) -> workload-plane (re, im). xs/ys are f32 pixel indices."""
    re0, im0, re1, im1 = bounds
    cr = re0 + xs * ((re1 - re0) / n)
    ci = im0 + ys * ((im1 - im0) / n)
    return cr, ci


def mandelbrot_init(cr: jax.Array, ci: jax.Array):
    """z0 = c (Adinetz's reference semantics; dwell counts from z0)."""
    return cr, ci


def mandelbrot_step(zr: jax.Array, zi: jax.Array,
                    cr: jax.Array, ci: jax.Array):
    """One z -> z^2 + c step, spelled exactly as the seed kernel did --
    every escape-time workload whose step matches these ops elementwise
    is bit-identical to the pre-refactor canvases."""
    return zr * zr - zi * zi + cr, 2.0 * zr * zi + ci


def escape_time(cr: jax.Array, ci: jax.Array, max_dwell: int, *,
                init=mandelbrot_init, step=mandelbrot_step,
                escape_radius2: float = 4.0, unroll: int = 1) -> jax.Array:
    """Generic escape-time iteration, vectorised, fixed trip count with
    masked updates (uniform control flow -- the TPU/VPU-idiomatic form).

    ``init(cr, ci) -> (zr0, zi0)`` seeds the orbit from the mapped plane
    point; ``step(zr, zi, cr, ci) -> (zr', zi')`` advances it (the plane
    point rides along so parameter-plane workloads like Mandelbrot see c
    while dynamic-plane workloads like Julia ignore it). The loop
    structure -- escape test BEFORE the step, masked updates -- is the
    single definition every engine and kernel backend shares.

    ``unroll`` groups the trip count into ``max_dwell // unroll``
    ``fori_loop`` iterations of ``unroll`` identical masked steps plus a
    statically-unrolled remainder -- exactly ``max_dwell`` applications
    of the SAME per-point op sequence in the same order, so the result
    is bit-identical for every ``unroll``. It is a pure scheduling knob
    (the autotuned tier's main lever on the jnp lowering: fewer loop-
    carried iterations, more straight-line vector work per iteration).
    """
    zr, zi = init(cr, ci)
    dw = jnp.zeros(cr.shape, dtype=jnp.int32)

    def one(carry):
        zr, zi, dw = carry
        active = (zr * zr + zi * zi) < escape_radius2
        nzr, nzi = step(zr, zi, cr, ci)
        zr = jnp.where(active, nzr, zr)
        zi = jnp.where(active, nzi, zi)
        dw = jnp.where(active, dw + 1, dw)
        return zr, zi, dw

    u = max(1, min(int(unroll), max_dwell)) if max_dwell > 0 else 1

    def body(_, carry):
        for _ in range(u):
            carry = one(carry)
        return carry

    carry = (zr, zi, dw)
    trips, rem = divmod(max_dwell, u)
    if trips > 0:
        carry = jax.lax.fori_loop(0, trips, body, carry)
    for _ in range(rem):
        carry = one(carry)
    return carry[2]


def dwell_compute(cr: jax.Array, ci: jax.Array, max_dwell: int, *,
                  workload=None, unroll: int = 1) -> jax.Array:
    """Per-point values at the mapped plane coordinates.

    ``workload`` is a ``repro.workloads.WorkloadSpec`` (duck-typed: only
    ``.values(cr, ci, max_dwell)`` is called, so this module never
    imports the workloads package); None keeps the classic Mandelbrot
    iteration -- the back-compat spelling every pre-workload caller
    relies on. ``unroll`` is the bit-identity-preserving loop grouping
    of ``escape_time`` (grid workloads have no loop and ignore it).
    """
    if workload is None:
        return escape_time(cr, ci, max_dwell, unroll=unroll)
    if unroll == 1:  # ad-hoc duck-typed specs may predate the unroll kwarg
        return workload.values(cr, ci, max_dwell)
    return workload.values(cr, ci, max_dwell, unroll=unroll)


@functools.partial(jax.jit,
                   static_argnames=("n", "max_dwell", "workload", "unroll"))
def mandelbrot_ref(n: int, bounds=DEFAULT_BOUNDS, max_dwell: int = 512,
                   workload=None, unroll: int = 1) -> jax.Array:
    """Oracle for the exhaustive flat kernel: full n x n value image.
    (Named for the seed workload; ``workload=`` makes it serve any.)

    ``bounds`` is runtime data, float32 like the served path's per-frame
    windows: one compiled program serves every window, and each pixel
    maps to the same plane point as in the subdivision engines. Folded in
    as compile-time constants, the window's mapping compiled to
    different float32 roundings than the engines' (pixels at the
    boundary then disagreed by a few dwell)."""
    ys = jax.lax.broadcasted_iota(jnp.float32, (n, n), 0)
    xs = jax.lax.broadcasted_iota(jnp.float32, (n, n), 1)
    cr, ci = map_coords(xs, ys, n, bounds)
    return dwell_compute(cr, ci, max_dwell, workload=workload, unroll=unroll)


def perimeter_coords(coords: jax.Array, side: int):
    """Pixel (y, x) positions of the 4 x side perimeter of each region.

    coords: [N, 2] int32 region coords at some level; region pixel origin is
    coords * side. Returns (ys, xs): [N, 4, side] f32. Rows: top, bottom,
    left, right (corners appear twice -- harmless for the homogeneity test).
    """
    py = (coords[:, 0] * side).astype(jnp.float32)[:, None, None]
    px = (coords[:, 1] * side).astype(jnp.float32)[:, None, None]
    j = jnp.arange(side, dtype=jnp.float32)[None, None, :]
    row = jnp.arange(4)[None, :, None]
    last = float(side - 1)
    ys = jnp.where(row == 0, py,
         jnp.where(row == 1, py + last,
         py + j))
    xs = jnp.where(row == 0, px + j,
         jnp.where(row == 1, px + j,
         jnp.where(row == 2, px, px + last)))
    ys = jnp.broadcast_to(ys, (coords.shape[0], 4, side))
    xs = jnp.broadcast_to(xs, (coords.shape[0], 4, side))
    return ys, xs


def perimeter_query_dyn(coords: jax.Array, *, side: int, n: int,
                        bounds=DEFAULT_BOUNDS, max_dwell: int = 512,
                        workload=None, unroll: int = 1):
    """Un-jitted border query Q: same math as ``perimeter_query_ref`` but
    ``bounds`` may be a traced [4] array -- the batched frame-serving path
    vmaps over it (one plane window per frame)."""
    ys, xs = perimeter_coords(coords, side)
    cr, ci = map_coords(xs, ys, n, bounds)
    dw = dwell_compute(cr, ci, max_dwell, workload=workload,
                       unroll=unroll)  # [N, 4, side]
    first = dw[:, 0, 0]
    eq = (dw == first[:, None, None] if workload is None
          else workload.region_equal(dw, first[:, None, None]))
    homog = jnp.all(eq, axis=(1, 2))
    return homog, first


@functools.partial(jax.jit,
                   static_argnames=("side", "n", "bounds", "max_dwell",
                                    "workload", "unroll"))
def perimeter_query_ref(coords: jax.Array, *, side: int, n: int,
                        bounds=DEFAULT_BOUNDS, max_dwell: int = 512,
                        workload=None, unroll: int = 1):
    """Oracle for the Mariani-Silver border query Q (paper Sec. 4.2.1).

    Returns (homog [N] bool, common [N] int32): whether all 4*side border
    values agree, and the shared value (row (0,0) -- junk if not homog).
    """
    return perimeter_query_dyn(coords, side=side, n=n, bounds=bounds,
                               max_dwell=max_dwell, workload=workload,
                               unroll=unroll)


def region_interior_dyn(coords: jax.Array, *, side: int, n: int,
                        bounds=DEFAULT_BOUNDS, max_dwell: int = 512,
                        workload=None, unroll: int = 1) -> jax.Array:
    """Un-jitted last-level work A (traced-bounds variant, see
    ``perimeter_query_dyn``): [N, side, side] values.

    A TPU tiles an array's two minor dimensions as (8, 128), so planes
    of ``[N, side, side]`` at a side below 128 fill side/128 of the
    lanes, and the escape loop streams that padding through HBM on every
    trip. Where side is not a multiple of 128 and side * side is, each
    tile's pixels are laid out row-major as ``[side * side / 128, 128]``
    instead, and the values come back as ``[N, side, side]`` after the
    loop. The planes are built in that layout from the pixel index, not
    reshaped into it: the compiler recomputes them inside the loop from
    the per-row origins, and a reshape there would relayout the padded
    planes on every trip. Pixel coordinates are exact small integers, so
    every pixel sees the same f32 ops on the same values in either
    layout, bit for bit. The Pallas bodies call ``dwell_compute`` on a
    ``(side, side)`` block and never see this."""
    area = side * side
    if side % LANES and area % LANES == 0:
        plane = (area // LANES, LANES)
        pix = jnp.arange(area, dtype=jnp.int32).reshape(plane)
        iy = (pix // side).astype(jnp.float32)
        ix = (pix % side).astype(jnp.float32)
    else:
        plane = (side, side)
        iy = jnp.arange(side, dtype=jnp.float32)[:, None]
        ix = iy.T
    py = (coords[:, 0] * side).astype(jnp.float32)
    px = (coords[:, 1] * side).astype(jnp.float32)
    planes = (coords.shape[0],) + plane
    ys = jnp.broadcast_to(py[:, None, None] + iy, planes)
    xs = jnp.broadcast_to(px[:, None, None] + ix, planes)
    cr, ci = map_coords(xs, ys, n, bounds)
    values = dwell_compute(cr, ci, max_dwell, workload=workload,
                           unroll=unroll)
    return values.reshape(coords.shape[0], side, side)


@functools.partial(jax.jit,
                   static_argnames=("side", "n", "bounds", "max_dwell",
                                    "workload", "unroll"))
def region_interior_ref(coords: jax.Array, *, side: int, n: int,
                        bounds=DEFAULT_BOUNDS, max_dwell: int = 512,
                        workload=None, unroll: int = 1) -> jax.Array:
    """Oracle for the last-level application work A: [N, side, side] value
    tiles for each region."""
    return region_interior_dyn(coords, side=side, n=n, bounds=bounds,
                               max_dwell=max_dwell, workload=workload,
                               unroll=unroll)


def compact_ranks_ref(flags):
    """Oracle for kernels/olt_compact.py: exclusive scan + total."""
    f = jnp.asarray(flags).astype(jnp.int32)
    inc = jnp.cumsum(f)
    return (inc - f).astype(jnp.int32), inc[-1].astype(jnp.int32)
