"""Per-kernel allclose validation: Pallas (interpret=True) vs ref.py
oracle, swept over shapes/blocks/dwells per the deliverable-(c) contract."""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ops, ref
from repro.kernels.mandelbrot_dwell import mandelbrot_dwell
from repro.kernels.olt_compact import compact_ranks_kernel
from repro.kernels.perimeter_query import perimeter_query
from repro.kernels.region_dwell import region_dwell
from repro.kernels.region_fill import region_fill


@pytest.mark.parametrize("n", [32, 64, 128])
@pytest.mark.parametrize("block", [(8, 8), (16, 32), (64, 64)])
@pytest.mark.parametrize("dwell", [16, 64])
def test_flat_dwell_kernel_matches_oracle(n, block, dwell):
    if n % min(block[0], n) or n % min(block[1], n):
        pytest.skip("block does not divide n")
    got = mandelbrot_dwell(n, max_dwell=dwell, block=block, interpret=True)
    want = ref.mandelbrot_ref(n, max_dwell=dwell)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    # unroll re-groups the escape loop without changing any per-point op
    # sequence: bit-identical for every factor (the tuned tier's lever)
    unrolled = mandelbrot_dwell(n, max_dwell=dwell, block=block,
                                interpret=True, unroll=4)
    np.testing.assert_array_equal(np.asarray(unrolled), np.asarray(want))


@pytest.mark.parametrize("side", [4, 8, 16])
@pytest.mark.parametrize("level_g", [2, 4])
def test_perimeter_query_matches_oracle(side, level_g):
    n = side * level_g
    key = jax.random.PRNGKey(0)
    coords = jax.random.randint(key, (7, 2), 0, level_g, jnp.int32)
    got_h, got_c = perimeter_query(coords, side=side, n=n, max_dwell=32,
                                   interpret=True)
    want_h, want_c = ref.perimeter_query_ref(coords, side=side, n=n,
                                             max_dwell=32)
    np.testing.assert_array_equal(np.asarray(got_h), np.asarray(want_h))
    np.testing.assert_array_equal(np.asarray(got_c), np.asarray(want_c))


@pytest.mark.parametrize("scheme,tile", [("sbr", 256), ("mbr", 4)])
def test_region_fill_kernel(scheme, tile):
    n, side = 32, 8
    canvas = jnp.arange(n * n, dtype=jnp.int32).reshape(n, n)
    coords = jnp.array([[0, 0], [3, 2], [0, 0]], jnp.int32)  # dup padding
    vals = jnp.array([7, 9, 7], jnp.int32)
    out = region_fill(canvas, coords, vals, jnp.ones((1,), jnp.int32),
                      side=side, n=n, scheme=scheme, tile=tile,
                      interpret=True)
    out = np.asarray(out)
    want = np.asarray(canvas).copy()
    want[0:8, 0:8] = 7
    want[24:32, 16:24] = 9
    np.testing.assert_array_equal(out, want)


def test_region_fill_empty_preserves_canvas():
    n, side = 16, 4
    canvas = jnp.arange(n * n, dtype=jnp.int32).reshape(n, n)
    coords = jnp.zeros((3, 2), jnp.int32)
    vals = jnp.zeros((3,), jnp.int32)
    out = region_fill(canvas, coords, vals, jnp.zeros((1,), jnp.int32),
                      side=side, n=n, interpret=True)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(canvas))


@pytest.mark.parametrize("scheme,tile", [("sbr", 256), ("mbr", 8)])
def test_region_dwell_kernel(scheme, tile):
    n, side, g = 64, 16, 4
    key = jax.random.PRNGKey(1)
    coords = jax.random.randint(key, (5, 2), 0, g, jnp.int32)
    canvas = jnp.full((n, n), -1, jnp.int32)
    out = region_dwell(canvas, coords, jnp.ones((1,), jnp.int32),
                       side=side, n=n, max_dwell=32, scheme=scheme,
                       tile=tile, interpret=True)
    tiles = ref.region_interior_ref(coords, side=side, n=n, max_dwell=32)
    out = np.asarray(out)
    for i in range(coords.shape[0]):
        cy, cx = int(coords[i, 0]) * side, int(coords[i, 1]) * side
        np.testing.assert_array_equal(
            out[cy:cy + side, cx:cx + side], np.asarray(tiles[i]))


@pytest.mark.parametrize("nbits", [1, 7, 64, 255])
def test_olt_compact_kernel(nbits):
    key = jax.random.PRNGKey(nbits)
    flags = jax.random.bernoulli(key, 0.4, (nbits,))
    ranks, count = compact_ranks_kernel(flags, interpret=True)
    want_r, want_c = ref.compact_ranks_ref(flags)
    np.testing.assert_array_equal(np.asarray(ranks), np.asarray(want_r))
    assert int(count[0]) == int(want_c)


def test_ops_backends_agree():
    """The public ops must give identical results on every policy rung."""
    from repro.kernels.policy import JNP_POLICY, PALLAS_POLICY, TUNED_POLICY

    n = 64
    b = ops.mandelbrot(n, max_dwell=32, policy=JNP_POLICY)
    for pol in (PALLAS_POLICY, TUNED_POLICY):
        a = ops.mandelbrot(n, max_dwell=32, policy=pol)
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))

    coords = jnp.array([[0, 1], [2, 3], [1, 1]], jnp.int32)
    for pol in (PALLAS_POLICY, JNP_POLICY, TUNED_POLICY):
        h, c = ops.perimeter_query(coords, side=16, n=n, max_dwell=32,
                                   policy=pol)
        hr, cr = ref.perimeter_query_ref(coords, side=16, n=n, max_dwell=32)
        np.testing.assert_array_equal(np.asarray(h), np.asarray(hr))
        np.testing.assert_array_equal(np.asarray(c), np.asarray(cr))


@pytest.mark.parametrize("n,e", [(16, 4), (128, 8), (255, 16)])
def test_moe_batched_ranks_kernel(n, e):
    """Pallas batched-rank kernel (MoE position_in_expert) vs olt oracle."""
    from repro.core.olt import batched_compact_ranks
    from repro.kernels.moe_dispatch import batched_ranks_kernel
    key = jax.random.PRNGKey(n * e)
    flags = jax.nn.one_hot(
        jax.random.randint(key, (n,), 0, e), e, dtype=jnp.int32)
    ranks, counts = batched_ranks_kernel(flags, interpret=True)
    want_r, want_c = batched_compact_ranks(flags)
    np.testing.assert_array_equal(np.asarray(ranks), np.asarray(want_r))
    np.testing.assert_array_equal(np.asarray(counts[0]), np.asarray(want_c))


def _interior_on_tiles(coords, bounds, *, side, n, max_dwell, workload):
    """Leaf dwell A written out on ``[N, side, side]`` planes: map_coords
    then dwell_compute, with no other layout in between."""
    py = (coords[:, 0] * side).astype(jnp.float32)
    px = (coords[:, 1] * side).astype(jnp.float32)
    iy = jnp.arange(side, dtype=jnp.float32)
    tiles = (coords.shape[0], side, side)
    ys = jnp.broadcast_to(py[:, None, None] + iy[None, :, None], tiles)
    xs = jnp.broadcast_to(px[:, None, None] + iy[None, None, :], tiles)
    cr, ci = ref.map_coords(xs, ys, n, bounds)
    return ref.dwell_compute(cr, ci, max_dwell, workload=workload)


# (side, rows): an 8 x 8 tile fills half a lane row, so side 8 keeps its
# tiles whether the rows' pixels together fill whole lane rows (8, 6) or
# not (8, 5); side 128 is lane-dense already
_INTERIOR_SHAPES = [(8, 5), (8, 6), (16, 3), (32, 3), (64, 2), (128, 2)]


@pytest.mark.parametrize("bounds_kind", ["static", "traced", "pooled"])
@pytest.mark.parametrize("workload_name",
                         ["mandelbrot", "julia", "burning_ship", "ssd_synth"])
@pytest.mark.parametrize("side,rows", _INTERIOR_SHAPES)
def test_region_interior_layout_is_bit_identical(side, rows, workload_name,
                                                 bounds_kind):
    """``region_interior_dyn`` runs A's loop on lane-dense planes where the
    shape allows; every pixel's value must be the one the
    ``[N, side, side]`` computation gives, bit for bit, for every
    workload and every way the serving paths pass the window."""
    from repro.workloads import get_workload

    workload = get_workload(workload_name)
    # four regions a side, so most of them straddle a dwell band's edge
    n, max_dwell, frames = 4 * side, 64, 3
    rng = np.random.default_rng(side * 100 + rows)
    coords = jnp.asarray(rng.integers(0, n // side, (rows, 2)), jnp.int32)
    re0, im0, re1, im1 = workload.default_bounds
    # the default window and two zooms towards its middle
    windows = [(re0 + (re1 - re0) * s, im0 + (im1 - im0) * s,
                re1 - (re1 - re0) * s, im1 - (im1 - im0) * s)
               for s in (0.0, 0.2, 0.35)]
    kw = dict(side=side, n=n, max_dwell=max_dwell, workload=workload)
    if bounds_kind == "static":
        got = ref.region_interior_ref(coords, bounds=windows[1], **kw)
        want = jax.jit(functools.partial(
            _interior_on_tiles, bounds=windows[1], **kw))(coords)
    else:
        if bounds_kind == "traced":
            bounds = jnp.asarray(windows[1], jnp.float32)
        else:
            frame = jnp.asarray(rng.integers(0, frames, rows), jnp.int32)
            bounds = ops.pooled_bounds(
                jnp.asarray(windows, jnp.float32),
                jnp.concatenate([frame[:, None], coords], axis=1))
        got = jax.jit(functools.partial(ref.region_interior_dyn, **kw))(
            coords, bounds=bounds)
        want = jax.jit(functools.partial(_interior_on_tiles, **kw))(
            coords, bounds)
    assert got.shape == (rows, side, side)
    assert got.dtype == want.dtype
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    if workload.kind == "escape":
        # the loop runs 128 lanes wide where side is below 128 and each
        # tile's pixels fill whole lane rows; other shapes keep the tiles
        jaxpr = jax.make_jaxpr(functools.partial(
            ref.region_interior_dyn,
            bounds=windows[1] if bounds_kind == "static" else bounds,
            **kw))(coords)
        loop, = [e for e in jaxpr.eqns
                 if e.primitive.name in ("scan", "while")]
        lane_dense = side % 128 and side * side % 128 == 0
        assert {v.aval.shape for v in loop.outvars if v.aval.ndim} == {
            (rows, side * side // 128, 128) if lane_dense
            else (rows, side, side)}
