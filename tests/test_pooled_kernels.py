"""Banded pooled Pallas kernel tier (ISSUE 10).

Three-way bit-identity for the cross-frame banded scatter: the pooled
Pallas kernels (interpret mode), the jnp pooled lowering, and the
per-frame square path stacked into bands must agree bit for bit on random
frame-tagged worklists -- including duplicate-padded tails and the
``nonempty = 0`` no-write guarantee. Plus the routing surface: the ops
entry points must dispatch the Pallas lowerings for pallas/tuned policies
(no jnp pin), and ``ask_pooled`` under a tuned policy with a pooled cache
must stay bit-identical to the jnp engine end to end. Last, the jnp
dwell's loop over the live blocks of the leaf ring against A over every
live row at once, at each edge of a block.
"""

import numpy as np
import pytest

import jax.numpy as jnp

from repro.kernels import autotune, ops, ref
from repro.kernels.policy import KernelPolicy
from repro.kernels.region_dwell_pooled import (
    region_dwell_pooled as pallas_dwell_pooled)
from repro.kernels.region_fill_pooled import (
    region_fill_pooled as pallas_fill_pooled)
from repro.testing.hypothesis_compat import given, settings, strategies as st

MAX_DWELL = 16

# a few distinct plane windows so frames genuinely disagree
_WINDOWS = [(-1.5, -1.0, 0.5, 1.0), (-0.7, -0.3, -0.2, 0.2),
            (-2.0, -1.2, 1.2, 1.2), (0.1, 0.1, 0.6, 0.7)]


@pytest.fixture(autouse=True)
def _fresh_memo():
    autotune.clear_memo()
    yield
    autotune.clear_memo()


def _worklist(data, F, regions, N):
    """Draw a duplicate-padded frame-tagged worklist [N, 3] + live count."""
    live = data.draw(st.integers(min_value=1, max_value=N), label="live")
    rows = np.zeros((N, 3), np.int32)
    for i in range(live):
        rows[i, 0] = data.draw(
            st.integers(min_value=0, max_value=F - 1), label=f"f{i}")
        rows[i, 1] = data.draw(
            st.integers(min_value=0, max_value=regions - 1), label=f"y{i}")
        rows[i, 2] = data.draw(
            st.integers(min_value=0, max_value=regions - 1), label=f"x{i}")
    rows[live:] = rows[0]  # duplicate-pad: idempotent rewrite contract
    return rows, live


def _per_frame_fill(canvas, rows, values, live, *, side, n, F):
    """Oracle: run the SQUARE jnp fill per frame, stack into bands."""
    out = np.asarray(canvas).copy()
    for f in range(F):
        sel = np.nonzero(rows[:live, 0] == f)[0]
        band = jnp.asarray(out[f * n:(f + 1) * n])
        if sel.size == 0:
            continue
        coords = np.asarray(rows[sel, 1:], np.int32)
        vals = np.asarray(values)[sel]
        got = ops.region_fill(
            band, jnp.asarray(coords), jnp.asarray(vals),
            jnp.ones((1,), jnp.int32), side=side, n=n, backend="jnp")
        out[f * n:(f + 1) * n] = np.asarray(got)
    return out


def _per_frame_dwell(canvas, rows, live, bounds_all, *, side, n, F):
    """Oracle: run the SQUARE jnp dwell per frame, stack into bands."""
    out = np.asarray(canvas).copy()
    for f in range(F):
        sel = np.nonzero(rows[:live, 0] == f)[0]
        if sel.size == 0:
            continue
        band = jnp.asarray(out[f * n:(f + 1) * n])
        coords = jnp.asarray(np.asarray(rows[sel, 1:], np.int32))
        got = ops.region_dwell(
            band, coords, jnp.ones((1,), jnp.int32), side=side, n=n,
            bounds=jnp.asarray(bounds_all[f]), max_dwell=MAX_DWELL,
            backend="jnp")
        out[f * n:(f + 1) * n] = np.asarray(got)
    return out


@settings(max_examples=20, deadline=None)
@given(data=st.data())
def test_pooled_fill_three_way_identity(data):
    F = data.draw(st.integers(min_value=1, max_value=3), label="F")
    n = 32
    side = data.draw(st.sampled_from([8, 16]), label="side")
    regions = n // side
    N = data.draw(st.integers(min_value=1, max_value=12), label="N")
    rows_np, live = _worklist(data, F, regions, N)
    rng = np.random.default_rng(live * 31 + N)
    # the engine's fill values are a function of the region (its common
    # perimeter dwell), so colliding rows always carry the same value --
    # mirror that, keeping duplicate writes idempotent
    values_np = (rows_np[:, 0] * 97 + rows_np[:, 1] * 13
                 + rows_np[:, 2] * 7 + 3).astype(np.int32)
    canvas = jnp.asarray(
        rng.integers(0, 7, size=(F * n, n)).astype(np.int32))
    rows = jnp.asarray(rows_np)
    values = jnp.asarray(values_np)
    ne = jnp.ones((1,), jnp.int32)

    jnp_out = ops.region_fill_pooled(
        canvas, rows, values, ne, side=side, n=n, backend="jnp")
    pallas_out = pallas_fill_pooled(
        canvas, rows, values, ne, side=side, n=n, F=F, interpret=True)
    per_frame = _per_frame_fill(
        canvas, rows_np, values_np, live, side=side, n=n, F=F)
    np.testing.assert_array_equal(np.asarray(jnp_out), np.asarray(pallas_out))
    np.testing.assert_array_equal(np.asarray(jnp_out), per_frame)


@settings(max_examples=10, deadline=None)
@given(data=st.data())
def test_pooled_dwell_three_way_identity(data):
    F = data.draw(st.integers(min_value=1, max_value=3), label="F")
    n = 32
    side = data.draw(st.sampled_from([8, 16]), label="side")
    regions = n // side
    N = data.draw(st.integers(min_value=1, max_value=8), label="N")
    rows_np, live = _worklist(data, F, regions, N)
    bounds_all = np.asarray(
        [_WINDOWS[data.draw(st.integers(min_value=0, max_value=3),
                            label=f"w{f}")] for f in range(F)], np.float32)
    rng = np.random.default_rng(live * 17 + N)
    canvas = jnp.asarray(
        rng.integers(0, 7, size=(F * n, n)).astype(np.int32))
    rows = jnp.asarray(rows_np)
    ba = jnp.asarray(bounds_all)
    ne = jnp.ones((1,), jnp.int32)

    jnp_out, _ = ops.region_dwell_pooled(
        canvas, rows, jnp.int32(live), side=side, n=n, bounds_all=ba,
        max_dwell=MAX_DWELL, backend="jnp")
    pallas_out = pallas_dwell_pooled(
        canvas, rows, ne, ba, side=side, n=n, F=F, max_dwell=MAX_DWELL,
        interpret=True)
    unroll4 = pallas_dwell_pooled(
        canvas, rows, ne, ba, side=side, n=n, F=F, max_dwell=MAX_DWELL,
        interpret=True, unroll=4)
    per_frame = _per_frame_dwell(
        canvas, rows_np, live, bounds_all, side=side, n=n, F=F)
    np.testing.assert_array_equal(np.asarray(jnp_out), np.asarray(pallas_out))
    np.testing.assert_array_equal(np.asarray(jnp_out), np.asarray(unroll4))
    np.testing.assert_array_equal(np.asarray(jnp_out), per_frame)


def test_pooled_kernels_nonempty_zero_no_write():
    """nonempty = 0 must suppress every write in BOTH lowerings, even
    when the (dead) rows alias the same blocks."""
    F, n, side = 2, 32, 8
    rng = np.random.default_rng(5)
    rows = jnp.asarray(np.zeros((6, 3), np.int32))  # all rows alias (0,0,0)
    values = jnp.asarray(rng.integers(1, 50, size=6).astype(np.int32))
    canvas = jnp.asarray(
        rng.integers(0, 9, size=(F * n, n)).astype(np.int32))
    ba = jnp.asarray(np.asarray(_WINDOWS[:F], np.float32))
    ne0 = jnp.zeros((1,), jnp.int32)
    for got in (
        ops.region_fill_pooled(canvas, rows, values, ne0, side=side, n=n,
                               backend="jnp"),
        pallas_fill_pooled(canvas, rows, values, ne0, side=side, n=n, F=F,
                           interpret=True),
        ops.region_dwell_pooled(canvas, rows, jnp.int32(0), side=side,
                                n=n, bounds_all=ba, max_dwell=MAX_DWELL,
                                backend="jnp")[0],
        pallas_dwell_pooled(canvas, rows, ne0, ba, side=side, n=n, F=F,
                            max_dwell=MAX_DWELL, interpret=True),
    ):
        np.testing.assert_array_equal(np.asarray(got), np.asarray(canvas))


def test_pooled_kernel_shape_validation():
    F, n, side = 2, 32, 8
    rows = jnp.zeros((4, 3), jnp.int32)
    vals = jnp.zeros((4,), jnp.int32)
    ne = jnp.ones((1,), jnp.int32)
    ba = jnp.asarray(np.asarray(_WINDOWS[:F], np.float32))
    square = jnp.zeros((n, n), jnp.int32)  # not the banded [F*n, n]
    with pytest.raises(ValueError, match="banded"):
        pallas_fill_pooled(square, rows, vals, ne, side=side, n=n, F=F,
                           interpret=True)
    with pytest.raises(ValueError, match="banded"):
        pallas_dwell_pooled(square, rows, ne, ba, side=side, n=n, F=F,
                            interpret=True)
    tall = jnp.zeros((F * n, n), jnp.int32)
    with pytest.raises(ValueError, match="divisible"):
        pallas_fill_pooled(tall, rows, vals, ne, side=7, n=n, F=F,
                           interpret=True)
    with pytest.raises(ValueError, match="bounds_all"):
        pallas_dwell_pooled(tall, rows, ne, ba[:1], side=side, n=n, F=F,
                            interpret=True)


# ---------------------------------------------------------------------------
# routing: the pooled entry points must dispatch the Pallas tier


def test_pooled_route_pallas_policy_no_jnp_pin(monkeypatch):
    """A pallas-backend policy must reach the banded Pallas kernels --
    the pre-ISSUE-10 jnp pin is gone."""
    seen = []
    fill = ops._region_fill_pooled_pallas
    dwell = ops._region_dwell_pooled_pallas
    monkeypatch.setattr(
        ops, "_region_fill_pooled_pallas",
        lambda *a, **k: seen.append("fill") or fill(*a, **k))
    monkeypatch.setattr(
        ops, "_region_dwell_pooled_pallas",
        lambda *a, **k: seen.append("dwell") or dwell(*a, **k))
    F, n, side = 2, 32, 8
    rows = jnp.zeros((4, 3), jnp.int32)
    canvas = jnp.zeros((F * n, n), jnp.int32)
    ne = jnp.ones((1,), jnp.int32)
    ba = jnp.asarray(np.asarray(_WINDOWS[:F], np.float32))
    pol = KernelPolicy(backend="pallas", interpret=True)
    ops.region_fill_pooled(canvas, rows, jnp.zeros((4,), jnp.int32), ne,
                           side=side, n=n, policy=pol)
    ops.region_dwell_pooled(canvas, rows, jnp.int32(4), side=side, n=n,
                            bounds_all=ba, max_dwell=8, policy=pol)
    assert seen == ["fill", "dwell"]


def test_pooled_tuned_cache_routes_pallas(tmp_path):
    """A tuning-cache entry for the pooled kernels must flip the route to
    the Pallas lowering (and its schedule params must flow through)."""
    F, n, side = 2, 32, 8
    cache = autotune.TuningCache()
    cache.put(autotune.cache_key("region_fill_pooled", side=side, n=n, F=F),
              autotune.Choice("pallas", us=1.0))
    cache.put(autotune.cache_key("region_dwell_pooled", side=side, n=n, F=F,
                                 max_dwell=8),
              autotune.Choice("pallas", (("unroll", 4),), us=1.0))
    path = tmp_path / "tc.json"
    cache.save(str(path))
    pol = KernelPolicy(backend="tuned", interpret=True,
                       tuning_cache=str(path))
    impl, _ = ops._route(pol, "region_fill_pooled", side=side, n=n, F=F)
    assert impl == "pallas"
    impl, params = ops._route(pol, "region_dwell_pooled", side=side, n=n,
                              F=F, max_dwell=8)
    assert impl == "pallas" and params["unroll"] == 4

    rng = np.random.default_rng(2)
    rows = jnp.asarray(np.stack([
        rng.integers(0, F, 6), rng.integers(0, n // side, 6),
        rng.integers(0, n // side, 6)], axis=1).astype(np.int32))
    canvas = jnp.asarray(rng.integers(0, 5, (F * n, n)).astype(np.int32))
    ba = jnp.asarray(np.asarray(_WINDOWS[:F], np.float32))
    count = jnp.int32(6)
    got, _ = ops.region_dwell_pooled(canvas, rows, count, side=side, n=n,
                                     bounds_all=ba, max_dwell=8, policy=pol)
    want, _ = ops.region_dwell_pooled(canvas, rows, count, side=side, n=n,
                                      bounds_all=ba, max_dwell=8,
                                      backend="jnp")
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def _pooled_cache_for(prob, F, path):
    """Seed a tuning cache that routes EVERY pooled dispatch of ``prob``
    (each level side for fill, the leaf side for dwell) to Pallas."""
    cache = autotune.TuningCache()
    side = prob.n // prob.g
    sides = []
    while side >= prob.B:
        sides.append(side)
        if side == prob.B:
            break
        side //= prob.r
    for s in sides:
        cache.put(
            autotune.cache_key("region_fill_pooled", workload=prob.workload,
                               side=s, n=prob.n, F=F),
            autotune.Choice("pallas", us=1.0))
    cache.put(
        autotune.cache_key("region_dwell_pooled", workload=prob.workload,
                           side=sides[-1], n=prob.n, F=F,
                           max_dwell=prob.max_dwell),
        autotune.Choice("pallas", (("unroll", 2),), us=1.0))
    cache.save(str(path))


@pytest.mark.parametrize("workload", ["mandelbrot", "julia"])
def test_ask_pooled_tuned_matches_jnp_end_to_end(tmp_path, workload):
    """The acceptance bar: ask_pooled under a tuned policy whose cache
    routes the banded kernels to Pallas is bit-identical to the all-jnp
    pooled engine on registry workloads."""
    from repro.core import pooled
    from repro.workloads import FrameProblem

    F = 3
    kw = dict(n=64, g=4, r=2, B=8, max_dwell=24, workload=workload)
    jnp_prob = FrameProblem(backend="jnp", **kw)
    path = tmp_path / "pooled-tc.json"
    _pooled_cache_for(jnp_prob, F, path)
    pol = KernelPolicy(backend="tuned", interpret=True,
                       tuning_cache=str(path))
    tuned_prob = FrameProblem(policy=pol, **kw)

    base = np.asarray(jnp_prob.bounds, np.float32)
    shift = np.linspace(0.0, 0.05, F, dtype=np.float32)[:, None]
    bounds = jnp.asarray(base[None, :] + shift * np.asarray(
        [1.0, 1.0, 1.0, 1.0], np.float32))
    want, _ = pooled.run_ask_pooled_batch(jnp_prob, bounds,
                                          safety_factor=1e9)
    got, st_p = pooled.run_ask_pooled_batch(tuned_prob, bounds,
                                            safety_factor=1e9)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    assert st_p.kernel_launches == 1


# ---------------------------------------------------------------------------
# the jnp lowering runs A only over the live blocks of the leaf ring

_BLOCK_CAP, _BLOCK_ROWS = 14, 4  # the last block's slice is clamped


@pytest.mark.parametrize("count", [0, 1, _BLOCK_ROWS - 1, _BLOCK_ROWS,
                                   _BLOCK_ROWS + 1, _BLOCK_CAP - 1,
                                   _BLOCK_CAP])
@pytest.mark.parametrize("workload_name",
                         ["mandelbrot", "julia", "burning_ship", "ssd_synth"])
def test_pooled_dwell_live_blocks_bit_identical(monkeypatch, workload_name,
                                                count):
    """The block loop of ``ops.region_dwell_pooled`` against A over all
    live rows at once, then a scatter: equal bit for bit at every edge
    of a block, for every workload kind. The rows past ``count`` hold
    random origins, live regions among them, so any of them written
    would change a live region's pixels or one outside them."""
    from repro.workloads import get_workload

    F, side, n = 3, 8, 32
    regions = n // side
    monkeypatch.setattr(ops, "DWELL_BLOCK_PIXELS",
                        _BLOCK_ROWS * side * side)
    assert ops.dwell_block_rows(side, _BLOCK_CAP) == _BLOCK_ROWS
    workload = get_workload(workload_name)
    rng = np.random.default_rng(count * 7 + len(workload_name))
    cells = np.stack(np.meshgrid(np.arange(F), np.arange(regions),
                                 np.arange(regions), indexing="ij"),
                     axis=-1).reshape(-1, 3)
    live = cells[rng.permutation(len(cells))[:count]]
    garbage = np.stack([rng.integers(0, F, _BLOCK_CAP - count),
                        rng.integers(0, regions, _BLOCK_CAP - count),
                        rng.integers(0, regions, _BLOCK_CAP - count)],
                       axis=1)
    rows = np.concatenate([live, garbage]).astype(np.int32)
    re0, im0, re1, im1 = workload.default_bounds
    bounds_all = np.asarray(
        [(re0 + (re1 - re0) * s, im0 + (im1 - im0) * s,
          re1 - (re1 - re0) * s, im1 - (im1 - im0) * s)
         for s in (0.0, 0.2, 0.35)], np.float32)
    canvas = rng.integers(0, 7, size=(F * n, n)).astype(np.int32)

    got, dwell_rows = ops.region_dwell_pooled(
        jnp.asarray(canvas), jnp.asarray(rows), jnp.int32(count),
        side=side, n=n, bounds_all=jnp.asarray(bounds_all),
        max_dwell=MAX_DWELL, policy=KernelPolicy(backend="jnp"),
        workload=workload)
    want = canvas.copy()
    if count:
        tiles = np.asarray(ref.region_interior_dyn(
            jnp.asarray(live[:, 1:], jnp.int32), side=side, n=n,
            bounds=ops.pooled_bounds(jnp.asarray(bounds_all),
                                     jnp.asarray(live, jnp.int32)),
            max_dwell=MAX_DWELL, workload=workload))
        for (f, cy, cx), tile in zip(live, tiles):
            y0, x0 = f * n + cy * side, cx * side
            want[y0:y0 + side, x0:x0 + side] = tile
    np.testing.assert_array_equal(np.asarray(got), want)
    assert int(dwell_rows) == -(-count // _BLOCK_ROWS) * _BLOCK_ROWS


@pytest.mark.parametrize("side,cap,rows", [
    (32, 65536, 2048), (32, 1024, 1024), (16, 65536, 8192),
    (8, 300, 300), (48, 8192, 512), (4096, 16, 1)])
def test_dwell_block_rows_from_shape(side, cap, rows):
    """``dwell_block_rows``: the power of two of at most 2^21 pixels a
    block, clamped to the ring, at least one row."""
    assert ops.dwell_block_rows(side, cap) == rows
