"""The traffic generator: the same seed gives the same inputs, and
seeds differ only in order."""

import json

import pytest

from bench import generator
from bench.tests.tiny import ROOT

BIG = 2 ** 31 + 12345


def _load(name):
    config, traffic = name.split(".")
    return (json.loads((ROOT / "bench" / "configs"
                        / f"{config}.json").read_text()),
            json.loads((ROOT / "bench" / "traffic"
                        / f"{traffic}.json").read_text()))


def test_frame_plan_is_seeded_and_spans_depths():
    cfg, trf = _load("zoom4k.interleaved")
    a = generator.frame_plan(cfg, trf, BIG, chunk=4)
    assert a == generator.frame_plan(cfg, trf, BIG, chunk=4)
    assert a != generator.frame_plan(cfg, trf, 3, chunk=4)
    stretch = cfg["frames"] // 4
    for c in range(0, len(a.frames) // 4):
        ks = sorted(a.frames[4 * c:4 * c + 4])
        # one frame from each quarter of the video
        assert [k // stretch for k in ks] == [0, 1, 2, 3]
    assert a.warmup == 4 * trf["warmup_chunks"]


@pytest.mark.parametrize("chunk", [4, 16])
def test_dealing_covers_the_video_once(chunk):
    cfg, trf = _load("zoom4k.interleaved")
    dealt = generator._deal(cfg["frames"], chunk, "serpentine")
    assert sorted(k for c in dealt for k in c) == list(range(cfg["frames"]))


def test_viewports_same_arrivals_every_seed():
    cfg, trf = _load("tiles256.zipf")
    a = generator.viewport_plan(cfg, trf, BIG, 10.0)
    b = generator.viewport_plan(cfg, trf, 7, 10.0)
    assert a == generator.viewport_plan(cfg, trf, BIG, 10.0)
    assert sorted(r.due for r in a.requests) == sorted(
        r.due for r in b.requests)
    assert [(r.z, r.ox) for r in a.requests] != [(r.z, r.ox)
                                                  for r in b.requests]
    assert len(a.warmup) == trf["warmup_requests"]
    assert all(r.due < 0 for r in a.warmup)
    assert all(0 <= r.due < 10.0 for r in a.window)


def test_viewports_cover_two_by_two_tiles_inside_zoom_range():
    cfg, trf = _load("tiles256.zipf")
    plan = generator.viewport_plan(cfg, trf, 11, 10.0)
    for r in plan.requests:
        assert cfg["min_zoom"] <= r.z <= cfg["max_zoom"]
        assert r.ox % 1 and r.oy % 1  # never on a tile edge
        tiles = generator.viewport_tiles(r)
        assert len(set(tiles)) == 4


def test_viewport_tiles_match_the_tile_service_cover():
    from repro.launch.tiles import tiles_for_viewport

    cfg, trf = _load("tiles256.zipf")
    plan = generator.viewport_plan(cfg, trf, 5, 5.0)
    for r in plan.window[:200]:
        addrs = tiles_for_viewport(generator.viewport_window(cfg, r),
                                   ref_bounds=cfg["window"], n=cfg["n"],
                                   max_dwell=cfg["max_dwell"])
        assert [(a.depth, a.iy, a.ix) for a in addrs] == list(
            generator.viewport_tiles(r))
        for a in addrs:
            assert a.bounds(cfg["window"]) == generator.tile_window(
                cfg, (a.depth, a.iy, a.ix))
