"""The check refuses a broken timed path: the whole harness runs on the
CPU at a tiny size (past its look for a chip) with the program broken
underneath it, and ``correct`` comes out false. Also the control, the
reference in bfloat16 in the program's place."""

import dataclasses
import time

import jax
import numpy as np
import pytest

from bench import control
from bench import run as bench_run
from bench import spec
from bench.tests import tiny


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return tiny.make_root(tmp_path_factory.mktemp("checkout"))


def _alter(canvas):
    out = np.array(canvas)
    out[:32, :32] += 1  # one block of one answer, wrong by one dwell
    return out


def _stream_fault(kind):
    from repro.launch.render_service import RenderService

    inner = RenderService.stream_chunks

    def stream_chunks(self, windows):
        last = None
        for k, r in enumerate(inner(self, windows)):
            c = np.asarray(r.canvases)
            if kind == "half_left_out":
                c = c[:max(1, c.shape[0] // 2)]
            elif kind == "altered" and k == 1:
                c = np.concatenate([_alter(c[:1]), c[1:]])
            elif kind == "state_unchanged" and last is not None:
                c = last[:c.shape[0]]
            last = c
            yield dataclasses.replace(r, canvases=c)

    return RenderService, "stream_chunks", stream_chunks


def _tile_fault(kind):
    from repro.launch.tiles import TileService

    inner = TileService.serve
    state = {"last": None, "n": 0}

    def serve(self, viewport, **kw):
        resp = inner(self, viewport, **kw)
        tiles = dict(resp.tiles)
        keys = list(tiles)
        state["n"] += 1
        if kind == "half_left_out" and resp.misses:
            for a in keys[:len(keys) // 2]:
                del tiles[a]
        elif kind == "altered" and state["n"] % 7 == 0:
            tiles[keys[0]] = _alter(tiles[keys[0]])
        elif kind == "state_unchanged" and state["last"] is not None:
            tiles = {a: c for a, c in zip(keys, state["last"])}
        state["last"] = list(resp.tiles.values())
        return dataclasses.replace(resp, tiles=tiles)

    return TileService, "serve", serve


FAULTS = ["half_left_out", "altered", "state_unchanged"]


@pytest.mark.parametrize("kind", FAULTS)
@pytest.mark.parametrize("name,fault", [("zoom4k.interleaved", _stream_fault),
                                        ("tiles256.zipf", _tile_fault)])
def test_broken_path_is_not_correct(root, monkeypatch, name, fault, kind):
    cls, attr, broken = fault(kind)
    monkeypatch.setattr(cls, attr, broken)
    cell = spec.load_cell(name, root)
    line = bench_run.run_cell(cell, seed=2 ** 31 + 5, seconds=2.0,
                              trace=False, devices=jax.devices()[:1],
                              t0=time.monotonic())
    assert line["correct"] is False, line["check"]


@pytest.mark.parametrize("name", ["zoom4k.interleaved", "tiles256.zipf"])
def test_control_is_not_correct(root, name):
    cell = spec.load_cell(name, root)
    for seed in (1, 2, 2 ** 31 + 3):
        v = control.control(cell, seed, count=8, seconds=2.0)
        assert not v.correct
        assert v.numbers["worst_px"] > v.limits["worst_px"]
