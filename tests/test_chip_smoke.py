"""``chip_smoke.py``'s phases on the CPU at a small size (n = 256,
max_dwell = 64, two chunks): the script's control flow and its pixel
checks run on every change, though the script itself runs only on a
TPU."""

import importlib.util
from pathlib import Path

import numpy as np
import pytest

ROOT = Path(__file__).resolve().parent.parent


@pytest.fixture(scope="module")
def smoke():
    spec = importlib.util.spec_from_file_location("chip_smoke",
                                                  ROOT / "chip_smoke.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_frames_phase_matches_exhaustive(smoke):
    report, failures = smoke.phase_frames(n=256, max_dwell=64, frames=4,
                                          chunk=2)
    assert failures == []
    for engine in smoke.ENGINES:
        stats = report[engine]
        assert stats["frames"] == 4 and stats["chunks"] >= 2
        assert stats["mismatched_pixels"] == 0
        assert stats["overflow_dropped"] == 0
        assert stats["dispatches_per_chunk"] == 1.0
        assert stats["wall_s"] > 0


def test_tiles_phase_misses_then_hits(smoke):
    report, failures = smoke.phase_tiles(max_dwell=64, depth=2, chunk=4)
    assert failures == []
    first, replay = report["first_pass"], report["replay"]
    assert report["distinct_tiles"] >= 16  # 4**2 from the pan + the zoom
    assert first["misses"] == report["distinct_tiles"]
    assert replay["misses"] == 0 and replay["dispatches"] == 0
    assert replay["hits"] == first["hits"] + first["misses"]


def test_mesh_phase_on_one_device(smoke):
    report, failures = smoke.phase_mesh(devices=1, n=256, max_dwell=64,
                                        frames_per_device=2)
    assert failures == []
    for engine in smoke.ENGINES:
        assert report[f"{engine}/differing_pixels"] == 0


def test_a_wrong_pixel_fails_the_phase(smoke, monkeypatch):
    real = smoke.reference

    def off_by_one(problem, bounds):
        canvas = np.array(real(problem, bounds))
        canvas[0, 0] += 1
        return canvas

    monkeypatch.setattr(smoke, "reference", off_by_one)
    _, failures = smoke.phase_frames(n=256, max_dwell=64, frames=2, chunk=2)
    assert len(failures) == len(smoke.ENGINES)
    assert all("2 pixels differ" in f for f in failures)


def test_main_refuses_without_a_tpu(smoke, capsys):
    assert smoke.main([]) != 0
    captured = capsys.readouterr()
    assert captured.out == ""  # no result line, no phase output
    assert "no TPU" in captured.err
