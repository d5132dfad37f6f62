"""Each cell end to end on the CPU at a tiny size: traffic, the served
path, the check and every metric reader. The measurement command itself
refuses the CPU."""

import json
import os
import subprocess
import sys
import time

import jax
import pytest

from bench import run as bench_run
from bench import spec, sweep, systems
from bench.tests import tiny

CELLS = ["zoom4k.interleaved", "tiles256.zipf"]


def _fake_reduce(path):
    """The CPU has no device plane: a reduction of the right shape."""
    assert path is not None and path.exists()
    return {"window_s": 2.0, "busy_s": 0.5, "idle_share": 0.75,
            "devices": 1, "device_ops": [["fusion", 0.5]],
            "idle_gaps": [["bench.serve", 1.5]]}


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return tiny.make_root(tmp_path_factory.mktemp("checkout"))


@pytest.mark.parametrize("trace", [0, 1])
@pytest.mark.parametrize("name", CELLS)
def test_cell_end_to_end(root, name, trace):
    cell = spec.load_cell(name, root)
    line = bench_run.run_cell(cell, seed=2 ** 31 + 99, seconds=2.0,
                              trace=bool(trace), devices=jax.devices()[:1],
                              t0=time.monotonic(), reduce=_fake_reduce)
    assert line["correct"] is True, line["check"]
    assert line["failed"] == 0 and line["attempted"] > 0
    want = cell.per_layer if trace else cell.end_to_end
    assert set(line["metrics"]) == {m.name for m in want}
    for m in want:
        assert line["metrics"][m.name]["unit"] == m.unit
        assert line["metrics"][m.name]["value"] > 0
    assert list(line)[-1] == "check"
    assert set(line["device"]) >= {"platform", "kind", "count",
                                   "memory_peak_bytes"}
    if trace:
        assert line["device"]["busy_s"] == 0.5
        assert line["breakdown"]["idle_gaps"] == [["bench.serve", 1.5]]


def test_no_compiles_in_the_tile_window(root):
    cell = spec.load_cell("tiles256.zipf", root)
    sut = systems.TileServer(cell.config, cell.traffic, 1, 5,
                             systems.Spans(False), 2.0)
    counter = systems.CompileCounter()
    sut.warmup()
    before = counter.count
    run = systems.Run(system="tile_server")
    sut.window(2.0, run)
    assert run.latencies_ms
    assert counter.count == before


def test_knee_sweep_rows(root):
    cell = spec.load_cell("tiles256.zipf", root)
    sut = systems.TileServer(cell.config, cell.traffic, 1, 5,
                             systems.Spans(False), 1.0)
    sut.prime()
    rows = list(sweep.sweep(sut, cell, [5.0, 10.0], 1.0, 5))
    assert [r["rate_per_s"] for r in rows] == [5.0, 10.0]
    assert all(r["requests"] > 0 for r in rows)


def _command(cwd, *extra):
    env = {**os.environ, "JAX_PLATFORMS": "cpu", "PYTHONPATH": ""}
    return subprocess.run(
        [sys.executable, "-m", "bench.run", "--workload",
         "zoom4k.interleaved", "--seed", "1", "--seconds", "1", "--trace",
         "0", *extra], cwd=cwd, env=env, capture_output=True, text=True,
        timeout=300)


def test_command_refuses_the_cpu():
    p = _command(tiny.ROOT)
    assert p.returncode != 0
    assert "no TPU" in p.stderr
    assert not p.stdout.strip()


def test_command_needs_the_program(tmp_path):
    """In a directory with only BENCHMARK.json and the benchmark's own
    files, the command fails before any result."""
    import shutil

    shutil.copy(tiny.ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(tiny.ROOT / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    p = _command(tmp_path)
    assert p.returncode != 0
    assert not p.stdout.strip()


def test_new_parts_are_found_by_name(tmp_path):
    """A configuration, a traffic mix and a metric added as files, with
    entries in BENCHMARK.json, and nothing else edited."""
    root = tiny.make_root(tmp_path)
    bench = json.loads((root / "BENCHMARK.json").read_text())
    cfg = json.loads((root / "bench/configs/zoom4k.json").read_text())
    cfg.update(name="zoom_julia", workload="julia")
    (root / "bench/configs/zoom_julia.json").write_text(json.dumps(cfg))
    trf = json.loads((root / "bench/traffic/interleaved.json").read_text())
    trf["jitter_px"] = 0
    (root / "bench/traffic/steady.json").write_text(json.dumps(trf))
    (root / "bench/metrics/chunks_done.py").write_text(
        "def read(run):\n    return float(len(run.chunks)) or None\n")
    bench["configs"].append({"name": "zoom_julia", "source": "x",
                             "file": "bench/configs/zoom_julia.json",
                             "reduced": [], "why": "x"})
    bench["workloads"].append({"name": "zoom_julia.steady",
                               "config": "zoom_julia", "traffic": "steady",
                               "chips": 1, "why": "x"})
    bench["per_layer"].append({"name": "chunks_done", "unit": "chunks",
                               "better": "higher",
                               "source": "program_counter",
                               "layer": "engine", "moves": "frames_per_s",
                               "workloads": ["zoom_julia.steady"]})
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    cell = spec.load_cell("zoom_julia.steady", root)
    assert cell.config["workload"] == "julia"
    assert cell.traffic["jitter_px"] == 0
    assert [m.name for m in cell.per_layer] == ["chunks_done"]
    line = bench_run.run_cell(cell, seed=3, seconds=1.0, trace=True,
                              devices=jax.devices()[:1],
                              t0=time.monotonic(), reduce=_fake_reduce)
    assert line["correct"] is True
    assert line["metrics"]["chunks_done"]["value"] > 0
