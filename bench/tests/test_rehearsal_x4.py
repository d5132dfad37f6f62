"""The four-chip cell ``zoom4k_x4.interleaved`` end to end on the CPU at a
tiny size, on four virtual devices: traffic, the sharded served path,
the check and every metric reader the cell's entries name.

XLA fixes the device count when JAX starts, so each run is a process of
its own with ``--xla_force_host_platform_device_count=4``."""

import json
import os
import subprocess
import sys
import textwrap

import pytest

from bench.tests import tiny

# like ``tiny.SHRINK["zoom4k"]``, with a video that deals into whole
# 16-frame chunks (4 frames a device)
SHRINK_X4 = {"n": 64, "B": 8, "max_dwell": 64, "frames": 32}

_CELL = """
    import json, sys, time
    from pathlib import Path

    import jax
    from bench import run as bench_run
    from bench import spec
    from bench.tests import tiny

    def fake_reduce(path):
        # the CPU has no device plane: a reduction of the right shape
        assert path is not None and path.exists()
        return {{"window_s": 2.0, "busy_s": 0.5, "idle_share": 0.75,
                 "devices": 4, "device_ops": [["fusion", 0.5]],
                 "idle_gaps": [["bench.stream_next", 1.5]]}}

    root = tiny.make_root(Path({tmp!r}))
    path = root / "bench" / "configs" / "zoom4k_x4.json"
    path.write_text(json.dumps({{**json.loads(path.read_text()),
                                 **{shrink!r}}}))
    cell = spec.load_cell("zoom4k_x4.interleaved", root)
    assert cell.chips == 4 and len(jax.devices()) == 4
    line = bench_run.run_cell(cell, seed=2 ** 31 + 7, seconds=2.0,
                              trace=bool({trace}), devices=jax.devices()[:4],
                              t0=time.monotonic(), reduce=fake_reduce)
    want = cell.per_layer if {trace} else cell.end_to_end
    print("WANT " + json.dumps([m.name for m in want]))
    print("LINE " + json.dumps(line))
"""


def _run_cell(tmp_path, trace: int):
    env = {**os.environ, "JAX_PLATFORMS": "cpu",
           "XLA_FLAGS": "--xla_force_host_platform_device_count=4",
           "PYTHONPATH": os.pathsep.join([str(tiny.ROOT),
                                          str(tiny.ROOT / "src")])}
    code = textwrap.dedent(_CELL.format(tmp=str(tmp_path), trace=trace,
                                        shrink=SHRINK_X4))
    p = subprocess.run([sys.executable, "-c", code], cwd=tiny.ROOT, env=env,
                       capture_output=True, text=True, timeout=600)
    assert p.returncode == 0, p.stderr[-4000:]
    out = {k: json.loads(v) for k, v in
           (ln.split(" ", 1) for ln in p.stdout.splitlines()
            if ln.startswith(("WANT ", "LINE ")))}
    notes = [json.loads(ln[len("bench: "):]) for ln in p.stderr.splitlines()
             if ln.startswith("bench: {")]
    return out["WANT"], out["LINE"], notes[-1]


@pytest.mark.parametrize("trace", [0, 1])
def test_four_chip_cell_end_to_end(tmp_path, trace):
    want, line, notes = _run_cell(tmp_path, trace)
    assert line["correct"] is True, line["check"]
    assert line["failed"] == 0 and line["attempted"] > 0
    assert line["check"]["unanswered"]["value"] == 0
    assert line["device"]["count"] == 4
    assert notes["compiles_in_window"] == 0
    # whole 16-frame chunks only
    assert notes["frames"] > 0 and notes["frames"] % 16 == 0
    assert want and set(line["metrics"]) == set(want)
    for name in want:
        assert line["metrics"][name]["value"] > 0, name
    if trace:
        assert "ring_fill_share" in want and "enqueue_ms.stream" in want
    else:
        assert set(want) == {"frames_per_s", "setup_s"}
