"""A checkout in miniature for the CPU tests: the real ``BENCHMARK.json``
and the real traffic mixes and metric readers, with each configuration
cut to a size the CPU renders in a second or two.

The slippy-map cell is not in ``BENCHMARK.json`` yet (its tail spreads
too widely, see PERF.md); ``TILE_ENTRIES`` adds it here so that its
system, traffic and readers stay rehearsed for the cell that follows."""

from __future__ import annotations

import json
import shutil
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]

SHRINK = {
    "zoom4k": {"n": 64, "B": 8, "max_dwell": 64, "frames": 16},
    "tiles256": {"n": 32, "B": 4, "max_dwell": 64, "max_zoom": 8,
                 "dense_tiles": [[3, 3, 1], [4, 7, 3]]},
}
TRAFFIC = {"zipf": {"rate_per_s": 20, "lead_s": 5, "warmup_requests": 10}}

TILE_ENTRIES = {
    "configs": [{"name": "tiles256", "source": "OpenStreetMap tiles",
                 "file": "bench/configs/tiles256.json",
                 "reduced": ["max_zoom"], "why": "slippy-map tiles"}],
    "workloads": [{"name": "tiles256.zipf", "config": "tiles256",
                   "traffic": "zipf", "chips": 1, "why": "map sessions"}],
    "end_to_end": [{"name": "request_p95_ms", "unit": "ms",
                    "better": "lower", "bound": 0.25,
                    "source": "host_clock", "workloads": ["tiles256.zipf"]}],
    "per_layer": [
        {"name": name, "unit": unit, "better": better, "source": source,
         "layer": layer, "moves": "request_p95_ms",
         "workloads": ["tiles256.zipf"]}
        for name, unit, better, source, layer in (
            ("tile_hit_share", "%", "higher", "program_counter",
             "tile cache"),
            ("enqueue_ms.tiles", "ms", "lower", "program_span",
             "render service host path"),
            ("device_idle_share.tiles", "%", "lower", "device_trace",
             "device"))],
}


def make_root(tmp: Path) -> Path:
    """A checkout under ``tmp`` whose configurations are the real ones
    shrunk by ``SHRINK``; ``src`` links to the real program."""
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    for key, entries in TILE_ENTRIES.items():
        known = {e["name"] for e in bench[key]}
        bench[key] += [e for e in entries if e["name"] not in known]
    (tmp / "bench").mkdir()
    for sub in ("traffic", "metrics"):
        shutil.copytree(ROOT / "bench" / sub, tmp / "bench" / sub)
    for name, patch in TRAFFIC.items():
        path = tmp / "bench" / "traffic" / f"{name}.json"
        path.write_text(json.dumps({**json.loads(path.read_text()),
                                    **patch}))
    (tmp / "bench" / "configs").mkdir()
    for c in bench["configs"]:
        cfg = json.loads((ROOT / c["file"]).read_text())
        cfg.update(SHRINK.get(c["name"], {}))
        (tmp / c["file"]).write_text(json.dumps(cfg))
    (tmp / "BENCHMARK.json").write_text(json.dumps(bench))
    (tmp / "src").symlink_to(ROOT / "src")
    return tmp
