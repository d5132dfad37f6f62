"""Find the knee of an open-loop cell: the highest offered rate at which
the generator's lateness does not grow over a window.

    python3 -m bench.sweep --workload tiles256.zipf --rates 40,80,120 --seconds 20 --seed 7

One process, on the chip: the cell is set up once, then each rate gets
the warm-up its traffic asks for and one window. For each rate it
prints the requests served, the median lateness of the window's first
and last thirds (how late the generator got to each request), the 95th
percentile latency and the hit share. The knee is the highest rate
whose last third runs no later than its first third by more than
``--grow-ms``. A cell's traffic file then fixes its rate at 0.8 x the
knee; the benchmark itself never searches for one.
"""

from __future__ import annotations

import argparse
import json
import sys
import time

from bench import run as bench_run
from bench import spec


def sweep(sut, cell, rates, seconds: float, seed: int):
    """Yield one row per offered rate, served by the set-up ``sut``."""
    import numpy as np

    from bench import generator, systems

    for rate in rates:
        traffic = {**cell.traffic, "rate_per_s": rate}
        sut.plan = generator.viewport_plan(cell.config, traffic, seed,
                                           seconds)
        for req in sut.plan.warmup:
            sut._serve(req)
        run = systems.Run(system="tile_server")
        sut.window(seconds, run, grace_s=seconds)
        late = run.lateness_ms
        third = max(1, len(late) // 3)
        yield {"rate_per_s": rate, "requests": len(late),
               "lateness_first_ms": float(np.median(late[:third])),
               "lateness_last_ms": float(np.median(late[-third:])),
               "p95_ms": float(np.percentile(run.latencies_ms, 95)),
               "hit_share": run.hits / max(1, run.hits + run.misses),
               "window_s": run.window_s}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True, help="ascending, comma-separated")
    ap.add_argument("--seconds", type=float, default=20.0)
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument("--grow-ms", type=float, default=20.0)
    args = ap.parse_args(argv)
    t0 = time.monotonic()
    cell = spec.load_cell(args.workload, bench_run.ROOT)
    devices, why = bench_run._chips(cell)
    if devices is None:
        print(f"bench.sweep: {why}", file=sys.stderr)
        return 2
    bench_run._compile_cache(bench_run.ROOT)
    sys.path.insert(0, str(cell.root / "src"))
    from bench import systems

    if cell.config["system"] != "tile_server":
        print("bench.sweep: only open-loop cells have a knee",
              file=sys.stderr)
        return 2
    sut = systems.TileServer(cell.config, cell.traffic, cell.chips,
                             args.seed, systems.Spans(False), args.seconds)
    sut.prime()
    print(json.dumps({"setup_s": time.monotonic() - t0}), flush=True)
    rates = [float(r) for r in args.rates.split(",")]
    knee = None
    for row in sweep(sut, cell, rates, args.seconds, args.seed):
        print(json.dumps(row), flush=True)
        if row["lateness_last_ms"] - row["lateness_first_ms"] > args.grow_ms:
            break  # the queue grows: every higher rate is past the knee
        knee = row["rate_per_s"]
    print(json.dumps({"knee_per_s": knee,
                      "rate_at_0.8_knee": None if knee is None
                      else 0.8 * knee}), flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
