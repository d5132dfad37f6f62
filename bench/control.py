"""The control of ``correct``: the plain reference put in the program's
place, computed in bfloat16, the precision below the float32 that the
configurations state. The check has to refuse it.

    python3 -m bench.control --workload zoom4k.interleaved --seeds 1,2,3 --answers 28 --seconds 40

For each seed it makes the answers a run's window would be judged on
(the first ``--answers`` frames after the warm-up of a frame stream, or
every tile of every viewport due in ``--seconds`` of a slippy-map
session mix), renders each with the reference in bfloat16 in place of
the program, and prints the compared numbers beside their limits. The
benchmark's own runs never run it. On the chip, at the cell's own size.
"""

from __future__ import annotations

import argparse
import json
import sys

import jax.numpy as jnp

from bench import check, generator
from bench import run as bench_run
from bench import spec


def answers(cell, seed: int, count: int, seconds: float):
    """The windows one run of ``cell`` with ``seed`` is judged on."""
    config, traffic = cell.config, cell.traffic
    if config["system"] == "frame_stream":
        chunk = int(config.get("frames_per_device", 4)) * cell.chips
        plan = generator.frame_plan(config, traffic, seed, chunk=chunk)
        return [check.Answer(w) for w in
                plan.windows[plan.warmup:plan.warmup + count]]
    plan = generator.viewport_plan(config, traffic, seed, seconds)
    return [check.Answer(generator.tile_window(config, t))
            for r in plan.window for t in generator.viewport_tiles(r)]


def control(cell, seed: int, count: int, seconds: float,
            dtype=jnp.bfloat16) -> check.Verdict:
    got = answers(cell, seed, count, seconds)
    for a in got:
        a.canvas = "control"  # answered: by the reference in ``dtype``
    return check.judge(got, cell.config, dtype=dtype,
                       block=int(cell.config.get("reference_block", 1)))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--answers", type=int, default=28,
                    help="frames judged, for a frame stream")
    ap.add_argument("--seconds", type=float, default=40.0)
    args = ap.parse_args(argv)
    cell = spec.load_cell(args.workload, bench_run.ROOT)
    devices, why = bench_run._chips(cell)
    if devices is None:
        print(f"bench.control: {why}", file=sys.stderr)
        return 2
    bench_run._compile_cache(bench_run.ROOT)
    for seed in (int(s) for s in args.seeds.split(",")):
        v = control(cell, seed, args.answers, args.seconds)
        print(json.dumps({"seed": seed, "correct": v.correct,
                          "answers": v.answers, "check": v.as_json()}),
              flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
