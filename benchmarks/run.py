"""Benchmark harness: one suite per paper table/figure.

Prints ``name,case,value`` CSV rows plus a ``suite_<x>,us_per_call,<t>``
summary per suite. Suites:

  cost_model  -> Fig. 3 (Omega) + Fig. 4 (theoretical SBR/MBR speedup)
  mandelbrot  -> Fig. 8 (measured Ex/DP/ASK speedups) + Table 2 analogue
  ask_scan    -> lambda-reduction ladder: ex/dp/ask/ask_fused/ask_scan
                 dispatches, OLT memory, wall time + batched frame serving
  landscape   -> Fig. 7 ({g,r,B} landscape, measured vs model)
  moe         -> beyond-paper: OLT-dispatch MoE
  flops       -> analytic flops/bytes model rows (deterministic; gated
                 against BENCH_FLOPS.json via compare_bench exact_ fields)
  roofline    -> deliverable (g): printed from experiments/dryrun if present

``python -m benchmarks.run [--suite X] [--full] [--json PATH]
[--json-pooled PATH] [--json-tiles PATH] [--json-pooled-tuned PATH]``

``--json PATH`` (ask_scan suite) additionally writes the machine-readable
tuned-tier comparison (``BENCH_6.json`` schema), ``--json-pooled PATH``
the pooled-vs-planned comparison (``BENCH_7.json`` schema),
``--json-tiles PATH`` the tile-cache serving comparison (``BENCH_9.json``
schema), and ``--json-pooled-tuned PATH`` the pooled-engine jnp-vs-tuned
comparison (``BENCH_10.json`` schema); CI's ``benchmarks.compare_bench``
gate diffs each against the checked-in baselines.
"""

from __future__ import annotations

import argparse
import time


def main(argv=None) -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--suite", default="all",
                    choices=("all", "cost_model", "mandelbrot", "ask_scan",
                             "landscape", "moe", "flops", "roofline"))
    ap.add_argument("--full", action="store_true")
    ap.add_argument("--json", default=None, metavar="PATH",
                    help="write the tuned-tier BENCH json (ask_scan suite)")
    ap.add_argument("--json-pooled", default=None, metavar="PATH",
                    help="write the pooled-tier BENCH json (ask_scan suite)")
    ap.add_argument("--json-tiles", default=None, metavar="PATH",
                    help="write the tile-cache BENCH json (ask_scan suite)")
    ap.add_argument("--json-pooled-tuned", default=None, metavar="PATH",
                    help="write the pooled-tuned BENCH json (ask_scan suite)")
    args = ap.parse_args(argv)

    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()

    def writer(name, case, value):
        print(f"{name},{case},{value}", flush=True)

    print("name,case,value")
    suites = []
    if args.suite in ("all", "cost_model"):
        from benchmarks import bench_cost_model
        suites.append(("cost_model", lambda: bench_cost_model.run(writer)))
    if args.suite in ("all", "mandelbrot"):
        from benchmarks import bench_mandelbrot
        suites.append(("mandelbrot",
                       lambda: bench_mandelbrot.run(writer, full=args.full)))
    if args.suite in ("all", "ask_scan"):
        from benchmarks import bench_ask_scan
        suites.append(("ask_scan",
                       lambda: bench_ask_scan.run(
                           writer, full=args.full, bench_json=args.json,
                           bench_json_pooled=args.json_pooled,
                           bench_json_tiles=args.json_tiles,
                           bench_json_pooled_tuned=args.json_pooled_tuned)))
    if args.suite in ("all", "landscape"):
        from benchmarks import bench_landscape
        suites.append(("landscape",
                       lambda: bench_landscape.run(writer, full=args.full)))
    if args.suite in ("all", "moe"):
        from benchmarks import bench_moe_dispatch
        suites.append(("moe", lambda: bench_moe_dispatch.run(writer)))
    if args.suite in ("all", "flops"):
        from benchmarks import bench_flops
        suites.append(("flops", lambda: bench_flops.run(writer)))

    for name, fn in suites:
        t0 = time.perf_counter()
        fn()
        print(f"suite_{name},us_per_call,"
              f"{(time.perf_counter() - t0) * 1e6:.0f}", flush=True)

    if args.suite in ("all", "roofline"):
        from pathlib import Path
        if Path("experiments/dryrun").exists() and \
                any(Path("experiments/dryrun").glob("*.json")):
            from benchmarks import roofline
            roofline.main(["--csv", "experiments/roofline.csv",
                           "--json", "experiments/roofline.json"])
        else:
            print("roofline,skipped,no dry-run artifacts "
                  "(run python -m repro.launch.dryrun --all first)")


if __name__ == "__main__":
    main()
