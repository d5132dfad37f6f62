"""Run one benchmark cell on the chip and print its result line.

    python3 -m bench.run --workload <cell> --seed <n> --seconds <s> --trace <0|1>

From the root of a checkout. The cell names a configuration
(``bench/configs/``) and a traffic mix (``bench/traffic/``); the
configuration's ``system`` says which deployment of the program to
build (``bench.systems``). The run:

1. finds the chips (a TPU, as many as the cell asks for, and a device
   kind in ``bench/peaks.json``) or exits 2 with no result;
2. sets up: JAX's compile cache at ``.jax_cache/`` in the checkout, the
   service, the traffic from ``--seed``, the warm-up. ``setup_s`` runs
   from process start to the start of the window;
3. measures for ``--seconds``, with the profiler on and host spans
   around each call into the program when ``--trace 1``;
4. reads the peak device memory, frees the program's state, and
   compares every answer of the window with the plain reference
   (``bench.check``);
5. prints the compared numbers with their limits as the last lines of
   standard error, and one JSON object as the last line of standard
   output: ``correct``, ``attempted``, ``failed``, ``metrics``,
   ``device``, with ``--trace 1`` a ``breakdown``, and last ``check``.
"""

from __future__ import annotations

import time

_T0 = time.monotonic()  # process start, as near as Python gets to it

import argparse  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

from bench import spec  # noqa: E402


def _fail(msg: str, code: int = 2) -> int:
    print(f"bench: {msg}", file=sys.stderr, flush=True)
    return code


def _chips(cell, *, platform: str = "tpu"):
    """The devices this cell runs on, or a reason why there are none."""
    import jax

    devices = jax.devices()
    if devices[0].platform != platform:
        return None, (f"no TPU: JAX runs on {devices[0].platform!r}; this "
                      "benchmark measures the chip and has no CPU fallback")
    if len(devices) < cell.chips:
        return None, (f"the cell asks for {cell.chips} chips, JAX sees "
                      f"{len(devices)}")
    peaks = json.loads((Path(__file__).parent / "peaks.json").read_text())
    if devices[0].device_kind not in peaks["devices"]:
        return None, (f"device kind {devices[0].device_kind!r} is not in "
                      "bench/peaks.json")
    return devices[:cell.chips], None


def _compile_cache(root: Path) -> None:
    """JAX's persistent compile cache at a fixed path in the checkout,
    every program in it, so that only a cell's first run compiles."""
    import jax

    cache = root / ".jax_cache"
    cache.mkdir(exist_ok=True)
    jax.config.update("jax_compilation_cache_dir", str(cache))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


def run_cell(cell, *, seed: int, seconds: float, trace: bool,
             devices, t0: float, reduce=None) -> dict:
    """Set up, measure and check one cell; returns the result line.
    ``reduce`` turns a trace file into device numbers
    (``trace_reduce.reduce_file``)."""
    import jax

    from bench import check, systems, trace_reduce

    src = cell.root / "src"
    if str(src) not in sys.path:
        sys.path.insert(0, str(src))
    config, traffic = cell.config, cell.traffic
    spans = systems.Spans(trace)
    compiles = systems.CompileCounter()
    build = systems.SYSTEMS[config["system"]]
    sut = build(config, traffic, cell.chips, seed, spans, seconds)
    sut.warmup()
    run = systems.Run(system=config["system"])
    trace_dir = tempfile.mkdtemp(prefix="bench-trace-") if trace else None
    if trace:
        jax.profiler.start_trace(trace_dir)
    run.notes["setup_compile"] = compiles.by_event()
    # the harness's own set-up objects stay out of the collector's scans
    gc.collect()
    gc.freeze()
    run.setup_s = time.monotonic() - t0
    before = compiles.count
    sut.window(seconds, run)
    run.compiles_in_window = compiles.count - before
    gc.unfreeze()
    if trace:
        jax.profiler.stop_trace()
        try:
            run.trace = (reduce or trace_reduce.reduce_file)(
                trace_reduce.find_trace(trace_dir))
        finally:
            shutil.rmtree(trace_dir, ignore_errors=True)
    stats = [d.memory_stats() or {} for d in devices]
    peak = max(int(s.get("peak_bytes_in_use", 0)) for s in stats)
    sut.close()
    del sut
    gc.collect()
    verdict = check.judge(run.answers, config,
                          block=int(config.get("reference_block", 1)))
    if verdict.reference_renders:
        run.reference_s_per_answer = (verdict.reference_s
                                      / verdict.reference_renders)
    kind = "per_layer" if trace else "end_to_end"
    metrics = spec.read_metrics(getattr(cell, kind), run, cell.root)
    device = {"platform": devices[0].platform,
              "kind": devices[0].device_kind, "count": len(devices),
              "memory_peak_bytes": peak}
    line = {"correct": verdict.correct, "attempted": len(run.answers),
            "failed": sum(a.canvas is None for a in run.answers),
            "metrics": metrics, "device": device}
    if trace and run.trace is not None:
        device["busy_s"] = run.trace["busy_s"]
        device["window_s"] = run.trace["window_s"]
        line["breakdown"] = {"device_ops": run.trace["device_ops"],
                             "idle_gaps": run.trace["idle_gaps"]}
    _notes(run, verdict)
    line["check"] = verdict.as_json()
    return line


def _notes(run, verdict) -> None:
    """What the run saw besides its metrics, on standard error."""
    lat = sorted(run.lateness_ms)
    notes = {"window_s": run.window_s, "frames": run.frames,
             "requests": len(run.latencies_ms),
             "generator_lateness_ms_max": lat[-1] if lat else None,
             "generator_lateness_ms_median": lat[len(lat) // 2] if lat
             else None,
             "compiles_in_window": run.compiles_in_window,
             "chunks": len(run.chunks), "hits": run.hits,
             "misses": run.misses, "answers": verdict.answers,
             "reference_s_per_answer": run.reference_s_per_answer,
             "setup_s": run.setup_s, **run.notes}
    print("bench: " + json.dumps(notes), file=sys.stderr, flush=True)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    if not (ROOT / "src" / "repro").is_dir():
        return _fail(f"no program under test at {ROOT / 'src'}")
    cell = spec.load_cell(args.workload, ROOT)
    devices, why = _chips(cell)
    if devices is None:
        return _fail(why)
    _compile_cache(ROOT)
    from bench import check

    line = run_cell(cell, seed=args.seed, seconds=args.seconds,
                    trace=bool(args.trace), devices=devices, t0=_T0)
    check.report_lines(line["check"])
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
