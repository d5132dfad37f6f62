"""Autotune harness for the ``tuned`` kernel tier.

The paper's cost model (Sec. 5) predicts *how many* dispatches ASK needs;
it says nothing about how fast each dispatch's kernel runs — that is pure
scheduling (block shape, grid, escape-loop unroll) and is exactly the kind
of knob an autotuner sweeps. This module is that sweep:

* ``tune(kernel, ...)`` times every candidate (impl, params) combination
  for one kernel under one static-shape signature and records the winner;
* ``tune_problem(problem, ...)`` walks a ``FrameProblem``'s subdivision
  chain and tunes every kernel the ask pipeline will dispatch (flat dwell
  at ``n``, perimeter query / region dwell at every level side, OLT
  compaction at every ring capacity);
* ``TuningCache`` persists winners as JSON, **keyed like the compile
  cache**: the cache key is built from the same static arguments that key
  ``core.ask``'s jitted-pipeline cache (kernel name, workload name, dtype,
  platform, and the per-kernel static shape signature), so one cache entry
  corresponds to exactly one compiled kernel variant;
* ``choose(kernel, ...)`` is the trace-time lookup ``kernels.ops`` calls
  when ``KernelPolicy.backend == TUNED``: cache hit -> the measured
  winner; cache cold -> ``heuristic()``, a measured-once-then-hardcoded
  rule table (the xFormers pattern: ship heuristics, let users re-tune).

Everything here happens at **trace time** with static Python values, so
the tuned tier adds zero runtime overhead — the choice is burned into the
jitted pipeline exactly like any other static argument.

Candidate axes (all bit-identity-preserving — see ``ref.escape_time``):

* ``impl``: ``jnp`` (XLA fusion) vs ``pallas`` (explicit blocking);
* ``block``: VMEM tile shape for ``dwell`` / block length for
  ``olt_compact``;
* ``unroll``: escape-loop grouping factor (same masked step sequence, so
  dwell output is bit-identical for any value).

CLI (the CI autotune-smoke job)::

    python -m repro.kernels.autotune --tiny --n 128 --max-dwell 32 \
        --out tuning-cache.json
"""

from __future__ import annotations

import dataclasses
import json
import os
import time
from typing import Any, Dict, Mapping, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.kernels import policy as policy_lib

__all__ = [
    "CACHE_VERSION",
    "Choice",
    "TuningCache",
    "cache_key",
    "choose",
    "clear_memo",
    "heuristic",
    "tune",
    "tune_problem",
]

CACHE_VERSION = 1

# Kernels the harness knows how to time. `batched_ranks` and `region_fill`
# are pure data movement with no candidate axis beyond impl, so they get
# heuristic-only routing (still cacheable for forward compatibility).
# The *_pooled pair are the banded cross-frame kernels: their signature
# carries the frame count F, so one cache entry per (side, n, F) variant.
_TUNABLE = ("dwell", "perimeter_query", "region_dwell", "olt_compact",
            "region_fill_pooled", "region_dwell_pooled")


# ---------------------------------------------------------------------------
# Choice: one resolved (impl, params) decision


@dataclasses.dataclass(frozen=True)
class Choice:
    """One routing decision: which lowering and which schedule params.

    ``params`` is a sorted tuple of (name, value) pairs — hashable, so a
    Choice can ride inside jit static arguments. ``source`` records where
    the decision came from (``heuristic`` / ``cache`` / ``measured``);
    ``us`` is the measured wall time in microseconds when available.
    """

    impl: str  # "jnp" | "pallas"
    params: Tuple[Tuple[str, Any], ...] = ()
    source: str = "heuristic"
    us: Optional[float] = None

    def __post_init__(self):
        if self.impl not in ("jnp", "pallas"):
            raise ValueError(f"impl must be 'jnp' or 'pallas', got {self.impl!r}")
        frozen = tuple(sorted(
            (str(k), tuple(v) if isinstance(v, list) else v)
            for k, v in (dict(self.params).items()
                         if not isinstance(self.params, tuple)
                         else self.params)))
        object.__setattr__(self, "params", frozen)

    def param_dict(self) -> Dict[str, Any]:
        return dict(self.params)

    def to_entry(self) -> Dict[str, Any]:
        return {
            "impl": self.impl,
            "params": {k: (list(v) if isinstance(v, tuple) else v)
                       for k, v in self.params},
            "source": self.source,
            "us": self.us,
        }

    @classmethod
    def from_entry(cls, entry: Mapping[str, Any]) -> "Choice":
        params = tuple(sorted(
            (k, tuple(v) if isinstance(v, list) else v)
            for k, v in dict(entry.get("params", {})).items()))
        return cls(impl=entry["impl"], params=params,
                   source=entry.get("source", "cache"),
                   us=entry.get("us"))


# ---------------------------------------------------------------------------
# Cache keys — the same statics that key the compile cache


def cache_key(kernel: str, *, workload=None, dtype: str = "int32",
              **sig: Any) -> str:
    """Stable string key for one compiled kernel variant.

    Mirrors ``core.ask._PIPELINE_CACHE``'s keying discipline: every static
    argument that selects a distinct compiled artifact appears in the key —
    kernel name, workload identity, canvas dtype, the JAX platform the
    timing ran on, and the kernel's static shape signature (n, side,
    max_dwell, ...). Two calls that would hit the same compiled kernel hit
    the same tuning entry.
    """
    wl = getattr(workload, "name", workload) or "mandelbrot"
    parts = [kernel, f"wl={wl}", f"dtype={dtype}",
             f"plat={jax.default_backend()}"]
    for k in sorted(sig):
        v = sig[k]
        if isinstance(v, (tuple, list)):
            v = "x".join(str(x) for x in v)
        parts.append(f"{k}={v}")
    return "|".join(parts)


# ---------------------------------------------------------------------------
# TuningCache: JSON persistence


class TuningCache:
    """Measured winners, persisted as versioned JSON.

    Format::

        {"version": 1,
         "entries": {"<cache_key>": {"impl": ..., "params": {...},
                                     "source": ..., "us": ...}, ...}}
    """

    def __init__(self, entries: Optional[Dict[str, Choice]] = None):
        self.entries: Dict[str, Choice] = dict(entries or {})

    def get(self, key: str) -> Optional[Choice]:
        return self.entries.get(key)

    def put(self, key: str, choice: Choice) -> None:
        self.entries[key] = choice

    def to_json(self) -> str:
        return json.dumps(
            {"version": CACHE_VERSION,
             "entries": {k: c.to_entry()
                         for k, c in sorted(self.entries.items())}},
            indent=2, sort_keys=True)

    @classmethod
    def from_json(cls, text: str) -> "TuningCache":
        data = json.loads(text)
        version = data.get("version")
        if version != CACHE_VERSION:
            raise ValueError(
                f"tuning cache version {version!r} != {CACHE_VERSION}; "
                "re-run `python -m repro.kernels.autotune` to regenerate")
        return cls({k: Choice.from_entry(e)
                    for k, e in data.get("entries", {}).items()})

    def save(self, path: str) -> None:
        with open(path, "w") as fh:
            fh.write(self.to_json() + "\n")

    @classmethod
    def load(cls, path: str) -> "TuningCache":
        with open(path) as fh:
            return cls.from_json(fh.read())


# Trace-time memo: (cache_path_or_None, key) -> Choice. Keeps `choose` O(1)
# on the hot trace path and avoids re-reading the JSON file per dispatch.
_MEMO: Dict[Tuple[Optional[str], str], Choice] = {}
_FILE_CACHES: Dict[str, Optional[TuningCache]] = {}


def clear_memo() -> None:
    """Drop the in-process memo (tests / after re-tuning a cache file)."""
    _MEMO.clear()
    _FILE_CACHES.clear()


def _load_file_cache(path: str) -> Optional[TuningCache]:
    ap = os.path.abspath(path)
    if ap not in _FILE_CACHES:
        try:
            _FILE_CACHES[ap] = TuningCache.load(ap)
        except (OSError, ValueError, KeyError):
            _FILE_CACHES[ap] = None  # cold/corrupt cache -> heuristics
    return _FILE_CACHES[ap]


# ---------------------------------------------------------------------------
# Heuristics: the cold-cache fallback


def heuristic(kernel: str, *, workload=None, **sig: Any) -> Choice:
    """Rule table used when no tuning cache entry matches: the jnp (XLA)
    lowering for every kernel on every platform.

    The Pallas kernels do not lower for the TPU at the paper's region
    sides (their ``(side, side)`` blocks break the (8, 128) tiling, and
    Mosaic has no ``cumsum`` for the compaction), so a cold cache never
    routes to them; only a tuning-cache entry can. Off the TPU a mild
    escape-loop unroll is chosen (unroll=2 shaved ~15% off the XLA CPU
    while-loop on the seed workloads; deeper unrolls lost it again to
    code bloat). On the TPU no schedule has been measured, so none is
    chosen. Grid workloads have no escape loop.
    """
    if kernel not in policy_lib.KERNEL_NAMES:
        raise ValueError(f"unknown kernel {kernel!r}")
    escape_loop = kernel in ("dwell", "perimeter_query", "region_dwell",
                             "region_dwell_pooled")
    if (escape_loop and getattr(workload, "kind", "escape") != "grid"
            and jax.default_backend() != "tpu"):
        return Choice("jnp", (("unroll", 2),))
    return Choice("jnp")


def choose(kernel: str, *, workload=None, cache: Optional[str] = None,
           dtype: str = "int32", **sig: Any) -> Choice:
    """Trace-time routing decision for one kernel dispatch.

    Lookup order: in-process memo -> JSON tuning cache (when ``cache``
    names a readable file) -> ``heuristic()``. All arguments are static,
    so this runs during tracing only.
    """
    key = cache_key(kernel, workload=workload, dtype=dtype, **sig)
    memo_key = (os.path.abspath(cache) if cache else None, key)
    hit = _MEMO.get(memo_key)
    if hit is not None:
        return hit
    choice = None
    if cache:
        fc = _load_file_cache(cache)
        if fc is not None:
            stored = fc.get(key)
            if stored is not None:
                choice = dataclasses.replace(stored, source="cache")
    if choice is None:
        choice = heuristic(kernel, workload=workload, **sig)
    _MEMO[memo_key] = choice
    return choice


# ---------------------------------------------------------------------------
# Measurement


def _best_us(fn, reps: int = 3) -> float:
    fn()  # compile + warm
    best = float("inf")
    for _ in range(max(1, reps)):
        t0 = time.perf_counter()
        fn()
        best = min(best, (time.perf_counter() - t0) * 1e6)
    return best


def _candidates(kernel: str, *, workload=None, tiny: bool = False,
                **sig: Any):
    """Yield (impl, params-dict) candidates for one kernel signature."""
    if getattr(workload, "kind", "escape") == "grid":
        yield ("jnp", {})
        return
    unrolls = (1, 4) if tiny else (1, 2, 4, 8)
    if kernel == "dwell":
        n = int(sig["n"])
        blocks = [(b, b) for b in (64, 128, 256) if b <= n and n % b == 0]
        if tiny:
            blocks = blocks[-1:] or [(n, n)]
        for u in unrolls:
            yield ("jnp", {"unroll": u})
            for blk in blocks:
                yield ("pallas", {"block": blk, "unroll": u})
    elif kernel in ("perimeter_query", "region_dwell",
                    "region_dwell_pooled"):
        for u in unrolls:
            yield ("jnp", {"unroll": u})
            yield ("pallas", {"unroll": u})
    elif kernel == "region_fill_pooled":
        # pure data movement: impl is the only axis
        yield ("jnp", {})
        yield ("pallas", {})
    elif kernel == "olt_compact":
        n = int(sig["n"])
        yield ("jnp", {})
        if n <= 1 << 16:  # single-VMEM-block kernel cap (olt_compact.py)
            yield ("pallas", {})
        for blk in (1024, 4096):
            # ragged n is fine: the runner (like ops.compact_ranks) pads
            # flags to the block multiple and slices the ranks back
            if n > blk:
                yield ("pallas", {"block": blk})
    else:
        yield ("jnp", {})


def _build_runner(kernel: str, impl: str, params: Dict[str, Any], *,
                  workload=None, interpret: bool | None = None, **sig: Any):
    """Return a zero-arg callable that runs one candidate to completion.
    ``interpret=None`` resolves from the kernel policy (not-on-TPU) so a
    TPU tune sweep measures compiled kernels, not the interpreter."""
    if interpret is None:
        interpret = policy_lib.default_interpret()
    from repro.kernels import ref
    import numpy as np

    if kernel == "dwell":
        n = int(sig["n"])
        max_dwell = int(sig["max_dwell"])
        bounds = tuple(workload.default_bounds) if workload is not None \
            else ref.DEFAULT_BOUNDS
        if impl == "jnp":
            def run():
                ref.mandelbrot_ref(
                    n, bounds, max_dwell, workload=workload,
                    unroll=params.get("unroll", 1)).block_until_ready()
        else:
            from repro.kernels.mandelbrot_dwell import mandelbrot_dwell

            def run():
                mandelbrot_dwell(
                    n, bounds, max_dwell,
                    block=tuple(params.get("block", (256, 256))),
                    interpret=interpret, workload=workload,
                    unroll=params.get("unroll", 1)).block_until_ready()
        return run

    if kernel in ("perimeter_query", "region_dwell"):
        side = int(sig["side"])
        n = int(sig["n"])
        max_dwell = int(sig["max_dwell"])
        bounds = tuple(workload.default_bounds) if workload is not None \
            else ref.DEFAULT_BOUNDS
        regions = n // side
        rng = np.random.default_rng(0)
        N = min(64, regions * regions)
        coords = jnp.asarray(
            rng.integers(0, regions, size=(N, 2)), dtype=jnp.int32)
        u = params.get("unroll", 1)
        if kernel == "perimeter_query":
            if impl == "jnp":
                def run():
                    h, c = ref.perimeter_query_ref(
                        coords, side=side, n=n, bounds=bounds,
                        max_dwell=max_dwell, workload=workload, unroll=u)
                    h.block_until_ready()
            else:
                from repro.kernels.perimeter_query import perimeter_query

                def run():
                    h, c = perimeter_query(
                        coords, side=side, n=n, bounds=bounds,
                        max_dwell=max_dwell, interpret=interpret,
                        workload=workload, unroll=u)
                    h.block_until_ready()
            return run
        canvas = jnp.zeros((n, n), jnp.int32)
        ne = jnp.ones((), jnp.int32)
        if impl == "jnp":
            def run():
                ref.region_interior_ref(
                    coords, side=side, n=n, bounds=bounds,
                    max_dwell=max_dwell, workload=workload,
                    unroll=u).block_until_ready()
        else:
            from repro.kernels.region_dwell import region_dwell

            def run():
                region_dwell(
                    canvas, coords, ne, side=side, n=n, bounds=bounds,
                    max_dwell=max_dwell, interpret=interpret,
                    workload=workload, unroll=u).block_until_ready()
        return run

    if kernel == "olt_compact":
        n = int(sig["n"])
        rng = np.random.default_rng(0)
        flags = jnp.asarray(rng.integers(0, 2, size=n), dtype=jnp.int32)
        if impl == "jnp":
            def run():
                inc = jnp.cumsum(flags)
                (inc - flags).block_until_ready()
        elif "block" in params:
            from repro.kernels.olt_compact import compact_ranks_blocked
            blk = int(params["block"])
            # same padding ops.compact_ranks applies for ragged n, so the
            # timing covers the schedule the route will actually run
            pad = -n % blk
            flags_b = flags if pad == 0 else jnp.concatenate(
                [flags, jnp.zeros((pad,), flags.dtype)])

            def run():
                r, c = compact_ranks_blocked(
                    flags_b, block=blk, interpret=interpret)
                r[:n].block_until_ready()
        else:
            from repro.kernels.olt_compact import compact_ranks_kernel

            def run():
                r, c = compact_ranks_kernel(flags, interpret=interpret)
                r.block_until_ready()
        return run

    if kernel in ("region_fill_pooled", "region_dwell_pooled"):
        side = int(sig["side"])
        n = int(sig["n"])
        F = int(sig["F"])
        regions = n // side
        rng = np.random.default_rng(0)
        N = min(64, F * regions * regions)
        rows = jnp.asarray(np.stack([
            rng.integers(0, F, size=N),
            rng.integers(0, regions, size=N),
            rng.integers(0, regions, size=N)], axis=1), dtype=jnp.int32)
        canvas = jnp.zeros((F * n, n), jnp.int32)
        ne = jnp.ones((1,), jnp.int32)
        base = tuple(workload.default_bounds) if workload is not None \
            else ref.DEFAULT_BOUNDS
        bounds_all = jnp.tile(
            jnp.asarray(base, jnp.float32)[None, :], (F, 1))
        from repro.kernels import ops
        if kernel == "region_fill_pooled":
            values = jnp.asarray(
                rng.integers(0, 256, size=N), dtype=jnp.int32)
            if impl == "jnp":
                def run():
                    ops._pooled_fill(canvas, rows, values, ne, side=side,
                                     n=n).block_until_ready()
            else:
                from repro.kernels.region_fill_pooled import (
                    region_fill_pooled)

                def run():
                    region_fill_pooled(
                        canvas, rows, values, ne, side=side, n=n, F=F,
                        interpret=interpret).block_until_ready()
            return run
        max_dwell = int(sig["max_dwell"])
        u = params.get("unroll", 1)
        if impl == "jnp":
            def run():
                ops._pooled_dwell_blocks(
                    canvas, rows, N, side=side, n=n, bounds_all=bounds_all,
                    max_dwell=max_dwell, workload=workload,
                    unroll=u)[0].block_until_ready()
        else:
            from repro.kernels.region_dwell_pooled import region_dwell_pooled

            def run():
                region_dwell_pooled(
                    canvas, rows, ne, bounds_all, side=side, n=n, F=F,
                    max_dwell=max_dwell, interpret=interpret,
                    workload=workload, unroll=u).block_until_ready()
        return run

    raise ValueError(f"no runner for kernel {kernel!r}")


def tune(kernel: str, *, workload=None, cache: Optional[TuningCache] = None,
         reps: int = 3, tiny: bool = False, interpret: bool | None = None,
         **sig: Any) -> Choice:
    """Time every candidate for one (kernel, signature) and return the
    winner as a ``Choice(source="measured")``; records it in ``cache``."""
    best: Optional[Choice] = None
    for impl, params in _candidates(kernel, workload=workload, tiny=tiny,
                                    **sig):
        run = _build_runner(kernel, impl, params, workload=workload,
                            interpret=interpret, **sig)
        us = _best_us(run, reps=reps)
        cand = Choice(impl, tuple(sorted(params.items())),
                      source="measured", us=us)
        if best is None or us < best.us:
            best = cand
    assert best is not None
    if cache is not None:
        key = cache_key(kernel, workload=workload, **sig)
        cache.put(key, best)
    return best


def tune_problem(problem, *, cache: Optional[TuningCache] = None,
                 reps: int = 3, tiny: bool = False,
                 interpret: bool | None = None,
                 pooled_frames: int = 0) -> TuningCache:
    """Tune every kernel the ask pipeline dispatches for ``problem``.

    Walks the subdivision chain (sides n/g, n/(g*r), ... down to B) and the
    OLT ring capacities, covering: flat dwell at ``n``, perimeter query and
    region dwell at every level side, and OLT compaction at each ring
    capacity (rounded to pow2). When ``pooled_frames`` F > 0, the pooled
    engine's banded kernels are swept too: ``region_fill_pooled`` at every
    non-leaf side, ``region_dwell_pooled`` at the leaf side (signature
    ``(side, n, F)``), and OLT compaction again at the F-scaled pooled
    capacities (the cross-frame worklist is the per-frame one, F times
    longer). Returns the (possibly pre-seeded) cache with the winners
    added.
    """
    from repro.core.ask import scan_capacities

    cache = cache if cache is not None else TuningCache()
    wl = problem.workload
    n, max_dwell = problem.n, problem.max_dwell
    tune("dwell", workload=wl, cache=cache, reps=reps, tiny=tiny,
         interpret=interpret, n=n, max_dwell=max_dwell)
    side = n // problem.g
    sides = []
    while side >= problem.B:
        sides.append(side)
        if side == problem.B:
            break
        side //= problem.r
    if tiny:
        sides = sides[:1] + sides[-1:] if len(sides) > 1 else sides
    for side in sides:
        for kernel in ("perimeter_query", "region_dwell"):
            tune(kernel, workload=wl, cache=cache, reps=reps, tiny=tiny,
                 interpret=interpret, side=side, n=n, max_dwell=max_dwell)
    caps = scan_capacities(n, problem.g, problem.r, problem.B)
    cap_sizes = sorted({int(c) for c in caps})
    if tiny:
        cap_sizes = cap_sizes[-1:]
    for cap in cap_sizes:
        tune("olt_compact", workload=wl, cache=cache, reps=reps, tiny=tiny,
             interpret=interpret, n=cap)
    F = int(pooled_frames)
    if F > 0:
        for side in sides:
            tune("region_fill_pooled", workload=wl, cache=cache, reps=reps,
                 tiny=tiny, interpret=interpret, side=side, n=n, F=F)
        leaf = sides[-1] if sides else problem.B
        tune("region_dwell_pooled", workload=wl, cache=cache, reps=reps,
             tiny=tiny, interpret=interpret, side=leaf, n=n, F=F,
             max_dwell=max_dwell)
        for cap in cap_sizes:
            tune("olt_compact", workload=wl, cache=cache, reps=reps,
                 tiny=tiny, interpret=interpret, n=F * cap)
    return cache


# ---------------------------------------------------------------------------
# CLI — the CI autotune-smoke job entry point


def main(argv=None) -> int:
    import argparse

    ap = argparse.ArgumentParser(
        description="Sweep kernel schedules and write a JSON tuning cache")
    ap.add_argument("--out", required=True, help="tuning cache JSON path")
    ap.add_argument("--n", type=int, default=256)
    ap.add_argument("--max-dwell", type=int, default=128)
    ap.add_argument("--g", type=int, default=4)
    ap.add_argument("--r", type=int, default=2)
    ap.add_argument("--B", type=int, default=16)
    ap.add_argument("--workloads", default="mandelbrot",
                    help="comma-separated registry names")
    ap.add_argument("--reps", type=int, default=3)
    ap.add_argument("--tiny", action="store_true",
                    help="reduced candidate sweep (CI smoke)")
    ap.add_argument("--pooled-frames", type=int, default=0,
                    help="also sweep the banded pooled kernels for this "
                         "many frames (0 = skip the pooled tier)")
    args = ap.parse_args(argv)

    from repro.workloads import FrameProblem

    cache = TuningCache()
    for name in args.workloads.split(","):
        name = name.strip()
        problem = FrameProblem(n=args.n, g=args.g, r=args.r, B=args.B,
                               max_dwell=args.max_dwell, backend="jnp",
                               workload=name)
        tune_problem(problem, cache=cache, reps=args.reps, tiny=args.tiny,
                     pooled_frames=args.pooled_frames)
        print(f"tuned {name}: {len(cache.entries)} entries total")
    cache.save(args.out)
    print(f"wrote {args.out} ({len(cache.entries)} entries, "
          f"platform={jax.default_backend()})")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
