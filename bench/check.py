"""Decide ``correct``: every answer the timed path gave in the window,
compared pixel for pixel with the plain reference (``bench.reference``).

An answer is one frame of a frame stream, or one tile of a viewport
(a cache hit as much as a fresh render). Two numbers are compared, each
against a limit that the configuration file states under ``correct``:

* ``unanswered``: answers that were due and never came (a frame of an
  offered chunk, or a tile of a viewport's 2 x 2 cover). Limit 0.
* ``worst_px``: the most pixels by which one answer differs from the
  reference render of its window. Mariani-Silver fills a region from
  its border alone, so a lone escaping pixel inside a region whose
  whole border sits at ``max_dwell`` comes out filled: a few such
  pixels are the method, not a fault. The limit sits between what sound
  runs read and what the bfloat16 control reads (``PERF.md``).
"""

from __future__ import annotations

import dataclasses
import sys
import time
from typing import Dict, Sequence, Tuple

import numpy as np

from bench import reference

__all__ = ["Answer", "Verdict", "judge", "NUMBERS"]

NUMBERS = ("unanswered", "worst_px")


@dataclasses.dataclass
class Answer:
    """One answer due in the window: the window it renders and what the
    timed path gave for it (None where it never came)."""

    window: Tuple[float, float, float, float]
    canvas: object = None


@dataclasses.dataclass
class Verdict:
    correct: bool
    numbers: Dict[str, int]
    limits: Dict[str, int]
    answers: int
    reference_s: float = 0.0  # host seconds of the reference renders
    reference_renders: int = 0  # after the first, which compiles

    def as_json(self) -> dict:
        return {k: {"value": self.numbers[k], "limit": self.limits[k]}
                for k in NUMBERS}


def judge(answers: Sequence[Answer], config: dict, *, dtype=None,
          block: int = 1) -> Verdict:
    """Render the reference of every distinct window once and count.
    ``dtype`` replaces the timed path by the reference itself in that
    float type: the control (``bench.control``)."""
    limits = {k: int(config["correct"][k]) for k in NUMBERS}
    by_window: Dict[tuple, list] = {}
    unanswered = 0
    for a in answers:
        if a.canvas is None:
            unanswered += 1
            continue
        by_window.setdefault(tuple(a.window), []).append(a.canvas)
    windows = list(by_window)
    kw = dict(n=int(config["n"]), max_dwell=int(config["max_dwell"]),
              workload=config["workload"], block=block)
    worst = 0
    served = (reference.render_many(windows, dtype=dtype, **kw)
              if dtype is not None else None)
    refs = reference.render_many(windows, **kw)
    ref_s, timed = 0.0, 0
    for i, window in enumerate(windows):
        t0 = time.perf_counter()
        ref = next(refs)
        if i >= block:
            ref_s += time.perf_counter() - t0
            timed += 1
        canvases = by_window[window]
        if served is not None:
            canvases = [next(served)]
        for canvas in _distinct(canvases):
            canvas = np.asarray(canvas)
            if canvas.shape != ref.shape:
                bad = ref.size
            else:
                bad = int(np.count_nonzero(canvas != ref))
            worst = max(worst, bad)
    numbers = {"unanswered": unanswered, "worst_px": worst}
    ok = all(numbers[k] <= limits[k] for k in NUMBERS)
    return Verdict(correct=ok, numbers=numbers, limits=limits,
                   answers=len(answers), reference_s=ref_s,
                   reference_renders=timed)


def _distinct(canvases):
    """A cache hit hands out the same array again: compare it once."""
    seen = set()
    for c in canvases:
        if id(c) not in seen:
            seen.add(id(c))
            yield c


def report_lines(numbers: dict) -> None:
    """The compared numbers beside their limits (``Verdict.as_json``),
    as the last lines on standard error."""
    for name, v in numbers.items():
        print(f"check {name} {v['value']} limit {v['limit']}",
              file=sys.stderr, flush=True)
