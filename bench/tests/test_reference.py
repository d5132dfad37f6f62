"""The plain reference agrees with the program's exhaustive render, and
the comparison refuses the same reference computed in bfloat16."""

import ast
from pathlib import Path

import jax.numpy as jnp
import numpy as np
import pytest

from bench import check, reference

ZOOM = (-0.7536447860, 0.1218252536, -0.7336447860, 0.1418252536)


@pytest.mark.parametrize("window", ["default", "zoomed"])
@pytest.mark.parametrize("workload", sorted(reference.STEPS))
def test_agrees_with_exhaustive(workload, window):
    from repro.workloads import exhaustive
    from repro.workloads.registry import escape_time_workloads

    assert workload in escape_time_workloads()
    bounds = reference.DEFAULT_WINDOWS[workload] if window == "default" \
        else ZOOM
    ex, _ = exhaustive(256, max_dwell=256, bounds=bounds, workload=workload)
    ref = reference.render(bounds, n=256, max_dwell=256, workload=workload)
    assert np.array_equal(np.asarray(ex), np.asarray(ref))


def test_covers_every_registered_escape_workload():
    from repro.workloads.registry import escape_time_workloads

    assert set(escape_time_workloads()) == set(reference.STEPS)


def test_blocks_match_single_renders():
    windows = [reference.DEFAULT_WINDOWS["mandelbrot"], ZOOM, ZOOM[::-1]]
    many = list(reference.render_many(windows, n=64, max_dwell=64, block=2))
    for w, img in zip(windows, many):
        assert np.array_equal(img, np.asarray(
            reference.render(w, n=64, max_dwell=64)))


def _config(limit):
    return {"n": 256, "max_dwell": 256, "workload": "mandelbrot",
            "correct": {"unanswered": 0, "worst_px": limit}}


def test_bfloat16_fails_the_comparison():
    answers = [check.Answer(ZOOM, "control"),
               check.Answer(reference.DEFAULT_WINDOWS["mandelbrot"],
                            "control")]
    v = check.judge(answers, _config(64), dtype=jnp.bfloat16)
    assert not v.correct
    assert v.numbers["worst_px"] > 1000


def test_float32_answers_pass():
    img = np.asarray(reference.render(ZOOM, n=256, max_dwell=256))
    v = check.judge([check.Answer(ZOOM, img)], _config(0))
    assert v.correct and v.numbers == {"unanswered": 0, "worst_px": 0}


def test_missing_answer_fails():
    v = check.judge([check.Answer(ZOOM, None)], _config(64))
    assert not v.correct and v.numbers["unanswered"] == 1


def test_imports_nothing_of_the_program():
    tree = ast.parse(Path(reference.__file__).read_text())
    names = [a.name for n in ast.walk(tree) if isinstance(n, ast.Import)
             for a in n.names]
    names += [n.module for n in ast.walk(tree)
              if isinstance(n, ast.ImportFrom)]
    assert not [m for m in names if m and m.split(".")[0] == "repro"]
