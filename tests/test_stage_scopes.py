"""Every stage of the level scan carries its ``jax.named_scope`` into the
compiled program, in each engine the service serves through.

A profile names device operations by their HLO ``op_name`` metadata, so
these names are what a trace reduction can attribute device time by:
``ask.query`` (Q), ``ask.fill`` (T), ``ask.dwell`` (A), ``ask.compact``
(worklist compaction) and ``ask.subdivide`` (children, ring, counts).
"""

import re

import jax.numpy as jnp
import pytest

from repro.core import ask as ask_lib
from repro.core import pooled as pooled_lib
from repro.core import progressive as progressive_lib
from repro.core.planner import worst_case_capacities
from repro.workloads import FrameProblem

STAGES = ("ask.query", "ask.fill", "ask.dwell", "ask.compact",
          "ask.subdivide")
F = 2


@pytest.fixture(scope="module")
def problem():
    # max_dwell of its own: the jitted-pipeline caches are keyed on the
    # problem, and this module's compiles must not be shared
    return FrameProblem(n=128, g=4, r=2, B=16, max_dwell=37)


@pytest.fixture(scope="module")
def bounds(problem):
    return jnp.asarray([problem.bounds] * F, jnp.float32)


def _op_names(compiled_text: str) -> str:
    return "\n".join(re.findall(r'op_name="([^"]*)"', compiled_text))


def _pooled_text(problem, bounds):
    fn = pooled_lib._jitted_pooled(problem, worst_case_capacities(problem), F)
    return fn.lower(bounds, jnp.ones((F,), bool)).compile().as_text()


def _scan_text(problem, bounds):
    fn = ask_lib._jitted_pipeline(problem, worst_case_capacities(problem),
                                  batched=True)
    return fn.lower(bounds).compile().as_text()


def _progressive_text(problem, bounds):
    caps = worst_case_capacities(problem)
    coarse, refine = progressive_lib._jitted_split(problem, caps, 1, True)
    preview, carry, _ = coarse(bounds)
    return (coarse.lower(bounds).compile().as_text()
            + refine.lower(carry, bounds).compile().as_text())


@pytest.mark.parametrize("engine", ["pooled", "scan", "progressive"])
def test_every_stage_names_operations_of_the_optimized_program(
        engine, problem, bounds):
    text = {"pooled": _pooled_text, "scan": _scan_text,
            "progressive": _progressive_text}[engine](problem, bounds)
    names = _op_names(text)
    # a scope under vmap reads ``vmap(ask.subdivide)/...``
    missing = [s for s in STAGES
               if not re.search(re.escape(s) + r"[/)]", names)]
    assert not missing, f"{engine}: no op_name under {missing}"


def test_leaf_dwell_loop_is_named_ask_dwell(problem, bounds):
    """The escape loop -- the op that takes most of a chunk's device
    time -- is a ``while`` whose op_name lies under ``ask.dwell``."""
    text = _pooled_text(problem, bounds)
    whiles = re.findall(r'= [^\n]* while\([^\n]*op_name="([^"]*)"', text)
    assert any("ask.dwell/" in w for w in whiles), whiles
