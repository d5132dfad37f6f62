"""Device time by stage and idle time by the program's chunk phases
(``bench.stages``), and the ``tf_op`` reader under it (``bench.xplane``)."""

import gzip
import shutil
from pathlib import Path

import pytest

from bench import stages, trace_reduce, xplane

DATA = Path(__file__).parent / "data"
TINY = DATA / "tiny_tpu.xplane.pb"
POOLED = DATA / "tiny_pooled.xplane.pb.gz"

SCOPED = "jit(one_pool)/ask.subdivide/while/body/"
# a level-scan loop holding a query fusion and a compaction fusion, then
# the leaf dwell loop holding its body fusion, then an op with no scope;
# times in ns
OPS = [("while.1", 100, 400, SCOPED + "while:"),
       ("fusion.1", 120, 200, SCOPED + "ask.query/reduce_and:"),
       ("fusion.2", 250, 300, SCOPED + "ask.compact/cumsum:"),
       ("while.2", 500, 900, "jit(one_pool)/ask.dwell/while:"),
       ("fusion.3", 500, 850, "jit(one_pool)/ask.dwell/while/body/mul:"),
       ("while.3", 860, 890, "jit(one_pool)/ask.dwell/scatter"),
       ("dynamic-update-slice.1", 865, 880, ""),
       ("copy.1", 950, 1000, "")]
SPANS = [("bench.window", 0, 1000, None),
         ("bench.stream_next", 0, 1000, None),
         ("repro.wait", 0, 90, 0),
         ("repro.dispatch", 420, 480, 1),
         ("repro.copy", 905, 940, 0),
         ("bench.fetch", 940, 950, None)]


def test_stage_is_the_innermost_ask_scope():
    assert stages.stage_of(SCOPED + "ask.query/while/body/add:") == "query"
    assert stages.stage_of(
        "jit(f)/vmap(ask.subdivide)/while/body/jit(subdivide_olt)/"
        "ask.compact/jit(compact_ranks)/cumsum:") == "compact"
    assert stages.stage_of("reduce_window_sum:") == "unscoped"
    assert stages.stage_of("") == "unscoped"


def test_self_time_goes_to_the_innermost_op():
    got = stages.stage_seconds(OPS, 0, 1000)
    # the level loop keeps what its body ops leave: [100,120] [200,250]
    # [300,400]; the dwell loop's [850,900] stays dwell, and so does the
    # unnamed op in the body of its scatter loop; copy.1 runs inside
    # nothing and has no scope
    assert got == pytest.approx({"subdivide": 170e-9, "query": 80e-9,
                                 "compact": 50e-9, "dwell": 400e-9,
                                 "unscoped": 50e-9})
    busy = trace_reduce.reduce_events(
        {"d": [(n, a, b) for n, a, b, _ in OPS]},
        [s[:3] for s in SPANS])["busy_s"]
    assert sum(got.values()) == pytest.approx(busy)


def test_an_unnamed_op_outside_any_op_is_unscoped():
    got = stages.stage_seconds([("copy.1", 0, 10, ""),
                                ("fusion.1", 20, 30, "add:")], 0, 100)
    assert got == pytest.approx({"unscoped": 20e-9})


def test_self_time_is_clipped_to_the_window():
    got = stages.stage_seconds(OPS, 150, 600)
    assert sum(got.values()) == pytest.approx(
        (400 - 150 + 600 - 500) * 1e-9)
    assert got["dwell"] == pytest.approx(100e-9)


def test_program_spans_win_over_harness_spans_in_idle_gaps():
    idle = stages.program_idle_gaps({"d": OPS}, SPANS, 0, 1000)
    # gaps [0,100] [400,500] [900,950]; a harness span keeps only what
    # no program span covers
    assert idle == pytest.approx({
        "repro.wait": 90e-9, "repro.dispatch": 60e-9, "repro.copy": 35e-9,
        "bench.stream_next": (10 + 40 + 5) * 1e-9, "bench.fetch": 10e-9})
    assert sum(idle.values()) == pytest.approx(250e-9)


def test_phases_are_means_over_the_chunks_dispatched():
    spans = SPANS + [("repro.dispatch", 10, 30, 0),
                     ("repro.wait", 960, 980, 1)]
    per_chunk, chunks = stages.phases(spans, 0, 1000)
    assert chunks == 2
    assert per_chunk["dispatch"] == pytest.approx((60 + 20) * 1e-9 / 2)
    assert per_chunk["wait"] == pytest.approx((90 + 20) * 1e-9 / 2)


def test_xplane_reads_tf_op_from_the_recorded_tpu_trace():
    paths = xplane.op_paths(TINY)
    assert list(paths) == ["/device:TPU:0"]
    tf_ops = {tf for _, tf in paths["/device:TPU:0"]}
    assert {"jit(<lambda>)/mul:", "jit(<lambda>)/dot_general:"} <= tf_ops
    assert trace_reduce.reduce_file(TINY)["devices"] == 1


def test_reduction_keeps_the_harness_numbers():
    """The trace_reduce keys are trace_reduce's own; on a program
    without scopes all busy time is unscoped."""
    base = trace_reduce.reduce_file(TINY)
    got = stages.reduce_file(TINY)
    for key in ("window_s", "busy_s", "idle_share", "devices",
                "device_ops"):
        assert got[key] == base[key]
    assert got["idle_gaps_bench"] == base["idle_gaps"]
    assert set(got["stages"]) == {"unscoped"}
    assert got["stages"]["unscoped"] == pytest.approx(base["busy_s"])
    assert got["phases"] == {} and got["chunks"] == 0


def test_recorded_pooled_stream(tmp_path):
    """Two chunks of a pooled ``RenderService`` stream recorded on one
    v5e chip by ``data/record_pooled_xplane.py``: every stage and the
    program's chunk phases come out, and the stages sum to busy."""
    trace = tmp_path / "tiny_pooled.xplane.pb"
    with gzip.open(POOLED, "rb") as src, open(trace, "wb") as dst:
        shutil.copyfileobj(src, dst)
    r = stages.reduce_file(trace)
    assert {"query", "fill", "dwell", "compact", "subdivide"} <= set(
        r["stages"])
    assert all(r["stages"][k] > 0 for k in ("query", "fill", "dwell",
                                            "compact", "subdivide"))
    assert sum(r["stages"].values()) == pytest.approx(r["busy_s"],
                                                      rel=1e-9)
    # the scatter that writes A's windows is a loop the compiler wrote:
    # the trace names neither it nor its body, the HLO module names the
    # loop, and the body inherits it
    assert r["stages"].get("unscoped", 0.0) < 0.02 * r["busy_s"]
    assert r["chunks"] == 2
    assert {"plan", "dispatch", "wait", "stats", "copy",
            "observe"} <= set(r["phases"])
    labels = {k for k, _ in r["idle_gaps"]}
    assert any(k.startswith("repro.") for k in labels)
    assert sum(v for _, v in r["idle_gaps"]) == pytest.approx(
        r["window_s"] - r["busy_s"], rel=1e-6)
