"""The reduction from a profiler trace to busy time, idle share, top
operations and idle gaps by host span."""

from pathlib import Path

import pytest

from bench import trace_reduce

DATA = Path(__file__).parent / "data" / "tiny_tpu.xplane.pb"

DEVICES = {"/device:TPU:0": [("fusion.1", 100, 200), ("fusion.2", 150, 300),
                             ("copy", 500, 600), ("late", 1100, 1200)]}
SPANS = [("bench.window", 0, 1000), ("bench.serve", 0, 400),
         ("bench.finalize", 300, 400), ("bench.fetch", 600, 900)]


def test_busy_is_the_union_of_op_intervals():
    assert trace_reduce.union([(5, 6), (1, 3), (2, 4), (4, 4)]) == [
        (1, 4), (5, 6)]
    r = trace_reduce.reduce_events(DEVICES, SPANS)
    assert r["window_s"] == pytest.approx(1000e-9)
    assert r["busy_s"] == pytest.approx(300e-9)  # [100,300] + [500,600]
    assert r["idle_share"] == pytest.approx(0.7)


def test_top_ops_sum_their_time_in_the_window():
    r = trace_reduce.reduce_events(DEVICES, SPANS)
    assert r["device_ops"][0] == ["fusion.2", pytest.approx(150e-9)]
    assert {k for k, _ in r["device_ops"]} == {"fusion.1", "fusion.2",
                                               "copy"}


def test_idle_gaps_go_to_the_innermost_host_span():
    r = trace_reduce.reduce_events(DEVICES, SPANS)
    idle = dict(r["idle_gaps"])
    # gaps [0,100] [300,500] [600,1000]
    assert idle["bench.serve"] == pytest.approx(100e-9)
    assert idle["bench.finalize"] == pytest.approx(100e-9)
    assert idle["bench.fetch"] == pytest.approx(300e-9)
    assert idle["outside"] == pytest.approx(200e-9)
    assert sum(idle.values()) == pytest.approx(700e-9)


def test_devices_are_averaged():
    two = {**DEVICES, "/device:TPU:1": [("fusion.1", 0, 1000)]}
    r = trace_reduce.reduce_events(two, SPANS)
    assert r["busy_s"] == pytest.approx(650e-9)
    assert r["devices"] == 2


def test_recorded_tpu_trace():
    """A trace recorded on one v5e chip by ``data/record_xplane.py``:
    three small batches inside ``bench.*`` host spans."""
    r = trace_reduce.reduce_file(DATA)
    assert r["devices"] == 1
    assert 0 < r["busy_s"] < r["window_s"]
    assert 0 < r["idle_share"] < 1
    assert r["device_ops"]
    labels = {k for k, _ in r["idle_gaps"]}
    assert labels & {"bench.serve", "bench.finalize", "bench.fetch",
                     "bench.dispatch_planned"}
    total_idle = sum(v for _, v in r["idle_gaps"])
    assert total_idle == pytest.approx(r["window_s"] - r["busy_s"],
                                       rel=1e-6)
