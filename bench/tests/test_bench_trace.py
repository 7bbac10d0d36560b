"""The trace reduction, on hand-made events and on a trace recorded on the
CPU (``data/cpu_trace.xplane.pb``, see ``data/record_cpu_trace.py``)."""
from pathlib import Path

import pytest

import tracered

TRACE = Path(__file__).with_name("data") / "cpu_trace.xplane.pb"


def _events():
    # Window 0..100 ns (first span start to last span end). Device ops
    # 10-30 and 25-40 overlap (busy 10-40) and 70-80; idle 0-10, 40-70,
    # 80-100. Spans: entry 0-20, block 20-60, readback 60-100.
    return tracered.Events(
        devices={"/device:TPU:0": [(10, 30, "fusion"), (25, 40, "kernel"),
                                   (70, 80, "fusion"), (120, 130, "late")]},
        spans=[(0, 20, "bench.entry"), (20, 60, "bench.block"),
               (60, 100, "bench.readback")])


def test_union_merges_and_clips():
    assert tracered.union([(5, 15), (10, 20), (30, 40), (-5, 2)], 0, 35) == [
        [0, 2], [5, 20], [30, 35]]


def test_reduce_hand_made_events():
    r = tracered.reduce(_events())
    assert r.window_s == pytest.approx(100e-9)
    assert r.busy_s == pytest.approx(40e-9)
    assert r.idle_share == pytest.approx(0.6)
    assert r.ops == pytest.approx({"fusion": 30e-9, "kernel": 15e-9})
    assert r.op_seconds("kern") == pytest.approx(15e-9)
    assert r.op_seconds("nothing") is None
    # idle 0-10 in entry; 40-60 in block, 60-70 in readback; 80-100 in
    # readback
    assert r.gaps == pytest.approx({"bench.entry": 10e-9,
                                    "bench.block": 20e-9,
                                    "bench.readback": 30e-9,
                                    tracered.OTHER: 0.0})
    b = r.breakdown(1)
    assert b["device_ops"] == [["fusion", pytest.approx(30e-9)]]
    assert b["idle_gaps"] == [["bench.readback", pytest.approx(30e-9)]]


def test_gap_outside_every_span_is_other():
    ev = _events()
    ev.spans = [(0, 20, "bench.entry"), (90, 100, "bench.readback")]
    r = tracered.reduce(ev)
    assert r.gaps[tracered.OTHER] == pytest.approx(40e-9)   # 40-70, 80-90


def test_reduce_averages_over_devices():
    ev = _events()
    ev.devices["/device:TPU:1"] = [(0, 100, "fusion")]
    r = tracered.reduce(ev)
    assert r.n_devices == 2
    assert r.busy_s == pytest.approx(70e-9)


def test_no_device_work_is_an_error():
    ev = _events()
    ev.devices = {}
    with pytest.raises(ValueError):
        tracered.reduce(ev)


def test_cpu_trace():
    ev = tracered.load(str(TRACE), tracered.cpu_ops)
    assert [n for _, _, n in ev.spans] == [
        "bench.entry", "bench.block", "bench.readback"] * 3
    r = tracered.reduce(ev)
    assert 0 < r.busy_s < r.window_s
    assert 0 < r.idle_share < 1
    assert r.op_seconds(r"^dot_general") > 0
    # every idle moment of the window lands in exactly one bucket
    assert sum(r.gaps.values()) == pytest.approx(r.window_s - r.busy_s,
                                                 rel=1e-9)
    assert tracered.find_xplane(str(TRACE.parent)) == str(TRACE)


def test_tpu_selector_ignores_host_lines():
    ev = tracered.load(str(TRACE))
    assert ev.devices == {}
    assert len(ev.spans) == 9


def test_tpu_selector():
    assert tracered.tpu_ops("/device:TPU:0", "XLA Ops")
    assert tracered.tpu_ops("/device:TPU:3", "XLA Modules")
    assert not tracered.tpu_ops("/device:TPU:0", "Steps")
    assert not tracered.tpu_ops("/host:CPU", "XLA Ops")
