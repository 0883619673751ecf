"""The reduction from trace events to busy time, idle share and gaps:
on hand-made events whose answer is known, and on a small trace
recorded on the chip (``data/``)."""

import json
import os

import pytest

from benchmark import trace_reduce as tr

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
MS = 1e6  # ns


def dev(chip, name, start_ms, dur_ms):
    return (f"/device:TPU:{chip}", tr.OPS_LINE, name, start_ms * MS, dur_ms * MS)


def host(name, start_ms, dur_ms):
    return ("/host:CPU", "python3", name, start_ms * MS, dur_ms * MS)


def test_union_clip_gaps():
    busy = tr.union([(0, 4), (3, 6), (10, 12), (11, 11.5)])
    assert busy == [(0, 6), (10, 12)]
    assert tr.clip(busy, 5, 11) == [(5, 6), (10, 11)]
    assert tr.gaps(tr.clip(busy, 5, 11), 5, 11) == [(6, 10)]
    assert tr.gaps([], 0, 3) == [(0, 3)]


def test_op_family():
    assert tr.op_family("fusion.123") == "fusion"
    assert tr.op_family("multiply_add_fusion") == "multiply_add_fusion"
    assert tr.op_family("slice-done.2.1") == "slice-done"
    assert tr.op_family("%convert_reduce_fusion.143 = (f32[64,256]{1,0}) fusion(bf16[4] %p.1)") == "convert_reduce_fusion"


def test_two_chips_known_answer():
    """Window 100 ms. Chip 0 idles 10 ms while the host draws inputs
    and 5 ms with no span over it; chip 1 idles 30 ms under the
    dispatch span. Nested operations are not counted twice."""
    events = [
        host(tr.WINDOW_SPAN, 1000, 100),
        host("host:_input", 1038, 14), host("host:_dispatch", 1060, 30),
        dev(0, "fusion.1", 990, 50),  # starts before the window: clipped to 40
        dev(0, "fusion.2", 1000, 20),  # nested in fusion.1
        dev(0, "copy.1", 1050, 45),  # gap 1040-1050 before it, 1095-1100 after
        dev(1, "fusion.1", 1000, 60), dev(1, "fusion.3", 1090, 20),  # gap 1060-1090
    ]
    got = tr.reduce_events(events)
    assert got["chips"] == 2
    assert got["window_s"] == pytest.approx(0.100)
    assert got["busy_s"] == pytest.approx((0.085 + 0.070) / 2)
    assert got["idle_share_worst"] == pytest.approx(0.30)
    gaps = dict((k, v) for k, v in got["idle_gaps"])
    assert gaps["host:_input"] == pytest.approx(0.010 / 2)
    assert gaps["host:_dispatch"] == pytest.approx(0.030 / 2)
    assert gaps[tr.UNATTRIBUTED] == pytest.approx(0.005 / 2)
    ops = dict((k, v) for k, v in got["device_ops"])
    assert ops["fusion_x3"] == pytest.approx(0.040 + 0.020 + 0.060 + 0.010)
    assert ops["copy_x1"] == pytest.approx(0.045)


def test_a_trace_without_device_work_is_an_error():
    with pytest.raises(ValueError, match="no device operation"):
        tr.reduce_events([host(tr.WINDOW_SPAN, 0, 10)])
    with pytest.raises(ValueError, match="inside the traced window"):
        tr.reduce_events([host(tr.WINDOW_SPAN, 0, 10), dev(0, "fusion.1", 20, 5)])
    with pytest.raises(ValueError, match="expected one"):
        tr.reduce_events([dev(0, "fusion.1", 20, 5)])


def test_recorded_v5e_trace():
    """The first 60 ms of ``lm-short-t256``'s traced part as the v5e
    recorded it (PR 23, ``dump_trace.py``; operation names cut to 100
    characters): 1,083 operations of one chip and the host's three
    spans. The figures were read off this file when it was cut."""
    with open(os.path.join(DATA, "v5e_lm_short_t256_first60ms.json")) as f:
        events = [tuple(e) for e in json.load(f)]
    got = tr.reduce_events(events)
    assert got["chips"] == 1
    assert got["window_s"] == pytest.approx(0.060)
    assert got["busy_s"] == pytest.approx(0.059998716, abs=1e-9)
    assert got["idle_share_worst"] == pytest.approx(2.14e-05, rel=1e-3)
    assert got["device_ops"][0][0] == "convert_reduce_fusion_x34"
    assert got["device_ops"][0][1] == pytest.approx(0.038584145, abs=1e-9)
    assert [name for name, _ in got["idle_gaps"]] == [
        "host:_wait", "host:_dispatch", "host:_input"
    ]
    assert sum(s for _, s in got["idle_gaps"]) == pytest.approx(
        got["window_s"] - got["busy_s"], abs=1e-9
    )
