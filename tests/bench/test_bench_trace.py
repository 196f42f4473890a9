"""Trace reduction and the peaks table (bench/trace_reduce.py, bench/peaks.py)."""

import pytest

from bench.peaks import PEAKS, least_time_s, peaks
from bench.trace_reduce import OPS_LINE, WINDOW, ops_line, overlap, reduce_events, union

def _hand_trace():
    return {
        "device": {"/device:TPU:0": {
            OPS_LINE: [["fusion.1", 10, 20], ["fusion.2", 15, 30], ["copy", 50, 60],
                       ["late", 90, 130]],
            "XLA Modules": [["run", 0, 200]],
        }},
        "host": [[WINDOW, 0, 100], ["bench.x", 5, 35], ["bench.x", 45, 70]],
    }


def test_reduce_hand_counted_trace():
    r = reduce_events(_hand_trace())
    assert r["window_s"] == pytest.approx(100e-9)
    # union [10, 30] + [50, 60] + [90, 100] (clipped to the window) = 40 ns
    assert r["busy_s"] == pytest.approx(40e-9)
    x = r["spans"]["bench.x"]
    assert x["calls"] == 2
    assert x["host_s"] == pytest.approx(55e-9)
    assert x["device_s"] == pytest.approx(30e-9)
    assert r["device_ops"][0] == ["fusion.2", pytest.approx(15e-9)]
    gaps = [(name, round(s * 1e9)) for name, s in r["idle_gaps"]]
    assert gaps == [("outside benchmark calls", 30), ("outside benchmark calls", 20),
                    ("bench.x", 10)]


def test_ops_line_prefers_op_events():
    assert ops_line(_hand_trace()["device"]["/device:TPU:0"])[0][0] == "fusion.1"
    assert ops_line({"XLA Modules": [["m", 0, 9]], "Other": [["a", 0, 1]]}) == [["a", 0, 1]]


def test_union_and_overlap():
    busy = union([(5, 7), (0, 2), (1, 3), (7, 8)])
    assert busy == [(0, 3), (5, 8)]
    assert overlap(busy, 2, 6) == 2
    assert overlap(busy, 10, 20) == 0


def test_reduce_needs_window_and_device():
    with pytest.raises(ValueError):
        reduce_events({"device": {}, "host": [[WINDOW, 0, 1]]})
    with pytest.raises(ValueError):
        reduce_events({"device": _hand_trace()["device"], "host": []})


def test_peaks_table():
    p = peaks("TPU v5 lite")
    assert (p.flops, p.hbm_bytes_per_s) == (197e12, 819e9)
    assert "TPU v5e" in p.source
    with pytest.raises(KeyError, match="no published peaks"):
        peaks("TPU v9 imaginary")
    assert all(isinstance(k, str) for k in PEAKS)


def test_least_time_names_its_bound():
    p = peaks("TPU v5 lite")
    assert least_time_s(1e6, 819e9, p) == (1.0, "bytes")
    t, bound = least_time_s(197e12, 1.0, p)
    assert (t, bound) == (1.0, "ops")
