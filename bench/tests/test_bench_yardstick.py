"""The yardstick: the work function and peaks, and the trace reduction
on synthetic planes and on a small trace recorded on a TPU v5e."""
from __future__ import annotations

import os
import types

import pytest

from bench import trace_reduce as trr
from bench import work

HERE = os.path.dirname(os.path.abspath(__file__))
RECORDED = os.path.join(HERE, "data", "v5e_small.xplane.pb")


def test_peaks_of_the_v5e_and_unknown_kind_is_an_error():
    p = work.peaks("TPU v5 lite")
    assert p["int8_ops_per_s"] == 393e12
    assert p["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError, match="no peaks"):
        work.peaks("TPU v9 imaginary")


@pytest.mark.parametrize("M,d_eff,n_full,steps", [
    (1024, 8192, 109 * 16, 1),      # a crowded step at full width
    (1024, 8192, 2, 1),             # a coherent step: two misses
    (1024, 4096, 109 * 16, 1),
    (1024, 8192, 0, 1),             # no full-path work: bytes bound
])
def test_roofline_share_of_an_ideal_kernel_is_at_most_one(M, d_eff, n_full,
                                                          steps):
    p = work.peaks("TPU v5 lite")
    n_ops = work.ops(M, d_eff, n_full, 0)
    n_bytes = work.scan_bytes(M, d_eff, n_full, steps)
    t, bound = work.roofline_s(n_ops, n_bytes, p)
    # a kernel that streams the same bytes and does the same operations
    # at the peaks takes at least this long: no share above 1
    ideal = max(n_ops / p["int8_ops_per_s"], n_bytes / p["hbm_bytes_per_s"])
    assert t == ideal and t / ideal <= 1.0
    assert bound == ("ops" if n_ops / p["int8_ops_per_s"] >=
                     n_bytes / p["hbm_bytes_per_s"] else "bytes")
    assert n_ops == 2 * M * d_eff * n_full


def _plane(name, lines):
    return types.SimpleNamespace(name=name, lines=[
        types.SimpleNamespace(name=ln, events=[
            types.SimpleNamespace(name=n, start_ns=s, duration_ns=d)
            for n, s, d in evs]) for ln, evs in lines.items()])


def test_reduce_synthetic_planes():
    planes = [
        _plane("/device:TPU:0", {"XLA Ops": [
            ("scan", 100, 50), ("topk", 140, 30), ("scan", 400, 100)]}),
        _plane("/host:CPU", {"main": [(trr.WINDOW, 0, 1000)],
                             "dispatcher": [("host_decide", 200, 150),
                                            ("collector_drain", 520, 400)]}),
    ]
    red = trr.reduce_planes(planes, ("host_decide", "collector_drain"))
    assert red["window_s"] == pytest.approx(1000e-9)
    assert red["busy_s"] == pytest.approx(170e-9)        # [100,170]+[400,500]
    # overlapping ops: each instant goes to the later-starting one
    assert red["op_s"]["scan"] == pytest.approx(140e-9)
    assert red["op_s"]["topk"] == pytest.approx(30e-9)
    gaps = [(s, e, label) for s, e, label in red["gaps"]]
    assert gaps == [(0, 100, trr.NO_SPAN), (170, 400, "host_decide"),
                    (500, 1000, "collector_drain")]
    assert red["busy_s"] + sum(red["idle_by_label"].values()) == \
        pytest.approx(red["window_s"])
    bd = trr.breakdown(red)
    assert bd["device_ops"][0][0] == "scan"
    assert len(bd["idle_gaps"]) == 3


def test_self_time_of_nested_ops():
    events = [("while", 0, 100), ("body", 10, 60), ("leaf", 20, 30),
              ("after", 120, 150)]
    got = trr.self_times(events, 0, 140)
    assert got["while"] == pytest.approx(50e-9)
    assert got["body"] == pytest.approx(40e-9)
    assert got["leaf"] == pytest.approx(10e-9)
    assert got["after"] == pytest.approx(20e-9)      # clipped at 140
    assert trr.op_name("%fusion.190 = s32[32768]{0:T(1024)} fusion(s32[16] "
                       "%x), kind=kCustom") == "%fusion.190 fusion"


def test_reduce_recorded_v5e_trace():
    red = trr.reduce_file(RECORDED, ("host_decide",))
    assert red["devices"] == 1
    assert 0 < red["busy_s"] <= red["window_s"]
    assert red["op_s"] and all(v > 0 for v in red["op_s"].values())
    assert sum(red["idle_by_label"].values()) == pytest.approx(
        red["window_s"] - red["busy_s"], rel=1e-6)
