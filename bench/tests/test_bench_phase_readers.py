"""The readers of the program's named phases, queue wait and dispatcher
wait, on synthetic contexts: what each reads, and nothing where the
program does not publish it."""
from __future__ import annotations

import pytest

from bench import run as bench_run
from bench_toy import ROOT


def _reader(name):
    spec = bench_run.Spec.__new__(bench_run.Spec)
    spec.root = ROOT
    return spec.reader(name)


def _series(labels, **fields):
    return {"labels": labels, **fields}


PHASE_OPS = {"torr_step_phase_ops": {"series": [
    _series({"phase": "delta_search", "ops": "fusion.190 fusion.166"},
            value=2),
    _series({"phase": "delta_apply", "ops": "fusion.165"}, value=1),
    _series({"phase": "reason", "ops": "sort.14 sort.15"}, value=2),
]}}
OP_S = {"%fusion.190 fusion": 3.7, "%fusion.165 fusion": 0.4,
        "%fusion.166 fusion": 0.3, "%sort.14 sort": 0.05,
        "%convert.26 convert": 0.15, "%copy.1 copy": 0.4}


def _steps(n):
    return {"torr_steps_total": {"series": [_series({}, value=n)]}}


def _ctx(before, after, trace=None, span_s=5.0):
    return bench_run.Context(snaps=(before, after), trace=trace,
                             span_s=span_s)


def test_delta_device_ms_per_step_sums_the_delta_phases():
    read = _reader("delta_device_ms_per_step")
    ctx = _ctx({**PHASE_OPS, **_steps(4)}, {**PHASE_OPS, **_steps(10)},
               trace={"op_s": OP_S})
    assert read(ctx) == pytest.approx((3.7 + 0.4 + 0.3) / 6 * 1e3)
    # the parent publishes no phase table; an untraced run has no ops
    assert read(_ctx(_steps(4), _steps(10), trace={"op_s": OP_S})) is None
    assert read(_ctx({**PHASE_OPS, **_steps(4)},
                     {**PHASE_OPS, **_steps(10)})) is None


def test_phased_device_share_is_the_named_ops_share():
    read = _reader("phased_device_share")
    ctx = _ctx(PHASE_OPS, PHASE_OPS, trace={"op_s": OP_S})
    named = 3.7 + 0.4 + 0.3 + 0.05
    assert read(ctx) == pytest.approx(named / sum(OP_S.values()) * 100)
    assert read(_ctx({}, {}, trace={"op_s": OP_S})) is None
    assert read(_ctx(PHASE_OPS, PHASE_OPS, trace={"op_s": {}})) is None


def test_queue_wait_ms_is_the_mean_over_the_slice():
    read = _reader("queue_wait_ms")

    def snap(total, n):
        return {"torr_window_queue_seconds": {"series": [
            _series({}, sum=total, count=n)]}}
    assert read(_ctx(snap(10.0, 7), snap(16.0, 10))) == pytest.approx(
        2000.0)
    assert read(_ctx({}, {})) is None
    assert read(_ctx(snap(1.0, 3), snap(1.0, 3))) is None


def test_dispatch_wait_share_reads_the_span_over_the_slice():
    read = _reader("dispatch_wait_share.open")

    def snap(total, n):
        return {"torr_span_duration_seconds": {"series": [
            _series({"span": "collector_drain"}, sum=9.0, count=9),
            _series({"span": "dispatch_wait"}, sum=total, count=n)]}}
    assert read(_ctx(snap(2.0, 4), snap(3.5, 6), span_s=5.0)) == \
        pytest.approx(30.0)
    # waited in no interval that ended in the slice: a share of 0
    assert read(_ctx(snap(2.0, 4), snap(2.0, 4))) == 0.0
    no_span = {"torr_span_duration_seconds": {"series": [
        _series({"span": "collector_drain"}, sum=9.0, count=9)]}}
    assert read(_ctx(no_span, no_span)) is None
