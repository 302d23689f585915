"""The harness end to end on the CPU at toy widths (the look for a chip
skipped): a sound run is correct; the control and each planted fault of
the timed path make ``correct`` come out false."""
from __future__ import annotations

import dataclasses
import json

import jax.numpy as jnp
import pytest

from bench import run as bench_run
from bench_toy import make_root
from repro.core import pipeline


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return make_root(str(tmp_path_factory.mktemp("bench_root")))


def _run(root, capsys, *extra, workload="toy_coherent", seed=3_000_000_019):
    rc = bench_run.run(["--workload", workload, "--seed", str(seed),
                        "--seconds", "1"] + list(extra), root=root,
                       require_chip=False)
    out = capsys.readouterr().out.strip().splitlines()
    assert rc == 0
    return json.loads(out[-1])


def _plant(monkeypatch, fault):
    orig = pipeline.torr_stream_batch_step

    def step(state, im, batch, cfg, serial=False, plan=None, fused=None,
             bucket_cap=None, decide=None):
        if fault == "half_batch":
            lanes = jnp.arange(batch.valid.shape[0]) % 2 == 0
            batch = dataclasses.replace(
                batch, valid=batch.valid & lanes[:, None])
        new, out, tel = orig(state, im, batch, cfg, serial, plan, fused,
                             bucket_cap, decide)
        if fault == "state_unchanged":
            new = state
        if fault == "answer_altered":
            out = dataclasses.replace(
                out, scores=out.scores.at[:, 0, 0].add(1.0))
        return new, out, tel
    monkeypatch.setattr(pipeline, "torr_stream_batch_step", step)


@pytest.mark.parametrize("workload", ["toy_coherent", "toy_open"])
def test_sound_run_is_correct(root, capsys, workload):
    res = _run(root, capsys, workload=workload)
    assert res["correct"] is True, res["checks"]
    assert list(res)[-1] == "checks"
    assert all(c["value"] <= c["limit"] for c in res["checks"].values())
    spec = bench_run.Spec(root, workload)
    assert set(res["metrics"]) == {m["name"] for m in spec.end_to_end}
    assert res["attempted"] > 0 and res["failed"] == 0


def test_control_is_not_correct(root, capsys):
    res = _run(root, capsys, "--control", "planes")
    assert res["correct"] is False
    assert res["checks"]["wrong_answers"]["value"] > 0


@pytest.mark.parametrize("fault,caught_by", [
    ("state_unchanged", "path_count_gap"),
    ("half_batch", "wrong_answers"),
    ("answer_altered", "wrong_answers"),
])
def test_planted_fault_is_not_correct(root, capsys, monkeypatch, fault,
                                      caught_by):
    _plant(monkeypatch, fault)
    res = _run(root, capsys)
    assert res["correct"] is False
    assert res["checks"][caught_by]["value"] > 0


@pytest.mark.parametrize("fault,caught_by", [
    (None, None),
    ("state_unchanged", "path_count_gap"),
    ("half_batch", "wrong_answers"),
    ("answer_altered", "wrong_answers"),
])
def test_pooled_check_agrees_with_serial(root, capsys, monkeypatch, fault,
                                         caught_by):
    """The check in worker processes sums to what the serial check gives
    on the same records: windows compared, path counts and verdicts."""
    seen = []
    orig = bench_run.check

    def both(spec, data, records, server_paths, seed, workers=0):
        serial = orig(spec, data, records, server_paths, seed)
        pooled = orig(spec, data, records, server_paths, seed, workers=3)
        seen.append((serial, pooled))
        return pooled
    monkeypatch.setattr(bench_run, "check", both)
    if fault is not None:
        _plant(monkeypatch, fault)
    res = _run(root, capsys)
    (serial, pooled), = seen
    assert pooled == serial and serial[1] > 0
    assert res["correct"] is (fault is None)
    if fault is not None:
        assert serial[0][caught_by][0] > 0


def test_no_chip_exits_nonzero_without_a_result(root, capsys):
    rc = bench_run.run(["--workload", "toy_coherent", "--seed", "1",
                        "--seconds", "1"], root=root)
    captured = capsys.readouterr()
    assert rc == 2
    assert captured.out == ""
    assert "no TPU found" in captured.err
