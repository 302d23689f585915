"""A configuration, its input module, a traffic mix and a per-layer metric
are found by name once added as new files, with no file that was there
edited."""
from __future__ import annotations

import hashlib
import json
import os

import numpy as np
import pytest

from bench import inputs
from bench import run as bench_run
from bench_toy import make_root


def _digests(root):
    out = {}
    for d, _dirs, files in os.walk(os.path.join(root, "bench")):
        for f in files:
            p = os.path.join(d, f)
            with open(p, "rb") as fh:
                out[os.path.relpath(p, root)] = hashlib.sha256(
                    fh.read()).hexdigest()
    return out


def test_new_files_are_found_by_name(tmp_path):
    root = make_root(str(tmp_path))
    before = _digests(root)
    with open(os.path.join(root, "bench/configs/toy.json")) as f:
        cfg = json.load(f)
    cfg["name"] = "toy_wide"
    cfg["torr"]["K"] = 16
    cfg["input"] = "bench/inputs/toy_wide.py"
    with open(os.path.join(root, "bench/configs/toy_wide.json"), "w") as f:
        json.dump(cfg, f)
    with open(os.path.join(root, "bench/inputs/toy_wide.py"), "w") as f:
        f.write("from bench.inputs.packed import *  # noqa: F403\n"
                "WIDE = True\n")
    with open(os.path.join(root, "bench/traffic/toy_burst.json"), "w") as f:
        json.dump({"loop": "open", "streams": 2, "tenants": 1, "warm": 1,
                   "valid": {"p": 0.5}, "content": {"bit_flips": 2},
                   "rate_per_s": 10.0, "schedule_seed": 1}, f)
    with open(os.path.join(root, "bench/metrics/toy_windows.py"), "w") as f:
        f.write("def read(ctx):\n"
                "    return ctx.counter('torr_windows_total') or None\n")
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        bench = json.load(f)
    bench["configs"].append({"name": "toy_wide", "source": "toy",
                             "file": "bench/configs/toy_wide.json",
                             "reduced": [], "why": "test"})
    bench["workloads"].append({"name": "toy_wide.burst",
                               "config": "toy_wide", "traffic": "toy_burst",
                               "chips": 1, "why": "test"})
    bench["per_layer"].append({"name": "toy_windows", "unit": "windows",
                               "better": "higher",
                               "source": "program_counter",
                               "layer": "engine host",
                               "moves": "p95_window_ms",
                               "workloads": ["toy_wide.burst"]})
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(bench, f)

    spec = bench_run.Spec(root, "toy_wide.burst")
    assert spec.config["torr"]["K"] == 16
    assert spec.input == os.path.join(root, "bench/inputs/toy_wide.py")
    mod = inputs.load(spec.input)
    assert mod.WIDE and callable(mod.windows) and callable(mod.reference)
    assert bench_run.Spec(root, "toy_coherent").input == os.path.join(
        root, inputs.DEFAULT)
    assert spec.traffic["content"] == {"bit_flips": 2}
    assert [m["name"] for m in spec.per_layer] == ["toy_windows"]
    assert {m["name"] for m in spec.end_to_end} == {
        m["name"] for m in bench["end_to_end"] if "workloads" not in m}
    snap = {"torr_windows_total": {"series": [{"labels": {}, "value": 7}]}}
    ctx = bench_run.Context(snaps=({}, snap))
    assert spec.reader("toy_windows")(ctx) == 7.0
    assert all(_digests(root).get(k) == v for k, v in before.items())


def test_configuration_files_are_the_programs_deployments():
    import dataclasses

    from bench import stack
    from bench_toy import ROOT
    from repro.configs import torr_edge

    def load(name):
        with open(os.path.join(ROOT, "bench", "configs", name + ".json")) as f:
            return stack.torr_config(json.load(f))
    assert load("torr_edge") == torr_edge()
    assert load("torr_edge_d4096") == dataclasses.replace(
        torr_edge(), D=4096, B=4, delta_budget=1024)


def test_client_lag_reads_open_loop_sends_only():
    from bench_toy import ROOT

    spec = bench_run.Spec.__new__(bench_run.Spec)
    spec.root = ROOT
    read = spec.reader("client_lag_ms")
    # [stream, seq, t_sched, t_send, t_reply, status, best, digest]
    recs = [[0, k, 1.0 + k, 1.0 + k + 0.001 * k, 2.0 + k, 200, [], ""]
            for k in range(20)]
    ctx = bench_run.Context(loop="open", records=recs, unsent=[],
                            t_open=0.0, t_close=30.0)
    assert read(ctx) == pytest.approx(np.percentile(np.arange(20.0), 95))
    ctx.unsent = [[0, 29.0]] * 5        # never sent: their age at the close
    assert read(ctx) == pytest.approx(1000.0)
    assert read(bench_run.Context(loop="closed", records=recs, unsent=[],
                                  t_open=0.0, t_close=30.0)) is None
