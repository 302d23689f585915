"""The features-in deployment (``bench/inputs/features.py``): served and
checked through ``bench/run.py`` on the CPU at toy widths, a planted
server-side fault caught, and the check side's plain encode equal to the
program's oracle."""
from __future__ import annotations

import json
import os
import shutil
import subprocess
import sys

import numpy as np
import pytest

from bench import inputs
from bench import run as bench_run
from bench import work
from bench_toy import ROOT, TOY_TORR, make_root

HERE = os.path.dirname(os.path.abspath(__file__))
TOY_FEATURES = dict(TOY_TORR, feat_dim=64)


def _load(rel: str) -> dict:
    with open(os.path.join(ROOT, rel)) as f:
        return json.load(f)


def _toy_config() -> dict:
    cfg = _load("bench/configs/torr_edge_features.json")
    cfg["torr"].update(TOY_FEATURES)
    cfg["features"]["dim"] = TOY_FEATURES["feat_dim"]
    return cfg


def _features():
    return inputs.load(os.path.join(ROOT, "bench/inputs/features.py"))


def _add_cell(root: str, name: str, module: str | None) -> None:
    """A toy features configuration (its input the features module, or a
    test module copied in as a new file), a toy open-loop mix of the
    cell's content, and the cell."""
    cfg = _toy_config()
    cfg["name"] = name
    if module is not None:
        cfg["input"] = f"bench/inputs/{name}.py"
        shutil.copy(os.path.join(HERE, "data", module),
                    os.path.join(root, cfg["input"]))
    with open(os.path.join(root, f"bench/configs/{name}.json"), "w") as f:
        json.dump(cfg, f)
    mix = _load("bench/traffic/features_drift_open16.json")
    mix.update(streams=4, tenants=2, warm=2, rate_per_s=40.0,
               schedule_seed=0)
    with open(os.path.join(root, "bench/traffic/toy_features.json"),
              "w") as f:
        json.dump(mix, f)
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        bench = json.load(f)
    bench["configs"].append({"name": name, "source": "toy",
                             "file": f"bench/configs/{name}.json",
                             "reduced": [], "why": "test"})
    bench["workloads"].append({"name": name, "config": name,
                               "traffic": "toy_features", "chips": 1,
                               "why": "test"})
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(bench, f)


def _run(root, capsys, monkeypatch, workload):
    seen = []
    orig = bench_run.check

    def keep(spec, data, records, server_paths, seed, workers=0):
        seen.append(server_paths)
        return orig(spec, data, records, server_paths, seed, workers)
    monkeypatch.setattr(bench_run, "check", keep)
    rc = bench_run.run(["--workload", workload, "--seed", "3000000161",
                        "--seconds", "1"], root=root, require_chip=False)
    out = capsys.readouterr().out.strip().splitlines()
    assert rc == 0
    return json.loads(out[-1]), seen[0]


def test_features_cell_is_served_and_correct(tmp_path, capsys, monkeypatch):
    root = make_root(str(tmp_path))
    _add_cell(root, "toy_features", None)
    res, server_paths = _run(root, capsys, monkeypatch, "toy_features")
    assert res["correct"] is True, res["checks"]
    assert all(c["value"] == 0 for c in res["checks"].values())
    assert set(res["metrics"]) == {"p95_window_ms", "setup_s"}
    # keep, drift and fresh rows took bypass, delta and full
    assert all(n > 0 for n in server_paths), server_paths


def test_planted_4bit_features_are_not_correct(tmp_path, capsys,
                                               monkeypatch):
    root = make_root(str(tmp_path))
    _add_cell(root, "toy_features_4bit", "input_features_4bit.py")
    res, _paths = _run(root, capsys, monkeypatch, "toy_features_4bit")
    assert res["correct"] is False
    assert res["checks"]["wrong_answers"]["value"] > 0


def test_configuration_is_torr_edge_with_features():
    from bench import stack
    from repro.configs import torr_edge

    cfg = _load("bench/configs/torr_edge_features.json")
    assert stack.torr_config(cfg) == torr_edge()
    assert cfg["torr"] == _load("bench/configs/torr_edge.json")["torr"]
    assert cfg["features"] == {"dim": torr_edge().feat_dim, "dtype": "int8",
                               "projection": "rademacher_int8"}
    assert cfg["input"] == "bench/inputs/features.py" and cfg["reduced"] == []


def test_data_adds_only_the_projection_to_the_stacks():
    from bench import stack

    cfg, mod = _toy_config(), _features()
    data = mod.make_data(cfg, 2**31 + 7)
    codes, task_bank = stack.make_data(cfg, 2**31 + 7)
    assert sorted(data) == ["codes", "projection", "task_bank"]
    assert np.array_equal(np.asarray(data["codes"]), np.asarray(codes))
    assert np.array_equal(np.asarray(data["task_bank"]),
                          np.asarray(task_bank))
    t = cfg["torr"]
    assert data["projection"].shape == (t["D"], t["feat_dim"])


def test_check_side_encode_equals_the_programs_oracle():
    import jax.numpy as jnp

    from repro.core import hdc
    from repro.kernels import ref

    cfg, mod = _toy_config(), _features()
    traffic = _load("bench/traffic/features_drift_open16.json")
    data = {k: np.asarray(v) for k, v in mod.make_data(cfg, 2**31 + 5)
            .items()}
    R = data["projection"]
    assert R.dtype == np.int8 and set(np.unique(R)) == {-1, 1}
    wins = mod.windows(traffic, cfg, 2**31 + 5, 3)
    z = np.concatenate([next(wins)[0][:8] for _ in range(6)])
    assert z.dtype == np.int8 and np.abs(z).max() == 127
    want = np.asarray(ref.sign_project_pack_ref(jnp.asarray(z),
                                                jnp.asarray(R)))
    r_t = R.astype(np.float32).T
    assert np.array_equal(mod.encode_packed(z, r_t), want)
    # a zero row sits on every hyperplane: all bits +1
    assert (mod.encode_packed(np.zeros((1, R.shape[1]), np.int8), r_t)
            == np.asarray(hdc.pack_bits(jnp.ones((1, R.shape[0]),
                                                 jnp.int8)))).all()


def test_windows_keep_drift_and_refresh_rows():
    """Kept rows send the same int8 feature; a drifted row's codes flip
    about drift_rel / pi of their bits; fresh rows about half."""
    cfg, mod = _load("bench/configs/torr_edge_features.json"), _features()
    traffic = _load("bench/traffic/features_drift_open16.json")
    rng = np.random.default_rng(0)
    R = np.where(rng.random((cfg["torr"]["D"], 512)) < 0.5, 1, -1) \
        .astype(np.float32).T
    wins = mod.windows(traffic, cfg, 2**31 + 9, 0)
    prev = next(wins)
    flips, same = [], 0
    for _ in range(40):
        cur = next(wins)
        n = traffic["valid"]["first"]
        assert not cur[0][n:].any() and cur[1].sum() == n
        same += sum(np.array_equal(a, b) for a, b in zip(cur[0][:n],
                                                         prev[0][:n]))
        a = mod.encode_packed(prev[0][:n], R)
        b = mod.encode_packed(cur[0][:n], R)
        flips += list(np.bitwise_count(a ^ b).sum(axis=1)
                      / cfg["torr"]["D"])
        prev = cur
    flips = np.asarray(flips)
    drifted = flips[(flips > 0) & (flips < 0.2)]
    assert same == (flips == 0).sum()
    assert 0.35 < same / flips.size < 0.55
    assert drifted.size and abs(np.median(drifted) - 0.1 / np.pi) < 0.01
    assert ((flips > 0.4) & (flips < 0.6)).sum() > 0


def test_features_module_client_and_check_sides_import_no_program():
    code = (
        "import sys, json; sys.path.insert(0, %r)\n"
        "import numpy as np\n"
        "from bench import inputs\n"
        "mod = inputs.load(%r)\n"
        "cfg = %r\n"
        "mix = json.load(open(%r))\n"
        "w = next(mod.windows(mix, cfg, 7, 0)); mod.fields(w)\n"
        "t = cfg['torr']\n"
        "data = {'codes': np.ones((t['M'], t['D']), np.int8),\n"
        "        'task_bank': np.ones((cfg['tasks'], t['M']), np.float32),\n"
        "        'projection': np.ones((t['D'], t['feat_dim']), np.int8)}\n"
        "mod.answer(mod.reference(cfg, data, 0)(w)[0])\n"
        "bad = [m for m in sys.modules if m.split('.')[0] in"
        " ('jax', 'repro')]\n"
        "assert not bad, bad\n"
        % (ROOT, os.path.join(ROOT, "bench/inputs/features.py"),
           _toy_config(),
           os.path.join(ROOT, "bench/traffic/features_drift_open16.json")))
    subprocess.run([sys.executable, "-c", code], check=True)


@pytest.mark.parametrize("name", ["encode_device_ms_per_step",
                                  "encode_roofline"])
def test_encode_readers_find_nothing_without_the_encode(name):
    """On a packed step, or a program without the encode, the readers
    return nothing and do not raise."""
    spec = bench_run.Spec(ROOT, "features_drift_open")
    trace = {"op_s": {"%bank_prefix_hamming.1 custom-call": 0.3}}
    snap = {"torr_steps_total": {"series": [{"labels": {}, "value": 10}]},
            "torr_step_phase_ops": {"series": [
                {"labels": {"phase": "prefix_scan",
                            "ops": "bank_prefix_hamming.1"}, "value": 1}]}}
    ctx = bench_run.Context(spec=spec, cfg=spec.config["torr"],
                            snaps=({}, snap), trace=trace,
                            peak={"int8_ops_per_s": 3.94e14,
                                  "hbm_bytes_per_s": 8.19e11},
                            work=work)
    assert spec.reader(name)(ctx) is None


def test_encode_readers_read_the_encode():
    spec = bench_run.Spec(ROOT, "features_drift_open")
    D, d = spec.config["torr"]["D"], spec.config["features"]["dim"]
    trace = {"op_s": {"%sign_project_pack.1 custom-call": 0.02,
                      "%copy.68 copy": 0.005,
                      "%bank_prefix_hamming.1 custom-call": 0.3}}
    snap = {"torr_steps_total": {"series": [{"labels": {}, "value": 100}]},
            "torr_encoded_rows_total": {"series": [
                {"labels": {}, "value": 2000}]},
            "torr_step_phase_ops": {"series": [
                {"labels": {"phase": "encode",
                            "ops": "copy.68 sign_project_pack.1"},
                 "value": 2}]}}
    peak = {"int8_ops_per_s": 3.94e14, "hbm_bytes_per_s": 8.19e11}
    ctx = bench_run.Context(spec=spec, cfg=spec.config["torr"],
                            snaps=({}, snap), trace=trace, peak=peak,
                            work=work)
    assert spec.reader("encode_device_ms_per_step")(ctx) == \
        pytest.approx(0.025 / 100 * 1e3)
    share = spec.reader("encode_roofline")(ctx)
    n_bytes = 100 * D * d + 2000 * (d + D / 8)
    assert share == pytest.approx(
        max(2 * D * d * 2000 / peak["int8_ops_per_s"],
            n_bytes / peak["hbm_bytes_per_s"]) / 0.02 * 100)
    assert ctx.roofline_bound == {"encode_roofline": "bytes"}
