"""Input modules: the default one sends, makes and answers exactly what the
harness did before it had them, and a configuration that names a module of
its own, added as new files, is served and checked through that module."""
from __future__ import annotations

import hashlib
import json
import os
import shutil
import subprocess
import sys

import numpy as np
import pytest

from bench import client
from bench import inputs
from bench import run as bench_run
from bench_toy import ROOT, TOY_TORR, make_root

HERE = os.path.dirname(os.path.abspath(__file__))
SEED = 2**31 + 77
# from the functions of the harness before input modules (``StreamGen``,
# ``encode`` and the client's body; ``stack.make_data``; ``Stream`` and
# ``answer``), on the inputs that the tests below rebuild
BODIES = {
    "torr_edge/coherent_closed16":
        "fd59885fe4f113c1e52229cf580ffcfc7e00209f974b32e1ec8e27f381749587",
    "torr_edge/crowded_closed16":
        "bd4ae8c7503dab80553d10004432f438801b6153f64a982a344ef3061681babc",
    "toy/coherent_closed16":
        "8dd7f3727c88fbea5867b4d3f0fe4c49daf617015bd235b07e0e828b3e19635c",
    "toy/crowded_closed16":
        "c86cee6d15cc2fb2cb6649a27a992fda61bd630959451216264f503e78afa12c",
}
DATA = {
    "torr_edge":
        "47bc87eb4001cb7cee75a66d87a436095c11dcf1f5cf3fa6b201558d6c5bea8b",
    "toy": "c4c6e0cc113e4a2df6efe14867678bcaec3219cd30a62c26a30671ac1dda840f",
}
ANSWERS = {
    "coherent_closed16":
        "d2034acc5fd5ca1ac5964f51c5f30094d050a4842823effa4e6118bf7f189d2c",
    "crowded_closed16":
        "60d57ca1aad6e7ada5278bead58e87428c1f631a59988a69d2e363f7949373bd",
}


def _load(rel: str) -> dict:
    with open(os.path.join(ROOT, rel)) as f:
        return json.load(f)


def _config(name: str) -> dict:
    cfg = _load("bench/configs/torr_edge.json")
    if name == "toy":
        cfg["torr"].update(TOY_TORR)
    return cfg


def _default():
    return inputs.load(inputs.path_of(ROOT, {}))


@pytest.mark.parametrize("case", sorted(BODIES))
def test_default_module_sends_the_same_bodies(case):
    cname, mix = case.split("/")
    cfg, mod = _config(cname), _default()
    traffic = _load(f"bench/traffic/{mix}.json")
    h = hashlib.sha256()
    for i in (0, 5):
        wins = mod.windows(traffic, cfg, SEED, i)
        for seq in range(4):
            body = client.window_body(f"t{i % 4}/c{i}", seq, 10000.0,
                                      mod.fields(next(wins)))
            h.update(json.dumps(body).encode())
    assert h.hexdigest() == BODIES[case]


@pytest.mark.parametrize("cname", sorted(DATA))
def test_default_module_makes_the_same_data(cname):
    data = _default().make_data(_config(cname), SEED)
    assert sorted(data) == ["codes", "task_bank"]
    raw = np.asarray(data["codes"]).tobytes() + \
        np.asarray(data["task_bank"]).tobytes()
    assert hashlib.sha256(raw).hexdigest() == DATA[cname]


@pytest.mark.parametrize("mix", sorted(ANSWERS))
def test_default_module_gives_the_same_answers(mix):
    cfg, mod = _config("toy"), _default()
    traffic = _load(f"bench/traffic/{mix}.json")
    data = {k: np.asarray(v) for k, v in mod.make_data(cfg, SEED).items()}
    h = hashlib.sha256()
    for i in (1, 2):
        replay = mod.reference(cfg, data, i)
        wins = mod.windows(traffic, cfg, SEED, i)
        for _ in range(12):
            scores, paths = replay(next(wins))
            best, digest = mod.answer(scores)
            h.update(json.dumps([best, digest, list(map(int, paths))])
                     .encode())
    assert h.hexdigest() == ANSWERS[mix]


def test_default_module_client_and_check_sides_import_no_program():
    code = (
        "import sys; sys.path.insert(0, %r)\n"
        "from bench import inputs\n"
        "import json, numpy as np\n"
        "mod = inputs.load(inputs.path_of(%r, {}))\n"
        "cfg = json.load(open(%r)); cfg['torr'].update(%r)\n"
        "mix = json.load(open(%r))\n"
        "w = next(mod.windows(mix, cfg, 7, 0)); mod.fields(w)\n"
        "t = cfg['torr']\n"
        "data = {'codes': np.ones((t['M'], t['D']), np.int8),\n"
        "        'task_bank': np.ones((cfg['tasks'], t['M']), np.float32)}\n"
        "mod.answer(mod.reference(cfg, data, 0)(w)[0])\n"
        "bad = [m for m in sys.modules if m.split('.')[0] in"
        " ('jax', 'repro')]\n"
        "assert not bad, bad\n"
        % (ROOT, ROOT, os.path.join(ROOT, "bench/configs/torr_edge.json"),
           TOY_TORR, os.path.join(ROOT, "bench/traffic/crowded_closed16.json")))
    subprocess.run([sys.executable, "-c", code], check=True)


def _digests(root):
    out = {}
    for d, _dirs, files in os.walk(os.path.join(root, "bench")):
        for f in files:
            p = os.path.join(d, f)
            with open(p, "rb") as fh:
                out[os.path.relpath(p, root)] = hashlib.sha256(
                    fh.read()).hexdigest()
    return out


def _add_cell(root: str, name: str, module: str) -> None:
    """New files only: the test module under ``bench/inputs``, a toy
    configuration that names it, and a cell (``BENCHMARK.json``)."""
    rel = f"bench/inputs/{name}.py"
    shutil.copy(os.path.join(HERE, "data", module), os.path.join(root, rel))
    with open(os.path.join(root, "bench/configs/toy.json")) as f:
        cfg = json.load(f)
    cfg.update(name=name, input=rel)
    with open(os.path.join(root, f"bench/configs/{name}.json"), "w") as f:
        json.dump(cfg, f)
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        bench = json.load(f)
    bench["configs"].append({"name": name, "source": "toy",
                             "file": f"bench/configs/{name}.json",
                             "reduced": [], "why": "test"})
    bench["workloads"].append({"name": name, "config": name,
                               "traffic": "toy_coherent", "chips": 1,
                               "why": "test"})
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(bench, f)


def _run(root, capsys, monkeypatch, workload):
    seen = []
    orig = bench_run.check

    def keep(spec, data, records, server_paths, seed, workers=0):
        seen.append((spec, data, records, server_paths, seed))
        return orig(spec, data, records, server_paths, seed, workers)
    monkeypatch.setattr(bench_run, "check", keep)
    rc = bench_run.run(["--workload", workload, "--seed", "3000000041",
                        "--seconds", "1"], root=root, require_chip=False)
    out = capsys.readouterr().out.strip().splitlines()
    assert rc == 0
    return json.loads(out[-1]), seen[0]


def test_configuration_brings_its_own_input(tmp_path, capsys, monkeypatch):
    root = make_root(str(tmp_path))
    before = _digests(root)
    _add_cell(root, "toy_fresh", "input_fresh.py")
    res, (spec, data, records, server_paths, seed) = _run(
        root, capsys, monkeypatch, "toy_fresh")
    assert spec.input == os.path.join(root, "bench/inputs/toy_fresh.py")
    assert res["correct"] is True, res["checks"]
    assert all(c["value"] == 0 for c in res["checks"].values())
    # the windows served and the data were the module's: the default
    # input's reference, on the same records, answers otherwise
    spec.input = inputs.path_of(root, {})
    checks, compared, _ = bench_run.check(spec, data, records, server_paths,
                                          seed)
    assert compared > 0 and checks["wrong_answers"][0] == compared
    assert all(_digests(root).get(k) == v for k, v in before.items())


def test_planted_wrong_reference_is_not_correct(tmp_path, capsys,
                                                monkeypatch):
    root = make_root(str(tmp_path))
    _add_cell(root, "toy_wrong", "input_wrong_reference.py")
    res, (_spec, _data, records, _paths, _seed) = _run(
        root, capsys, monkeypatch, "toy_wrong")
    assert res["correct"] is False
    answered = sum(r[5] == 200 for r in records)
    assert res["checks"]["wrong_answers"]["value"] == answered > 0
    assert res["checks"]["path_count_gap"]["value"] == 0
