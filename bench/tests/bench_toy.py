"""A checkout root for the harness's CPU tests: the benchmark's files plus
one toy configuration, two toy mixes and their cells, so a whole run fits
in a few seconds."""
from __future__ import annotations

import json
import os
import shutil

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

TOY_TORR = dict(D=1024, B=4, M=64, K=8, N_max=16, delta_budget=64)


def make_root(dst: str) -> str:
    """Copy ``BENCHMARK.json`` and ``bench/`` to ``dst`` and add the cells
    ``toy_coherent`` (closed loop) and ``toy_open`` (open loop)."""
    shutil.copytree(os.path.join(ROOT, "bench"), os.path.join(dst, "bench"),
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    with open(os.path.join(ROOT, "bench/configs/torr_edge.json")) as f:
        cfg = json.load(f)
    cfg["name"] = "toy"
    cfg["torr"].update(TOY_TORR)
    _dump(dst, "bench/configs/toy.json", cfg)
    with open(os.path.join(ROOT, "bench/traffic/coherent_closed16.json")) as f:
        mix = json.load(f)
    mix.update(streams=4, tenants=2, warm=2)
    _dump(dst, "bench/traffic/toy_coherent.json", mix)
    _dump(dst, "bench/traffic/toy_open.json",
          dict(mix, loop="open", rate_per_s=40.0, schedule_seed=0))
    bench["configs"].append({"name": "toy", "source": "toy widths",
                             "file": "bench/configs/toy.json",
                             "reduced": ["D", "B", "M", "N_max"],
                             "why": "CPU tests"})
    for mix_name in ("toy_coherent", "toy_open"):
        bench["workloads"].append({"name": mix_name, "config": "toy",
                                   "traffic": mix_name, "chips": 1,
                                   "why": "CPU tests"})
    _dump(dst, "BENCHMARK.json", bench)
    return dst


def _dump(root: str, rel: str, obj) -> None:
    with open(os.path.join(root, rel), "w") as f:
        json.dump(obj, f, indent=1)
