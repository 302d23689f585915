"""The plain reference's arithmetic: its top-k is a stable sort's first k,
and the full dot products it keeps from window to window equal the ones
taken afresh."""
from __future__ import annotations

import json
import os

import numpy as np
import pytest

from bench import reference as ref
from bench import traffic as tr
from bench_toy import ROOT, TOY_TORR


@pytest.mark.parametrize("n,k,levels", [
    (64, 5, 3), (64, 5, 64), (1024, 5, 7), (5, 5, 2), (3, 5, 2), (16, 1, 2),
])
def test_topk_stable_is_a_stable_sorts_first_k(n, k, levels):
    rng = np.random.default_rng([n, k, levels])
    for _ in range(200):
        s = rng.integers(0, levels, n).astype(np.float32) / 4
        np.testing.assert_array_equal(
            ref.topk_stable(s, k), np.argsort(-s, kind="stable")[:k])


@pytest.mark.parametrize("mix", ["coherent_closed16", "crowded_closed16"])
def test_kept_full_dots_equal_a_direct_matmul(mix):
    with open(os.path.join(ROOT, "bench/configs/torr_edge.json")) as f:
        cfg = json.load(f)["torr"]
    cfg.update(TOY_TORR)
    with open(os.path.join(ROOT, f"bench/traffic/{mix}.json")) as f:
        traffic = json.load(f)
    rng = np.random.default_rng(5)
    codes = np.where(rng.random((cfg["M"], cfg["D"])) < 0.5, 1,
                     -1).astype(np.int8)
    stream = ref.Stream(cfg, codes, np.ones(cfg["M"], np.float32))
    gen = tr.StreamGen(traffic, cfg["N_max"], cfg["D"], 2**31 + 9, 1)
    kept = afresh = 0
    for _ in range(60):
        q, valid, _boxes = gen.next()
        # queue depths that change D' from window to window
        depth = int(rng.integers(0, 40))
        before = stream.dot_q.copy()
        stream.window(q, valid, depth)
        rows = np.flatnonzero(valid)
        dims = ref.select_banks(cfg, rows.size, depth) * cfg["D"] // cfg["B"]
        direct = tr.unpack_bits(q[rows], cfg["D"])[:, :dims].astype(
            np.int64) @ codes[:, :dims].T.astype(np.int64)
        np.testing.assert_array_equal(stream.dot[rows], direct)
        n_flip = np.bitwise_count(q[rows] ^ before[rows]).sum(axis=1)
        kept += int(((n_flip > 0) & (n_flip <= dims // 16)).sum())
        afresh += int((n_flip > dims // 16).sum())
    assert kept > 0 and afresh > 0
