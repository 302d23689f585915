"""Feature windows: the engine encodes q = sign(R z) on the device (paper
Sec. 3.2) inside the served step, bit for bit what the packed path serves
on the reference's packed codes.

Features are int8 and R is ±1 int8, so every projection is an exact
integer: the int8 kernel (int32 accumulation), the ``kernels.ref`` oracle
and a numpy f32 matmul agree on every bit, ties at y = 0 included (they
map to +1).
"""
import dataclasses

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.core import hdc, pipeline
from repro.core.item_memory import random_item_memory
from repro.kernels import fused_window, ops, ref
from repro.serving import protocol
from repro.serving.async_engine import AsyncStreamEngine
from repro.serving.stream_engine import StreamEngine

from test_gateway import _FakeFront, _frame, _gw, _open_session, _req
from test_multistream import CFG as _CFG

# a cache as deep as a window's proposals, so kept rows can bypass
CFG = dataclasses.replace(_CFG, K=_CFG.N_max)
S, T = 3, 7


def _rademacher(rng, D, d):
    return np.where(rng.random((D, d)) < 0.5, 1, -1).astype(np.int8)


def _np_pack(z, R):
    """Packed words from an f32 numpy matmul: exact on these integers."""
    y = z.astype(np.float32) @ R.astype(np.float32).T
    bits = np.packbits(y >= 0, axis=-1, bitorder="little")
    return np.ascontiguousarray(bits).view("<u4").astype(np.uint32)


def _tied_rows(d, n):
    """Rows whose projections against a ±1 R sit at y in {-1, 0, +1}:
    e_a - e_b (y in {-2, 0, 2}), e_a (y = ±1), e_a + e_b - e_c
    (y in {±1, ±3}) and the zero row (every y = 0), then random int8."""
    z = np.zeros((n, d), np.int8)
    z[0, 0], z[0, 1] = 1, -1
    z[1, 2] = 1
    z[2, 3], z[2, 4], z[2, 5] = 1, 1, -1
    z[4:] = np.random.default_rng(1).integers(-127, 128, (n - 4, d))
    return z


@pytest.mark.parametrize("N,d,D", [(16, 64, 512), (128, 512, 1024)])
def test_int8_sign_project_pack_is_exact_with_ties(N, d, D):
    R = _rademacher(np.random.default_rng(0), D, d)
    z = _tied_rows(d, N)
    y = z.astype(np.int64) @ R.astype(np.int64).T
    assert (y == 0).any() and (np.abs(y) == 1).any()   # the ties are there
    want = _np_pack(z, R)
    oracle = ref.sign_project_pack_ref(jnp.asarray(z), jnp.asarray(R))
    kernel = fused_window.sign_project_pack(jnp.asarray(z), jnp.asarray(R),
                                            interpret=True)
    assert np.array_equal(np.asarray(oracle), want)
    assert np.array_equal(np.asarray(kernel), want)
    assert np.array_equal(np.asarray(ops.encode_packed(z, R)), want)
    # the zero row projects to y = 0 everywhere: every bit +1
    assert (want[3] == np.uint32(0xFFFFFFFF)).all()
    # the int8 oracle is the f32 bipolar projection of the same values
    assert np.array_equal(
        np.asarray(hdc.pack_bits(hdc.sign_project(z, R))), want)


def _quantize(x):
    """Symmetric int8 per row; the scale is dropped (sign(R cz) = sign(R z)
    for c > 0)."""
    scale = 127.0 / np.maximum(np.abs(x).max(axis=-1, keepdims=True), 1e-30)
    return np.clip(np.rint(x * scale), -127, 127).astype(np.int8)


def _feature_windows(seed):
    """``[(z int8 [S, N_max, d], valid, boxes)]`` over T windows: from the
    second window each valid row keeps its feature, drifts by noise of a
    tenth of its norm, or is drawn fresh, in thirds."""
    rng = np.random.default_rng(seed)
    d = CFG.feat_dim
    x = rng.standard_normal((S, CFG.N_max, d))
    out = []
    for t in range(T):
        valid = np.ones((S, CFG.N_max), bool)
        valid[1, -1] = t % 2 == 0
        if t:
            r = rng.random((S, CFG.N_max))
            noise = rng.standard_normal(x.shape)
            noise *= 0.1 * np.linalg.norm(x, axis=-1, keepdims=True) \
                / np.linalg.norm(noise, axis=-1, keepdims=True)
            drift = (r >= 1 / 3) & (r < 2 / 3)
            x = np.where(drift[..., None], x + noise, x)
            fresh = r >= 2 / 3
            x = np.where(fresh[..., None], rng.standard_normal(x.shape), x)
        z = np.where(valid[..., None], _quantize(x), 0).astype(np.int8)
        boxes = rng.random((S, CFG.N_max, 4)).astype(np.float32)
        out.append((z, valid, boxes))
    return out


def _serve(eng, windows, queries):
    """Every window through ``eng``; ``[(out, tel)]`` per step as numpy."""
    task_w = np.asarray(jax.random.uniform(jax.random.PRNGKey(1),
                                           (S, CFG.M)))
    for s in range(S):
        eng.admit(f"cam{s}", task_w[s])
    res = []
    for (z, valid, boxes), q in zip(windows, queries):
        for s in range(S):
            eng.submit(f"cam{s}", q[s], valid[s], boxes[s])
        step = eng.step()
        res.append([jax.tree.map(np.asarray, step[f"cam{s}"])
                    for s in range(S)])
    return res


@pytest.mark.parametrize("fused", [None, "off"])
def test_feature_engine_matches_packed_engine(fused):
    im = random_item_memory(jax.random.PRNGKey(0), CFG)
    R = _rademacher(np.random.default_rng(5), CFG.D, CFG.feat_dim)
    windows = _feature_windows(7)
    feat = StreamEngine(CFG, im, n_slots=S, fused=fused, projection=R)
    packed = StreamEngine(CFG, im, n_slots=S, fused=fused)
    got = _serve(feat, windows, [w[0] for w in windows])
    want = _serve(packed, windows,
                  [_np_pack(z, R) for z, _v, _b in windows])
    paths = set()
    for (_z, valid, _b), step_got, step_want in zip(windows, got, want):
        for s, (g, w) in enumerate(zip(step_got, step_want)):
            for a, b in zip(jax.tree.leaves(g), jax.tree.leaves(w)):
                assert np.array_equal(a, b)
            paths |= set(g[1].path[valid[s]].tolist())
    assert paths == {0, 1, 2}     # keep, drift and fresh took every path
    for a, b in zip(jax.tree.leaves(feat.state), jax.tree.leaves(
            packed.state)):
        assert np.array_equal(np.asarray(a), np.asarray(b))
    assert feat.feat_dim == CFG.feat_dim and packed.feat_dim is None


@pytest.mark.parametrize("fused", ["switch", "off"])
def test_single_stream_step_encodes_features(fused):
    """The single-stream step, the oracle's, given R and a window's
    features answers as on the packed codes."""
    im = random_item_memory(jax.random.PRNGKey(0), CFG)
    R = _rademacher(np.random.default_rng(5), CFG.D, CFG.feat_dim)
    state = pipeline.init_state(CFG, jnp.ones((CFG.M,), jnp.float32))
    outs = []
    for z, valid, boxes in _feature_windows(2)[:3]:
        got = pipeline.torr_window_step(
            state, im, jnp.asarray(z[0]), valid[0], boxes[0], jnp.int32(0),
            CFG, fused=fused, projection=jnp.asarray(R))
        want = pipeline.torr_window_step(
            state, im, jnp.asarray(_np_pack(z[0], R)), valid[0], boxes[0],
            jnp.int32(0), CFG, fused=fused)
        for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
            assert np.array_equal(np.asarray(a), np.asarray(b))
        state = got[0]
        outs.append(got[2].path)
    assert len({int(p) for p in np.concatenate(outs)}) > 1


def test_feature_engine_counts_encoded_rows():
    from repro.obs import MetricsRegistry

    im = random_item_memory(jax.random.PRNGKey(0), CFG)
    R = _rademacher(np.random.default_rng(5), CFG.D, CFG.feat_dim)
    windows = _feature_windows(3)
    reg = MetricsRegistry()
    eng = StreamEngine(CFG, im, n_slots=S, projection=R, metrics=reg)
    _serve(eng, windows, [w[0] for w in windows])
    (series,) = reg.snapshot()["torr_encoded_rows_total"]["series"]
    assert series["value"] == sum(int(v.sum()) for _z, v, _b in windows)


def test_feature_engine_rejects_a_mesh():
    from types import SimpleNamespace

    mesh = SimpleNamespace(devices=np.empty(2))    # two devices, unread
    im = random_item_memory(jax.random.PRNGKey(0), CFG)
    with pytest.raises(ValueError):
        AsyncStreamEngine(CFG, im, n_slots=2, mesh=mesh, paused=True,
                          projection=np.ones((CFG.D, CFG.feat_dim), np.int8))


class _FeatureFront(_FakeFront):
    feat_dim = CFG.feat_dim


def _feature_frame(z, seq=0):
    body = _frame(seq=seq)
    del body["q"]
    body["z"] = protocol.encode_array(z)
    return body


def test_gateway_takes_features_and_refuses_bad_ones():
    gw, _ = _gw(front=_FeatureFront())
    try:
        st, _, cfg = _req(gw.port, "GET", "/v1/config")
        assert st == 200 and cfg["window"] == {
            "field": "z", "dtype": "int8",
            "shape": [CFG.N_max, CFG.feat_dim]}
        _open_session(gw.port)
        z = np.ones((CFG.N_max, CFG.feat_dim), np.int8)
        bad = [_feature_frame(z.astype(np.uint8)),
               _feature_frame(z.astype(np.float32)),
               _feature_frame(z[:, :-1]),
               _feature_frame(z[:-1]),
               _frame(seq=0)]               # packed words, no z
        for body in bad:
            st, _, b = _req(gw.port, "POST", "/v1/window", body)
            assert st == 400, b
            assert "z" in b["detail"], b
        st, _, b = _req(gw.port, "POST", "/v1/window", _feature_frame(z))
        assert st == 200 and b["seq"] == 0
    finally:
        gw.close()


def test_gateway_serves_feature_windows_through_the_engine():
    """Client -> gateway -> async engine with a projection: the digest of
    a feature window equals the packed engine's on the reference's
    packed codes."""
    im = random_item_memory(jax.random.PRNGKey(0), CFG)
    R = _rademacher(np.random.default_rng(5), CFG.D, CFG.feat_dim)
    (z, valid, boxes), = _feature_windows(11)[:1]
    digests = []
    for projection, q in ((R, z[0]), (None, _np_pack(z[0], R))):
        eng = AsyncStreamEngine(CFG, im, n_slots=2, projection=projection)
        gw, _ = _gw(front=eng, request_deadline_s=60.0)
        try:
            _open_session(gw.port)
            field = "z" if projection is not None else "q"
            body = {"session": "t0/s0", "seq": 0,
                    field: protocol.encode_array(q),
                    "valid": protocol.encode_array(valid[0]),
                    "boxes": protocol.encode_array(boxes[0])}
            st, _, b = _req(gw.port, "POST", "/v1/window", body, timeout=120)
            assert st == 200, b
            digests.append(b["scores_sha256"])
        finally:
            gw.close()
            eng.close()
    assert digests[0] == digests[1]
