"""Pallas kernels vs pure-jnp oracles: shape/dtype sweeps (interpret mode)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from _hypothesis_compat import given, settings, st

from repro.core import hdc
from repro.kernels import fused_window, ops, ref


@pytest.mark.parametrize("D,M,N", [(1024, 8, 1), (4096, 128, 8),
                                   (8192, 64, 4), (2048, 256, 2)])
def test_packed_similarity_shapes(D, M, N):
    hv = hdc.random_hv(jax.random.PRNGKey(0), (M, D))
    q = hdc.random_hv(jax.random.PRNGKey(1), (N, D))
    imp, qp = hdc.pack_bits(hv), hdc.pack_bits(q)
    B = 8
    bw = D // B // 32
    for banks in (1, 3, B):
        if (banks * bw) % 128 and banks != B:
            continue
        acc, cos = ops.packed_similarity(qp, imp, banks=banks, bank_words=bw)
        d_eff = banks * bw * 32
        want = jnp.einsum("nd,md->nm", q[:, :d_eff].astype(jnp.int32),
                          hv[:, :d_eff].astype(jnp.int32))
        assert (acc == want).all(), (D, M, N, banks)


@given(st.integers(0, 2**31 - 1), st.sampled_from([64, 128, 384]),
       st.sampled_from([8, 64, 96]))
@settings(max_examples=10, deadline=None)
def test_delta_update_property(seed, M, budget):
    D = 2048
    ks = jax.random.split(jax.random.PRNGKey(seed), 4)
    hv = hdc.random_hv(ks[0], (M, D))
    dmaj = jnp.transpose(hv)
    acc = jax.random.randint(ks[1], (M,), -1000, 1000, jnp.int32)
    idx = jax.random.randint(ks[2], (budget,), 0, D, jnp.int32)
    w = jnp.where(jax.random.bernoulli(ks[3], 0.5, (budget,)), 2, -2)
    w = w.astype(jnp.int32).at[budget // 2:].set(0)  # padding
    out = ops.delta_update(acc, dmaj, idx, w)
    want = ref.delta_update_ref(acc, dmaj, idx, w)
    assert (out == want).all()


@pytest.mark.parametrize("N,d,D", [(8, 64, 512), (16, 512, 4096),
                                   (8, 100, 1024)])
def test_sign_project_shapes(N, d, D):
    z = jax.random.normal(jax.random.PRNGKey(0), (N, d))
    R = jax.random.normal(jax.random.PRNGKey(1), (D, d))
    assert (ops.sign_project(z, R) == ref.sign_project_ref(z, R)).all()


def test_fallback_on_ragged_shapes():
    """Off-tile shapes must transparently use the oracle."""
    z = jax.random.normal(jax.random.PRNGKey(0), (3, 33))   # N=3 not /8
    R = jax.random.normal(jax.random.PRNGKey(1), (100, 33))  # D=100 not /128
    assert (ops.sign_project(z, R) == ref.sign_project_ref(z, R)).all()

    hv = hdc.random_hv(jax.random.PRNGKey(2), (7, 64))       # M=7 not /8
    q = hdc.random_hv(jax.random.PRNGKey(3), (2, 64))
    acc, _ = ops.packed_similarity(hdc.pack_bits(q), hdc.pack_bits(hv),
                                   banks=1, bank_words=2)
    want = jnp.einsum("nd,md->nm", q.astype(jnp.int32), hv.astype(jnp.int32))
    assert (acc == want).all()


# --- fused window-step kernel family ---------------------------------------

@pytest.mark.parametrize("D,M,N", [(1024, 8, 1), (2048, 64, 16),
                                   (4096, 128, 8), (2048, 256, 3)])
def test_fused_scores_grid(D, M, N):
    """Interpret-mode kernel grid: acc, argmax and top-2 readout are all
    bit-identical to the oracle (ties: lowest index, lax.top_k order)."""
    hv = hdc.random_hv(jax.random.PRNGKey(0), (M, D))
    q = hdc.random_hv(jax.random.PRNGKey(1), (N, D))
    imp, qp = hdc.pack_bits(hv), hdc.pack_bits(q)
    acc, best, top2 = fused_window.fused_scores(qp, imp, d_eff=D,
                                                interpret=True)
    w_acc, w_best, w_top2 = ref.fused_scores_ref(qp, imp, d_eff=D)
    assert np.array_equal(np.asarray(acc), np.asarray(w_acc))
    assert np.array_equal(np.asarray(best), np.asarray(w_best))
    assert np.array_equal(np.asarray(top2), np.asarray(w_top2))


def test_fused_scores_argmax_tie_breaking():
    """Duplicated item-memory rows force exact ties; the fused readout must
    keep jnp.argmax's lowest-index winner."""
    D, N = 1024, 8
    hv0 = hdc.random_hv(jax.random.PRNGKey(0), (8, D))
    hv = jnp.concatenate([hv0, hv0], axis=0)            # every row twice
    q = hdc.random_hv(jax.random.PRNGKey(1), (N, D))
    imp, qp = hdc.pack_bits(hv), hdc.pack_bits(q)
    acc, best, top2 = fused_window.fused_scores(qp, imp, d_eff=D,
                                                interpret=True)
    assert np.array_equal(np.asarray(best),
                          np.asarray(jnp.argmax(acc, -1)))
    assert (np.asarray(best) < 8).all()                 # first copy wins
    assert np.array_equal(np.asarray(top2),
                          np.asarray(jax.lax.top_k(acc, 2)[0]))
    # the duplicated memory makes top-1 == top-2 exactly
    assert (np.asarray(top2)[:, 0] == np.asarray(top2)[:, 1]).all()


@pytest.mark.parametrize("D,M,N,cap", [(1024, 8, 4, 8), (2048, 64, 16, 8),
                                       (2048, 64, 5, 4), (4096, 32, 8, 2)])
def test_bank_prefix_hamming_grid(D, M, N, cap):
    hv = hdc.random_hv(jax.random.PRNGKey(2), (M, D))
    q = hdc.random_hv(jax.random.PRNGKey(3), (N, D))
    imp, qp = hdc.pack_bits(hv), hdc.pack_bits(q)
    out = fused_window.bank_prefix_hamming(qp, imp, cap=cap, interpret=True)
    want = ref.bank_prefix_hamming_ref(qp, imp, cap=cap)
    assert out.shape == (N, cap, M)
    assert np.array_equal(np.asarray(out), np.asarray(want))


@pytest.mark.parametrize("D,M,N", [(2048, 64, 16), (1024, 24, 5)])
def test_blocked_lowerings_match_kernel(D, M, N):
    """The CPU blocked-jnp lowering == the interpret-mode Pallas grid ==
    the oracle, for both the fused-scores and bank-prefix family members."""
    hv = hdc.random_hv(jax.random.PRNGKey(4), (M, D))
    q = hdc.random_hv(jax.random.PRNGKey(5), (N, D))
    imp, qp = hdc.pack_bits(hv), hdc.pack_bits(q)
    blocked = fused_window._blocked_scores(qp, imp, d_eff=D)
    kern = fused_window.fused_scores(qp, imp, d_eff=D, interpret=True)
    want = ref.fused_scores_ref(qp, imp, d_eff=D)
    for b, k, w in zip(blocked, kern, want):
        assert np.array_equal(np.asarray(b), np.asarray(w))
        assert np.array_equal(np.asarray(k), np.asarray(w))
    bp = fused_window._blocked_prefix(qp, imp, cap=8)
    kp = fused_window.bank_prefix_hamming(qp, imp, cap=8, interpret=True)
    wp = ref.bank_prefix_hamming_ref(qp, imp, cap=8)
    assert np.array_equal(np.asarray(bp), np.asarray(wp))
    assert np.array_equal(np.asarray(kp), np.asarray(wp))


def test_fused_any_ragged_falls_back():
    """M not a multiple of 8 transparently uses the oracle."""
    hv = hdc.random_hv(jax.random.PRNGKey(6), (7, 1024))
    q = hdc.random_hv(jax.random.PRNGKey(7), (3, 1024))
    imp, qp = hdc.pack_bits(hv), hdc.pack_bits(q)
    acc, best, top2 = fused_window.fused_scores_any(qp, imp, d_eff=1024)
    w = ref.fused_scores_ref(qp, imp, d_eff=1024)
    assert np.array_equal(np.asarray(acc), np.asarray(w[0]))
    assert np.array_equal(np.asarray(best), np.asarray(w[1]))
    hp = fused_window.bank_prefix_hamming_any(qp, imp, cap=4)
    assert np.array_equal(np.asarray(hp),
                          np.asarray(ref.bank_prefix_hamming_ref(
                              qp, imp, cap=4)))


@pytest.mark.parametrize("N,d,D", [(8, 64, 512), (16, 512, 4096),
                                   (8, 100, 1024), (3, 33, 100)])
def test_sign_project_pack(N, d, D):
    """Fused encode->pack == pack_bits(sign_project) — kernel where D packs
    to words (D % 32 == 0), oracle fallback elsewhere via ops."""
    z = jax.random.normal(jax.random.PRNGKey(0), (N, d))
    R = jax.random.normal(jax.random.PRNGKey(1), (D, d))
    if D % 32 == 0:
        want = hdc.pack_bits(ref.sign_project_ref(z, R))
        if D % 128 == 0 and N % 8 == 0:
            out = fused_window.sign_project_pack(z, R, interpret=True)
            assert np.array_equal(np.asarray(out), np.asarray(want))
        out2 = ops.encode_packed(z, R)
        assert np.array_equal(np.asarray(out2), np.asarray(want))
    else:
        with pytest.raises(ValueError):
            ref.sign_project_pack_ref(z, R)


def test_fused_similarity_matches_packed_similarity():
    """ops.fused_similarity (acc, cos) == ops.packed_similarity under every
    (banks, planes) plan; best/top2 match the oracle readout."""
    from repro.core.item_memory import random_item_memory
    from repro.core.types import TorrConfig
    cfg = TorrConfig(D=1024, B=8, M=32, K=4, N_max=8, delta_budget=128,
                     feat_dim=64)
    im = random_item_memory(jax.random.PRNGKey(0), cfg)
    qp = hdc.pack_bits(hdc.random_hv(jax.random.PRNGKey(1), (5, cfg.D)))
    for banks, planes in [(8, 4), (8, 2), (4, 1), (2, 2)]:
        acc, cos, best, top2 = ops.fused_similarity(
            qp, im.packed, banks=banks, bank_words=cfg.bank_words,
            planes=planes, plane_total=cfg.bit_planes, pmajor=im.pmajor)
        acc2, cos2 = ops.packed_similarity(
            qp, im.packed, banks=banks, bank_words=cfg.bank_words,
            planes=planes, plane_total=cfg.bit_planes, pmajor=im.pmajor)
        assert np.array_equal(np.asarray(acc), np.asarray(acc2))
        assert np.allclose(np.asarray(cos), np.asarray(cos2))
        assert np.array_equal(np.asarray(best),
                              np.asarray(jnp.argmax(acc, -1)))
        assert np.array_equal(np.asarray(top2),
                              np.asarray(jax.lax.top_k(acc, 2)[0]))


def test_delta_apply_dispatch():
    """fused_window.delta_apply == the oracle in every lowering (kernel via
    explicit interpret, vectorized form via the default CPU dispatch,
    oracle fallback on ragged M)."""
    D, budget = 1024, 64
    for M in (64, 7):
        ks = jax.random.split(jax.random.PRNGKey(M), 4)
        dmaj = jnp.transpose(hdc.random_hv(ks[0], (M, D)))
        acc = jax.random.randint(ks[1], (M,), -500, 500, jnp.int32)
        idx = jax.random.randint(ks[2], (budget,), 0, D, jnp.int32)
        w = jnp.where(jax.random.bernoulli(ks[3], 0.5, (budget,)), 2, -2)
        w = w.astype(jnp.int32).at[budget // 2:].set(0)
        want = ref.delta_update_ref(acc, dmaj, idx, w)
        for interpret in (None, True):
            out = fused_window.delta_apply(acc, dmaj, idx, w,
                                           interpret=interpret)
            assert np.array_equal(np.asarray(out), np.asarray(want)), \
                (M, interpret)


def test_tune_file_precedence(tmp_path, monkeypatch):
    """TORR_TUNE_FILE loads the autotune artifact's block shapes; explicit
    TORR_TQ/TORR_TM still win; a corrupt file is an error."""
    import importlib
    import json as _json
    from repro.kernels import xnor_popcount_sim as xps

    art = tmp_path / "tune.json"
    art.write_text(_json.dumps({"best": {"tq": 4, "tm": 16}}))
    monkeypatch.setenv("TORR_TUNE_FILE", str(art))
    monkeypatch.delenv("TORR_TQ", raising=False)
    monkeypatch.delenv("TORR_TM", raising=False)
    try:
        mod = importlib.reload(xps)
        assert mod.TQ_DEFAULT == 4 and mod.TM_DEFAULT == 16
        monkeypatch.setenv("TORR_TQ", "2")
        mod = importlib.reload(xps)
        assert mod.TQ_DEFAULT == 2 and mod.TM_DEFAULT == 16  # env wins
        art.write_text("not json")
        with pytest.raises(ValueError):
            importlib.reload(xps)
    finally:
        monkeypatch.delenv("TORR_TUNE_FILE", raising=False)
        monkeypatch.delenv("TORR_TQ", raising=False)
        importlib.reload(xps)


def test_delta_equals_full_rescan():
    """Integration: accumulator + delta corrections == fresh full scan."""
    D, M, budget = 2048, 64, 256
    hv = hdc.random_hv(jax.random.PRNGKey(0), (M, D))
    q0 = hdc.random_hv(jax.random.PRNGKey(1), (D,))
    flips = jax.random.choice(jax.random.PRNGKey(2), D, (100,), replace=False)
    q1 = q0.at[flips].multiply(-1)

    acc0, _ = ops.packed_similarity(hdc.pack_bits(q0)[None], hdc.pack_bits(hv),
                                    banks=8, bank_words=D // 8 // 32)
    from repro.core import aligner
    from repro.core.item_memory import build_item_memory, word_mask
    from repro.core.types import TorrConfig
    cfg = TorrConfig(D=D, B=8, M=M, delta_budget=budget)
    im = build_item_memory(hv)
    idx, w, cnt = aligner.delta_indices(
        hdc.pack_bits(q1), hdc.pack_bits(q0), word_mask(cfg, 8), budget, D)
    assert int(cnt) == 100
    acc1 = ops.delta_update(acc0[0], im.dmajor, idx, w)
    want, _ = ops.packed_similarity(hdc.pack_bits(q1)[None], hdc.pack_bits(hv),
                                    banks=8, bank_words=D // 8 // 32)
    assert (acc1 == want[0]).all()
