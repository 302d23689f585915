"""QoS control plane: knob plans, plan-tag exactness, governor dynamics.

The tentpole invariants (ISSUE 3):

  * pinned to the full plan, the governed engine/step is *bit-identical* to
    the ungoverned one;
  * under any reduced plan, full-path scores equal the jnp oracle restricted
    to the same dims/bit-planes;
  * a delta accumulator tagged under one (banks, planes) plan is rejected
    after any plan switch (Eq. 6 exactness), property-tested across plan
    pairs via the hypothesis-optional shim.
"""
import numpy as np
import pytest
from _hypothesis_compat import given, settings, st

import jax
import jax.numpy as jnp

from repro.control import (Governor, GovernorPolicy, KnobPlan, build_ladder,
                           full_plan, ladder_rel_cost, plan_level)
from repro.core import aligner, hdc, pipeline, query_cache
from repro.core.item_memory import (plan_dim_mask, plan_word_mask,
                                    plan_word_sel, random_item_memory)
from repro.core.types import PATH_DELTA, PATH_FULL, TorrConfig
from repro.kernels import ops

CFG = TorrConfig(D=1024, B=8, M=32, K=4, N_max=8, delta_budget=128,
                 feat_dim=64)

PLANS = [(8, 4), (8, 2), (8, 1), (4, 4), (4, 1), (2, 2), (1, 1)]


def _plan(banks, planes, cfg=CFG):
    return KnobPlan(banks=banks, planes=planes, plane_total=cfg.bit_planes)


def _window(cfg, seed, n_valid=None):
    q_bip = hdc.random_hv(jax.random.PRNGKey(seed), (cfg.N_max, cfg.D))
    valid = np.arange(cfg.N_max) < (n_valid if n_valid is not None else cfg.K - 1)
    return q_bip, jnp.asarray(valid), jnp.zeros((cfg.N_max, 4), jnp.float32)


# --- plan geometry ----------------------------------------------------------

def test_plan_word_sel_matches_mask():
    """The static kernel-side word selection and the traced mask agree for
    every (banks, planes) knob setting."""
    for banks, planes in PLANS:
        sel = plan_word_sel(CFG, banks, planes)
        mask = np.asarray(plan_word_mask(CFG, banks, planes))
        assert sorted(sel.tolist()) == np.nonzero(mask)[0].tolist(), \
            (banks, planes)
        assert sel.size * 32 == int(CFG.d_eff_planned(banks, planes))


def test_pmajor_is_plane_permuted_packed():
    im = random_item_memory(jax.random.PRNGKey(0), CFG)
    from repro.core.item_memory import plane_permutation
    perm = plane_permutation(CFG.words, CFG.bit_planes)
    assert np.array_equal(np.asarray(im.pmajor),
                          np.asarray(im.packed)[:, perm])


# --- kernel wrappers vs jnp oracle -----------------------------------------

@pytest.mark.parametrize("banks,planes", PLANS)
def test_packed_similarity_planned_matches_oracle(banks, planes):
    """Plane-gated scan == integer dot over the plan's enabled dims."""
    hv = hdc.random_hv(jax.random.PRNGKey(0), (CFG.M, CFG.D))
    q = hdc.random_hv(jax.random.PRNGKey(1), (5, CFG.D))
    im = random_item_memory(jax.random.PRNGKey(0), CFG)
    dmask = np.asarray(plan_dim_mask(CFG, banks, planes))
    assert np.array_equal(np.asarray(im.bipolar), np.asarray(hv))

    acc, cos = ops.packed_similarity(
        hdc.pack_bits(q), im.packed, banks=banks, bank_words=CFG.bank_words,
        planes=planes, plane_total=CFG.bit_planes, pmajor=im.pmajor)
    want = jnp.einsum("nd,md->nm",
                      jnp.where(dmask, q.astype(jnp.int32), 0),
                      jnp.where(dmask, hv.astype(jnp.int32), 0))
    assert np.array_equal(np.asarray(acc), np.asarray(want)), (banks, planes)
    d_eff = int(CFG.d_eff_planned(banks, planes))
    assert np.allclose(np.asarray(cos), np.asarray(want) / d_eff)

    # without the pmajor fast path (static gather) the result is identical
    acc2, _ = ops.packed_similarity(
        hdc.pack_bits(q), im.packed, banks=banks, bank_words=CFG.bank_words,
        planes=planes, plane_total=CFG.bit_planes)
    assert np.array_equal(np.asarray(acc2), np.asarray(acc))


@pytest.mark.parametrize("banks,planes", [(8, 4), (8, 2), (4, 1), (2, 2)])
def test_cache_nearest_planned_matches_core(banks, planes):
    cache = query_cache.init_cache(CFG)
    from repro.core.types import plan_tag
    for i in range(3):
        qe = hdc.pack_bits(hdc.random_hv(jax.random.PRNGKey(10 + i), (CFG.D,)))
        cache = query_cache.write_entry(
            cache, jnp.int32(i), packed=qe,
            acc=jnp.zeros((CFG.M,), jnp.int32),
            acc_tag=plan_tag(banks, planes),
            out=jnp.zeros((CFG.M,), jnp.float32),
            topk_key=jnp.zeros((CFG.top_k,), jnp.int32), margin=jnp.float32(0))
    qs = jax.vmap(hdc.pack_bits)(
        hdc.random_hv(jax.random.PRNGKey(99), (4, CFG.D)))
    idx, rho, ham = ops.cache_nearest(
        qs, cache.packed, cache.valid, banks=banks,
        bank_words=CFG.bank_words, planes=planes,
        plane_total=CFG.bit_planes)
    for n in range(qs.shape[0]):
        i1, r1, h1 = query_cache.nearest(cache, qs[n], CFG, banks, planes)
        assert int(idx[n]) == int(i1)
        assert float(rho[n]) == float(r1)
        assert int(ham[n]) == int(h1)


# --- pipeline under plans ---------------------------------------------------

def test_full_plan_is_bit_exact_noop():
    """plan=full_plan(cfg) reproduces plan=None bit-for-bit over a warm
    cache sequence (full -> delta -> bypass traffic)."""
    cfg = CFG
    im = random_item_memory(jax.random.PRNGKey(0), cfg)
    task_w = jax.random.uniform(jax.random.PRNGKey(1), (cfg.M,))
    step = jax.jit(pipeline.torr_window_step,
                   static_argnames=("cfg", "plan"))

    states = [pipeline.init_state(cfg, task_w) for _ in range(2)]
    q_bip, valid, boxes = _window(cfg, seed=2)
    for t, qd in enumerate([0, 0, cfg.q_hi]):
        q = jax.vmap(hdc.pack_bits)(
            q_bip.at[:, t::131].multiply(-1) if t else q_bip)
        outs = []
        for i, plan in enumerate([None, full_plan(cfg)]):
            states[i], out, tel = step(states[i], im, q, valid, boxes,
                                       jnp.int32(qd), cfg, plan=plan)
            outs.append((out, tel))
        (o0, t0), (o1, t1) = outs
        assert np.array_equal(np.asarray(o0.scores), np.asarray(o1.scores))
        for f in ("path", "delta_count", "banks", "rho", "planes",
                  "high_load"):
            assert np.array_equal(np.asarray(getattr(t0, f)),
                                  np.asarray(getattr(t1, f))), (t, f)


@pytest.mark.parametrize("banks,planes", [(8, 2), (4, 4), (4, 2), (2, 1)])
def test_reduced_plan_full_scores_match_oracle(banks, planes):
    """Cold-cache full-path scores under a reduced plan == the jnp oracle
    restricted to the plan's dims/planes (times the task weights — the
    reasoner multiply, ungated on a cold cache)."""
    cfg = CFG
    im = random_item_memory(jax.random.PRNGKey(0), cfg)
    task_w = jax.random.uniform(jax.random.PRNGKey(1), (cfg.M,))
    plan = _plan(banks, planes)
    state = pipeline.init_state(cfg, task_w)
    q_bip, valid, boxes = _window(cfg, seed=3)
    q = jax.vmap(hdc.pack_bits)(q_bip)

    _, out, tel = pipeline.torr_window_step(
        state, im, q, valid, boxes, jnp.int32(0), cfg, plan=plan)
    nv = int(np.sum(np.asarray(valid)))
    assert (np.asarray(tel.path)[:nv] == PATH_FULL).all()
    assert int(tel.banks) == banks and int(tel.planes) == planes

    wmask = plan_word_mask(cfg, banks, planes)
    d_eff = int(cfg.d_eff_planned(banks, planes))
    for n in range(nv):
        acc = aligner.full_dot(q[n], im, wmask)
        want = acc.astype(jnp.float32) / d_eff * task_w
        assert np.array_equal(np.asarray(out.scores[n]), np.asarray(want)), n


@given(st.integers(0, 2**31 - 1),
       st.sampled_from(PLANS), st.sampled_from(PLANS))
@settings(max_examples=8, deadline=None)
def test_plan_switch_rejects_stale_delta(seed, pa, pb):
    """Property (Eq. 6): a delta accumulator tagged under plan A is never
    delta-corrected under plan B != A — the window re-scans full, and its
    scores are bit-identical to a cold-cache run under plan B."""
    if pa == pb:
        return
    cfg = CFG
    rng = np.random.default_rng(seed)
    im = random_item_memory(jax.random.PRNGKey(seed % 7), cfg)
    task_w = jax.random.uniform(jax.random.PRNGKey(1), (cfg.M,))
    step = jax.jit(pipeline.torr_window_step,
                   static_argnames=("cfg", "plan"))
    plan_a, plan_b = _plan(*pa), _plan(*pb)

    q_bip = hdc.random_hv(jax.random.PRNGKey(seed % 1009), (cfg.N_max, cfg.D))
    valid = jnp.asarray(np.arange(cfg.N_max) < cfg.K - 1)
    boxes = jnp.zeros((cfg.N_max, 4), jnp.float32)
    q0 = jax.vmap(hdc.pack_bits)(q_bip)
    # drift a few dims of word 0 (plane 0, bank 0: enabled under every plan)
    flips = rng.choice(32, size=4, replace=False)
    q_bip2 = q_bip.at[:, flips].multiply(-1)
    q1 = jax.vmap(hdc.pack_bits)(q_bip2)

    state = pipeline.init_state(cfg, task_w)
    state, _, tel0 = step(state, im, q0, valid, boxes, jnp.int32(0), cfg,
                          plan=plan_a)
    nv = int(np.sum(np.asarray(valid)))
    assert (np.asarray(tel0.path)[:nv] == PATH_FULL).all()

    # same plan: drift takes the delta path (the tag matches)...
    st_a, out_a, tel_a = step(state, im, q1, valid, boxes, jnp.int32(0), cfg,
                              plan=plan_a)
    assert (np.asarray(tel_a.path)[:nv] == PATH_DELTA).all(), (pa, pb)

    # ...switched plan: the stale tag must force a full re-scan, and the
    # re-scan is exact — scores equal the oracle over plan B's dims (for
    # proposals where the reasoner multiply ran; a gated proposal forwards
    # its cached output by design)
    _, out_b, tel_b = step(state, im, q1, valid, boxes, jnp.int32(0), cfg,
                           plan=plan_b)
    assert (np.asarray(tel_b.path)[:nv] == PATH_FULL).all(), (pa, pb)
    wmask_b = plan_word_mask(cfg, plan_b.banks, plan_b.planes)
    d_eff_b = int(cfg.d_eff_planned(plan_b.banks, plan_b.planes))
    for n in range(nv):
        if bool(tel_b.reasoner_active[n]):
            acc = aligner.full_dot(q1[n], im, wmask_b)
            want = acc.astype(jnp.float32) / d_eff_b * task_w
            assert np.array_equal(np.asarray(out_b.scores[n]),
                                  np.asarray(want)), (pa, pb, n)


# --- fused full path vs oracle (ISSUE 4 tentpole) ---------------------------

TELEM_CHECK = ("path", "delta_count", "banks", "rho", "planes", "high_load")


def _run_windows(cfg, im, task_w, plan, fused, n_windows=3, qd_seq=None,
                 seed=11):
    """Drive a warm full -> delta -> bypass sequence through one lowering;
    returns (state, [(out, tel), ...])."""
    step = jax.jit(pipeline.torr_window_step,
                   static_argnames=("cfg", "plan", "fused"))
    state = pipeline.init_state(cfg, task_w)
    q_bip, valid, boxes = _window(cfg, seed=seed)
    outs = []
    for t in range(n_windows):
        q = jax.vmap(hdc.pack_bits)(
            q_bip.at[:, t::131].multiply(-1) if t else q_bip)
        qd = jnp.int32((qd_seq or [0] * n_windows)[t])
        state, out, tel = step(state, im, q, valid, boxes, qd, cfg,
                               plan=plan, fused=fused)
        outs.append((out, tel))
    return state, outs


@pytest.mark.parametrize("banks,planes", PLANS)
@pytest.mark.parametrize("mode", ["switch", "prefix"])
def test_fused_full_path_bit_identical_over_plan_grid(banks, planes, mode):
    """Acceptance (ISSUE 4): the fused jitted full path is bit-identical to
    the jnp-oracle step — argmax, scores, telemetry AND cache state — for
    every (banks, planes) plan in the ladder, in both fused lowerings,
    over a warm window sequence that exercises full, delta and bypass."""
    cfg = CFG
    im = random_item_memory(jax.random.PRNGKey(0), cfg)
    task_w = jax.random.uniform(jax.random.PRNGKey(1), (cfg.M,))
    plan = _plan(banks, planes)
    qd_seq = [0, 0, cfg.q_hi]

    st0, base = _run_windows(cfg, im, task_w, plan, "off", qd_seq=qd_seq)
    st1, got = _run_windows(cfg, im, task_w, plan, mode, qd_seq=qd_seq)
    for t, ((o0, t0), (o1, t1)) in enumerate(zip(base, got)):
        assert np.array_equal(np.asarray(o0.scores), np.asarray(o1.scores))
        assert np.array_equal(np.asarray(o0.best), np.asarray(o1.best))
        for f in TELEM_CHECK:
            assert np.array_equal(np.asarray(getattr(t0, f)),
                                  np.asarray(getattr(t1, f))), (t, f)
    for a, b in zip(jax.tree_util.tree_leaves(st0.cache),
                    jax.tree_util.tree_leaves(st1.cache)):
        assert np.array_equal(np.asarray(a), np.asarray(b))


# a budget small enough, and tau_q low enough, that every plan of PLANS
# can hold a reuse at budget + 1 flips that Alg. 1 would take as delta
EDGE_CFG = TorrConfig(D=1024, B=8, M=32, K=4, N_max=8, delta_budget=8,
                      tau_q=0.3, feat_dim=64)


def _drift(q_bip, flips, dmask, rng, n_off=7):
    """Flip ``flips[s][n]`` plan-enabled dims of proposal n of stream s,
    and ``n_off`` disabled ones, which |Delta| and Eq. 6 must not see."""
    q = np.array(q_bip)
    on, off = np.flatnonzero(dmask), np.flatnonzero(~dmask)
    for s, row in enumerate(flips):
        for n, c in enumerate(row):
            dims = np.concatenate([rng.choice(on, c, replace=False),
                                   rng.choice(off, min(n_off, off.size),
                                              replace=False)])
            q[s, n, dims] *= -1
    return q


@pytest.mark.parametrize("banks,planes", PLANS)
def test_prefix_delta_budget_edge_bit_identical_over_plan_grid(banks,
                                                                planes):
    """The vmapped multi-stream ``prefix`` step evaluates Eq. 6 densely,
    with no index list: bit-identical to the ``off`` oracle — scores,
    ``best``, telemetry and the whole cache after every window — at the
    delta budget's edge: reuses at exactly ``delta_budget`` flips (delta),
    at ``delta_budget + 1`` (escalated to full), with zero flips, and with
    flips in the plan's disabled dims, under every plan of the ladder."""
    cfg, S = EDGE_CFG, 2
    budget = cfg.delta_budget
    im = random_item_memory(jax.random.PRNGKey(0), cfg)
    task_w = jax.random.uniform(jax.random.PRNGKey(1), (S, cfg.M))
    plan = _plan(banks, planes, cfg)
    dmask = np.asarray(plan_dim_mask(cfg, banks, planes))
    rng = np.random.default_rng(banks * 10 + planes)
    step = jax.jit(pipeline.torr_multi_stream_step,
                   static_argnames=("cfg", "plan", "fused"))
    valid = jnp.asarray(np.arange(cfg.N_max) < cfg.K)[None].repeat(S, 0)
    boxes = jnp.zeros((S, cfg.N_max, 4), jnp.float32)

    edge = [0, budget, budget + 1, 3]            # flips of valid proposals
    w0 = np.asarray(hdc.random_hv(jax.random.PRNGKey(2),
                                  (S, cfg.N_max, cfg.D)))
    w1 = _drift(w0, [edge, edge[::-1]], dmask, rng)
    w2 = _drift(w1, [edge[1:] + edge[:1], edge[2:] + edge[:2]], dmask, rng)
    windows = [(w0, [0, 0]), (w1, [0, 0]), (w2, [0, cfg.q_hi])]

    res = {}
    for fused in ("off", "prefix"):
        st = pipeline.init_multi_stream_state(cfg, task_w)
        res[fused] = []
        for q_bip, qd in windows:
            q = jax.vmap(jax.vmap(hdc.pack_bits))(jnp.asarray(q_bip))
            st, out, tel = step(st, im, q, valid, boxes,
                                jnp.asarray(qd, jnp.int32), cfg, plan=plan,
                                fused=fused)
            res[fused].append((st, out, tel))

    tel1 = res["prefix"][1][2]
    assert np.asarray(tel1.banks).tolist() == [banks] * S
    assert np.asarray(tel1.delta_count)[:, :cfg.K].tolist() == [
        edge, edge[::-1]]
    assert np.asarray(tel1.path)[:, :cfg.K].tolist() == [
        [PATH_DELTA, PATH_DELTA, PATH_FULL, PATH_DELTA],
        [PATH_DELTA, PATH_FULL, PATH_DELTA, PATH_DELTA]]
    for t, ((s0, o0, t0), (s1, o1, t1)) in enumerate(
            zip(res["off"], res["prefix"])):
        assert np.array_equal(np.asarray(o0.scores), np.asarray(o1.scores))
        assert np.array_equal(np.asarray(o0.best), np.asarray(o1.best))
        for f in TELEM_CHECK:
            assert np.array_equal(np.asarray(getattr(t0, f)),
                                  np.asarray(getattr(t1, f))), (t, f)
        for a, b in zip(jax.tree_util.tree_leaves(s0.cache),
                        jax.tree_util.tree_leaves(s1.cache)):
            assert np.array_equal(np.asarray(a), np.asarray(b)), t


@pytest.mark.parametrize("mode", ["switch", "prefix"])
def test_fused_ragged_fallback_bit_identical(mode):
    """Ragged M (not a multiple of 8) rides the transparent oracle
    fallback inside the fused dispatch — still bit-identical end to end."""
    cfg = TorrConfig(D=1024, B=8, M=27, K=4, N_max=5, delta_budget=128,
                     feat_dim=64)
    im = random_item_memory(jax.random.PRNGKey(3), cfg)
    task_w = jax.random.uniform(jax.random.PRNGKey(4), (cfg.M,))
    _, base = _run_windows(cfg, im, task_w, None, "off", seed=5)
    _, got = _run_windows(cfg, im, task_w, None, mode, seed=5)
    for (o0, _), (o1, _) in zip(base, got):
        assert np.array_equal(np.asarray(o0.scores), np.asarray(o1.scores))


@pytest.mark.parametrize("mode", ["switch", "prefix"])
def test_fused_delta_then_full_exact_after_plan_switch(mode):
    """Eq. 6 exactness through the fused path: delta-correct under plan A,
    then a plan switch forces a full re-scan whose scores equal the oracle
    restricted to plan B — same invariant as the oracle-path property test,
    run end-to-end on the fused lowering."""
    cfg = CFG
    im = random_item_memory(jax.random.PRNGKey(0), cfg)
    task_w = jax.random.uniform(jax.random.PRNGKey(1), (cfg.M,))
    step = jax.jit(pipeline.torr_window_step,
                   static_argnames=("cfg", "plan", "fused"))
    plan_a, plan_b = _plan(8, 4), _plan(4, 2)
    q_bip, valid, boxes = _window(cfg, seed=7)
    nv = int(np.sum(np.asarray(valid)))
    q0 = jax.vmap(hdc.pack_bits)(q_bip)
    q1 = jax.vmap(hdc.pack_bits)(q_bip.at[:, :4].multiply(-1))

    state = pipeline.init_state(cfg, task_w)
    state, _, tel0 = step(state, im, q0, valid, boxes, jnp.int32(0), cfg,
                          plan=plan_a, fused=mode)
    assert (np.asarray(tel0.path)[:nv] == PATH_FULL).all()
    st_a, _, tel_a = step(state, im, q1, valid, boxes, jnp.int32(0), cfg,
                          plan=plan_a, fused=mode)
    assert (np.asarray(tel_a.path)[:nv] == PATH_DELTA).all()
    # delta-corrected accumulators are exact (== a fresh full scan would be)
    wmask_a = plan_word_mask(cfg, plan_a.banks, plan_a.planes)
    for n in range(nv):
        acc = np.asarray(aligner.full_dot(q1[n], im, wmask_a))
        slot = int(np.argwhere(
            (np.asarray(st_a.cache.packed) == np.asarray(q1[n])).all(-1)
        )[0, 0])
        assert np.array_equal(np.asarray(st_a.cache.acc[slot]), acc), n

    # plan switch: stale tag -> full re-scan, exact under plan B
    _, out_b, tel_b = step(st_a, im, q1, valid, boxes, jnp.int32(0), cfg,
                           plan=plan_b, fused=mode)
    assert (np.asarray(tel_b.path)[:nv] == PATH_FULL).all()
    wmask_b = plan_word_mask(cfg, plan_b.banks, plan_b.planes)
    d_eff_b = int(cfg.d_eff_planned(plan_b.banks, plan_b.planes))
    for n in range(nv):
        if bool(tel_b.reasoner_active[n]):
            acc = aligner.full_dot(q1[n], im, wmask_b)
            want = acc.astype(jnp.float32) / d_eff_b * task_w
            assert np.array_equal(np.asarray(out_b.scores[n]),
                                  np.asarray(want)), n


@pytest.mark.parametrize("serial", [False, True])
def test_fused_multi_stream_bit_identical(serial):
    """Both batched lowerings (vmap -> hoisted prefix kernel, lax.map ->
    switch) are bit-identical to the oracle step under heterogeneous
    per-stream load (different Alg. 1 bank choices per slot)."""
    cfg = TorrConfig(D=1024, B=8, M=32, K=4, N_max=8, delta_budget=128,
                     feat_dim=64, fps_target=40000.0)
    im = random_item_memory(jax.random.PRNGKey(0), cfg)
    S = 4
    task_w = jax.random.uniform(jax.random.PRNGKey(1), (S, cfg.M))
    step = jax.jit(pipeline.torr_multi_stream_step,
                   static_argnames=("cfg", "serial", "plan", "fused"))
    q_bip = hdc.random_hv(jax.random.PRNGKey(2), (S, cfg.N_max, cfg.D))
    valid = jnp.asarray(np.arange(cfg.N_max) < 6)[None].repeat(S, 0)
    boxes = jnp.zeros((S, cfg.N_max, 4), jnp.float32)
    qd = jnp.asarray([0, 2, 8, 30], jnp.int32)   # forces banks 8/8/3/1

    res = {}
    for fused in ("off", None):
        st = pipeline.init_multi_stream_state(cfg, task_w)
        outs = []
        for t in range(3):
            q = jax.vmap(jax.vmap(hdc.pack_bits))(
                q_bip.at[:, :, t::97].multiply(-1) if t else q_bip)
            st, out, tel = step(st, im, q, valid, boxes, qd, cfg,
                                serial=serial, fused=fused)
            outs.append((out, tel))
        res[fused] = (st, outs)
    banks_seen = np.asarray(res[None][1][0][1].banks)
    assert len(set(banks_seen.tolist())) > 1, "want heterogeneous banks"
    for t in range(3):
        (o0, t0), (o1, t1) = res["off"][1][t], res[None][1][t]
        assert np.array_equal(np.asarray(o0.scores), np.asarray(o1.scores))
        for f in TELEM_CHECK:
            assert np.array_equal(np.asarray(getattr(t0, f)),
                                  np.asarray(getattr(t1, f))), (t, f)
    for a, b in zip(jax.tree_util.tree_leaves(res["off"][0].cache),
                    jax.tree_util.tree_leaves(res[None][0].cache)):
        assert np.array_equal(np.asarray(a), np.asarray(b))


# --- governor dynamics ------------------------------------------------------

def test_ladder_shape_and_costs():
    ladder = build_ladder(CFG)
    assert ladder[0] == full_plan(CFG)
    rel = ladder_rel_cost(ladder, CFG)
    assert rel[0] == 1.0
    assert (np.diff(rel) < 0).all()          # strictly cheaper down the ladder
    for p in ladder:
        p.validate(CFG)


def test_governor_degrades_immediately_recovers_with_hysteresis():
    pol = GovernorPolicy(budget_s=1.0, slack_margin=0.0, recover_hold=3)
    gov = Governor(CFG, pol)
    deepest = len(gov.ladder) - 1

    # optimistic start: no measurement => full plan
    assert gov.update(slack_s=1.0, step_s=0.0).is_full and gov.level == 0
    # hopeless slack => immediate drop to the deepest level
    gov.update(slack_s=0.001, step_s=0.9)
    assert gov.level == deepest
    # ample slack: recovery is held back, then climbs ONE level at a time
    for _ in range(pol.recover_hold - 1):
        gov.update(slack_s=1.0, step_s=0.001)
        assert gov.level == deepest
    gov.update(slack_s=1.0, step_s=0.001)
    assert gov.level == deepest - 1
    assert gov.switches == 2

    # backlog shrinks effective slack: deep backlog forces a deeper level
    lvl = gov.level
    gov.update(slack_s=1.0, step_s=0.9, backlog=10)
    assert gov.level > lvl


def test_energy_governor_caps_level():
    pol = GovernorPolicy(budget_s=1 / 60, slack_margin=0.0, recover_hold=1,
                         energy_budget_mj=50.0)
    gov = Governor(CFG, pol)
    # plentiful slack, but the EWMA energy is far over budget: the energy
    # governor must keep the plan off the full level
    gov.observe_energy(120.0)
    gov.update(slack_s=10.0, step_s=1e-6)
    assert gov.level > 0
    # and with energy back under budget, slack rules again
    gov.energy_ewma_mj = 10.0
    for _ in range(len(gov.ladder)):
        gov.update(slack_s=10.0, step_s=1e-6)
    assert gov.level == 0


def test_plan_level_is_pure():
    pol = GovernorPolicy(budget_s=1.0, slack_margin=0.0, recover_hold=2)
    rel = np.array([1.0, 0.5, 0.25])
    a = plan_level(0.3, 0, 0.4, 0, 0, rel, pol)
    b = plan_level(0.3, 0, 0.4, 0, 0, rel, pol)
    assert a == b == (1, 0)                  # level 1 fits (0.2 <= 0.3)
    # nothing fits => deepest
    assert plan_level(0.01, 0, 1.0, 0, 0, rel, pol)[0] == 2


# --- engine integration -----------------------------------------------------

def test_async_engine_governor_pinned_full_bit_identical():
    """Acceptance: governor pinned to the full plan => engine outputs are
    bit-identical to the ungoverned async engine."""
    from repro.serving.async_engine import AsyncStreamEngine
    from repro.serving.deadline import DeadlinePolicy, DeadlineTracker
    from test_multistream import TELEM_FIELDS, _make_inputs

    cfg = CFG
    S, T = 3, 4
    im = random_item_memory(jax.random.PRNGKey(0), cfg)
    task_w = np.asarray(jax.random.uniform(jax.random.PRNGKey(1), (S, cfg.M)))
    steps = _make_inputs(cfg, S, T)
    pol = DeadlinePolicy(budget_s=1e6, escalate_margin_s=1e6)  # never fires

    def run(eng):
        futs = {s: [] for s in range(S)}
        for s in range(S):
            eng.admit(s, task_w[s])
            for q, v, b, _qd in steps:
                futs[s].append(eng.submit(s, q[s], v[s], b[s]))
        eng.start()
        eng.flush(timeout=120)
        return {s: [f.result(timeout=10) for f in futs[s]] for s in range(S)}

    with AsyncStreamEngine(cfg, im, n_slots=S, paused=True) as eng0:
        base = run(eng0)
    gov = Governor(cfg, GovernorPolicy(budget_s=1e6),
                   ladder=(full_plan(cfg),))
    with AsyncStreamEngine(cfg, im, n_slots=S, paused=True,
                           tracker=DeadlineTracker(pol),
                           governor=gov) as eng1:
        gvd = run(eng1)
    assert gov.level == 0 and sum(gov.windows_by_level) == S * T
    for s in range(S):
        for t in range(T):
            (o0, t0), (o1, t1) = base[s][t], gvd[s][t]
            assert np.array_equal(o0.scores, o1.scores), (s, t)
            assert np.array_equal(o0.best, o1.best), (s, t)
            for f in TELEM_FIELDS + ("planes",):
                assert np.array_equal(np.asarray(getattr(t0, f)),
                                      np.asarray(getattr(t1, f))), (s, t, f)


def test_table8_governor_beats_static_on_the_ramp():
    """Acceptance (ISSUE 3): under table8's load ramp the governor meets
    the RT-60 budget where the static-banks baseline misses deadlines, at
    lower modeled energy than always-full-D'."""
    from benchmarks.table8_pareto import simulate

    full = simulate("RT-60", "full", n_frames=150)
    static = simulate("RT-60", "static", n_frames=150)
    gov = simulate("RT-60", "governor", n_frames=150)
    assert static["miss_rate"] > 0.2          # the ramp breaks the static knob
    assert gov["miss_rate"] == 0.0            # the closed loop holds RT-60
    assert gov["energy_mj"] < full["energy_mj"]
    assert gov["planes_mean"] < CFG.bit_planes  # precision gating engaged


def test_async_engine_governor_degrades_under_pressure():
    """A hopeless RT budget (shedding disabled) drives the governor to the
    deepest plan; served windows record the reduced (banks, planes)."""
    from repro.serving.async_engine import AsyncStreamEngine
    from repro.serving.deadline import DeadlinePolicy, DeadlineTracker
    from test_multistream import _make_inputs

    cfg = CFG
    S, T = 2, 5
    im = random_item_memory(jax.random.PRNGKey(0), cfg)
    task_w = np.asarray(jax.random.uniform(jax.random.PRNGKey(1), (S, cfg.M)))
    steps = _make_inputs(cfg, S, T)
    pol = DeadlinePolicy(budget_s=1e-9, escalate_margin_s=1e-9,
                         allow_shed=False)
    gov = Governor(cfg, GovernorPolicy(budget_s=1e-9, recover_hold=10**6))

    with AsyncStreamEngine(cfg, im, n_slots=S, paused=True,
                           tracker=DeadlineTracker(pol),
                           governor=gov) as eng:
        futs = []
        for s in range(S):
            eng.admit(s, task_w[s])
            for q, v, b, _qd in steps:
                futs.append(eng.submit(s, q[s], v[s], b[s]))
        eng.start()
        eng.flush(timeout=120)
        tels = [f.result(timeout=10)[1] for f in futs]

    deepest = gov.ladder[-1]
    assert gov.level == len(gov.ladder) - 1
    assert gov.switches >= 1
    assert gov.energy_ewma_mj > 0.0
    # at least one window actually ran the deepest plan's knobs
    planes_run = {(int(t.banks), int(t.planes)) for t in tels}
    assert (deepest.banks, deepest.planes) in planes_run
    summary = eng.governor_summary()
    assert summary["windows_by_level"][-1] > 0
