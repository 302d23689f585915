"""TorR end-to-end window step (paper Fig. 3/4/5).

One call processes one event window: for each of up to N_max proposal
queries, the PSU finds the nearest cached query, Alg. 1 selects
bypass / delta / full, the associative aligner produces class scores, the
reasoner applies (or gates) task weights, and the query cache is refreshed.
Proposals are processed sequentially (lax.scan) so later proposals can hit
entries written earlier in the same window — matching the ASIC's per-window
FSM — and the three paths are real `lax.switch` branches, so only the
selected path executes.

By default the full path runs through the fused Pallas kernel family
(``fused="switch"``/``"prefix"``, see :func:`torr_window_step`): the whole
window's proposal batch takes one bank/plane-gated XNOR-popcount pass
*before* the scan (the full branch then only gathers its row), and the
delta branch's Eq. 6 correction streams through the scalar-prefetch
kernel. ``fused="off"`` restores the per-proposal jnp-oracle executable,
which the fused path is tested bit-identical against.

``fused="compact"`` goes one step further (the reuse-aware dispatch): a
metadata-only *decide* pass produces the window's path vector first, and
the fused scan then runs only over the full-path proposals, compacted into
a dense bucket padded to a static ``bucket_cap`` tier
(``core.policy.bucket_ladder``). Cache hits *skip* the scan instead of
merely masking it — the kernel bytes scale with the miss rate.

The decide pass itself has two bit-identical lowerings (static
``decide`` knob): the sequential per-proposal FSM scan (``"scan"``, the
reference oracle) and the batched intra-window decide (``"batched"``, the
default) — one wide snapshot-nearest pass plus a K-metadata
conflict-resolution scan that replays the FSM's intra-window coupling
(self-hits on slots written earlier in the window, LRU eviction chains)
update-for-update. On the vmapped multi-stream lowering the batched
decide's writer chains additionally unlock the *batched apply*
(:func:`_apply_pass_batched`): Eq. 6 corrections become one dense matmul,
the reasoner's top-k one dispatch-wide pass, and the per-proposal scan
reduces to two cheap chain-resolution loops — the first lowering to break
the sequential FSM machinery's CPU floor, still bit-exact against the
oracle (``tests/test_decide_batched.py``).

The returned :class:`WindowTelemetry` trace is the input to the
cycle-accurate model (`repro.perf.cycle_model`), keeping the functional and
timing models in lock-step by construction.

Every lowering names its phases with the same named scopes
(:data:`repro.obs.phases.PHASES`: ``policy``, ``full_select``,
``nearest``, ``delta_search``, ``path_select``, ``delta_apply``,
``reason``, ``cache_write``, ``finish``; ``prefix_scan`` in the aligner),
so each op of the compiled step carries its phase in its metadata. Scopes
change no op and no result.
"""
from __future__ import annotations

import dataclasses
import warnings

import jax
import jax.numpy as jnp

from ..obs.phases import phase
from . import aligner as al
from . import policy, query_cache, reasoner
from .item_memory import ItemMemory, plan_word_mask
from .query_cache import CacheState
from .types import (DECIDE_IDS, DECIDE_NONE, FUSED_IDS, PATH_BYPASS,
                    PATH_FULL, StreamBatch, TorrConfig, WindowTelemetry,
                    plan_tag)


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass
class TorrState:
    cache: CacheState
    task_weights: jax.Array  # f32 [M] precomputed w_j for the active task

    def tree_flatten(self):
        return ((self.cache, self.task_weights), None)

    @classmethod
    def tree_unflatten(cls, aux, children):
        del aux
        return cls(*children)


def init_state(cfg: TorrConfig, task_w: jax.Array) -> TorrState:
    return TorrState(cache=query_cache.init_cache(cfg), task_weights=task_w)


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass
class WindowOutput:
    scores: jax.Array   # f32 [N_max, M] final task-weighted scores
    best: jax.Array     # int32 [N_max] argmax class per proposal
    boxes: jax.Array    # f32 [N_max, 4] passthrough proposal boxes

    def tree_flatten(self):
        return ((self.scores, self.best, self.boxes), None)

    @classmethod
    def tree_unflatten(cls, aux, children):
        del aux
        return cls(*children)


def _proposal_body(cfg: TorrConfig, im: ItemMemory, task_w, banks, planes,
                   wmask, high, acc_full_all=None, fused_delta=False,
                   decided=False):
    """Scan body over proposals for a fixed window context (all closures are
    window-constant traced values; ``planes`` is static — the latched plan).

    ``acc_full_all`` is the fused path's pre-computed int32 [N_max, M]
    full-scan accumulator batch (``aligner.full_scores_all``): the full
    branch then just gathers its row, so the scan never re-reads the item
    memory. ``None`` keeps the legacy per-proposal jnp oracle in-branch
    (the reference executable the fused path is tested against).

    ``decided=True`` is the compact dispatch's apply pass: the scan input
    additionally carries the decide pass's per-proposal decisions
    (action, nearest idx, LRU slot, delta indices/weights/count, rho), so
    the body skips the PSU/Alg. 1 work entirely and only applies the
    value-carrying branch — its cache updates replay the decide pass's
    metadata updates exactly, keeping the two passes in lock-step."""
    d_eff = cfg.d_eff_planned(banks, planes)
    tag = plan_tag(banks, planes)

    def body(cache: CacheState, inp):
        if decided:
            (q_packed, valid, i,
             action, idx, lru, d_idx, d_weight, d_count, rho) = inp
        else:
            q_packed, valid, i = inp
            lru = None
            with phase("nearest"):
                idx, rho, _ham = query_cache.nearest(cache, q_packed, cfg,
                                                     banks, planes)
            with phase("delta_search"):
                d_idx, d_weight, d_count = al.delta_indices(
                    q_packed, cache.packed[idx], wmask, cfg.delta_budget,
                    cfg.D)
            with phase("path_select"):
                # Eq. 6 exactness: the cached accumulator is only
                # delta-correctable under the exact (banks, planes) it
                # was computed with
                tag_ok = cache.acc_tag[idx] == tag
                action = policy.select_path(rho, d_count, tag_ok, high, cfg)

        def bypass_branch(cache):
            out = cache.out[idx]
            return query_cache.touch(cache, idx), out, jnp.array(False)

        def delta_branch(cache):
            with phase("delta_apply"):
                if fused_delta:
                    acc = al.delta_apply(cache.acc[idx], im, d_idx, d_weight)
                else:
                    acc = al.delta_correct(cache.acc[idx], im, d_idx,
                                           d_weight)
                s = al.readout(acc, d_eff)
            with phase("reason"):
                out, active, key, margin = reasoner.gate_and_apply(
                    s, task_w, cache.out[idx], cache.topk_key[idx],
                    cache.margin[idx], cfg,
                )
            cache = query_cache.write_entry(
                cache, idx, packed=q_packed, acc=acc, acc_tag=tag,
                out=out, topk_key=key, margin=margin,
            )
            return cache, out, active

        def full_branch(cache):
            with phase("full_select"):
                if acc_full_all is None:
                    acc = al.full_dot(q_packed, im, wmask)
                else:
                    acc = acc_full_all[i]
            with phase("reason"):
                s = al.readout(acc, d_eff)
                out, active, key, margin = reasoner.gate_and_apply(
                    s, task_w, cache.out[idx], cache.topk_key[idx],
                    cache.margin[idx], cfg,
                )
            slot = query_cache.lru_slot(cache) if lru is None else lru
            cache = query_cache.write_entry(
                cache, slot, packed=q_packed, acc=acc, acc_tag=tag,
                out=out, topk_key=key, margin=margin,
            )
            return cache, out, active

        # Invalid (padding) proposals take a free branch that touches nothing.
        def pad_branch(cache):
            return cache, jnp.zeros((cfg.M,), jnp.float32), jnp.array(False)

        if decided:
            eff_action = action       # the decide pass already padded it
            d_count_t, rho_t = d_count, rho
        else:
            with phase("path_select"):
                eff_action = jnp.where(valid, action, jnp.int32(3))
                d_count_t = jnp.where(valid, d_count, 0)
                rho_t = jnp.where(valid, rho, 0.0)
        # the cache's touch and writes are the cache_write phase, and so,
        # under vmap, are the branches' operands broadcast to every lane
        # and the select that merges their results; the branches' own
        # scopes name their value math
        with phase("cache_write"):
            cache, out, active = jax.lax.switch(
                eff_action,
                [bypass_branch, delta_branch, full_branch, pad_branch], cache)
        telem = (eff_action, d_count_t, rho_t, active)
        return cache, (out, telem)

    return body


def _apply_pass_batched(state: TorrState, im: ItemMemory, q_packed_all,
                        valid, boxes, queue_depth, cfg: TorrConfig, banks,
                        planes, high, n_valid, dec, aux, acc_rows,
                        bucket_tier=0):
    """Batched apply: replay a whole [S, N] dispatch's decisions without a
    value-carrying scan — the ``decide="batched"`` counterpart of the
    per-proposal :func:`_proposal_body` apply scan, bit-identical to it.

    The apply scan's floor at serving shapes is not the cache scatter (the
    [K, M] carry updates are cheap) but the per-lane *value math* it
    serializes: the Eq. 6 gather-einsum and the reasoner's top-k run once
    per proposal per stream. With the decisions — and the decide pass's
    conflict byproducts (``aux``: the per-proposal writer ``src`` and the
    final slot metadata) — known up front, every value becomes a batched
    dispatch-wide computation:

      1. Eq. 6 corrections are *accumulator-independent*
         (``delta_correct = acc + corr``), so one dense
         :func:`aligner.delta_corrections` matmul covers all S x N lanes;
      2. accumulators resolve along writer chains in an N-step scan whose
         per-step work is one [S, M] gather + add (``src`` says whether a
         proposal reads its slot's snapshot row or an earlier proposal's
         result — the intra-window coupling invariant, now data);
      3. the gate's top-k key/margin depend only on each proposal's own
         scores, so one batched ``lax.top_k`` covers the dispatch, and the
         *cached* key/margin each proposal compares against is a direct
         ``src`` gather (the writer's stored key IS its computed key);
      4. gated outputs resolve in a second N-step scan (a match forwards
         the read value, which may itself be a forwarded value);
      5. the final cache is assembled in one shot: each slot takes its
         last writer's resolved values (``aux``'s final writer table), and
         age/validity come from the decide carry, which already replayed
         ``meta_touch``/``meta_write`` update-for-update.

    Bit-exactness: every per-element op (int32 adds, the f32 readout
    divide, ``top_k`` tie order, the margin compare, ``scores * weights``)
    is the same op the scan body runs, merely batched — enforced by the
    differential harness in ``tests/test_decide_batched.py``."""
    eff, idx, lru, d_idx, d_weight, d_count, rho = dec
    src, writer_f, age_f, valid_f = aux
    cache = state.cache
    S, N, _W = q_packed_all.shape
    M = cfg.M
    del lru  # already folded into the decide pass's writer table

    is_byp = eff == jnp.int32(0)
    is_full = eff == jnp.int32(2)
    is_pad = eff == jnp.int32(3)
    is_write = jnp.logical_or(eff == jnp.int32(1), is_full)

    d_eff = cfg.d_eff_planned(jnp.asarray(banks, jnp.int32), planes)  # [S]
    tag = jnp.asarray(plan_tag(banks, planes), jnp.int32)             # [S]
    s_ix = jnp.arange(S)
    src_safe = jnp.maximum(src, 0)
    with phase("delta_apply"):
        corr = al.delta_corrections(
            d_idx.reshape(S * N, -1), d_weight.reshape(S * N, -1), im, cfg.D
        ).reshape(S, N, M)
        # each proposal's snapshot view of its nearest slot
        snap_acc = jnp.take_along_axis(cache.acc, idx[..., None], axis=1)

        def acc_body(acc_res, i):
            read = jnp.where(src[:, i, None] < 0, snap_acc[:, i],
                             acc_res[s_ix, src_safe[:, i]])
            acc_i = jnp.where(is_full[:, i, None], acc_rows[:, i],
                              read + corr[:, i])
            return acc_res.at[:, i].set(acc_i), None

        acc_res, _ = jax.lax.scan(acc_body, jnp.zeros((S, N, M), jnp.int32),
                                  jnp.arange(N))

    with phase("reason"):
        snap_out = jnp.take_along_axis(cache.out, idx[..., None], axis=1)
        snap_key = jnp.take_along_axis(cache.topk_key, idx[..., None],
                                       axis=1)
        snap_margin = jnp.take_along_axis(cache.margin, idx, axis=1)
        s_all = al.readout(acc_res, d_eff[:, None, None])    # [S, N, M]
        vals, kidx = jax.lax.top_k(s_all.reshape(S * N, M), cfg.top_k)
        # without these barriers XLA-CPU sees the sliced/reshaped consumers
        # and re-lowers TopK as a full row sort — ~5x the whole pass at
        # M = 1024. One barrier per output: a single barrier over the
        # (vals, kidx) tuple crashes XLA-CPU's TopK decomposer inside a
        # shard_map.
        vals = jax.lax.optimization_barrier(vals)
        kidx = jax.lax.optimization_barrier(kidx)
        key_all = kidx.astype(jnp.int32).reshape(S, N, cfg.top_k)
        margin_all = (vals[:, 0] - vals[:, 1]).reshape(S, N)
        cached_key = jnp.where(
            src[..., None] < 0, snap_key,
            jnp.take_along_axis(key_all, src_safe[..., None], axis=1))
        cached_margin = jnp.where(
            src < 0, snap_margin,
            jnp.take_along_axis(margin_all, src_safe, axis=1))
        match = jnp.logical_and(
            jnp.all(key_all == cached_key, axis=-1),
            jnp.abs(margin_all - cached_margin) <= cfg.margin_eps)
        reasoned = s_all * state.task_weights[:, None, :]
        active = jnp.logical_and(is_write, jnp.logical_not(match))

        def out_body(out_res, i):
            read = jnp.where(src[:, i, None] < 0, snap_out[:, i],
                             out_res[s_ix, src_safe[:, i]])
            out_w = jnp.where(match[:, i, None], read, reasoned[:, i])
            emit = jnp.where(is_pad[:, i, None], 0.0,
                             jnp.where(is_byp[:, i, None], read, out_w))
            return out_res.at[:, i].set(out_w), emit

        out_res, outs = jax.lax.scan(
            out_body, jnp.zeros((S, N, M), jnp.float32), jnp.arange(N))
        outs = jnp.moveaxis(outs, 0, 1)                      # [S, N, M]

    with phase("cache_write"):
        written = writer_f >= 0                              # [S, K]
        wsafe = jnp.maximum(writer_f, 0)
        w2 = written[..., None]

        def last_write(arr_prop, arr_snap):
            return jnp.where(
                w2, jnp.take_along_axis(arr_prop, wsafe[..., None], axis=1),
                arr_snap)

        cache = CacheState(
            packed=last_write(q_packed_all, cache.packed),
            acc=last_write(acc_res, cache.acc),
            acc_tag=jnp.where(written, tag[:, None], cache.acc_tag),
            out=last_write(out_res, cache.out),
            topk_key=last_write(key_all, cache.topk_key),
            margin=jnp.where(written,
                             jnp.take_along_axis(margin_all, wsafe, axis=1),
                             cache.margin),
            age=age_f,
            valid=valid_f,
        )
    telem = (eff, d_count, rho, active)
    return jax.vmap(
        _finish_window,
        in_axes=(0, 0, 0, 0, 0, 0, 0, 0, 0, 0, None, None, None, None))(
        cache, state.task_weights, outs, telem, valid, boxes, queue_depth,
        banks, n_valid, high, planes, FUSED_IDS["compact"],
        DECIDE_IDS["batched"], bucket_tier)


def _decide_body(cfg: TorrConfig, banks, planes, wmask, high):
    """Metadata-only FSM pass: the compact dispatch's *decide* scan.

    Runs Alg. 1 per proposal (cache-nearest, delta feasibility, path
    selection) and applies only the cache-*metadata* updates later
    proposals' decisions can observe — packed query, plan tag, age,
    validity — preserving the per-window FSM's intra-window hit semantics
    without touching a single item-memory row. The scan carries a
    :class:`query_cache.MetaCache`, NOT the full cache: the [K, M] value
    arrays (``acc``/``out``) must never ride the decide carry, or moving
    them through the loop costs more than the scan this pass exists to
    skip. The value-carrying work (full scans, Eq. 6 corrections,
    reasoner) is deferred to the apply pass, which replays these exact
    decisions."""
    tag = plan_tag(banks, planes)

    def body(meta: query_cache.MetaCache, inp):
        q_packed, valid = inp
        with phase("nearest"):
            idx, rho, _ham = query_cache.nearest(meta, q_packed, cfg, banks,
                                                 planes)
        with phase("delta_search"):
            d_idx, d_weight, d_count = al.delta_indices(
                q_packed, meta.packed[idx], wmask, cfg.delta_budget, cfg.D
            )
        with phase("path_select"):
            tag_ok = meta.acc_tag[idx] == tag
            action = policy.select_path(rho, d_count, tag_ok, high, cfg)
            eff = jnp.where(valid, action, jnp.int32(3))
        with phase("cache_write"):
            # the LRU choice the apply pass's full branch will make —
            # computed here because both passes see identical age/validity
            # sequences
            lru = query_cache.lru_slot(meta)

        def bypass_branch(meta):
            return query_cache.meta_touch(meta, idx)

        def delta_branch(meta):
            return query_cache.meta_write(meta, idx, packed=q_packed,
                                          acc_tag=tag)

        def full_branch(meta):
            return query_cache.meta_write(meta, lru, packed=q_packed,
                                          acc_tag=tag)

        def pad_branch(meta):
            return meta

        with phase("cache_write"):
            meta = jax.lax.switch(
                eff, [bypass_branch, delta_branch, full_branch, pad_branch],
                meta)
        dec = (eff, idx, lru, d_idx, d_weight,
               jnp.where(valid, d_count, 0), jnp.where(valid, rho, 0.0))
        return meta, dec

    return body


def _decide_pass(cache: CacheState, q_packed_all, valid, cfg: TorrConfig,
                 banks, planes, high):
    """Run the sequential decide scan over one window; returns the
    per-proposal decision arrays (action, idx, lru, d_idx, d_weight,
    d_count, rho).

    This is the *reference oracle* for the batched decide
    (:func:`_decide_pass_batched`, ``decide="batched"``): the differential
    harness in ``tests/test_decide_batched.py`` asserts the two produce
    bit-identical decision tuples and final cache state. Keep them
    update-for-update in lock-step."""
    wmask = plan_word_mask(cfg, banks, planes)
    _, dec = jax.lax.scan(
        _decide_body(cfg, banks, planes, wmask, high),
        query_cache.meta_view(cache), (q_packed_all, valid))
    return dec


def _decide_pass_batched_aux(cache: CacheState, q_packed_all, valid,
                             cfg: TorrConfig, banks, planes, high):
    """Batched intra-window decide: one wide similarity pass + a cheap
    conflict-resolution scan, bit-identical to :func:`_decide_pass`.

    The sequential scan's per-proposal cost is a K-entry masked nearest
    ([K, W] xor-popcount) plus an O(D) delta-index search — serialized
    N_max times. Here the similarity work is hoisted into two batched
    lookup passes over the *frozen* window-entry snapshot (the PSU's
    one-wide-pass shape):

      * ``ham_snap`` [N, K] — every proposal vs every snapshot entry;
      * ``ham_prop`` [N, N] — every proposal vs every *other proposal*,
        because the only packed values an intra-window write can install
        are earlier proposals' own queries (``meta_write(packed=q_j)``).

    The conflict pass is then a scan whose carry is only K-sized metadata
    — ``writer`` (which proposal last wrote each slot, -1 = snapshot),
    ``age`` and ``valid`` — so each step is O(K) gathers from the
    precomputed tables instead of popcount work: slot k's hamming is
    ``ham_snap[i, k]`` while untouched and ``ham_prop[i, writer[k]]``
    after a write. This preserves the intra-window coupling invariant
    (``policy.intra_window_coupled``): self-hits on slots written earlier
    in the window, LRU eviction chains and plan-tag refreshes resolve
    exactly as the sequential FSM would, because the carried metadata
    replays ``meta_touch``/``meta_write`` update-for-update. rho keeps
    Eq. 5's f32 arithmetic and argmax's first-max tie-breaking, so
    decisions are bit-exact, not merely equivalent.

    Delta-index extraction (the other per-proposal O(D) cost) is deferred
    to one vmapped pass after the scan, against each proposal's *resolved*
    old entry (snapshot row or earlier proposal's query, per the recorded
    writer).

    Returns ``(dec, aux)``: ``dec`` is the decision 7-tuple in the exact
    layout of :func:`_decide_pass` (the apply scan replays it unchanged),
    ``aux`` the conflict pass's byproducts the *batched* apply pass
    (:func:`_apply_pass_batched`) needs to resolve intra-window read
    chains without a value-carrying scan: ``src`` [N] (which earlier
    proposal wrote each proposal's nearest slot at decision time, -1 =
    snapshot) and the final ``(writer, age, valid)`` [K] metadata."""
    wmask = plan_word_mask(cfg, banks, planes)
    tag = plan_tag(banks, planes)
    meta = query_cache.meta_view(cache)
    with phase("nearest"):
        ham_snap = query_cache.hamming_all(meta, q_packed_all, cfg, banks,
                                           planes)                # [N, K]
        ham_prop = al.lookup_hamming_all(q_packed_all, q_packed_all,
                                         wmask)                   # [N, N]
    d_eff = jnp.asarray(
        cfg.d_eff_planned(jnp.asarray(banks, jnp.int32), planes), jnp.float32)
    snap_tag_ok = meta.acc_tag == tag                             # [K]
    int_max = jnp.iinfo(jnp.int32).max

    def body(carry, inp):
        writer, age, valid_k = carry
        hs, hp, v, i = inp
        live = writer >= 0
        with phase("nearest"):
            ham_k = jnp.where(live, hp[jnp.maximum(writer, 0)], hs)  # [K]
            rho_k = 1.0 - 2.0 * ham_k.astype(jnp.float32) / d_eff   # Eq. 5
            rho_k = jnp.where(valid_k, rho_k, -jnp.inf)
            idx = jnp.argmax(rho_k).astype(jnp.int32)
            rho = rho_k[idx]
            d_count = ham_k[idx]
            src = writer[idx]
        with phase("path_select"):
            tag_ok = jnp.where(live[idx], True, snap_tag_ok[idx])
            action = policy.select_path(rho, d_count, tag_ok, high, cfg)
            eff = jnp.where(v, action, jnp.int32(3))
        with phase("cache_write"):
            lru = jnp.argmax(jnp.where(valid_k, age, int_max)).astype(
                jnp.int32)
            # replay the meta_touch / meta_write metadata updates
            is_pad = eff == jnp.int32(3)
            is_write = jnp.logical_or(eff == jnp.int32(1),
                                      eff == jnp.int32(2))
            slot = jnp.where(eff == jnp.int32(2), lru, idx)
            bump = jnp.logical_not(is_pad)
            age = age + bump.astype(jnp.int32)
            age = age.at[slot].set(jnp.where(bump, 0, age[slot]))
            writer = writer.at[slot].set(jnp.where(is_write, i,
                                                   writer[slot]))
            valid_k = valid_k.at[slot].set(
                jnp.logical_or(valid_k[slot], is_write))
        out = (eff, idx, lru, jnp.where(v, d_count, 0),
               jnp.where(v, rho, 0.0), src)
        return (writer, age, valid_k), out

    writer0 = jnp.full((cfg.K,), -1, jnp.int32)
    arange = jnp.arange(cfg.N_max, dtype=jnp.int32)
    carry_f, (eff, idx, lru, d_count, rho, src) = jax.lax.scan(
        body, (writer0, meta.age, meta.valid),
        (ham_snap, ham_prop, valid, arange))

    # one vmapped delta-index pass against the resolved old entries
    with phase("delta_search"):
        old_packed = jnp.where(src[:, None] < 0, cache.packed[idx],
                               q_packed_all[jnp.maximum(src, 0)])
        d_idx, d_weight, _cnt = jax.vmap(
            lambda qn, qo: al.delta_indices(qn, qo, wmask, cfg.delta_budget,
                                            cfg.D))(q_packed_all, old_packed)
    dec = (eff, idx, lru, d_idx, d_weight, d_count, rho)
    return dec, (src,) + carry_f


def _decide_pass_batched(cache: CacheState, q_packed_all, valid,
                         cfg: TorrConfig, banks, planes, high):
    """:func:`_decide_pass_batched_aux` restricted to the decision 7-tuple
    — the drop-in signature-compatible counterpart of :func:`_decide_pass`
    for callers that replay decisions through the apply *scan*."""
    dec, _aux = _decide_pass_batched_aux(cache, q_packed_all, valid, cfg,
                                         banks, planes, high)
    return dec


_FUSED_MODES = ("switch", "prefix", "compact", "off")
_DECIDE_MODES = ("scan", "batched")


def _resolve_decide(decide) -> str:
    """Static decide-pass lowering for the compact dispatch: the batched
    intra-window decide by default, ``"scan"`` pinning the sequential
    reference oracle."""
    if decide is None:
        decide = "batched"
    if decide not in _DECIDE_MODES:
        raise ValueError(f"decide={decide!r} not in {_DECIDE_MODES}")
    return decide


def _plan_static(plan, cfg: TorrConfig):
    """Resolve the latched plan to its static knobs: (planes, cap, cfg')."""
    if plan is None:
        return cfg.bit_planes, cfg.B, cfg
    plan.validate(cfg)
    return plan.planes, min(plan.banks, cfg.B), plan.thresholds(cfg)


def _resolve_bucket_cap(bucket_cap, plan, n_rows: int) -> int:
    """Static bucket capacity for the compact dispatch. Precedence (pinned
    by ``tests/test_decide_batched.py::test_bucket_cap_precedence``): the
    explicit ``bucket_cap`` argument wins, else the latched plan's
    ``KnobPlan.bucket_cap``, else full capacity (no overflow possible, no
    savings either).

    An explicit capacity above the dispatch's row count is clamped — a
    bucket can never hold more rows than exist — but *warns* (at trace
    time; the cap is static): silently shrinking a user's tier would let a
    ladder misconfigured for a different batch shape (e.g. an engine plan
    sized for S x N_max latched onto a single-window step) masquerade as a
    deliberate full-capacity choice."""
    cap, src = bucket_cap, "bucket_cap"
    if cap is None and plan is not None:
        cap, src = plan.bucket_cap, "plan.bucket_cap"
    if cap is None:
        return n_rows
    cap = int(cap)
    if cap < 1:
        raise ValueError(f"bucket_cap={cap} must be >= 1")
    if cap > n_rows:
        warnings.warn(
            f"{src}={cap} exceeds the dispatch's {n_rows} rows; clamping to "
            f"full capacity (the no-savings tier). The latched ladder was "
            f"likely sized for a different batch shape.",
            stacklevel=3)
        cap = n_rows
    return cap


def torr_window_step(
    state: TorrState,
    im: ItemMemory,
    q_packed_all: jax.Array,   # uint32 [N_max, D//32] proposal query HVs
    valid: jax.Array,          # bool [N_max]
    boxes: jax.Array,          # f32 [N_max, 4]
    queue_depth: jax.Array,    # int32 []
    cfg: TorrConfig,
    plan=None,                 # static KnobPlan (None = uncontrolled)
    fused=None,                # static: "switch" | "prefix" | "compact" | "off"
    ham_prefix_all=None,       # int32 [N_max, cap, M] hoisted prefix counts
    bucket_cap=None,           # static compact-dispatch bucket capacity
    decide=None,               # static: "batched" | "scan" (compact only)
) -> tuple[TorrState, WindowOutput, WindowTelemetry]:
    """Process one window; returns (new_state, detections, telemetry).

    ``plan`` is a static :class:`repro.control.plan.KnobPlan` latched by the
    QoS control plane: it caps Alg. 1's bank choice (``min`` — the full cap
    is a bit-exact no-op), selects the bit-slice planes the scans read, and
    offsets the tau thresholds. ``plan=None`` (or the full plan) reproduces
    the uncontrolled step bit-for-bit.

    ``fused`` (static) picks the full path's lowering. The default
    (``None`` -> ``"switch"``) routes the whole window's full-path scan
    through the Pallas kernel family (``aligner.full_scores_all``): all
    N_max proposals go through one fused bank/plane-gated XNOR-popcount
    pass *before* the scan, and the delta branch's Eq. 6 correction rides
    the scalar-prefetch kernel — bit-identical to the jnp oracle.
    ``"prefix"`` is the vmap-shaped lowering the batched multi-stream step
    selects (one bank-prefix pass instead of a per-bank switch;
    ``ham_prefix_all`` carries the counts when the caller hoisted the
    kernel over a whole stream batch); ``"compact"`` is the reuse-aware
    compact-then-compute dispatch: a metadata-only decide pass produces the
    path vector first, the fused scan runs only over the full-path
    proposals compacted to the static ``bucket_cap`` tier (see
    ``aligner.compact_full_scores`` — overflow falls back exactly), and an
    apply pass replays the decisions; ``"off"`` keeps the legacy
    per-proposal oracle in-branch (the reference executable, and the
    cheaper trade for windows that rarely take the full path on branchy
    CPU backends — the hoisted scan runs per window, where the in-branch
    oracle runs per full-path proposal).

    ``bucket_cap`` (static, ``fused="compact"`` only) caps the compacted
    bucket; ``None`` defers to the latched plan's ``bucket_cap``, else full
    capacity. Engines pick it per window from the telemetry path-mix EWMA
    (``fused="auto"``), bounded by ``core.policy.bucket_ladder``.

    ``decide`` (static, ``fused="compact"`` only) picks the decide pass's
    lowering: ``"batched"`` (the ``None`` default) runs the batched
    intra-window decide — one wide snapshot-nearest pass plus the
    conflict-resolution scan (:func:`_decide_pass_batched`) — while
    ``"scan"`` pins the sequential per-proposal FSM
    (:func:`_decide_pass`), kept as the reference oracle. Both are
    bit-identical by construction; the differential harness in
    ``tests/test_decide_batched.py`` enforces it.
    """
    if fused is None:
        fused = "switch"
    if fused not in _FUSED_MODES:
        raise ValueError(f"fused={fused!r} not in {_FUSED_MODES}")
    planes, cap, cfg = _plan_static(plan, cfg)
    with phase("policy"):
        n_valid = jnp.sum(valid.astype(jnp.int32))
        high = policy.high_load(n_valid, queue_depth, cfg)
        banks = policy.select_banks(n_valid, queue_depth, cfg)
        if plan is not None and plan.banks < cfg.B:
            banks = jnp.minimum(banks, jnp.int32(plan.banks))
        wmask = plan_word_mask(cfg, banks, planes)
    arange = jnp.arange(cfg.N_max, dtype=jnp.int32)

    decide_id, btier = DECIDE_NONE, 0
    if fused == "compact":
        decide_mode = _resolve_decide(decide)
        decide_id = DECIDE_IDS[decide_mode]
        btier = _resolve_bucket_cap(bucket_cap, plan, cfg.N_max)
        decide_fn = (_decide_pass_batched if decide_mode == "batched"
                     else _decide_pass)
        dec = decide_fn(state.cache, q_packed_all, valid, cfg, banks,
                        planes, high)
        with phase("full_select"):
            acc_rows = al.compact_full_scores(
                q_packed_all, dec[0] == PATH_FULL,
                jnp.broadcast_to(banks, (cfg.N_max,)), im, cfg,
                planes=planes, cap=cap, bucket_cap=btier)
        body = _proposal_body(cfg, im, state.task_weights, banks, planes,
                              wmask, high, acc_full_all=acc_rows,
                              fused_delta=True, decided=True)
        cache, (outs, telem) = jax.lax.scan(
            body, state.cache, (q_packed_all, valid, arange) + dec)
    else:
        acc_full_all = None
        if fused != "off":
            with phase("full_select"):
                acc_full_all = al.full_scores_all(
                    q_packed_all, im, banks, cfg, planes=planes, cap=cap,
                    mode=fused, ham_prefix=ham_prefix_all)

        # The scalar-prefetch delta kernel pays off where branch economy is
        # real (the "switch" lowering: only the selected path executes).
        # Under the vmapped "prefix" lowering every lane computes all three
        # branches, and a budget-deep scalar-streaming grid per lane is the
        # wrong shape — the vectorized jnp gather-einsum IS the batched
        # scatter-accumulate there, so the oracle form is kept deliberately.
        body = _proposal_body(cfg, im, state.task_weights, banks, planes,
                              wmask, high, acc_full_all=acc_full_all,
                              fused_delta=fused == "switch")
        cache, (outs, telem) = jax.lax.scan(
            body, state.cache, (q_packed_all, valid, arange))

    return _finish_window(cache, state.task_weights, outs, telem, valid,
                          boxes, queue_depth, banks, n_valid, high, planes,
                          fused_mode=FUSED_IDS[fused], decide_mode=decide_id,
                          bucket_tier=btier)


def _finish_window(cache, task_w, outs, telem, valid, boxes, queue_depth,
                   banks, n_valid, high, planes, fused_mode=FUSED_IDS["off"],
                   decide_mode=DECIDE_NONE, bucket_tier=0):
    """Assemble (state, output, telemetry) from one window's scan results —
    shared by every lowering of the step so the trace vocabulary cannot
    drift between them. ``fused_mode``/``decide_mode``/``bucket_tier`` are
    the *static* resolved lowering knobs (``types.FUSED_IDS`` /
    ``types.DECIDE_IDS`` encodings) the dispatching step records into the
    trace."""
    with phase("finish"):
        actions, d_counts, rhos, active = telem
        # padding actions (3) are reported as bypass with zero cost
        path = jnp.where(actions == 3, PATH_BYPASS, actions)
        telemetry = WindowTelemetry(
            path=path.astype(jnp.int32),
            delta_count=d_counts.astype(jnp.int32),
            banks=banks,
            rho=rhos.astype(jnp.float32),
            n_valid=n_valid,
            reasoner_active=jnp.logical_and(active, valid),
            queue_depth=jnp.asarray(queue_depth, jnp.int32),
            high_load=high,
            planes=jnp.int32(planes),
            fused_mode=jnp.int32(fused_mode),
            decide_mode=jnp.int32(decide_mode),
            bucket_tier=jnp.int32(bucket_tier),
        )
        out = WindowOutput(
            scores=outs,
            best=jnp.argmax(outs, axis=-1).astype(jnp.int32),
            boxes=boxes,
        )
        return TorrState(cache=cache, task_weights=task_w), out, telemetry


# ---------------------------------------------------------------------------
# Multi-stream batched engine substrate
# ---------------------------------------------------------------------------

def init_multi_stream_state(cfg: TorrConfig, task_w: jax.Array) -> TorrState:
    """Stacked state for S independent streams.

    ``task_w`` is f32 [S, M] — one precomputed reasoner-weight row per
    stream slot (streams may serve different tasks). Every state leaf gains
    a leading stream axis; the per-stream query caches start empty.
    """
    task_w = jnp.asarray(task_w, jnp.float32)
    n_streams = task_w.shape[0]
    return TorrState(
        cache=query_cache.init_cache_batch(cfg, n_streams),
        task_weights=task_w,
    )


def torr_multi_stream_step(
    state: TorrState,          # stacked: every leaf has leading [S] axis
    im: ItemMemory,            # shared item memory (task knowledge)
    q_packed_all: jax.Array,   # uint32 [S, N_max, D//32]
    valid: jax.Array,          # bool [S, N_max]
    boxes: jax.Array,          # f32 [S, N_max, 4]
    queue_depth: jax.Array,    # int32 [S] per-stream backlog
    cfg: TorrConfig,
    serial: bool = False,      # static: lax.map instead of vmap
    plan=None,                 # static KnobPlan shared by all S windows
    fused=None,                # static: "switch"|"prefix"|"compact"|"off"
    bucket_cap=None,           # static compact-dispatch bucket capacity
    decide=None,               # static: "batched" | "scan" (compact only)
) -> tuple[TorrState, WindowOutput, WindowTelemetry]:
    """One compiled step over S streams' windows.

    All S windows of one batched step share the latched ``plan`` (the
    window-latched register analogue: one plan per dispatch); each window's
    telemetry still records it individually.

    Semantically identical to running ``torr_window_step`` once per stream:
    each slot keeps its own cache, task weights and queue depth, so Alg. 1's
    load gating (H, D') is evaluated per stream. Idle slots (``valid``
    all-False) ride the pad branch and leave their cache intact.

    Two bit-identical lowerings, selected by the static ``serial`` flag:

      * ``serial=False`` (default) — ``jax.vmap`` of the window FSM: the
        XNOR-popcount and delta arithmetic of all S slots batch across
        vector lanes. Under vmap the per-proposal ``lax.switch`` lowers to
        compute-all-paths-and-select, the right trade on a TPU whose wide
        VPU is otherwise idle between windows.
      * ``serial=True`` — ``jax.lax.map`` over slots: streams run
        sequentially *inside one executable*, preserving scalar branch
        economy (only the selected path executes) while still amortizing
        the per-window host dispatch. The right trade on branchy CPU
        backends; ~2x over the per-stream Python loop in table6.

    ``fused`` defaults per lowering: the vmap lowering takes the
    ``"prefix"`` kernel dispatch (under vmap a per-bank ``lax.switch``
    would execute every branch on the whole batch), the serial lowering
    takes ``"switch"`` (branch economy survives inside ``lax.map``). In
    prefix mode the bank-prefix kernel is hoisted *out* of the per-stream
    lowering and runs once over the flattened S x N_max proposal batch —
    the item-memory tile is read once per query block for the whole step,
    and each stream's window selects its traced bank choice from the
    precomputed boundary counts. All of it is bit-identical to
    ``fused="off"``, the legacy oracle step.

    ``fused="compact"`` is the reuse-aware third lowering: the decide pass
    runs per stream (vmapped — metadata only, no item-memory reads), the
    full-path proposals of *all* S windows are compacted together into one
    static ``bucket_cap``-sized bucket (``core.policy.bucket_ladder`` tiers
    up to S x N_max), one fused kernel pass scans only the bucket, and the
    apply pass (vmap or lax.map per ``serial``) replays the decisions.
    Bit-identical to ``fused="off"`` for any tier — an overflowing bucket
    falls back to the hoisted all-rows pass via a scalar cond.
    """
    if fused is None:
        fused = "switch" if serial else "prefix"

    if fused == "compact":
        return _multi_stream_compact_step(
            state, im, q_packed_all, valid, boxes, queue_depth, cfg,
            serial=serial, plan=plan, bucket_cap=bucket_cap, decide=decide)

    ham_prefix = None
    if fused == "prefix":
        planes, cap, _ = _plan_static(plan, cfg)
        S, N, W = q_packed_all.shape
        ham_prefix = al.plan_prefix_hamming(
            q_packed_all.reshape(S * N, W), im, cfg, planes=planes, cap=cap,
        ).reshape(S, N, cap, cfg.M)

    if serial:
        def body(args):
            st, q, v, b, qd, hp = args
            return torr_window_step(st, im, q, v, b, qd, cfg, plan=plan,
                                    fused=fused, ham_prefix_all=hp)

        return jax.lax.map(
            body,
            (state, q_packed_all, valid, boxes, queue_depth, ham_prefix),
        )

    def step(st, im_, q, v, b, qd, hp):
        return torr_window_step(st, im_, q, v, b, qd, cfg, plan=plan,
                                fused=fused, ham_prefix_all=hp)

    return jax.vmap(step, in_axes=(0, None, 0, 0, 0, 0, 0))(
        state, im, q_packed_all, valid, boxes, queue_depth, ham_prefix
    )


def _multi_stream_compact_step(
    state: TorrState, im: ItemMemory, q_packed_all, valid, boxes,
    queue_depth, cfg: TorrConfig, *, serial: bool, plan, bucket_cap,
    decide=None,
) -> tuple[TorrState, WindowOutput, WindowTelemetry]:
    """The batched compact-then-compute lowering (``fused="compact"``).

    Three hoisted stages instead of one monolithic per-stream FSM:

      1. *decide* — the metadata-only Alg. 1 pass runs per stream (vmapped;
         it reads the depth-K cache, never the item memory), yielding each
         window's path vector and per-proposal decisions;
      2. *compact + compute* — the full-path rows of all S windows are
         compacted together into one static ``bucket_cap`` bucket and a
         single fused kernel pass scans only the bucket
         (``aligner.compact_full_scores``), so the XNOR-popcount bytes
         scale with the *miss* rate, not the proposal count;
      3. *apply* — the value-carrying scan replays the recorded decisions
         per stream (vmap lanes, or lax.map when ``serial`` for scalar
         branch economy), gathering full-path accumulators from the bucket.
    """
    planes, cap, cfg = _plan_static(plan, cfg)
    S, N, W = q_packed_all.shape
    bcap = _resolve_bucket_cap(bucket_cap, plan, S * N)

    with phase("policy"):
        n_valid = jnp.sum(valid.astype(jnp.int32), axis=-1)    # [S]
        high = policy.high_load(n_valid, queue_depth, cfg)      # [S]
        banks = jax.vmap(lambda n, qd: policy.select_banks(n, qd, cfg))(
            n_valid, queue_depth)                               # [S]
        if plan is not None and plan.banks < cfg.B:
            banks = jnp.minimum(banks, jnp.int32(plan.banks))

    decide_mode = _resolve_decide(decide)
    if decide_mode == "batched":
        dec, aux = jax.vmap(
            lambda c, q, v, b, h: _decide_pass_batched_aux(c, q, v, cfg, b,
                                                           planes, h)
        )(state.cache, q_packed_all, valid, banks, high)
    else:
        dec = jax.vmap(
            lambda c, q, v, b, h: _decide_pass(c, q, v, cfg, b, planes, h)
        )(state.cache, q_packed_all, valid, banks, high)
        aux = None

    with phase("full_select"):
        acc_rows = al.compact_full_scores(
            q_packed_all.reshape(S * N, W),
            (dec[0] == PATH_FULL).reshape(S * N),
            jnp.broadcast_to(banks[:, None], (S, N)).reshape(S * N),
            im, cfg, planes=planes, cap=cap, bucket_cap=bcap,
        ).reshape(S, N, cfg.M)

    # The batched decide's conflict byproducts unlock the batched apply
    # (value math hoisted dispatch-wide); ``decide="scan"`` pins the
    # sequential reference pipeline end-to-end — decide scan + per-proposal
    # apply scan — which is also the baseline the bench rows compare
    # against. The serial lowering keeps the apply scan regardless: its
    # lax.switch branch economy is real there.
    if decide_mode == "batched" and not serial:
        return _apply_pass_batched(state, im, q_packed_all, valid, boxes,
                                   queue_depth, cfg, banks, planes, high,
                                   n_valid, dec, aux, acc_rows,
                                   bucket_tier=bcap)

    def apply_one(args):
        st, q, v, b, qd, bk, h, nv, dec_s, accs = args
        with phase("policy"):
            wmask = plan_word_mask(cfg, bk, planes)
        body = _proposal_body(cfg, im, st.task_weights, bk, planes,
                              wmask, h, acc_full_all=accs,
                              fused_delta=True, decided=True)
        cache, (outs, telem) = jax.lax.scan(
            body, st.cache,
            (q, v, jnp.arange(cfg.N_max, dtype=jnp.int32)) + dec_s)
        return _finish_window(cache, st.task_weights, outs, telem, v, b, qd,
                              bk, nv, h, planes,
                              fused_mode=FUSED_IDS["compact"],
                              decide_mode=DECIDE_IDS[decide_mode],
                              bucket_tier=bcap)

    args = (state, q_packed_all, valid, boxes, queue_depth, banks, high,
            n_valid, dec, acc_rows)
    if serial:
        return jax.lax.map(apply_one, args)
    return jax.vmap(apply_one)(args)


def torr_stream_batch_step(
    state: TorrState, im: ItemMemory, batch: StreamBatch, cfg: TorrConfig,
    serial: bool = False, plan=None, fused=None, bucket_cap=None,
    decide=None,
) -> tuple[TorrState, WindowOutput, WindowTelemetry]:
    """`torr_multi_stream_step` over a packed :class:`StreamBatch`."""
    return torr_multi_stream_step(
        state, im, batch.q_packed, batch.valid, batch.boxes,
        batch.queue_depth, cfg, serial=serial, plan=plan, fused=fused,
        bucket_cap=bucket_cap, decide=decide,
    )
