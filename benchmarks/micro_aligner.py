"""Microbenchmarks: full vs delta vs bypass aligner paths (Sec. 4.3 claims).

Measures (a) modeled accelerator cycles — the paper's cycles_full ~= D'*M/W
vs cycles_delta ~= |Delta|*M/W scaling, (b) wall-clock of the jitted
functional kernels on this host (interpret-mode Pallas + XLA), (c) the
bank-gating (D') sweep, and (d) the three-way full-path comparison at the
table6 default shapes:

  * ``fullpath_oracle``  — the legacy jitted full path: one masked
    ``aligner.full_dot`` ([M, W] xor) per proposal inside a scan;
  * ``fullpath_batched`` — the host-latched static-banks kernel wrapper
    (``ops.packed_similarity``) over the whole proposal batch;
  * ``fullpath_fused``   — the traced-banks fused dispatch the jitted
    pipeline now defaults to (``aligner.full_scores_all``), in both the
    ``switch`` and ``prefix`` lowerings.

The fused-vs-oracle ratio is a CPU acceptance gate (>= 1.3x at the table6
shapes), and (e) the reuse-mix sweep (``--reuse-mix 0,0.5,0.9,0.99``):
synthetic traces at fixed bypass/delta/full ratios, comparing the
always-hoisted ``prefix`` scan against the reuse-aware ``compact``
dispatch at both the full-path-dispatch and end-to-end-step level (see
``reuse_mix_rows``) — the ISSUE 5 acceptance gate is compact >= 1.3x
prefix dispatch windows/sec at mix 0.9, S = 64, on CPU. The same sweep
also reports step-level windows/sec for the compact dispatch under the
*sequential* vs *batched* decide pass (``decide="scan"`` vs
``"batched"``): the ISSUE 6 acceptance gate is batched >= 3x the
sequential-decide baseline at mix 0.9, S = 64, M = 1024, on CPU. Finally
(f) the observability overhead gate (``--obs-overhead``, see
``obs_overhead_rows``): the same step-level drive with a live
``repro.obs`` metrics registry + flight recorder attached must stay
within 3% windows/sec of the bare drive (ISSUE 7 acceptance, asserted
in-benchmark). ``python -m benchmarks.micro_aligner --json PATH`` writes
``{"rows": [[name, value, derived], ...]}`` for the bench-smoke CI
artifact; rows are also printed as CSV either way.
"""
from __future__ import annotations

import argparse
import json
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import aligner, hdc, pipeline, policy
from repro.core.item_memory import random_item_memory, word_mask
from repro.core.types import PATH_BYPASS, PATH_DELTA, PATH_FULL, TorrConfig
from repro.kernels import ops

# the table6 multi-stream serving shapes — the fused-path acceptance point
# (imported so a table6 retune moves this gate with it)
from benchmarks.table6_multistream import CFG as TABLE6_CFG


def _time(fn, *args, iters: int = 20):
    fn(*args)  # compile
    t0 = time.perf_counter()
    for _ in range(iters):
        out = fn(*args)
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / iters * 1e6  # us


def fullpath_three_way(cfg: TorrConfig = TABLE6_CFG, n_streams: int = 64,
                       iters: int = 30):
    """Rows for oracle vs batched-kernel vs fused-path.

    Measured on the flattened S x N_max proposal batch of one multi-stream
    step (the default serving substrate since PR 1) — the shape at which
    the fused dispatch is actually invoked by ``torr_multi_stream_step``.
    All four variants are verified to produce identical integer
    accumulators before timing; times are best-of-5 rounds.
    """
    im = random_item_memory(jax.random.PRNGKey(0), cfg)
    n_rows = n_streams * cfg.N_max
    qp = hdc.pack_bits(hdc.random_hv(jax.random.PRNGKey(1),
                                     (n_rows, cfg.D)))
    banks_t = jnp.int32(cfg.B)

    # (1) legacy oracle: one masked full_dot per proposal inside a scan,
    # traced banks — exactly the full path the jitted pipeline ran before
    # the fused dispatch landed.
    @jax.jit
    def oracle(q, banks):
        wm = word_mask(cfg, banks)

        def body(c, qr):
            return c, aligner.full_dot(qr, im, wm)

        _, accs = jax.lax.scan(body, jnp.int32(0), q)
        return accs

    # (1b) batched oracle: ref-style whole-batch xor — materializes the
    # [N, M, W] intermediate the fused path exists to kill.
    @jax.jit
    def oracle_batched(q, banks):
        wm = word_mask(cfg, banks)
        x = jnp.bitwise_xor(q[:, None, :], im.packed[None, :, :])
        pc = jnp.where(wm[None, None, :],
                       jax.lax.population_count(x).astype(jnp.int32), 0)
        return 32 * jnp.sum(wm.astype(jnp.int32)) - 2 * jnp.sum(pc, -1)

    # (2) host-latched batched kernel wrapper (static banks).
    batched = jax.jit(lambda q: ops.packed_similarity(
        q, im.packed, banks=cfg.B, bank_words=cfg.bank_words)[0])

    # (3) traced-banks fused dispatch (what the jitted step now runs).
    def fused(mode):
        @jax.jit
        def f(q, banks):
            return aligner.full_scores_all(
                q, im, banks, cfg, planes=cfg.bit_planes, cap=cfg.B,
                mode=mode)
        return f

    f_switch, f_prefix = fused("switch"), fused("prefix")

    # sanity: all variants produce identical integer accumulators
    want = np.asarray(oracle(qp, banks_t))
    for name, got in (("oracle_batched", oracle_batched(qp, banks_t)),
                      ("batched", batched(qp)),
                      ("switch", f_switch(qp, banks_t)),
                      ("prefix", f_prefix(qp, banks_t))):
        assert np.array_equal(np.asarray(got), want), name

    def best_of(fn, rounds=5):
        return min(_time(fn, iters=iters) for _ in range(rounds))

    us_oracle = best_of(lambda: oracle(qp, banks_t))
    us_oracle_b = best_of(lambda: oracle_batched(qp, banks_t))
    us_batched = best_of(lambda: batched(qp))
    us_switch = best_of(lambda: f_switch(qp, banks_t))
    us_prefix = best_of(lambda: f_prefix(qp, banks_t))

    shape = f"N{n_rows}_M{cfg.M}_D{cfg.D}"
    best_fused = min(us_switch, us_prefix)
    return [
        (f"micro/fullpath_oracle_{shape}", round(us_oracle, 1), "us"),
        (f"micro/fullpath_oracle_batched_{shape}", round(us_oracle_b, 1),
         "us (materializes [N,M,W])"),
        (f"micro/fullpath_batched_{shape}", round(us_batched, 1),
         f"speedup_vs_oracle={us_oracle / us_batched:.2f}"),
        (f"micro/fullpath_fused_switch_{shape}", round(us_switch, 1),
         f"speedup_vs_oracle={us_oracle / us_switch:.2f}"),
        (f"micro/fullpath_fused_prefix_{shape}", round(us_prefix, 1),
         f"speedup_vs_oracle={us_oracle / us_prefix:.2f}"),
        (f"micro/fullpath_fused_speedup_{shape}",
         round(us_oracle / best_fused, 2), "acceptance: >= 1.3"),
    ]


# --- reuse-mix sweep: compact vs always-hoisted dispatch --------------------

# serving-shaped config for the reuse sweep: the paper's edge class count
# (M = 1024) so the full scan is serving-scale, and K >= N_max so a window
# cannot thrash its own cache out of reuse range
REUSE_CFG = TorrConfig(D=2048, B=8, M=1024, K=16, N_max=16,
                       delta_budget=128)


def _mix_trace(cfg: TorrConfig, mix: float, S: int, T: int, seed: int = 0,
               numpy: bool = False, n_valid: int | None = None):
    """S streams x (T+1) windows at a fixed bypass/delta/full mix.

    Window 0 is the cold-cache warm-up (all full). From window 1 on, each
    proposal independently keeps its previous query exactly (rho = 1 ->
    bypass under the pinned high load), flips D/32 dims (rho = 0.9375 ->
    delta at any dimension) or resamples fresh (rho ~0 -> full), with
    probabilities mix/2, mix/2, 1 - mix. Queue depth is pinned at q_hi so
    the bypass gate H(N, q) is open; the *achieved* mix is measured from
    telemetry (LRU evictions pull a few intended hits back to full at
    middle mixes). The single reuse-mix synthesizer — the compact-dispatch
    bit-identity tests drive the same traces (``numpy=True`` returns host
    arrays for the engine submit path). ``n_valid`` keeps only the first
    proposals of each window valid (default all N_max): a cache of depth K
    can only hold reuse for windows of at most K proposals.
    """
    rng = np.random.default_rng(seed)
    n_flip = max(1, cfg.D // 32)
    base = (rng.integers(0, 2, (S, cfg.N_max, cfg.D)) * 2 - 1).astype(np.int8)
    valid = np.zeros((S, cfg.N_max), bool)
    valid[:, :cfg.N_max if n_valid is None else n_valid] = True
    boxes = np.zeros((S, cfg.N_max, 4), np.float32)
    qd = np.full((S,), cfg.q_hi, np.int32)
    windows = []
    for t in range(T + 1):
        if t:
            r = rng.random((S, cfg.N_max))
            for s in range(S):
                for n in range(cfg.N_max):
                    if r[s, n] < mix / 2:
                        continue                              # bypass
                    if r[s, n] < mix:                         # delta
                        flips = rng.choice(cfg.D, n_flip, replace=False)
                        base[s, n, flips] *= -1
                    else:                                     # full
                        base[s, n] = (rng.integers(0, 2, cfg.D) * 2
                                      - 1).astype(np.int8)
        q = np.asarray(jax.vmap(hdc.pack_bits)(jnp.asarray(base)))
        win = (q, valid.copy(), boxes, qd)
        windows.append(win if numpy else
                       tuple(jnp.asarray(x) for x in win))
    return windows


def reuse_mix_rows(mixes=(0.0, 0.5, 0.9, 0.99), cfg: TorrConfig = REUSE_CFG,
                   n_streams: int = 64, n_windows: int = 10,
                   rounds: int = 3) -> list[tuple]:
    """Compact vs always-hoisted full-path dispatch at fixed reuse mixes.

    Two row families per mix, both on the same trace:

      * ``*_dispatch_*`` — the full-path *scoring dispatch* alone (this
        module's genre, like ``fullpath_three_way``): producing each
        window's full-path accumulators via the always-hoisted prefix pass
        over all S x N_max rows vs the compacted bucket at the oracle tier
        (smallest ladder capacity holding the trace's worst window — what
        a perfect ``fused="auto"`` dispatcher latches). This isolates the
        paper's memory-traffic claim — hits *skip* the scan — and carries
        the ISSUE 5 acceptance gate (>= 1.3x at mix 0.9, S = 64, CPU).
      * ``*_step_*`` — the end-to-end jitted multi-stream step under each
        lowering. The ``decide_scan`` row pins the sequential reference
        pipeline end-to-end (per-proposal decide FSM + per-proposal apply
        scan — the step as it stood before the batched decide), while
        ``decide_batched`` is the compact default: batched decide plus the
        batched apply (``pipeline._apply_pass_batched``), which hoists the
        Eq. 6 corrections into one dense matmul and the reasoner top-k
        into one dispatch-wide pass. This is the ISSUE 6 step-level gate
        (>= 3x at mix 0.9, S = 64, M = 1024, CPU): the sequential FSM
        machinery used to floor every lowering at ~0.6 s/step on CPU; the
        batched pipeline is the first to break that floor.
    """
    im = random_item_memory(jax.random.PRNGKey(0), cfg)
    task_w = jax.random.uniform(jax.random.PRNGKey(1), (n_streams, cfg.M))
    step = jax.jit(pipeline.torr_multi_stream_step,
                   static_argnames=("cfg", "serial", "plan", "fused",
                                    "bucket_cap", "decide"))
    R = n_streams * cfg.N_max
    rows = []
    for mix in mixes:
        windows = _mix_trace(cfg, mix, n_streams, n_windows)
        warm, timed = windows[0], windows[1:]

        def drive(fused, bucket_cap=None, collect=False, decide=None):
            st = pipeline.init_multi_stream_state(cfg, task_w)
            st, _, _ = step(st, im, *warm, cfg, fused=fused,
                            bucket_cap=bucket_cap, decide=decide)
            tels = []
            for q, v, b, qd in timed:
                st, _out, tel = step(st, im, q, v, b, qd, cfg, fused=fused,
                                     bucket_cap=bucket_cap, decide=decide)
                if collect:
                    tels.append(tel)
            jax.block_until_ready(st.cache.age)
            return st, tels

        # reference drive: achieved mix, per-window path vectors, and the
        # oracle bucket tier
        _, tels = drive("prefix", collect=True)
        paths = np.stack([np.asarray(t.path) for t in tels])
        frac = {p: float(np.mean(paths == p))
                for p in (PATH_BYPASS, PATH_DELTA, PATH_FULL)}
        max_full = max(int(np.sum(p == PATH_FULL)) for p in paths)
        tier = policy.bucket_tier(R, max(max_full, 1))

        # sanity: compact at the chosen tier is bit-identical to prefix
        st_p, _ = drive("prefix")
        st_c, _ = drive("compact", tier)
        for a, b in zip(jax.tree_util.tree_leaves(st_p.cache),
                        jax.tree_util.tree_leaves(st_c.cache)):
            assert np.array_equal(np.asarray(a), np.asarray(b)), mix

        def best_of(fn):
            fn()                               # compile outside the timing
            best = float("inf")
            for _ in range(rounds):
                t0 = time.perf_counter()
                fn()
                best = min(best, time.perf_counter() - t0)
            return best

        n_win = n_streams * len(timed)
        t_sprefix = best_of(lambda: drive("prefix"))
        # compact's decide default IS "batched"; time the sequential-decide
        # baseline separately for the ISSUE 6 step-level gate
        t_scompact = best_of(lambda: drive("compact", tier))
        t_sscan = best_of(lambda: drive("compact", tier, decide="scan"))

        # dispatch-only: the recorded path vectors replay through the two
        # full-path scoring dispatches (what the decide pass hands them)
        qs = [w[0].reshape(R, cfg.words) for w in timed]
        masks = [jnp.asarray(p == PATH_FULL).reshape(R) for p in paths]
        banks_rows = jnp.full((R,), cfg.B, jnp.int32)
        prefix_fn = jax.jit(lambda q, banks: aligner.full_scores_all(
            q, im, banks, cfg, planes=cfg.bit_planes, cap=cfg.B,
            mode="prefix"))
        compact_fn = jax.jit(lambda q, m: aligner.compact_full_scores(
            q, m, banks_rows, im, cfg, planes=cfg.bit_planes, cap=cfg.B,
            bucket_cap=tier))

        def d_prefix():
            for q in qs:
                r = prefix_fn(q, jnp.int32(cfg.B))
            jax.block_until_ready(r)

        def d_compact():
            for q, m in zip(qs, masks):
                r = compact_fn(q, m)
            jax.block_until_ready(r)

        t_dprefix = best_of(d_prefix)
        t_dcompact = best_of(d_compact)

        tag = f"S{n_streams}_mix{mix}"
        rows.extend([
            (f"micro/reuse_{tag}_achieved", round(frac[PATH_FULL], 3),
             f"bypass={frac[PATH_BYPASS]:.2f},delta={frac[PATH_DELTA]:.2f},"
             f"full={frac[PATH_FULL]:.2f}"),
            (f"micro/reuse_{tag}_dispatch_prefix_wps",
             round(n_win / t_dprefix, 1),
             "windows/sec, full-path dispatch (always-hoisted scan)"),
            (f"micro/reuse_{tag}_dispatch_compact_wps",
             round(n_win / t_dcompact, 1),
             f"tier={tier};speedup_vs_prefix={t_dprefix / t_dcompact:.2f}"
             + (";acceptance: >= 1.3" if mix == 0.9 else "")),
            (f"micro/reuse_{tag}_step_prefix_wps",
             round(n_win / t_sprefix, 1),
             "windows/sec, end-to-end step (FSM-machinery-bound on CPU)"),
            (f"micro/reuse_{tag}_step_compact_wps",
             round(n_win / t_scompact, 1),
             f"tier={tier};speedup_vs_prefix={t_sprefix / t_scompact:.2f}"),
            (f"micro/reuse_{tag}_step_decide_scan_wps",
             round(n_win / t_sscan, 1),
             "windows/sec, compact step, sequential decide FSM"),
            (f"micro/reuse_{tag}_step_decide_batched_wps",
             round(n_win / t_scompact, 1),
             f"speedup_vs_scan={t_sscan / t_scompact:.2f}"
             + (";acceptance: >= 3.0" if mix == 0.9 else "")),
        ])
    return rows


# --- observability overhead gate -------------------------------------------

# registry snapshot of the last instrumented obs_overhead drive; embedded
# in the JSON artifact (benchmarks.run and --json) via metrics_snapshot()
_METRICS_SNAPSHOT = None


def metrics_snapshot():
    """Metrics of the last instrumented run, for the JSON artifact."""
    return _METRICS_SNAPSHOT


def obs_overhead_rows(cfg: TorrConfig = REUSE_CFG, n_streams: int = 64,
                      n_windows: int = 10, rounds: int = 3) -> list[tuple]:
    """Per-step observability overhead on the serving-shaped compact drive.

    Times the mix-0.9 step-level drive (S = 64, M = 1024 — the ISSUE 6
    gate's shape) twice: bare, and with a live ``repro.obs`` stack (metrics
    registry + flight recorder + ``StepObserver``) *plus* write-through
    state-store snapshots (``snapshot_every=1``, every stream every step —
    the worst-case externalization cadence) folded exactly the way the
    sync engine folds it — deferred one step behind dispatch, so the host
    never blocks on in-flight device work, with the final drain inside
    the timed region (the engine pays it at ``summary()``). The ISSUE 7
    acceptance gate is overhead <= 3% windows/sec, asserted here so CI
    bench-smoke fails loudly if instrumentation (or snapshotting) creeps
    onto the hot path.
    """
    from collections import deque

    from repro.obs import FlightRecorder, MetricsRegistry, StepObserver
    from repro.serving import state_store as ss

    im = random_item_memory(jax.random.PRNGKey(0), cfg)
    task_w = jax.random.uniform(jax.random.PRNGKey(1), (n_streams, cfg.M))
    step = jax.jit(pipeline.torr_multi_stream_step,
                   static_argnames=("cfg", "serial", "plan", "fused",
                                    "bucket_cap", "decide"))
    R = n_streams * cfg.N_max
    windows = _mix_trace(cfg, 0.9, n_streams, n_windows)
    warm, timed = windows[0], windows[1:]

    # oracle tier for the trace, same as reuse_mix_rows
    st = pipeline.init_multi_stream_state(cfg, task_w)
    st, _, _ = step(st, im, *warm, cfg, fused="prefix")
    max_full = 1
    for q, v, b, qd in timed:
        st, _o, tel = step(st, im, q, v, b, qd, cfg, fused="prefix")
        max_full = max(max_full, int(np.sum(np.asarray(tel.path) == PATH_FULL)))
    tier = policy.bucket_tier(R, max_full)

    def drive(obs, store=None):
        st = pipeline.init_multi_stream_state(cfg, task_w)
        st, _, _ = step(st, im, *warm, cfg, fused="compact", bucket_cap=tier)
        backlog = deque()
        for t, (q, v, b, qd) in enumerate(timed):
            st, _out, tel = step(st, im, q, v, b, qd, cfg, fused="compact",
                                 bucket_cap=tier)
            if obs is not None:
                rec = obs.on_dispatch(n_streams, 0,
                                      requested=("compact", tier, None))
                # the engine's lazy per-slot snapshot slices ride the same
                # deferred fold as the telemetry (cadence 1: every stream)
                snaps = None
                if store is not None:
                    snaps = [ss.snapshot_rows(st, s, f"stream{s}", t + 1,
                                              {"engine": "bench"})
                             for s in range(n_streams)]
                backlog.append((tel, rec, snaps))
                # the sync engine's deferred fold: everything but the
                # newest (possibly in-flight) step
                while len(backlog) > 1:
                    tel0, rec0, sn0 = backlog.popleft()
                    obs.observe_step(
                        jax.tree_util.tree_map(np.asarray, tel0), rec0)
                    memo = {}
                    for pending in sn0 or ():
                        store.put(ss.materialize_snapshot(pending, memo))
        jax.block_until_ready(st.cache.age)
        while backlog:                         # flush_telemetry()
            tel0, rec0, sn0 = backlog.popleft()
            obs.observe_step(jax.tree_util.tree_map(np.asarray, tel0), rec0)
            memo = {}
            for pending in sn0 or ():
                store.put(ss.materialize_snapshot(pending, memo))

    # interleave base/obs rounds so slow host drift (the drives are ~1 s
    # each) cancels instead of biasing one arm; best-of over rounds
    drive(None)                                # compile / warm caches
    t_base = t_obs = float("inf")
    obs = store = None
    for _ in range(rounds):
        t0 = time.perf_counter()
        drive(None)
        t_base = min(t_base, time.perf_counter() - t0)
        obs = StepObserver(MetricsRegistry(), FlightRecorder())
        store = ss.InMemoryStateStore(metrics=obs.registry)
        t0 = time.perf_counter()
        drive(obs, store)
        t_obs = min(t_obs, time.perf_counter() - t0)

    # the instrumented drive must have actually observed every step and
    # written through every snapshot (cadence 1: one per stream per step)
    snap = obs.registry.snapshot()
    n_steps = snap["torr_steps_total"]["series"][0]["value"]
    assert n_steps == len(timed), (n_steps, len(timed))
    assert len(obs.flight.records()) == len(timed)
    assert all("telemetry" in r for r in obs.flight.records())
    assert len(store.keys()) == n_streams
    assert store.latest_seq("stream0") == len(timed)
    global _METRICS_SNAPSHOT
    _METRICS_SNAPSHOT = snap

    n_win = n_streams * len(timed)
    pct = (t_obs - t_base) / t_base * 100.0
    rows = [
        (f"micro/obs_overhead_S{n_streams}_mix0.9_base_wps",
         round(n_win / t_base, 1), "windows/sec, compact step, no obs"),
        (f"micro/obs_overhead_S{n_streams}_mix0.9_obs_wps",
         round(n_win / t_obs, 1),
         "windows/sec, metrics+flight+state-store snapshots "
         "(deferred fold, snapshot_every=1)"),
        (f"micro/obs_overhead_S{n_streams}_mix0.9_pct", round(pct, 2),
         "acceptance: <= 3.0"),
    ]
    assert pct <= 3.0, f"observability overhead {pct:.2f}% > 3% gate"
    return rows


def run() -> list[tuple]:
    cfg = TorrConfig(D=8192, B=8, M=1024, W=64, delta_budget=1024)
    key = jax.random.PRNGKey(0)
    im = random_item_memory(key, cfg)
    q = hdc.random_hv(jax.random.PRNGKey(1), (8, cfg.D))
    qp = hdc.pack_bits(q)
    mw = -(-cfg.M // cfg.W)

    rows = []
    # (a) modeled cycles: full sweep over banks vs delta
    for banks in (2, 4, 8):
        d_eff = banks * cfg.bank_dims
        rows.append((f"micro/cycles_full_D{d_eff}", d_eff * mw,
                     "paper: D'*ceil(M/W)"))
    for delta in (128, 512, 1024):
        rows.append((f"micro/cycles_delta_{delta}", delta * mw,
                     f"speedup_vs_full={cfg.D * mw / (delta * mw):.1f}x"))

    # (b) wall-clock of the functional kernels (CPU, interpret-mode Pallas)
    for banks in (2, 8):
        us = _time(lambda qp=qp, banks=banks: ops.packed_similarity(
            qp, im.packed, banks=banks, bank_words=cfg.bank_words)[0])
        rows.append((f"micro/wallclock_full_banks{banks}", round(us, 1), "us"))

    acc = jnp.zeros((cfg.M,), jnp.int32)
    idx = jax.random.randint(jax.random.PRNGKey(2), (cfg.delta_budget,), 0, cfg.D)
    w = jnp.where(jax.random.bernoulli(jax.random.PRNGKey(3), 0.5,
                                       (cfg.delta_budget,)), 2, -2).astype(jnp.int32)
    us = _time(lambda: ops.delta_update(acc, im.dmajor, idx, w))
    rows.append(("micro/wallclock_delta", round(us, 1), "us"))

    z = jax.random.normal(jax.random.PRNGKey(4), (8, 512))
    R = jax.random.normal(jax.random.PRNGKey(5), (cfg.D, 512))
    us = _time(lambda: ops.sign_project(z, R))
    rows.append(("micro/wallclock_sign_project", round(us, 1), "us"))
    us = _time(lambda: ops.encode_packed(z, R))
    rows.append(("micro/wallclock_encode_packed", round(us, 1),
                 "us (fused sign+pack)"))

    # (d) the three-way full-path comparison (PR acceptance gate)
    rows.extend(fullpath_three_way())
    # (e) compact-vs-hoisted dispatch at the reuse-mix extremes (the full
    # sweep is `--reuse-mix 0,0.5,0.9,0.99`; CI tracks these two points)
    rows.extend(reuse_mix_rows(mixes=(0.0, 0.9)))
    # (f) observability overhead gate (metrics+flight within 3% of bare)
    rows.extend(obs_overhead_rows())
    return rows


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--json", default="", metavar="PATH",
                    help="also write rows as JSON to PATH")
    ap.add_argument("--reuse-mix", default="", metavar="MIXES",
                    help="run only the reuse-mix sweep at these comma-"
                         "separated bypass+delta fractions (e.g. "
                         "0,0.5,0.9,0.99): per-lowering windows/sec for "
                         "the always-hoisted prefix vs compact dispatch")
    ap.add_argument("--obs-overhead", action="store_true",
                    help="run only the observability overhead gate "
                         "(metrics+flight vs bare step drive, <= 3%%)")
    args = ap.parse_args()
    if args.obs_overhead:
        rows = obs_overhead_rows()
    elif args.reuse_mix:
        mixes = tuple(float(m) for m in args.reuse_mix.split(",") if m)
        rows = reuse_mix_rows(mixes=mixes)
    else:
        rows = run()
    for r in rows:
        print(",".join(str(x) for x in r))
    if args.json:
        with open(args.json, "w") as f:
            json.dump({"rows": [list(r) for r in rows],
                       "backend": jax.default_backend(),
                       "metrics": metrics_snapshot()}, f, indent=1)


if __name__ == "__main__":
    main()
