"""Fault-tolerant serving: state store, supervised restart, chaos harness.

The ISSUE 9 acceptance properties, pinned as tests:

* the state store round-trips a stream's session state (in memory and
  through the JSONL encoding, including torn-trailing-line recovery and
  TTL reaping);
* an injected dispatcher/collector death under the supervisor recovers
  with zero lost windows and outputs *bit-identical* to a fault-free run
  (async and sync engines, snapshot cadences 1 and 2);
* worker death is a typed ``EngineDead`` (cause + in-flight count),
  distinguishable from ``WindowShed``;
* the crash-loop breaker degrades the knob plan; ``max_restarts`` makes
  the death terminal and fails every pending future;
* metrics and flight events reconcile (restart/replay counters ==
  ``recovery_events`` payloads);
* a SIGKILLed ``repro.launch.serve`` process resumes from its JSONL
  store with a gap-free, bit-identical output ledger (subprocess test).
"""
import json
import os
import subprocess
import sys
import time

import numpy as np
import pytest

import jax

from repro.core.item_memory import random_item_memory
from repro.runtime.fault import EngineDead, FaultPlan, InjectedFault
from repro.serving.async_engine import AsyncStreamEngine
from repro.serving.state_store import (CACHE_FIELDS, InMemoryStateStore,
                                       JsonlStateStore, StreamSnapshot)
from repro.serving.stream_engine import StreamEngine
from repro.serving.supervisor import ServeSupervisor, recovery_events

from test_multistream import CFG, _make_inputs

SRC = os.path.join(os.path.dirname(__file__), "..", "src")
FLUSH_S = 120


def _snap(sid="cam0", seq=3, seed=0, m=8):
    rng = np.random.default_rng(seed)
    cache = {
        "packed": rng.integers(0, 2**32, (4, 2), dtype=np.uint32),
        "acc": rng.integers(-50, 50, (4, m), dtype=np.int32),
        "acc_tag": rng.integers(0, 4, (4,), dtype=np.int32),
        "out": rng.standard_normal((4, m)).astype(np.float32),
        "topk_key": rng.integers(0, 2**32, (4, 2), dtype=np.uint32),
        "margin": rng.standard_normal((4,)).astype(np.float32),
        "age": rng.integers(0, 9, (4,), dtype=np.int32),
        "valid": rng.integers(0, 2, (4,)).astype(bool),
    }
    return StreamSnapshot(stream_id=sid, window_seq=seq, cache=cache,
                          task_w=rng.standard_normal((m,)).astype(np.float32),
                          meta={"engine": "test"})


# --- state store ------------------------------------------------------------

def test_snapshot_record_roundtrip():
    snap = _snap()
    back = StreamSnapshot.from_record(
        json.loads(json.dumps(snap.to_record())))
    assert back.stream_id == snap.stream_id
    assert back.window_seq == snap.window_seq
    for f in CACHE_FIELDS:
        assert np.array_equal(back.cache[f], snap.cache[f]), f
        assert back.cache[f].dtype == snap.cache[f].dtype, f
    np.testing.assert_array_equal(back.task_w, snap.task_w)
    assert back.meta == snap.meta


def test_snapshot_schema_validation():
    snap = _snap()
    del snap.cache["margin"]
    with pytest.raises(ValueError, match="margin"):
        snap.validate()
    rec = _snap().to_record()
    rec["v"] = 99
    with pytest.raises(ValueError, match="schema"):
        StreamSnapshot.from_record(rec)


def test_inmemory_store_ttl_and_monotonic():
    now = [0.0]
    store = InMemoryStateStore(ttl_s=10.0, clock=lambda: now[0])
    store.put(_snap(seq=5))
    # a stale write (abandoned engine's late delivery) can't regress
    store.put(_snap(seq=4))
    assert store.latest_seq("cam0") == 5
    store.put(_snap(seq=6))
    assert store.latest_seq("cam0") == 6
    now[0] = 5.0
    assert store.get("cam0") is not None
    now[0] = 20.0
    assert store.get("cam0") is None        # TTL-expired: reaped on read
    assert store.latest_seq("cam0") == 0
    assert store.keys() == []


def test_jsonl_store_persistence_torn_line_and_tombstone(tmp_path):
    path = tmp_path / "state.jsonl"
    store = JsonlStateStore(path)
    store.put(_snap(sid="a", seq=1))
    store.put(_snap(sid="a", seq=2, seed=1))
    store.put(_snap(sid="b", seq=7))
    store.close()

    # a fresh process sees latest-record-wins
    store2 = JsonlStateStore(path)
    assert store2.latest_seq("a") == 2
    assert store2.latest_seq("b") == 7
    got = store2.get("a")
    want = _snap(sid="a", seq=2, seed=1)
    for f in CACHE_FIELDS:
        assert np.array_equal(got.cache[f], want.cache[f]), f
    store2.delete("a")                      # appends a tombstone
    store2.close()

    # SIGKILL mid-append: torn trailing line is skipped, prior state wins
    with open(path, "a", encoding="utf-8") as f:
        f.write(json.dumps(_snap(sid="b", seq=9).to_record())[:37])
    store3 = JsonlStateStore(path)
    assert store3.get("a") is None          # tombstone survived reload
    assert store3.latest_seq("b") == 7      # torn seq-9 write discarded
    n = store3.compact()
    assert n == 1
    store3.close()
    lines = [l for l in path.read_text().splitlines() if l.strip()]
    assert len(lines) == 1
    assert json.loads(lines[0])["stream_id"] == "b"


# --- typed EngineDead + chaos plan ------------------------------------------

def test_fault_plan_fires_once_per_thread():
    plan = FaultPlan(at_step=2, thread="collector")
    plan.maybe_fire("dispatcher", 5)        # wrong thread: no-op
    plan.maybe_fire("collector", 1)         # before at_step: no-op
    with pytest.raises(InjectedFault, match="chaos"):
        plan.maybe_fire("collector", 2)
    plan.maybe_fire("collector", 3)         # fired=True: never again
    with pytest.raises(ValueError):
        FaultPlan(at_step=0, thread="scheduler")


def test_engine_dead_is_typed_with_context():
    cfg = CFG
    S, T = 2, 4
    im = random_item_memory(jax.random.PRNGKey(0), cfg)
    task_w = np.asarray(jax.random.uniform(jax.random.PRNGKey(1),
                                           (S, cfg.M)))
    steps = _make_inputs(cfg, S, T)
    eng = AsyncStreamEngine(cfg, im, n_slots=S, paused=True,
                            fault_plan=FaultPlan(at_step=1,
                                                 thread="dispatcher"))
    futs = []
    for s in range(S):
        eng.admit(f"cam{s}", task_w[s])
        for q, valid, boxes, _qd in steps:
            futs.append(eng.submit(f"cam{s}", q[s], valid[s], boxes[s]))
    eng.start()
    # the message keeps the historical "worker died" phrasing AND the
    # exception is the typed EngineDead carrying crash context
    with pytest.raises(EngineDead, match="worker died") as ei:
        eng.flush(timeout=FLUSH_S)
    assert isinstance(ei.value, RuntimeError)   # backwards compatible
    assert ei.value.thread == "dispatcher"
    assert ei.value.inflight > 0
    assert isinstance(ei.value.cause, InjectedFault)
    eng.close(drain=False)
    # every pending future fails with the same typed death
    failed = [f for f in futs if f.done() and f.exception() is not None]
    assert failed, "worker death must fail in-flight futures"
    assert all(isinstance(f.exception(), EngineDead) for f in failed)


# --- supervised recovery ----------------------------------------------------

def _reference_outputs(cfg, im, task_w, steps, S):
    """Fault-free unsupervised async outputs keyed (stream, seq)."""
    outs = {}
    with AsyncStreamEngine(cfg, im, n_slots=S, paused=True) as eng:
        futs = {}
        for s in range(S):
            eng.admit(f"cam{s}", task_w[s])
            for t, (q, valid, boxes, _qd) in enumerate(steps):
                futs[(s, t)] = eng.submit(f"cam{s}", q[s], valid[s],
                                          boxes[s])
        eng.start()
        eng.flush(timeout=FLUSH_S)
        for k, f in futs.items():
            out, tel = f.result(timeout=10)
            outs[k] = out
    return outs


def _drive_supervised(cfg, im, task_w, steps, S, make_engine, store,
                      **sup_kw):
    sup = ServeSupervisor(make_engine, store, **sup_kw)
    futs = {}
    for s in range(S):
        sup.admit(f"cam{s}", task_w[s])
        for t, (q, valid, boxes, _qd) in enumerate(steps):
            futs[(s, t)] = sup.submit(f"cam{s}", q[s], valid[s], boxes[s])
    if isinstance(sup.engine, AsyncStreamEngine):
        sup.engine.start()
    sup.flush(timeout=FLUSH_S)
    outs = {k: f.result(timeout=10)[0] for k, f in futs.items()}
    return sup, outs


def _assert_outputs_equal(got, want):
    assert set(got) == set(want)
    for k in want:
        assert np.array_equal(np.asarray(got[k].scores),
                              np.asarray(want[k].scores)), k
        assert np.array_equal(np.asarray(got[k].best),
                              np.asarray(want[k].best)), k


@pytest.mark.parametrize("kind", ["dispatcher", "collector"])
@pytest.mark.parametrize("cadence", [1, 2])
def test_async_recovery_bit_identical(kind, cadence):
    cfg = CFG
    S, T = 3, 6
    im = random_item_memory(jax.random.PRNGKey(0), cfg)
    task_w = np.asarray(jax.random.uniform(jax.random.PRNGKey(1),
                                           (S, cfg.M)))
    steps = _make_inputs(cfg, S, T)
    ref = _reference_outputs(cfg, im, task_w, steps, S)

    from repro.obs import FlightRecorder, MetricsRegistry
    reg, flight = MetricsRegistry(), FlightRecorder(1024)
    store = InMemoryStateStore(metrics=reg)
    fault = FaultPlan(at_step=2, thread=kind)

    def make_engine():
        return AsyncStreamEngine(cfg, im, n_slots=S, paused=True,
                                 store=store, snapshot_every=cadence,
                                 fault_plan=fault)

    sup, outs = _drive_supervised(cfg, im, task_w, steps, S, make_engine,
                                  store, metrics=reg, flight=flight)
    _assert_outputs_equal(outs, ref)
    assert sup.summary()["restarts"] == 1
    assert sup.summary()["pending"] == 0

    # metric/flight reconciliation: the counters and the epoch events
    # describe the same recovery
    snap = reg.snapshot()

    def counter(name):
        return snap[name]["series"][0]["value"]

    evs = recovery_events(flight.records())
    assert [e["event"] for e in evs] == ["engine_crash", "engine_recovered"]
    assert evs[0]["thread"] == kind
    assert counter("torr_engine_restarts_total") == 1 == evs[1]["restarts"]
    assert counter("torr_windows_replayed_total") == evs[1]["replayed"] > 0
    assert counter("torr_state_store_writes_total") > 0
    sup.close(drain=False)


def test_sync_engine_recovery_bit_identical():
    cfg = CFG
    S, T = 3, 6
    im = random_item_memory(jax.random.PRNGKey(0), cfg)
    task_w = np.asarray(jax.random.uniform(jax.random.PRNGKey(1),
                                           (S, cfg.M)))
    steps = _make_inputs(cfg, S, T)
    ref = _reference_outputs(cfg, im, task_w, steps, S)
    store = InMemoryStateStore()
    fault = FaultPlan(at_step=3, thread="dispatcher")

    def make_engine():
        return StreamEngine(cfg, im, n_slots=S, store=store,
                            snapshot_every=1, fault_plan=fault)

    sup, outs = _drive_supervised(cfg, im, task_w, steps, S, make_engine,
                                  store)
    _assert_outputs_equal(outs, ref)
    assert sup.summary()["restarts"] == 1


def test_retire_deletes_session_state():
    cfg = CFG
    S = 2
    im = random_item_memory(jax.random.PRNGKey(0), cfg)
    task_w = np.asarray(jax.random.uniform(jax.random.PRNGKey(1),
                                           (S, cfg.M)))
    steps = _make_inputs(cfg, S, 2)
    store = InMemoryStateStore()

    def make_engine():
        return AsyncStreamEngine(cfg, im, n_slots=S, paused=True,
                                 store=store, snapshot_every=1)

    sup, _ = _drive_supervised(cfg, im, task_w, steps, S, make_engine,
                               store)
    assert sorted(store.keys()) == ["cam0", "cam1"]
    sup.retire("cam0")
    assert store.keys() == ["cam1"]
    sup.close(drain=False)


def test_crash_loop_breaker_degrades_plan():
    cfg = CFG
    S, T = 2, 5
    im = random_item_memory(jax.random.PRNGKey(0), cfg)
    task_w = np.asarray(jax.random.uniform(jax.random.PRNGKey(1),
                                           (S, cfg.M)))
    steps = _make_inputs(cfg, S, T)
    ref = _reference_outputs(cfg, im, task_w, steps, S)
    store = InMemoryStateStore()
    built = [0]

    def make_engine():
        # engines 1 and 2 die immediately; engine 3 is healthy — two
        # crashes inside the breaker window trip graceful degradation
        built[0] += 1
        fault = FaultPlan(at_step=0, thread="dispatcher") \
            if built[0] <= 2 else None
        return AsyncStreamEngine(cfg, im, n_slots=S, paused=True,
                                 store=store, snapshot_every=1,
                                 fault_plan=fault)

    sup, outs = _drive_supervised(cfg, im, task_w, steps, S, make_engine,
                                  store, breaker_restarts=2,
                                  backoff_s=0.001)
    assert sup.summary()["restarts"] == 2
    assert sup.summary()["degraded"] is True
    # the surviving engine was latched onto the cheapest ladder plan
    from repro.control.governor import build_ladder
    cheap = build_ladder(cfg)[-1]
    assert sup.engine._plan == cheap
    # degraded plans change banks/precision, not correctness of the
    # cache bookkeeping: every window resolved exactly once
    assert set(outs) == set(ref)
    sup.close(drain=False)


def test_shed_retry_hint_survives_supervised_restart():
    """ISSUE 10 satellite: the ``WindowShed.retry_after_s`` drain-model
    hint must still be attached to sheds raised *after* a supervised
    restart. The DeadlineTracker outlives the engine (the factory closes
    over it), so the replayed windows' sheds carry the same projection a
    fault-free engine would have produced — the gateway forwards it as
    the 429 Retry-After."""
    from repro.serving.deadline import (DeadlinePolicy, DeadlineTracker,
                                        WindowShed)
    cfg = CFG
    S, T = 1, 4
    im = random_item_memory(jax.random.PRNGKey(0), cfg)
    task_w = np.asarray(jax.random.uniform(jax.random.PRNGKey(1),
                                           (S, cfg.M)))
    steps = _make_inputs(cfg, S, T)
    store = InMemoryStateStore()
    # impossible budget: every admitted window sheds, with a hint from
    # the tracker's drain projection (nonzero step prior so the
    # projection is meaningful before the first completed step)
    tracker = DeadlineTracker(DeadlinePolicy(budget_s=1e-12,
                                             escalate_margin_s=1e-12,
                                             step_init_s=0.004))
    built = [0]

    def make_engine():
        # engine 1 dies at its first dispatch; engine 2 is healthy and
        # REUSES the tracker — recovery must not reset the drain model
        built[0] += 1
        fault = FaultPlan(at_step=0, thread="dispatcher") \
            if built[0] == 1 else None
        return AsyncStreamEngine(cfg, im, n_slots=S, paused=True,
                                 store=store, snapshot_every=1,
                                 tracker=tracker, fault_plan=fault)

    sup = ServeSupervisor(make_engine, store, backoff_s=0.001)
    sup.admit("cam0", task_w[0])
    futs = [sup.submit("cam0", q[0], valid[0], boxes[0])
            for q, valid, boxes, _qd in steps]
    sup.engine.start()
    sup.flush(timeout=FLUSH_S)
    assert sup.summary()["restarts"] == 1
    assert built[0] == 2
    hints = []
    for f in futs:
        exc = f.exception(timeout=10)
        # the shed (not the crash) is what the client sees: replay turned
        # the journaled windows into typed sheds, not EngineDead
        assert isinstance(exc, WindowShed), exc
        hints.append(exc.retry_after_s)
    assert all(h is not None and h > 0 for h in hints), hints
    assert tracker.shed == T
    sup.close(drain=False)


def test_max_restarts_terminal_death_fails_pending():
    cfg = CFG
    S, T = 2, 3
    im = random_item_memory(jax.random.PRNGKey(0), cfg)
    task_w = np.asarray(jax.random.uniform(jax.random.PRNGKey(1),
                                           (S, cfg.M)))
    steps = _make_inputs(cfg, S, T)
    store = InMemoryStateStore()

    def make_engine():
        return AsyncStreamEngine(cfg, im, n_slots=S, paused=True,
                                 store=store,
                                 fault_plan=FaultPlan(
                                     at_step=0, thread="dispatcher"))

    sup = ServeSupervisor(make_engine, store, max_restarts=2,
                          backoff_s=0.001)
    futs = []
    for s in range(S):
        sup.admit(f"cam{s}", task_w[s])
        for q, valid, boxes, _qd in steps:
            futs.append(sup.submit(f"cam{s}", q[s], valid[s], boxes[s]))
    sup.engine.start()
    with pytest.raises(EngineDead):
        sup.flush(timeout=FLUSH_S)
    assert sup.summary()["restarts"] == sup.max_restarts + 1
    for f in futs:
        assert isinstance(f.exception(timeout=10), EngineDead)
    sup.close(drain=False)


# --- cross-process SIGKILL resume (serve.py end-to-end) ---------------------

def _read_ledger(path):
    recs = {}
    with open(path, "r", encoding="utf-8") as f:
        for line in f:
            line = line.strip()
            if not line:
                continue
            try:
                r = json.loads(line)
            except json.JSONDecodeError:
                continue        # torn trailing write from the SIGKILL
            recs[(r["stream"], r["seq"])] = r
    return recs


def test_serve_sigkill_resume_bit_identical(tmp_path):
    """SIGKILL a supervised serve run mid-wave; the resumed process must
    cover every window exactly, bit-identical to a fault-free ledger."""
    S, T = 2, 10
    env = {**os.environ, "PYTHONPATH": SRC}
    ref = tmp_path / "ref.jsonl"
    out = tmp_path / "out.jsonl"
    store = tmp_path / "state.jsonl"
    base = [sys.executable, "-m", "repro.launch.serve",
            "--torr-streams", str(S), "--torr-frames", str(T), "--async"]

    r = subprocess.run(base + ["--outputs-jsonl", str(ref)], env=env,
                       capture_output=True, text=True, timeout=600)
    assert r.returncode == 0, r.stderr
    want = _read_ledger(ref)
    assert len(want) == S * T

    cmd = base + ["--supervise", "--state-store", str(store),
                  "--outputs-jsonl", str(out)]
    p = subprocess.Popen(cmd, env=env, stdout=subprocess.DEVNULL,
                         stderr=subprocess.DEVNULL)
    try:
        deadline = time.monotonic() + 600
        while time.monotonic() < deadline:
            if p.poll() is not None:
                break           # finished before the kill landed: still a
                #                 valid (vacuous-resume) run, asserted below
            if out.exists() and len(_read_ledger(out)) >= 3:
                p.kill()        # SIGKILL: no cleanup, no flush
                p.wait(timeout=60)
                break
            time.sleep(0.05)
        else:
            pytest.fail("serve run neither progressed nor finished")
    finally:
        if p.poll() is None:
            p.kill()
            p.wait(timeout=60)

    covered = _read_ledger(out)
    # what the resumed process will find: the killed one's snapshots. A
    # kill that lands after the run retired its streams (the run is far
    # shorter than the poll above) finds them tombstoned: nothing to skip
    st = JsonlStateStore(store)
    skip = sum(st.latest_seq(f"stream{s}") for s in range(S))
    st.close()
    r2 = subprocess.run(cmd, env=env, capture_output=True, text=True,
                        timeout=600)
    assert r2.returncode == 0, r2.stderr
    if skip:
        assert f"resumed: skipped {skip} windows" in r2.stdout
    else:
        assert "resumed" not in r2.stdout

    merged = _read_ledger(out)
    assert set(merged) == set(want), "lost windows across SIGKILL"
    for k, rec in want.items():
        assert merged[k]["best"] == rec["best"], k
        assert merged[k]["scores_sha256"] == rec["scores_sha256"], k
    # windows the first process had already shipped were not re-served
    # out from under their ledger records — coverage only ever grows
    assert set(covered) <= set(merged)
