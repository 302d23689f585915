"""Observability subsystem: registry semantics, exposition, flight replay.

Covers the ISSUE 7 tentpole from the outside in: metric family semantics
(registration idempotence, label cardinality bound, histogram bucket
edges, snapshot isolation), the Prometheus text exposition (line-format
golden test + a parse check over a real serving run), the flight
recorder's bounded ring + JSONL round-trip, span nesting, and the two
engine integrations — a sync engine whose registry counters reconcile
with its own summary, and the acceptance-criteria property: a governed
async run whose flight-recorder plan timeline bit-matches the governor's
own ``plan_log``. The cancelled-future path pins the telemetry-loss
accounting (``telemetry_dropped``) the subsystem exists to close.
"""
import glob
import json
import os
import threading
import time
import urllib.request

import numpy as np
import pytest

import jax

from repro.control import Governor, GovernorPolicy
from repro.core.item_memory import random_item_memory
from repro.obs.bridge import StepObserver, telemetry_digest
from repro.obs.export import (MetricsServer, health_response,
                              prometheus_text, write_json_snapshot)
from repro.obs.flight import (FLIGHT_SCHEMA_VERSION, FlightRecorder,
                              load_jsonl, plan_timeline, replay)
from repro.obs.metrics import MetricsRegistry
from repro.obs.spans import NULL_SPAN, span
from repro.serving.async_engine import AsyncStreamEngine
from repro.serving.deadline import DeadlinePolicy, DeadlineTracker
from repro.serving.stream_engine import StreamEngine

from test_multistream import CFG, _make_inputs

FLUSH_S = 120


# --- metrics registry -------------------------------------------------------


def test_counter_gauge_basics():
    reg = MetricsRegistry()
    c = reg.counter("torr_c_total", "help")
    c.inc()
    c.inc(2.5)
    assert c.value == 3.5
    g = reg.gauge("torr_g")
    g.set(4.0)
    g.inc()
    g.dec(2.0)
    assert g.value == 3.0
    snap = reg.snapshot()
    assert snap["torr_c_total"]["type"] == "counter"
    assert snap["torr_c_total"]["series"] == [{"labels": {}, "value": 3.5}]
    assert snap["torr_g"]["series"][0]["value"] == 3.0


def test_registration_idempotent_and_schema_conflicts():
    reg = MetricsRegistry()
    a = reg.counter("torr_x_total", "h", ["k"])
    assert reg.counter("torr_x_total", "h", ["k"]) is a
    with pytest.raises(ValueError):
        reg.gauge("torr_x_total")                      # kind conflict
    with pytest.raises(ValueError):
        reg.counter("torr_x_total", "h", ["other"])    # label conflict
    h = reg.histogram("torr_h_seconds", buckets=(1.0, 2.0))
    assert reg.histogram("torr_h_seconds", buckets=(1.0, 2.0)) is h
    with pytest.raises(ValueError):
        reg.histogram("torr_h_seconds", buckets=(1.0, 3.0))
    with pytest.raises(ValueError):
        reg.counter("0bad")                            # invalid name
    with pytest.raises(ValueError):
        reg.counter("torr_y_total", "h", ["bad-label"])


def test_label_cardinality_bound():
    reg = MetricsRegistry(max_series=3)
    c = reg.counter("torr_many_total", "h", ["k"])
    for i in range(3):
        c.labels(k=str(i)).inc()
    c.labels(k="0").inc()                              # cached: no new series
    with pytest.raises(ValueError, match="max_series"):
        c.labels(k="overflow")
    with pytest.raises(ValueError, match="takes labels"):
        c.labels(wrong="x")


def test_histogram_bucket_edges():
    reg = MetricsRegistry()
    h = reg.histogram("torr_lat_seconds", "h", buckets=(1.0, 2.0))
    for v in (0.5, 1.0, 1.5, 5.0):                     # le is inclusive
        h.observe(v)
    (s,) = reg.snapshot()["torr_lat_seconds"]["series"]
    assert s["bucket_edges"] == [1.0, 2.0]
    assert s["buckets"] == [2, 1, 1]                   # per-bucket, not cum
    assert s["count"] == 4
    assert s["sum"] == pytest.approx(8.0)
    with pytest.raises(ValueError):
        reg.histogram("torr_bad", buckets=(2.0, 1.0))  # not increasing
    with pytest.raises(ValueError):
        reg.histogram("torr_bad2", buckets=(1.0, float("inf")))


def test_snapshot_isolation():
    reg = MetricsRegistry()
    c = reg.counter("torr_c_total")
    h = reg.histogram("torr_h_seconds", buckets=(1.0,))
    c.inc()
    h.observe(0.5)
    snap = reg.snapshot()
    c.inc(10)
    h.observe(0.5)
    assert snap["torr_c_total"]["series"][0]["value"] == 1.0
    assert snap["torr_h_seconds"]["series"][0]["count"] == 1
    assert reg.snapshot()["torr_c_total"]["series"][0]["value"] == 11.0


# --- Prometheus text exposition ---------------------------------------------


def test_prometheus_text_golden():
    reg = MetricsRegistry()
    c = reg.counter("torr_widgets_total", "Widgets made.", ["kind"])
    c.labels(kind="a").inc()
    c.labels(kind='we"ird\\').inc(2)
    reg.gauge("torr_temp", "Temp.").set(1.5)
    h = reg.histogram("torr_lat_seconds", "Lat.", buckets=(0.1, 1.0))
    for v in (0.05, 0.5, 2.0):
        h.observe(v)
    assert prometheus_text(reg) == (
        "# HELP torr_lat_seconds Lat.\n"
        "# TYPE torr_lat_seconds histogram\n"
        'torr_lat_seconds_bucket{le="0.1"} 1\n'
        'torr_lat_seconds_bucket{le="1"} 2\n'
        'torr_lat_seconds_bucket{le="+Inf"} 3\n'
        "torr_lat_seconds_sum 2.55\n"
        "torr_lat_seconds_count 3\n"
        "# HELP torr_temp Temp.\n"
        "# TYPE torr_temp gauge\n"
        "torr_temp 1.5\n"
        "# HELP torr_widgets_total Widgets made.\n"
        "# TYPE torr_widgets_total counter\n"
        'torr_widgets_total{kind="a"} 1\n'
        'torr_widgets_total{kind="we\\"ird\\\\"} 2\n'
    )
    # rendering an already-taken snapshot is identical to the live registry
    assert prometheus_text(reg.snapshot()) == prometheus_text(reg)


def _assert_parseable(text: str) -> set:
    """Minimal 0.0.4 line-format check; returns the family names."""
    import re
    sample = re.compile(
        r'^[a-zA-Z_:][a-zA-Z0-9_:]*(\{[a-zA-Z_][a-zA-Z0-9_]*="[^"]*"'
        r'(,[a-zA-Z_][a-zA-Z0-9_]*="(\\.|[^"\\])*")*\})? \S+$')
    families = set()
    for line in text.strip().splitlines():
        if line.startswith("# TYPE "):
            families.add(line.split()[2])
        elif not line.startswith("#"):
            assert sample.match(line), line
    return families


def test_metrics_server_scrape(tmp_path):
    reg = MetricsRegistry()
    reg.counter("torr_scrapes_total", "h").inc(7)
    srv = MetricsServer(reg, port=0)
    port = srv.start()
    try:
        with urllib.request.urlopen(
                f"http://127.0.0.1:{port}/metrics", timeout=10) as r:
            assert r.headers["Content-Type"].startswith(
                "text/plain; version=0.0.4")
            text = r.read().decode()
        assert "torr_scrapes_total 7" in text
        _assert_parseable(text)
        with urllib.request.urlopen(
                f"http://127.0.0.1:{port}/metrics.json", timeout=10) as r:
            snap = json.loads(r.read())
        assert snap["torr_scrapes_total"]["series"][0]["value"] == 7
    finally:
        srv.close()
    path = tmp_path / "metrics.json"
    write_json_snapshot(reg, str(path))
    doc = json.loads(path.read_text())
    assert doc["format"] == "torr-metrics-snapshot-v1"
    assert doc["metrics"]["torr_scrapes_total"]["series"][0]["value"] == 7


def test_health_response_shapes_and_fail_closed():
    # None / bools
    assert health_response(None) == (200, {"ready": True})
    assert health_response(True) == (200, {"ready": True})
    assert health_response(False) == (503, {"ready": False})
    # callable returning a bool or a supervisor-style health dict
    assert health_response(lambda: True)[0] == 200
    st, state = health_response(
        lambda: {"ready": False, "recovering": True, "restarts": 2})
    assert st == 503 and state["recovering"] is True
    # a raising readiness check must fail CLOSED, never 200
    def boom():
        raise RuntimeError("probe crashed")
    st, state = health_response(boom)
    assert st == 503 and state["ready"] is False
    assert "RuntimeError" in state["error"]


def test_metrics_server_healthz_and_readyz():
    reg = MetricsRegistry()
    state = {"ready": True}
    srv = MetricsServer(reg, port=0, ready=lambda: dict(state))
    port = srv.start()

    def probe(path):
        try:
            with urllib.request.urlopen(
                    f"http://127.0.0.1:{port}{path}", timeout=10) as r:
                return r.status, json.loads(r.read())
        except urllib.error.HTTPError as e:
            return e.code, json.loads(e.read())

    try:
        assert probe("/healthz") == (200, {"ok": True})
        assert probe("/readyz")[0] == 200
        # readiness flips with the source (a recovering supervisor)
        state["ready"] = False
        state["recovering"] = True
        st, body = probe("/readyz")
        assert st == 503 and body["recovering"] is True
        # liveness is unaffected by readiness
        assert probe("/healthz") == (200, {"ok": True})
        # launchers wire the supervisor in late: set_ready rebinds
        srv.set_ready(lambda: True)
        assert probe("/readyz")[0] == 200
    finally:
        srv.close()


# --- flight recorder --------------------------------------------------------


def test_flight_ring_wraparound():
    fl = FlightRecorder(capacity=4)
    for i in range(10):
        fl.record(n_windows=i)
    assert len(fl) == 4
    assert fl.dropped == 6
    recs = fl.records()
    assert [r["step"] for r in recs] == [6, 7, 8, 9]   # oldest fell off
    assert all(r["v"] == FLIGHT_SCHEMA_VERSION for r in recs)
    # the returned record is mutable: late completion lands in the ring
    rec = fl.record()
    rec["telemetry"] = {"n_windows": 1}
    assert fl.records()[-1]["telemetry"] == {"n_windows": 1}


def test_flight_drop_counter_and_first_drop_warning():
    """Wraparound is surfaced: a counter when a registry is wired, and a
    one-line RuntimeWarning on the *first* dropped record only."""
    import warnings
    reg = MetricsRegistry()
    fl = FlightRecorder(capacity=2, metrics=reg)
    with warnings.catch_warnings(record=True) as caught:
        warnings.simplefilter("always")
        for i in range(5):
            fl.record(n_windows=i)
    assert fl.dropped == 3
    snap = reg.snapshot()
    assert snap["torr_flight_dropped_total"]["series"][0]["value"] == 3
    warns = [w for w in caught if issubclass(w.category, RuntimeWarning)]
    assert len(warns) == 1                             # first drop only
    assert "capacity=2" in str(warns[0].message)
    # no registry: the Python-side counter still counts, silently
    with warnings.catch_warnings():
        warnings.simplefilter("error", RuntimeWarning)
        fl2 = FlightRecorder(capacity=1)
        fl2.record()
        with pytest.raises(RuntimeWarning):
            fl2.record()
    assert fl2.dropped == 1


def test_flight_jsonl_round_trip(tmp_path):
    fl = FlightRecorder()
    fl.record(n_windows=np.int32(3), plan={"banks": np.int64(8), "planes": 4},
              governor={"slack": np.float32(0.5), "level": 0})
    fl.record(n_windows=2, lowering={"fused": "compact", "decide": None,
                                     "bucket_tier": 64})
    path = tmp_path / "flight.jsonl"
    assert fl.dump_jsonl(str(path)) == 2
    loaded = load_jsonl(str(path))
    assert loaded == [
        {"v": 1, "n_windows": 3, "plan": {"banks": 8, "planes": 4},
         "governor": {"slack": 0.5, "level": 0}, "step": 0},
        {"v": 1, "n_windows": 2, "lowering": {"fused": "compact",
                                              "decide": None,
                                              "bucket_tier": 64}, "step": 1},
    ]
    steps = replay(loaded)
    assert [s.step for s in steps] == [0, 1]
    assert steps[0].plan == (8, 4, 0)
    assert steps[1].fused == "compact" and steps[1].bucket_tier == 64


def test_replay_skips_foreign_versions_and_sorts():
    recs = [
        {"v": FLIGHT_SCHEMA_VERSION, "step": 2,
         "plan": {"banks": 4, "planes": 2}, "governor": {"level": 3}},
        {"v": 999, "step": 0, "plan": {"banks": 1, "planes": 1}},
        {"step": 1},                                   # unversioned: skipped
        {"v": FLIGHT_SCHEMA_VERSION, "step": 1,
         "plan": {"banks": 8, "planes": 4}, "governor": {"level": 0}},
    ]
    assert plan_timeline(recs) == [(8, 4, 0), (4, 2, 3)]


# --- spans ------------------------------------------------------------------


def _profiled_events(log_dir, names):
    """``[(name, start_ns, end_ns, thread line)]`` of the host events with
    one of ``names`` in the profile written under ``log_dir``."""
    from jax.profiler import ProfileData

    (path,) = glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                        recursive=True)
    return [(e.name, e.start_ns, e.start_ns + e.duration_ns, line.name)
            for plane in ProfileData.from_file(path).planes
            for line in plane.lines for e in line.events if e.name in names]


def test_span_nesting_and_histogram(tmp_path):
    """Spans nest in the profiler's trace, and each exit observes its
    histogram; NULL_SPAN records neither."""
    reg = MetricsRegistry()

    @span("work", reg)
    def work():
        time.sleep(0.001)

    with jax.profiler.trace(str(tmp_path)):
        with span("outer", reg):
            with span("inner", reg):
                time.sleep(0.001)
            work()
        with NULL_SPAN:
            work()
    ev = _profiled_events(str(tmp_path), {"outer", "inner", "work"})
    by_name = {}
    for name, t0, t1, _line in ev:
        by_name.setdefault(name, []).append((t0, t1))
    (outer,), (inner,) = by_name["outer"], by_name["inner"]
    assert outer[0] <= inner[0] < inner[1] <= outer[1]
    works = sorted(by_name["work"])
    assert len(works) == 2
    assert outer[0] <= works[0][0] < works[0][1] <= outer[1]
    assert works[1][0] >= outer[1]          # the second call is outside
    snap = reg.snapshot()["torr_span_duration_seconds"]
    by_label = {s["labels"]["span"]: s for s in snap["series"]}
    assert by_label["outer"]["count"] == 1
    assert by_label["inner"]["count"] == 1
    assert by_label["work"]["count"] == 2
    assert by_label["outer"]["sum"] >= by_label["inner"]["sum"] >= 1e-3
    assert set(by_label) == {"outer", "inner", "work"}  # NULL_SPAN: none


def test_span_writes_its_name_into_a_cpu_profile(tmp_path):
    """Both forms annotate the profile of the thread they run on, and with
    no registry still do (the histogram is the only part they skip)."""
    done = threading.Event()

    @span("decorated_span")
    def decorated():
        time.sleep(0.001)

    def worker():
        decorated()
        with span("managed_span"):
            time.sleep(0.001)
        done.set()

    with jax.profiler.trace(str(tmp_path)):
        t = threading.Thread(target=worker, name="span-worker")
        t.start()
        t.join()
    assert done.is_set()
    ev = _profiled_events(str(tmp_path), {"decorated_span", "managed_span"})
    assert sorted(name for name, *_ in ev) == ["decorated_span",
                                               "managed_span"]
    assert all(t1 - t0 >= 1e6 for _n, t0, t1, _l in ev)   # >= the 1 ms sleep
    assert len({line for *_x, line in ev}) == 1           # one thread


# --- engine integration -----------------------------------------------------


def _submit_all(eng, task_w, steps, S):
    futs = []
    for s in range(S):
        eng.admit(f"cam{s}", task_w[s])
        for q, valid, boxes, _qd in steps:
            futs.append(eng.submit(f"cam{s}", q[s], valid[s], boxes[s]))
    return futs


def test_sync_engine_metrics_reconcile_with_summary():
    cfg = CFG
    S, T = 3, 4
    im = random_item_memory(jax.random.PRNGKey(0), cfg)
    task_w = np.asarray(jax.random.uniform(jax.random.PRNGKey(1), (S, cfg.M)))
    steps = _make_inputs(cfg, S, T)
    reg, fl = MetricsRegistry(), FlightRecorder()
    eng = StreamEngine(cfg, im, n_slots=S, metrics=reg, flight=fl)
    for s in range(S):
        eng.admit(f"cam{s}", task_w[s])
        for q, valid, boxes, _qd in steps:
            eng.submit(f"cam{s}", q[s], valid[s], boxes[s])
    eng.drain()
    summ = eng.summary()
    assert summ["telemetry_dropped"] == 0
    snap = reg.snapshot()

    def total(name):
        return sum(s["value"] for s in snap[name]["series"])

    assert total("torr_steps_total") == summ["steps"] == T
    assert total("torr_windows_total") == summ["windows"] == S * T
    assert total("torr_streams_admitted_total") == S
    # every valid proposal resolved exactly one path — exact even though
    # the submitted valid masks are not prefix-packed
    assert total("torr_path_total") == sum(
        int(np.sum(v)) for _q, v, _b, _qd in steps)
    # flight: one completed record per step, digest attached after fold
    recs = fl.records()
    assert len(recs) == T
    assert all("telemetry" in r and "lowering" in r for r in recs)
    assert sum(r["telemetry"]["n_windows"] for r in recs) == S * T


def test_governed_async_flight_matches_governor_plan_log():
    """Acceptance: the replayed flight plan timeline IS the governor log."""
    cfg = CFG
    S, T = 4, 6
    im = random_item_memory(jax.random.PRNGKey(0), cfg)
    task_w = np.asarray(jax.random.uniform(jax.random.PRNGKey(1), (S, cfg.M)))
    steps = _make_inputs(cfg, S, T)
    reg, fl = MetricsRegistry(), FlightRecorder()
    # generous budget + shedding off: every window is served, so the
    # record count is deterministic (T steps)
    tracker = DeadlineTracker(
        DeadlinePolicy(budget_s=30.0, escalate_margin_s=15.0,
                       allow_shed=False),
        metrics=reg)
    gov = Governor(cfg, GovernorPolicy(budget_s=30.0), metrics=reg)
    with AsyncStreamEngine(cfg, im, n_slots=S, tracker=tracker, governor=gov,
                           paused=True, metrics=reg, flight=fl) as eng:
        futs = _submit_all(eng, task_w, steps, S)
        eng.start()
        eng.flush(timeout=FLUSH_S)
        for f in futs:
            f.result(timeout=10)
    recs = fl.records()
    assert len(recs) == len(gov.plan_log) == T
    assert plan_timeline(recs) == gov.plan_log
    assert all("telemetry" in r and "lowering" in r for r in recs)
    for r in recs:
        assert isinstance(r["governor"]["level"], int)
        assert r["governor"]["slack"] is not None
    # digest vocabulary: recorded lowering matches what was requested
    assert all(r["lowering"]["fused"] == r["requested"]["fused"]
               or r["requested"]["fused"] is None for r in recs)
    # exposition covers the acceptance floor of 12 distinct families
    families = _assert_parseable(prometheus_text(reg))
    assert len(families) >= 12
    assert {"torr_steps_total", "torr_path_total", "torr_plan_level",
            "torr_energy_ewma_mj", "torr_deadline_decisions_total",
            "torr_window_latency_seconds", "torr_span_duration_seconds",
            "torr_telemetry_dropped_total"} <= families
    assert eng.summary()["telemetry_dropped"] == 0


def test_cancelled_future_counts_as_telemetry_dropped():
    """A window orphaned mid-flight is counted, not silently lost."""
    cfg = CFG
    S, T = 2, 3
    im = random_item_memory(jax.random.PRNGKey(0), cfg)
    task_w = np.asarray(jax.random.uniform(jax.random.PRNGKey(1), (S, cfg.M)))
    steps = _make_inputs(cfg, S, T)
    reg = MetricsRegistry()
    with AsyncStreamEngine(cfg, im, n_slots=S, paused=True,
                           metrics=reg) as eng:
        futs = _submit_all(eng, task_w, steps, S)
        assert futs[0].cancel()          # orphan one pending window
        eng.start()
        eng.flush(timeout=FLUSH_S)
        for f in futs[1:]:
            f.result(timeout=10)
    assert eng.stats.telemetry_dropped == 1
    assert eng.summary()["telemetry_dropped"] == 1
    snap = reg.snapshot()
    assert snap["torr_telemetry_dropped_total"]["series"][0]["value"] == 1


def test_step_observer_digest_without_registry():
    """flight-only / metrics-only degradation paths stay functional."""
    fl = FlightRecorder()
    obs = StepObserver(registry=None, flight=fl)
    obs.on_admit()
    rec = obs.on_dispatch(2, 0, requested=("switch", None, None))
    assert rec is not None and rec["requested"]["fused"] == "switch"
    obs2 = StepObserver(registry=MetricsRegistry(), flight=None)
    assert obs2.on_dispatch(2, 0) is None               # no flight: no rec


# --- queue wait, dispatcher wait, phase table -------------------------------


def _spy_queue_waits(eng) -> list:
    """Record each served window's ``(extra, wait)`` as the engine computes
    them for ``torr_window_queue_seconds``."""
    seen, orig = [], eng._queue_waits

    def spy(served, now):
        waits = orig(served, now)
        seen.extend((extra, w) for (_sid, _slot, extra), w
                    in zip(served, waits))
        return waits
    eng._queue_waits = spy
    return seen


def _queue_hist(reg):
    (series,) = reg.snapshot()["torr_window_queue_seconds"]["series"]
    return series


def test_async_window_queue_seconds_once_per_window_within_its_latency():
    cfg = CFG
    S, T = 3, 4
    im = random_item_memory(jax.random.PRNGKey(0), cfg)
    task_w = np.asarray(jax.random.uniform(jax.random.PRNGKey(1), (S, cfg.M)))
    steps = _make_inputs(cfg, S, T)
    reg = MetricsRegistry()
    sent, done = {}, {}
    with AsyncStreamEngine(cfg, im, n_slots=S, paused=True,
                           metrics=reg) as eng:
        seen = _spy_queue_waits(eng)
        for s in range(S):
            eng.admit(f"cam{s}", task_w[s])
        for q, valid, boxes, _qd in steps:
            for s in range(S):
                t = time.monotonic()
                fut = eng.submit(f"cam{s}", q[s], valid[s], boxes[s])
                sent[fut] = t
                fut.add_done_callback(
                    lambda f: done.setdefault(f, time.monotonic()))
        eng.start()
        eng.flush(timeout=FLUSH_S)
    hist = _queue_hist(reg)
    windows = reg.snapshot()["torr_windows_total"]["series"][0]["value"]
    assert hist["count"] == windows == S * T == len(seen)
    for (fut, _arrival, _ctx), wait in seen:
        assert 0.0 <= wait <= done[fut] - sent[fut]
    assert hist["sum"] == pytest.approx(sum(w for _e, w in seen))


def test_sync_window_queue_seconds_once_per_window():
    cfg = CFG
    S, T = 3, 4
    im = random_item_memory(jax.random.PRNGKey(0), cfg)
    task_w = np.asarray(jax.random.uniform(jax.random.PRNGKey(1), (S, cfg.M)))
    steps = _make_inputs(cfg, S, T)
    reg = MetricsRegistry()
    eng = StreamEngine(cfg, im, n_slots=S, metrics=reg)
    seen = _spy_queue_waits(eng)
    t_first = time.monotonic()
    _submit_all(eng, task_w, steps, S)
    t_submitted = time.monotonic()
    eng.drain()
    t_last = time.monotonic()
    eng.summary()
    hist = _queue_hist(reg)
    assert hist["count"] == eng.stats.windows == S * T == len(seen)
    assert all(0.0 <= w <= t_last - t_first for _e, w in seen)
    # each wait runs from the window's own submit (``extra[1]``) to the
    # dispatch of its step: all submitted before the drain, every step
    # dispatched after the last submit, and in step order
    dispatched = [extra[1] + w for extra, w in seen]
    assert all(d >= t_submitted for d in dispatched)
    assert all(b >= a - 1e-9 for a, b in zip(dispatched, dispatched[1:]))
    assert dispatched[-1] > dispatched[0]


def test_dispatch_wait_recorded_when_async_engine_idles():
    cfg = CFG
    im = random_item_memory(jax.random.PRNGKey(0), cfg)
    steps = _make_inputs(cfg, 1, 1)
    reg = MetricsRegistry()
    with AsyncStreamEngine(cfg, im, n_slots=1, metrics=reg) as eng:
        eng.warmup()
        eng.admit("cam0", np.ones((cfg.M,), np.float32))
        time.sleep(0.2)                     # the dispatcher has no backlog
        q, valid, boxes, _qd = steps[0]
        eng.submit("cam0", q[0], valid[0], boxes[0]).result(timeout=FLUSH_S)
        series = reg.snapshot()["torr_span_duration_seconds"]["series"]
        waited = {s["labels"]["span"]: s for s in series}["dispatch_wait"]
        # entered once per idle spell, not once per pass
        assert waited["count"] == 1
        assert waited["sum"] >= 0.15


def test_phase_table_published_at_warmup_under_max_series():
    from repro.obs.phases import PHASES, phase_table

    cfg = CFG
    im = random_item_memory(jax.random.PRNGKey(0), cfg)
    reg = MetricsRegistry(max_series=len(PHASES))
    eng = StreamEngine(cfg, im, n_slots=2, metrics=reg)
    eng.warmup()
    series = reg.snapshot()["torr_step_phase_ops"]["series"]
    # every phase but ``encode``, which only a feature step runs
    assert {s["labels"]["phase"] for s in series} == set(PHASES) - {"encode"}
    # the table of the executable the warm-up ran, one series a phase
    S = eng.n_slots
    args, kw = eng._step_call(eng._batch(
        np.zeros((S, cfg.N_max, cfg.words), np.uint32),
        np.zeros((S, cfg.N_max), bool),
        np.zeros((S, cfg.N_max, 4), np.float32),
        np.zeros((S,), np.int32)), eng._resolve_fused())
    table = phase_table(eng._step.lower(*args, **kw).compile().as_text())
    assert {op: s["labels"]["phase"] for s in series
            for op in s["labels"]["ops"].split()} == table
    assert all(s["value"] == len(s["labels"]["ops"].split())
               for s in series)
    # without a registry nothing is built and nothing fails
    StreamEngine(cfg, im, n_slots=2, flight=FlightRecorder()).warmup()
