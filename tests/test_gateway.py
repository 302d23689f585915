"""Network gateway hardening: every failure mode is a typed client outcome.

The ISSUE 10 acceptance properties, pinned as tests:

* malformed / oversized / truncated frames are 4xx responses, never a
  worker exception — and the gateway keeps serving afterwards;
* slow-loris senders hit the absolute read deadline (408) instead of
  pinning a connection thread;
* per-tenant token buckets and session quotas produce 429s whose
  ``Retry-After``/``X-Retry-After-S`` hints reflect the server's own
  drain model, and the ``torr_gateway_requests_total`` ledger reconciles
  exactly against the client's view;
* shed windows roll the sequence back (a retry of the same seq is a
  fresh, bit-safe submission); deadline-expired windows park and a retry
  of the same seq *collects* the in-flight result;
* a mid-flight client disconnect cancels the wait, marks the seq
  consumed (409 on retry) and shows up in the disconnect counters;
* an engine death behind the gateway is a recovery-aware 503 — the
  gateway itself stays up — and through a supervised engine the whole
  socket round trip survives an injected crash with outputs
  bit-identical to a fault-free run;
* SIGTERM drains gracefully: in-flight requests finish, new ones are
  refused, the process exits 0 (subprocess test).
"""
import http.client
import json
import os
import re
import signal
import socket
import subprocess
import sys
import threading
import time
from concurrent.futures import Future
from types import SimpleNamespace

import numpy as np
import pytest

import jax

from repro.core.item_memory import random_item_memory
from repro.runtime.fault import EngineDead, FaultPlan
from repro.serving import protocol
from repro.serving.async_engine import AsyncStreamEngine
from repro.serving.deadline import WindowShed
from repro.serving.gateway import Gateway, GatewayLimits, SyncDriver
from repro.serving.state_store import InMemoryStateStore
from repro.serving.stream_engine import StreamEngine
from repro.serving.supervisor import ServeSupervisor

from test_multistream import CFG

SRC = os.path.join(os.path.dirname(__file__), "..", "src")


# --- plumbing ---------------------------------------------------------------


class _FakeFront:
    """Minimal admit/submit/retire front with scriptable outcomes, so the
    protocol state machine is testable without an engine (no health/heal:
    the gateway must fall back to its defaults)."""

    def __init__(self, n_slots=4):
        self.n_slots = n_slots
        self.slots = {}
        self.futures = []
        self.mode = "ok"            # ok | pending | shed | dead
        self.shed_retry_s = 0.7
        self._n = 0

    def admit(self, sid, task_w, snapshot=None):
        if self.mode == "dead":
            raise EngineDead(RuntimeError("boom"), 0, "disp")
        if len(self.slots) >= self.n_slots:
            raise RuntimeError("no free stream slot")
        self.slots[sid] = slot = len(self.slots)
        return slot

    def retire(self, sid):
        del self.slots[sid]

    def submit(self, sid, q, valid, boxes):
        fut = Future()
        self._n += 1
        if self.mode == "ok":
            wout = SimpleNamespace(
                best=[self._n, 0], scores=np.full((4,), self._n, np.float32))
            fut.set_result((wout, {}))
        elif self.mode == "shed":
            fut.set_exception(WindowShed(sid, 0.01,
                                         retry_after_s=self.shed_retry_s))
        elif self.mode == "dead":
            fut.set_exception(EngineDead(RuntimeError("boom"), 1, "disp"))
        self.futures.append(fut)
        return fut


def _gw(front=None, **limit_kw):
    from repro.obs import MetricsRegistry
    reg = MetricsRegistry()
    limits = GatewayLimits(**limit_kw)
    task_bank = np.eye(4, CFG.M, dtype=np.float32)
    gw = Gateway(front if front is not None else _FakeFront(), CFG,
                 task_bank, limits=limits, metrics=reg, port=0)
    gw.start()
    return gw, reg


def _req(port, method, path, body=None, timeout=15.0, raw=None):
    """One-shot request; returns (status, headers_lowercase, parsed_body)."""
    conn = http.client.HTTPConnection("127.0.0.1", port, timeout=timeout)
    try:
        data = raw if raw is not None else (
            json.dumps(body).encode() if body is not None else None)
        conn.request(method, path, body=data,
                     headers={"Content-Type": "application/json"}
                     if data else {})
        r = conn.getresponse()
        rawb = r.read()
        hdr = {k.lower(): v for k, v in r.getheaders()}
        try:
            return r.status, hdr, json.loads(rawb)
        except ValueError:
            return r.status, hdr, rawb
    finally:
        conn.close()


def _open_session(port, tenant="t0", stream="s0", task=0, rt="RT-60"):
    st, _, body = _req(port, "POST", "/v1/session",
                       {"tenant": tenant, "stream": stream, "task": task,
                        "rt": rt})
    assert st == 200, body
    return body


def _frame(seed=0, deadline_ms=None, session="t0/s0", seq=0):
    rng = np.random.default_rng(seed)
    body = {
        "session": session, "seq": seq,
        "q": protocol.encode_array(rng.integers(
            0, 1 << 32, (CFG.N_max, CFG.words), dtype=np.uint32)),
        "valid": protocol.encode_array(np.ones(CFG.N_max, bool)),
        "boxes": protocol.encode_array(
            rng.random((CFG.N_max, 4)).astype(np.float32)),
    }
    if deadline_ms is not None:
        body["deadline_ms"] = deadline_ms
    return body


# --- happy path + idempotency ----------------------------------------------


def test_config_health_and_session_roundtrip():
    gw, reg = _gw()
    try:
        st, _, cfg = _req(gw.port, "GET", "/v1/config")
        assert st == 200
        assert cfg["N_max"] == CFG.N_max and cfg["words"] == CFG.words
        assert cfg["n_tasks"] == 4 and "limits" in cfg

        assert _req(gw.port, "GET", "/healthz")[0] == 200
        st, _, state = _req(gw.port, "GET", "/readyz")
        assert st == 200 and state["ready"] is True

        body = _open_session(gw.port)
        assert body["slot"] == 0 and body["next_seq"] == 0
        # idempotent re-open: same shape -> 200 with existing session
        again = _open_session(gw.port)
        assert again["slot"] == 0
        # conflicting re-open -> 409
        st, _, b = _req(gw.port, "POST", "/v1/session",
                        {"tenant": "t0", "stream": "s0", "task": 1})
        assert st == 409 and b["error"] == "session_exists"

        st, _, first = _req(gw.port, "POST", "/v1/window", _frame(seq=0))
        assert st == 200 and first["seq"] == 0
        assert re.fullmatch(r"[0-9a-f]{64}", first["scores_sha256"])
        # idempotent retry replays the byte-identical cached body
        st, _, replay = _req(gw.port, "POST", "/v1/window", _frame(seq=0))
        assert st == 200 and replay == first
        # out-of-order -> 409 with the expected seq in the detail
        st, _, b = _req(gw.port, "POST", "/v1/window", _frame(seq=5))
        assert st == 409 and b["error"] == "out_of_order"
        assert "expected seq 1" in b["detail"]

        st, _, b = _req(gw.port, "DELETE", "/v1/session/t0/s0")
        assert st == 200 and b["closed"] == "t0/s0"
        st, _, b = _req(gw.port, "POST", "/v1/window", _frame(seq=1))
        assert st == 404 and b["error"] == "no_session"
        # every window request is decoded; only a fresh result is encoded
        # (the retry replays the cached body)
        spans = {s["labels"]["span"]: s["count"] for s in
                 reg.snapshot()["torr_span_duration_seconds"]["series"]}
        assert spans == {"gateway_decode": 4, "gateway_encode": 1}
    finally:
        gw.close()


# --- malformed input battery ------------------------------------------------


def test_malformed_frames_are_400s_and_the_gateway_survives():
    gw, _ = _gw()
    try:
        _open_session(gw.port)
        good = _frame(seq=0)

        bad_json = (b"{nope", b"", b"[1,2]", b'"str"')
        for raw in bad_json:
            st, _, b = _req(gw.port, "POST", "/v1/window", raw=raw)
            assert st == 400, (raw, b)
            assert b["error"] in ("bad_request", "bad_frame")

        # schema violations: every one a 400, named field in the detail
        cases = []
        f = dict(good)
        del f["q"]
        cases.append((f, "q"))
        f = dict(good, seq=True)
        cases.append((f, "seq"))
        f = dict(good, seq=-1)
        cases.append((f, "seq"))
        f = dict(good, session="not-a-session-id")
        cases.append((f, "session"))
        f = dict(good, deadline_ms=0)
        cases.append((f, "deadline_ms"))
        f = dict(good, q=dict(good["q"], dtype="float32"))
        cases.append((f, "q"))
        f = dict(good, q=dict(good["q"], shape=[1, 1]))
        cases.append((f, "q"))
        f = dict(good, q=dict(good["q"],
                              data=good["q"]["data"][:8]))     # truncated
        cases.append((f, "q"))
        f = dict(good, q=dict(good["q"], data="!!!not base64!!!"))
        cases.append((f, "q"))
        nan_boxes = np.full((CFG.N_max, 4), np.nan, np.float32)
        f = dict(good, boxes=protocol.encode_array(nan_boxes))
        cases.append((f, "boxes"))
        for frame, field in cases:
            st, _, b = _req(gw.port, "POST", "/v1/window", frame)
            assert st == 400, (field, st, b)
            assert field in b["detail"] or b["error"] == "bad_frame", b

        # unknown route, wrong method
        assert _req(gw.port, "GET", "/v1/nope")[0] == 404
        assert _req(gw.port, "DELETE", "/v1/window", good)[0] == 405

        # raw garbage on the socket -> 400, connection closed
        s = socket.create_connection(("127.0.0.1", gw.port), timeout=5)
        s.sendall(b"GARBAGE\r\n\r\n")
        resp = s.recv(4096)
        assert b"400" in resp.split(b"\r\n", 1)[0]
        s.close()

        # after the whole battery the same gateway still serves
        st, _, b = _req(gw.port, "POST", "/v1/window", good)
        assert st == 200 and b["seq"] == 0
    finally:
        gw.close()


def test_oversized_body_is_413():
    gw, _ = _gw(max_body_bytes=1024)
    try:
        _open_session(gw.port)
        st, hdr, b = _req(gw.port, "POST", "/v1/window", _frame(seq=0))
        assert st == 413 and b["error"] == "too_large"
        assert hdr.get("connection") == "close"
        # fresh connection still served
        assert _req(gw.port, "GET", "/healthz")[0] == 200
    finally:
        gw.close()


def test_slow_loris_hits_the_read_deadline():
    gw, _ = _gw(read_timeout_s=0.3)
    try:
        t0 = time.monotonic()
        s = socket.create_connection(("127.0.0.1", gw.port), timeout=10)
        s.sendall(b"POST /v1/window HTTP/1.1\r\nContent-")   # ...stall
        resp = s.recv(4096)
        assert b"408" in resp.split(b"\r\n", 1)[0], resp
        assert time.monotonic() - t0 < 5.0
        s.close()

        # truncated body: full headers, half the promised Content-Length
        s = socket.create_connection(("127.0.0.1", gw.port), timeout=10)
        s.sendall(b"POST /v1/window HTTP/1.1\r\n"
                  b"Content-Length: 1000\r\n\r\n" + b"x" * 100)
        resp = s.recv(4096)
        assert b"408" in resp.split(b"\r\n", 1)[0], resp
        s.close()

        assert _req(gw.port, "GET", "/healthz")[0] == 200
    finally:
        gw.close()


# --- overload: rate limits, quotas, shed -----------------------------------


def test_rate_limit_429_with_retry_after_and_ledger_reconcile():
    gw, reg = _gw(rate_per_s=0.5, burst=3)
    try:
        _open_session(gw.port)          # consumes 1 token
        statuses = []
        hints = []
        for seq in (0, 1, 2, 3):
            st, hdr, b = _req(gw.port, "POST", "/v1/window", _frame(seq=seq))
            statuses.append(st)
            if st == 429:
                assert b["error"] == "rate_limit"
                assert int(hdr["retry-after"]) >= 1
                hints.append(float(hdr["x-retry-after-s"]))
                assert b["retry_after_s"] == pytest.approx(hints[-1],
                                                           abs=1e-4)
        assert statuses[:2] == [200, 200] and 429 in statuses
        # integer header rounds the precise hint up, never down
        assert all(h <= int(h + 0.999) for h in hints)

        snap = reg.snapshot()["torr_gateway_requests_total"]["series"]
        server = {(s["labels"]["route"], s["labels"]["status"]): s["value"]
                  for s in snap}
        n200 = sum(1 for s in statuses if s == 200)
        n429 = sum(1 for s in statuses if s == 429)
        assert server[("window", "200")] == n200
        assert server[("window", "429")] == n429
        assert server[("session", "200")] == 1
    finally:
        gw.close()


def test_tenant_quota_and_slot_exhaustion_are_429s():
    gw, _ = _gw(front=_FakeFront(n_slots=2), max_sessions_per_tenant=1)
    try:
        _open_session(gw.port, tenant="a", stream="s0")
        st, _, b = _req(gw.port, "POST", "/v1/session",
                        {"tenant": "a", "stream": "s1", "task": 0})
        assert st == 429 and b["error"] == "tenant_quota"
        _open_session(gw.port, tenant="b", stream="s0")
        st, hdr, b = _req(gw.port, "POST", "/v1/session",
                          {"tenant": "c", "stream": "s0", "task": 0})
        assert st == 429 and b["error"] == "no_slot"
        assert "retry-after" in hdr
    finally:
        gw.close()


def test_shed_rolls_back_seq_and_propagates_the_hint():
    front = _FakeFront()
    gw, reg = _gw(front=front)
    try:
        _open_session(gw.port)
        front.mode = "shed"
        st, hdr, b = _req(gw.port, "POST", "/v1/window", _frame(seq=0))
        assert st == 429 and b["error"] == "shed"
        # the WindowShed.retry_after_s drain-model hint reaches the wire
        assert float(hdr["x-retry-after-s"]) == pytest.approx(0.7)
        assert int(hdr["retry-after"]) == 1
        # shed never advanced engine state: the SAME seq retries fresh
        front.mode = "ok"
        st, _, b = _req(gw.port, "POST", "/v1/window", _frame(seq=0))
        assert st == 200 and b["seq"] == 0
        snap = reg.snapshot()["torr_gateway_rejects_total"]["series"]
        reasons = {s["labels"]["reason"]: s["value"] for s in snap}
        assert reasons.get("shed") == 1
    finally:
        gw.close()


# --- deadlines, parking, disconnects ---------------------------------------


def test_deadline_503_parks_and_the_same_seq_collects():
    front = _FakeFront()
    front.mode = "pending"
    gw, _ = _gw(front=front, request_deadline_s=0.2, poll_interval_s=0.02)
    try:
        _open_session(gw.port)
        t0 = time.monotonic()
        st, hdr, b = _req(gw.port, "POST", "/v1/window",
                          _frame(seq=0, deadline_ms=200))
        assert st == 503 and b["error"] == "deadline"
        assert "retry the same seq" in b["detail"]
        assert 0.15 < time.monotonic() - t0 < 5.0
        # the window is parked in flight; resolve it and collect
        wout = SimpleNamespace(best=[7, 7], scores=np.zeros(4, np.float32))
        front.futures[-1].set_result((wout, {}))
        st, _, b = _req(gw.port, "POST", "/v1/window",
                        _frame(seq=0, deadline_ms=200))
        assert st == 200 and b["seq"] == 0 and b["best"] == [7, 7]
        # and the cached-dedupe path still works after collection
        st, _, b2 = _req(gw.port, "POST", "/v1/window",
                         _frame(seq=0, deadline_ms=200))
        assert st == 200 and b2 == b
    finally:
        gw.close()


def test_mid_flight_disconnect_cancels_and_consumes_the_seq():
    front = _FakeFront()
    front.mode = "pending"
    gw, reg = _gw(front=front, request_deadline_s=30.0,
                  poll_interval_s=0.02)
    try:
        _open_session(gw.port)
        frame = json.dumps(_frame(seq=0)).encode()
        s = socket.create_connection(("127.0.0.1", gw.port), timeout=10)
        s.sendall(b"POST /v1/window HTTP/1.1\r\n"
                  b"Content-Type: application/json\r\n"
                  + f"Content-Length: {len(frame)}\r\n\r\n".encode()
                  + frame)
        # wait until the gateway is blocked on the (never-resolving)
        # future, then vanish
        for _ in range(200):
            if front.futures:
                break
            time.sleep(0.01)
        assert front.futures
        time.sleep(0.1)
        s.close()
        # liveness polling notices, cancels the wait, counts the drop
        for _ in range(300):
            if front.futures[0].cancelled():
                break
            time.sleep(0.01)
        assert front.futures[0].cancelled()
        snap = reg.snapshot()
        assert snap["torr_gateway_disconnects_total"]["series"][0][
            "value"] >= 1
        reasons = {x["labels"]["reason"]: x["value"]
                   for x in snap["torr_gateway_rejects_total"]["series"]}
        assert reasons.get("disconnect", 0) >= 1
        # the engine saw the window once: the seq stays consumed
        st, _, b = _req(gw.port, "POST", "/v1/window", _frame(seq=0))
        assert st == 409 and b["error"] == "seq_consumed"
        assert "resume at seq 1" in b["detail"]
        # the stream resumes cleanly at the next seq
        front.mode = "ok"
        st, _, b = _req(gw.port, "POST", "/v1/window", _frame(seq=1))
        assert st == 200 and b["seq"] == 1
    finally:
        gw.close()


def test_engine_dead_is_a_503_and_the_gateway_stays_up():
    front = _FakeFront()
    gw, _ = _gw(front=front)
    try:
        _open_session(gw.port)
        front.mode = "dead"
        st, _, b = _req(gw.port, "POST", "/v1/window", _frame(seq=0))
        # no heal() on this front: the death is terminal, not recovering
        assert st == 503 and b["error"] == "engine_dead"
        assert _req(gw.port, "GET", "/healthz")[0] == 200
        st, _, b = _req(gw.port, "POST", "/v1/session",
                        {"tenant": "t9", "stream": "s0", "task": 0})
        assert st == 503 and b["error"] == "engine_dead"
    finally:
        gw.close()


def test_drain_refuses_new_work_and_reports_not_ready():
    gw, reg = _gw()
    try:
        _open_session(gw.port)
        assert gw.drain(timeout=5.0) is True
        assert gw.summary()["draining"] is True
        # new connections get a typed 503 (accept thread winding down)
        # or a TCP refusal (listener gone) — never a hang or a 200
        try:
            st, _, b = _req(gw.port, "GET", "/readyz", timeout=5)
            assert st == 503 and b["error"] == "draining", (st, b)
        except OSError:
            pass
        snap = reg.snapshot()
        assert snap["torr_gateway_draining"]["series"][0]["value"] == 1
    finally:
        gw.close()


# --- real engines behind the gateway ---------------------------------------


def test_sync_driver_front_serves_windows():
    im = random_item_memory(jax.random.PRNGKey(0), CFG)
    eng = StreamEngine(CFG, im, n_slots=2)
    front = SyncDriver(eng)
    gw, _ = _gw(front=front, request_deadline_s=60.0)
    try:
        _open_session(gw.port)
        shas = []
        for seq in range(3):
            st, _, b = _req(gw.port, "POST", "/v1/window",
                            _frame(seed=seq, seq=seq), timeout=120)
            assert st == 200 and b["seq"] == seq
            shas.append(b["scores_sha256"])
        assert len(set(shas)) >= 1     # served, digests well-formed
        st, _, b = _req(gw.port, "DELETE", "/v1/session/t0/s0")
        assert st == 200
    finally:
        gw.close()
        front.close()


def _drive_through_gateway(port, n_windows, deadline_ms=None):
    """Serial client with bounded Retry-After-honouring retries; returns
    (bodies, statuses_seen)."""
    bodies, seen = [], []
    seq = 0
    for w in range(n_windows):
        frame = _frame(seed=1000 + w, seq=seq, deadline_ms=deadline_ms)
        for _attempt in range(400):
            st, hdr, b = _req(port, "POST", "/v1/window", frame, timeout=120)
            seen.append(st)
            if st == 200:
                bodies.append(b)
                seq += 1
                break
            assert st in (429, 503), (st, b)
            time.sleep(min(float(hdr.get("x-retry-after-s", 0.05)), 0.5))
        else:
            raise AssertionError(f"window {w} never served: {seen[-5:]}")
    return bodies, seen


def test_gateway_chaos_recovery_bit_identical():
    """An injected dispatcher death under the supervisor, seen from the
    socket: the client gets recovery-aware 503s, retries the same seq,
    and the final output stream is bit-identical to a fault-free run."""
    im = random_item_memory(jax.random.PRNGKey(0), CFG)
    n_windows = 8

    def _run(fault, backoff_s):
        store = InMemoryStateStore()

        def make_engine():
            return AsyncStreamEngine(CFG, im, n_slots=2, paused=True,
                                     store=store, snapshot_every=1,
                                     fault_plan=fault)

        sup = ServeSupervisor(make_engine, store, backoff_s=backoff_s)
        sup.engine.warmup()
        sup.engine.start()
        gw, _ = _gw(front=sup, request_deadline_s=0.25,
                    poll_interval_s=0.02)
        try:
            _open_session(gw.port)
            bodies, seen = _drive_through_gateway(gw.port, n_windows,
                                                  deadline_ms=250)
        finally:
            gw.drain(timeout=5.0)
            gw.close()
            sup.close(drain=False)
        return bodies, seen, sup.summary()

    ref, _seen_ref, _ = _run(fault=None, backoff_s=0.02)

    fault = FaultPlan(at_step=3, thread="dispatcher")
    got, seen, summary = _run(fault=fault, backoff_s=0.6)
    assert summary["restarts"] == 1, summary
    # the crash was client-visible as a typed retryable outcome...
    assert any(s == 503 for s in seen), seen
    # ...and zero accepted windows were lost: every seq served exactly
    # once, bit-identical to the fault-free reference
    assert [b["seq"] for b in got] == list(range(n_windows))
    assert [b["scores_sha256"] for b in got] == \
        [b["scores_sha256"] for b in ref]
    assert [b["best"] for b in got] == [b["best"] for b in ref]


@pytest.mark.slow
def test_sigterm_drains_and_exits_zero(tmp_path):
    """SIGTERM mid-traffic: the server drains in-flight work, refuses new
    requests and exits 0 (the orchestrator-facing contract)."""
    env = dict(os.environ,
               PYTHONPATH=SRC + os.pathsep + os.environ.get("PYTHONPATH", ""),
               PYTHONUNBUFFERED="1", JAX_PLATFORMS="cpu")
    proc = subprocess.Popen(
        [sys.executable, "-m", "repro.launch.serve", "--gateway-port", "0",
         "--supervise", "--torr-slots", "2"],
        env=env, stdout=subprocess.PIPE, stderr=subprocess.STDOUT, text=True)
    port = None
    try:
        t0 = time.time()
        while time.time() - t0 < 300:
            line = proc.stdout.readline()
            m = re.search(r"listening on http://127\.0\.0\.1:(\d+)", line)
            if m:
                port = int(m.group(1))
                break
        assert port, "no gateway handshake"
        _open_session(port)
        # the subprocess serves its own (bigger) config: size the frame
        # from /v1/config, not the in-process test CFG
        st, _, cfg = _req(port, "GET", "/v1/config")
        assert st == 200, cfg
        rng = np.random.default_rng(0)
        frame = {
            "session": "t0/s0", "seq": 0,
            "q": protocol.encode_array(rng.integers(
                0, 1 << 32, (cfg["N_max"], cfg["words"]), dtype=np.uint32)),
            "valid": protocol.encode_array(np.ones(cfg["N_max"], bool)),
            "boxes": protocol.encode_array(
                rng.random((cfg["N_max"], 4)).astype(np.float32)),
        }
        st, _, b = _req(port, "POST", "/v1/window", frame, timeout=120)
        assert st == 200, b
        proc.send_signal(signal.SIGTERM)
        out, _ = proc.communicate(timeout=120)
        assert proc.returncode == 0, out[-3000:]
        assert "drained=True" in out and "exit 0" in out
    finally:
        if proc.poll() is None:
            proc.kill()
