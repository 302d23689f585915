"""Serving launcher: batched prefill + decode with optional TorR reranker,
plus the multi-stream TorR window engine.

Examples:
    PYTHONPATH=src python -m repro.launch.serve --arch musicgen-large \
        --smoke --batch 4 --prompt-len 32 --gen 32 --rerank
    PYTHONPATH=src python -m repro.launch.serve --torr-streams 8 \
        --torr-frames 30
    # async dispatch/collect runtime, sharded over all devices, RT-60
    # deadline admission control:
    XLA_FLAGS=--xla_force_host_platform_device_count=4 PYTHONPATH=src \
        python -m repro.launch.serve --torr-streams 8 --torr-frames 30 \
        --async --mesh 4 --rt RT-60
    # closed-loop QoS control plane (slack-driven bank/precision gating
    # with the energy governor) on top of RT-60 admission control:
    TORR_GOV_ENERGY_MJ=60 PYTHONPATH=src python -m repro.launch.serve \
        --torr-streams 8 --torr-frames 30 --rt RT-60 --governor
    # the paper's edge deployment (D=8192, M=1024, K=8, N_max=128) behind
    # the network gateway; the default --deployment is the CPU-sized toy:
    PYTHONPATH=src python -m repro.launch.serve --deployment torr_edge \
        --gateway-port 0 --async

QoS control plane (``--governor``)
==================================

``--governor`` arms the closed loop of ``repro.control``: per dispatched
step, the RT-deadline tracker's projected slack, the deepest per-slot
backlog and an EWMA of modeled window energy (``perf.cycle_model`` priced
on each window's own telemetry) drive a slack ladder of knob plans — D'
bank caps, bit-slice precision (dropping low-order planes of the packed
scan) and tau_q/tau_byp offsets — and the chosen plan is latched for the
step exactly like the ASIC's window-latched registers. Requires (and with
a bare ``--governor`` defaults to) an ``--rt`` operating point.

Governor knobs, their hysteresis defaults, and env overrides (read once by
``repro.control.governor.policy_from_env``):

    knob              | env var               | default | meaning
    ----------------- | --------------------- | ------- | -------------------
    slack margin      | ``TORR_GOV_MARGIN``   |    0.25 | fraction of the RT
                      |                       |         | budget held back as
                      |                       |         | safety slack
    recovery hold     | ``TORR_GOV_HOLD``     |       4 | consecutive
                      |                       |         | comfortable windows
                      |                       |         | before widening D'
                      |                       |         | back out (one ladder
                      |                       |         | level at a time)
    energy budget     | ``TORR_GOV_ENERGY_MJ``|     off | mJ/window target the
                      |                       |         | energy governor caps
                      |                       |         | the ladder level to
                      |                       |         | (0 disables)
    energy EWMA alpha | ``TORR_GOV_ALPHA``    |     0.2 | weight of the newest
                      |                       |         | window's modeled mJ

Degrading is immediate (a missed deadline beats a narrow window);
recovering takes ``TORR_GOV_HOLD`` comfortable windows per level so the
plan latch doesn't thrash the specialized executables. Every window's
telemetry records the (banks, planes) it actually ran with.

Reuse-aware kernel dispatch (``--torr-fused``)
==============================================

``--torr-fused`` pins the full path's kernel dispatch. Besides the PR-4
lowerings (``switch``/``prefix``/``off``), ``compact`` selects the
compact-then-compute dispatch — a metadata-only decide pass produces the
path vector, and the fused XNOR-popcount scan runs only over the
full-path proposals, compacted to a static power-of-two bucket tier
(``core.policy.bucket_ladder``; any tier is bit-exact, overflow falls
back to the hoisted scan) — and ``auto`` lets the engine pick compact vs
hoisted (and the bucket tier) per step from the telemetry path-mix EWMA,
so reuse-heavy traffic stops paying the full scan over lanes that resolve
via bypass/delta:

    PYTHONPATH=src python -m repro.launch.serve --torr-streams 8 \\
        --torr-frames 30 --torr-fused auto

Observability (``--metrics-port/-json`` / ``--flight-jsonl`` / ``--trace-json``)
================================================================================

Any of the four flags arms the ``repro.obs`` observability tier on the
stream engine, the deadline tracker and the governor:

* ``--metrics-port N`` serves Prometheus text on
  ``http://127.0.0.1:N/metrics`` (0 = ephemeral port, printed at startup)
  for the duration of the run — windows/path-mix/deadline/plan/span/SLO
  metric families, catalog in ``docs/observability.md``;
* ``--metrics-json PATH`` dumps the final registry snapshot as JSON (the
  CI bench-smoke artifact shape);
* ``--flight-jsonl PATH`` spills the flight recorder — one structured
  record per dispatched step (resolved lowering, latched plan, governor
  slack/energy, telemetry digest, per-window trace contexts) — replayable
  offline with ``repro.obs.flight.replay`` into the exact governor plan
  timeline;
* ``--trace-json PATH`` additionally arms per-window causal tracing
  (``repro.obs.trace``) and writes a Chrome trace-event JSON —
  ``chrome://tracing`` / https://ui.perfetto.dev load it directly, with
  per-window flow arrows across the async dispatcher→collector hand-off
  and counter tracks for plan level / energy EWMA / queue depth
  (trace-context model + Perfetto how-to in ``docs/observability.md``).

With an ``--rt`` operating point armed alongside observability, window
completions additionally feed the RT-SLO burn-rate engine
(``repro.obs.slo``): fast/slow rolling-window burn rates over the
deadline-miss budget, exported as ``torr_slo_*`` gauges and flight
events — semantics and the threshold table in ``docs/observability.md``.

Shutdown: SIGINT/SIGTERM unwind the serving loop cleanly — in-flight
windows are cancelled and every armed artifact (metrics JSON, flight
JSONL, Chrome trace) is still flushed before the process exits.
"""
from __future__ import annotations

import argparse
import dataclasses
import signal
import threading
import time
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

from ..configs import DEPLOYMENTS, deployment, get, get_smoke
from ..core.types import TorrConfig
from ..models import transformer as tf
from ..serving import reranker as rr


def _install_signal_handlers():
    """Route SIGINT/SIGTERM into KeyboardInterrupt so the serving loop
    unwinds through its cleanup path and flushes observability artifacts
    (a docker stop / CI cancel must not lose the flight log). Returns the
    previous handlers for restoration, or None off the main thread
    (signal.signal is main-thread-only)."""
    if threading.current_thread() is not threading.main_thread():
        return None

    def _raise(signum, _frame):
        raise KeyboardInterrupt(f"signal {signum}")

    previous = {}
    for sig in (signal.SIGINT, signal.SIGTERM):
        try:
            previous[sig] = signal.signal(sig, _raise)
        except (ValueError, OSError):  # exotic embeddings may refuse
            pass
    return previous


def _restore_signal_handlers(previous) -> None:
    if previous:
        for sig, handler in previous.items():
            try:
                signal.signal(sig, handler)
            except (ValueError, OSError):
                pass


def run_torr_streams(n_streams: int, n_frames: int, n_slots: int = 0,
                     serial: bool = False, use_async: bool = False,
                     mesh_devices: int = 0, rt: str = "",
                     governor: bool = False, fused: str | None = None,
                     metrics_port: int | None = None, metrics_json: str = "",
                     flight_jsonl: str = "", flight_capacity: int = 4096,
                     trace_json: str = "", supervise: bool = False,
                     state_store: str = "", snapshot_every: int = 1,
                     fault_at: int | None = None,
                     fault_kind: str = "dispatcher",
                     outputs_jsonl: str = "", deployment_name: str = "toy"):
    """Serve S synthetic TOOD streams through the batched window engine.

    ``deployment_name`` selects the served :class:`TorrConfig` from
    ``configs.DEPLOYMENTS`` ("toy" by default; "torr_edge" is the paper's
    edge deployment).

    ``use_async`` routes through the dispatch/collect
    :class:`repro.serving.async_engine.AsyncStreamEngine`; ``mesh_devices``
    additionally shards the stream slots over that many devices (0 = all).
    ``rt`` ("RT-30"/"RT-60") arms the deadline admission controller;
    ``governor`` closes the QoS loop (slack-driven bank/precision gating
    plus the energy governor — see the module docstring). ``fused`` picks
    the full path's kernel dispatch (None = the lowering-appropriate fused
    default, "off" = the jnp-oracle step; see ``repro.core.pipeline``).

    Any of ``metrics_port`` (HTTP exposition; 0 = ephemeral), their JSON
    dump (``metrics_json``), the flight-recorder spill (``flight_jsonl``)
    or the Chrome-trace export (``trace_json``, which also arms per-window
    causal tracing) arms the ``repro.obs`` tier across the
    engine/tracker/governor; an armed ``rt`` additionally feeds the RT-SLO
    burn-rate monitor. Returns None when observability is off; otherwise a
    dict with the final ``registry``/``flight``/``tracer``/``slo`` objects,
    the scraped ``metrics_text`` (when a server ran) and the engine
    ``summary`` — what ``tests/test_obs.py`` asserts the acceptance
    criteria against.

    Fault tolerance: ``supervise`` (implied by ``fault_at``) wraps the
    engine in a :class:`repro.serving.supervisor.ServeSupervisor` (which
    implies the async runtime); ``state_store`` points it at a JSONL
    session store (empty = in-memory), snapshotting every
    ``snapshot_every`` served windows. ``fault_at``/``fault_kind`` inject
    one deterministic worker death (the chaos harness); recovery replays
    the lost windows and the run still must account for every admitted
    window — any lost window raises SystemExit(3). ``outputs_jsonl``
    streams one fsync'd record per resolved window (stream, seq, best
    classes, scores digest) — the bit-match ledger the SIGKILL recovery
    test compares across runs; a killed process resumes from the store,
    skipping each stream's already-covered windows.
    """
    from ..core import hdc
    from ..data import tood_synth as ts
    from ..serving import tood_pipelines as tp
    from ..serving.stream_engine import StreamEngine

    # deadline admission, sharding, the governor and supervision live on
    # the async runtime; honor them for programmatic callers too, not just
    # main()'s CLI plumbing
    supervise = supervise or fault_at is not None
    use_async = (use_async or bool(rt) or governor or mesh_devices != 0
                 or supervise)

    cfg = deployment(deployment_name)
    world = ts.make_world(seed=0, M=cfg.M, d=cfg.feat_dim)
    sys_ = tp.build_system(world, cfg, seed=0)
    n_slots = n_slots or n_streams
    registry = flight = server = tracer = slo = None
    if metrics_port is not None or metrics_json or flight_jsonl or trace_json:
        from ..obs import FlightRecorder, MetricsRegistry, MetricsServer
        registry = MetricsRegistry()
        flight = FlightRecorder(flight_capacity, metrics=registry)
        if trace_json:
            from ..obs import Tracer
            tracer = Tracer(metrics=registry)
        if metrics_port is not None:
            server = MetricsServer(registry, port=metrics_port)
            print(f"[serve/torr] metrics endpoint "
                  f"http://127.0.0.1:{server.start()}/metrics")
    # fault-tolerance plumbing: session store, chaos plan, supervisor.
    # The FaultPlan instance is shared across engine rebuilds — it fires
    # exactly once, so the supervisor's replacement engine runs clean.
    store = None
    fault = None
    sup = None
    if supervise or state_store:
        from ..serving.state_store import InMemoryStateStore, JsonlStateStore
        store = (JsonlStateStore(state_store, metrics=registry)
                 if state_store else InMemoryStateStore(metrics=registry))
    if fault_at is not None:
        from ..runtime.fault import FaultPlan
        fault = FaultPlan(at_step=fault_at, thread=fault_kind,
                          kind=fault_kind)
    if use_async:
        from ..runtime import sharding as shd
        from ..serving.async_engine import AsyncStreamEngine
        from ..serving.deadline import DeadlineTracker, policy_for
        # sharding is opt-in via --mesh; bare --async stays single-device
        # (e.g. --torr-serial is valid async but cannot shard)
        mesh = None if mesh_devices == 0 else shd.stream_mesh(
            None if mesh_devices < 0 else mesh_devices)
        if governor and not rt:
            rt = "RT-60"    # the governor is slack-driven: needs a deadline
        tracker = None
        if rt:
            if registry is not None:
                from ..obs import SLOMonitor
                slo = SLOMonitor(metrics=registry, flight=flight)
            tracker = DeadlineTracker(policy_for(rt), metrics=registry,
                                      slo=slo)
        gov = None
        if governor:
            from ..control import Governor, policy_from_env
            gov = Governor(cfg, policy_from_env(rt), metrics=registry)

        def make_engine():
            # tracker/governor survive rebuilds deliberately: their EMAs
            # are measurements of the workload, not of one engine instance
            return AsyncStreamEngine(
                cfg, sys_.im, n_slots=n_slots, serial=serial, fused=fused,
                mesh=mesh, tracker=tracker, governor=gov, paused=True,
                metrics=registry, flight=flight, tracer=tracer,
                store=store, snapshot_every=snapshot_every,
                fault_plan=fault)

        if supervise:
            from ..serving.supervisor import ServeSupervisor
            sup = ServeSupervisor(make_engine, store, metrics=registry,
                                  flight=flight)
            eng = sup.engine
            if server is not None:
                server.set_ready(sup.health)    # /readyz mirrors recovery
        else:
            eng = make_engine()
    else:
        eng = StreamEngine(cfg, sys_.im, n_slots=n_slots, serial=serial,
                           fused=fused, metrics=registry, flight=flight,
                           tracer=tracer, store=store,
                           snapshot_every=snapshot_every, fault_plan=fault)
    front = sup if sup is not None else eng

    R = jnp.asarray(sys_.R)
    n_tasks = world.relevance.shape[0]
    paths, valids = [], []
    eng.warmup()  # compile the batched step outside the timed drains
    if use_async:
        eng.start()
    t_total = 0.0
    shed = 0
    submitted = accounted = resumed_skip = 0
    out_f = open(outputs_jsonl, "a", encoding="utf-8") \
        if outputs_jsonl else None
    out_lock = threading.Lock()

    def _ledger_cb(sid, seq):
        # async ledger writes ride the window's future resolution (the
        # collector thread) — strictly BEFORE that step's state-store
        # snapshot put, so a snapshot covering a window implies its
        # ledger record is on disk (the resume path's no-gap invariant)
        def cb(fut):
            if fut.cancelled() or fut.exception() is not None:
                return
            wout, _tel = fut.result()
            with out_lock:
                _write_output(out_f, sid, seq, wout)
        return cb

    interrupted = False
    engine_dead = None
    prev_handlers = None
    try:
        # handlers armed and the armed-line printed *inside* the try: an
        # operator (or the shutdown test) reacting to this line with an
        # immediate signal must land in the graceful-flush handler even
        # if it arrives before print() has returned
        prev_handlers = _install_signal_handlers()
        print("[serve/torr] serving (SIGINT/SIGTERM flushes artifacts)",
              flush=True)
        # admit streams in waves of n_slots: slots < streams just queues work
        for wave_start in range(0, n_streams, n_slots):
            wave = range(wave_start, min(wave_start + n_slots, n_streams))
            # synthesize + encode the wave's windows outside the timed
            # region: the async engine must not get a head start on
            # untimed work
            # (stream_id, q, valid, boxes, seq), submission order
            windows = []
            for s in wave:
                task = s % n_tasks
                front.admit(f"stream{s}", sys_.task_w[task])
                frames = ts.simulate_sequence(world, task, n_frames, seed=s,
                                              n_max=cfg.N_max)
                # cross-process resume: the store already covers the first
                # latest_seq windows of this (deterministic) stream — a
                # previous process served them before dying
                skip = 0
                if sup is not None:
                    skip = min(store.latest_seq(f"stream{s}"), len(frames))
                    resumed_skip += skip
                for seq, f in enumerate(frames[skip:], start=skip):
                    q = hdc.pack_bits(
                        hdc.sign_project(jnp.asarray(f.feats), R))
                    windows.append(
                        (f"stream{s}", np.asarray(q), f.valid, f.boxes,
                         seq))
            futures = []   # (future, valid-mask, sid, seq), submission order
            t0 = time.time()
            for sid, q, fvalid, fboxes, seq in windows:
                fut = front.submit(sid, q, fvalid, fboxes)
                submitted += 1
                if use_async:
                    if out_f is not None:
                        fut.add_done_callback(_ledger_cb(sid, seq))
                    futures.append((fut, fvalid, sid, seq))
                else:
                    valids.append(fvalid)
            if use_async:
                from ..serving.deadline import WindowShed
                front.flush()
                t_total += time.time() - t0
                for fut, vmask, sid, seq in futures:
                    try:
                        wout, tel = fut.result()
                    except WindowShed:
                        shed += 1
                        accounted += 1
                        continue
                    except Exception:   # noqa: BLE001 — lost window,
                        continue        # tallied by the zero-loss gate
                    accounted += 1
                    paths.append(np.asarray(tel.path))
                    valids.append(vmask)
            else:
                results = eng.drain()
                eng.sync()
                t_total += time.time() - t0
                for s in wave:
                    for seq, (wout, tel) in enumerate(
                            results[f"stream{s}"]):
                        accounted += 1
                        paths.append(np.asarray(tel.path))
                        if out_f is not None:
                            _write_output(out_f, f"stream{s}", seq, wout)
            for s in wave:
                front.retire(f"stream{s}")
    except KeyboardInterrupt:
        # SIGINT/SIGTERM (or a ^C): stop serving but keep going — the
        # whole point of the handler is that the artifact flush below
        # still runs on an interrupted run
        interrupted = True
        print("[serve/torr] interrupted — cancelling in-flight windows "
              "and flushing observability artifacts")
    except Exception as e:  # noqa: BLE001 — terminal engine death
        from ..runtime.fault import EngineDead
        if not isinstance(e, EngineDead):
            raise
        engine_dead = e
        print(f"[serve/torr] engine terminally dead: {e}")
    finally:
        if prev_handlers is not None:
            _restore_signal_handlers(prev_handlers)

    if use_async:
        if sup is not None:
            from ..runtime.fault import EngineDead
            try:
                sup.close(drain=not interrupted and engine_dead is None)
            except EngineDead:
                pass    # already accounted as lost windows
            eng = sup.engine    # a recovery may have swapped the instance
        else:
            eng.close(drain=not interrupted)
    mode = "async" if use_async else "sync"
    print(f"[serve/torr] streams={n_streams} slots={eng.n_slots} "
          f"frames/stream={n_frames} mode={mode}")
    if paths:
        # count only real proposal lanes: padding lanes report as bypass
        pvals = np.concatenate(paths)[np.concatenate(valids)]
        print(f"[serve/torr] {eng.stats.windows} windows in "
              f"{t_total*1e3:.1f} ms ({eng.stats.windows/t_total:.1f} "
              f"windows/s, occupancy {eng.stats.occupancy:.2f})")
    else:
        print("[serve/torr] no windows served")
    if shed:
        print(f"[serve/torr] shed {shed} windows past deadline")
    if paths:
        print(f"[serve/torr] path mix: bypass={np.mean(pvals == 0):.2f} "
              f"delta={np.mean(pvals == 1):.2f} full={np.mean(pvals == 2):.2f}")
    if use_async:
        summary = eng.deadline_summary()
        if summary is not None:
            print(f"[serve/torr] deadline: p99={summary['p99_ms']:.2f} ms "
                  f"jitter={summary['jitter_ms']:.2f} ms "
                  f"miss_rate={summary['miss_rate']:.3f} "
                  f"shed={summary['shed']} escalated={summary['escalated']}")
        gsum = eng.governor_summary()
        if gsum is not None:
            print(f"[serve/torr] governor: level={gsum['level']}"
                  f"/{gsum['n_levels'] - 1} "
                  f"plan=(banks={gsum['plan_banks']}, "
                  f"planes={gsum['plan_planes']}) "
                  f"switches={gsum['plan_switches']} "
                  f"energy_ewma={gsum['energy_ewma_mj']:.1f} mJ "
                  f"windows_by_level={gsum['windows_by_level']}")
        if slo is not None:
            ssum = slo.summary()
            print(f"[serve/torr] slo: alert={ssum['alert']} "
                  f"burn(fast={ssum['burn_fast']:.2f}, "
                  f"slow={ssum['burn_slow']:.2f}) "
                  f"missed={ssum['missed']}/{ssum['completed']} "
                  f"(objective {ssum['objective']:.2f})")

    sup_summary = None
    lost = 0
    if sup is not None:
        sup_summary = sup.summary()
        print(f"[serve/torr] supervisor: restarts={sup_summary['restarts']} "
              f"replayed={sup_summary['windows_replayed']} "
              f"rerun={sup_summary['windows_rerun']} "
              f"degraded={sup_summary['degraded']}")
        if resumed_skip:
            print(f"[serve/torr] resumed: skipped {resumed_skip} windows "
                  "already covered by the state store")
        if not interrupted:
            lost = submitted - accounted
            if lost:
                print(f"[serve/torr] LOST {lost} of {submitted} admitted "
                      "windows — recovery failed to replay them")
    if out_f is not None:
        out_f.close()

    if registry is None:
        if store is not None and hasattr(store, "close"):
            store.close()
        if lost:
            raise SystemExit(3)
        return None
    # fold any telemetry still deferred by the sync engine's double
    # buffering before the registry is read (no-op on the async runtime,
    # whose collector owns the fold)
    eng.flush_telemetry()
    metrics_text = None
    if server is not None:
        import urllib.request
        with urllib.request.urlopen(
                f"http://127.0.0.1:{server.port}/metrics") as resp:
            metrics_text = resp.read().decode()
        n_fam = metrics_text.count("# TYPE ")
        print(f"[serve/torr] metrics: {n_fam} families exposed at /metrics")
        server.close()
    if metrics_json:
        from ..obs import write_json_snapshot
        write_json_snapshot(registry, metrics_json)
        print(f"[serve/torr] metrics snapshot -> {metrics_json}")
    if flight_jsonl:
        n_rec = flight.dump_jsonl(flight_jsonl)
        print(f"[serve/torr] flight recorder: {n_rec} step records -> "
              f"{flight_jsonl}")
    if trace_json:
        from ..obs import write_chrome_trace
        n_ev = write_chrome_trace(flight.records(), trace_json)
        print(f"[serve/torr] chrome trace: {n_ev} events "
              f"({tracer.minted} windows traced) -> {trace_json}")
    result = {"registry": registry, "flight": flight, "tracer": tracer,
              "slo": slo, "metrics_text": metrics_text,
              "summary": eng.summary(), "interrupted": interrupted,
              "supervisor": sup_summary, "lost": lost,
              "submitted": submitted}
    if store is not None and hasattr(store, "close"):
        store.close()
    if lost:
        raise SystemExit(3)
    return result


@dataclasses.dataclass
class GatewayStack:
    """The engine stack behind the network gateway: the served config and
    synthetic TOOD system, the observability tier, state store, the engine
    (sync behind a :class:`SyncDriver`, async, or supervised) and the
    :class:`repro.serving.gateway.Gateway` itself — built and warmed by
    :func:`build_torr_gateway`, not yet listening. ``None`` marks a part
    the flags did not arm."""

    cfg: TorrConfig
    world: Any                 # data.tood_synth world
    sys_: Any                  # serving.tood_pipelines system
    gw: Any                    # serving.gateway.Gateway
    limits: Any                # serving.gateway.GatewayLimits
    eng: Any                   # StreamEngine / AsyncStreamEngine
    sup: Any                   # ServeSupervisor | None
    driver: Any                # SyncDriver | None
    registry: Any              # obs.MetricsRegistry | None
    flight: Any                # obs.FlightRecorder | None
    server: Any                # obs.MetricsServer | None
    store: Any                 # state store | None
    metrics_json: str
    flight_jsonl: str
    trace_json: str

    def close(self, interrupted: bool = False) -> dict:
        """Drain in-flight requests, close the engine, write every armed
        artifact; returns the run summary."""
        from ..runtime.fault import EngineDead

        gw, limits = self.gw, self.limits
        drained = gw.drain(timeout=max(10.0, 2 * limits.request_deadline_s))
        gw.close()
        summary = gw.summary()
        print(f"[serve/gateway] drained={drained} "
              f"sessions={summary['sessions']}")
        eng, sup = self.eng, self.sup
        if sup is not None:
            try:
                sup.close(drain=False)
            except EngineDead:
                pass
            eng = sup.engine
            s = sup.summary()
            print(f"[serve/gateway] supervisor: restarts={s['restarts']} "
                  f"replayed={s['windows_replayed']} "
                  f"rerun={s['windows_rerun']} degraded={s['degraded']}")
        elif self.driver is not None:
            self.driver.close()
        else:
            try:
                eng.close(drain=False)
            except EngineDead:
                pass

        registry, flight = self.registry, self.flight
        if registry is not None:
            eng.flush_telemetry()
            if self.server is not None:
                self.server.close()
            if self.metrics_json:
                from ..obs import write_json_snapshot
                write_json_snapshot(registry, self.metrics_json)
                print(f"[serve/gateway] metrics snapshot -> "
                      f"{self.metrics_json}")
            if self.flight_jsonl:
                n_rec = flight.dump_jsonl(self.flight_jsonl)
                print(f"[serve/gateway] flight recorder: {n_rec} records -> "
                      f"{self.flight_jsonl}")
            if self.trace_json:
                from ..obs import write_chrome_trace
                n_ev = write_chrome_trace(flight.records(), self.trace_json)
                print(f"[serve/gateway] chrome trace: {n_ev} events -> "
                      f"{self.trace_json}")
        if self.store is not None and hasattr(self.store, "close"):
            self.store.close()
        print(f"[serve/gateway] exit 0 (interrupted={interrupted})",
              flush=True)
        return {"registry": registry, "flight": flight, "drained": drained,
                "summary": summary,
                "supervisor": sup.summary() if sup is not None else None}


def build_torr_gateway(n_slots: int = 8, serial: bool = False, rt: str = "",
                       governor: bool = False, fused: str | None = None,
                       metrics_port: int | None = None,
                       metrics_json: str = "", flight_jsonl: str = "",
                       flight_capacity: int = 4096, trace_json: str = "",
                       supervise: bool = False, state_store: str = "",
                       snapshot_every: int = 1, fault_at: int | None = None,
                       fault_kind: str = "dispatcher",
                       gateway_port: int = 0,
                       gateway_host: str = "127.0.0.1",
                       gateway_rate: float = 200.0, gateway_burst: int = 100,
                       gateway_deadline_ms: float = 2000.0,
                       gateway_max_conns: int = 64,
                       gateway_tenant_sessions: int = 8,
                       use_async: bool = True,
                       deployment_name: str = "toy") -> GatewayStack:
    """Build and warm the engine stack behind the network gateway. The
    arguments are ``main``'s engine and ``--gateway-*`` flags; the caller
    starts ``stack.gw`` and ends with ``stack.close()``."""
    from ..data import tood_synth as ts
    from ..serving import tood_pipelines as tp
    from ..serving.gateway import Gateway, GatewayLimits, SyncDriver

    supervise = supervise or fault_at is not None
    use_async = use_async or bool(rt) or governor or supervise

    cfg = deployment(deployment_name)
    world = ts.make_world(seed=0, M=cfg.M, d=cfg.feat_dim)
    sys_ = tp.build_system(world, cfg, seed=0)

    registry = flight = server = tracer = slo = None
    if metrics_port is not None or metrics_json or flight_jsonl or trace_json:
        from ..obs import FlightRecorder, MetricsRegistry, MetricsServer
        registry = MetricsRegistry()
        flight = FlightRecorder(flight_capacity, metrics=registry)
        if trace_json:
            from ..obs import Tracer
            tracer = Tracer(metrics=registry)
        if metrics_port is not None:
            server = MetricsServer(registry, port=metrics_port)
            print(f"[serve/gateway] metrics endpoint "
                  f"http://127.0.0.1:{server.start()}/metrics")

    store = fault = sup = None
    if supervise or state_store:
        from ..serving.state_store import InMemoryStateStore, JsonlStateStore
        store = (JsonlStateStore(state_store, metrics=registry)
                 if state_store else InMemoryStateStore(metrics=registry))
    if fault_at is not None:
        from ..runtime.fault import FaultPlan
        fault = FaultPlan(at_step=fault_at, thread=fault_kind,
                          kind=fault_kind)

    driver = None
    if use_async:
        from ..serving.async_engine import AsyncStreamEngine
        from ..serving.deadline import DeadlineTracker, policy_for
        if governor and not rt:
            rt = "RT-60"
        tracker = None
        if rt:
            if registry is not None:
                from ..obs import SLOMonitor
                slo = SLOMonitor(metrics=registry, flight=flight)
            tracker = DeadlineTracker(policy_for(rt), metrics=registry,
                                      slo=slo)
        gov = None
        if governor:
            from ..control import Governor, policy_from_env
            gov = Governor(cfg, policy_from_env(rt), metrics=registry)

        def make_engine():
            return AsyncStreamEngine(
                cfg, sys_.im, n_slots=n_slots, serial=serial, fused=fused,
                tracker=tracker, governor=gov, paused=True,
                metrics=registry, flight=flight, tracer=tracer,
                store=store, snapshot_every=snapshot_every,
                fault_plan=fault)

        if supervise:
            from ..serving.supervisor import ServeSupervisor
            sup = ServeSupervisor(make_engine, store, metrics=registry,
                                  flight=flight)
            eng = sup.engine
            if server is not None:
                server.set_ready(sup.health)
        else:
            eng = make_engine()
        front = sup if sup is not None else eng
    else:
        from ..serving.stream_engine import StreamEngine
        eng = StreamEngine(cfg, sys_.im, n_slots=n_slots, serial=serial,
                           fused=fused, metrics=registry, flight=flight,
                           tracer=tracer, store=store,
                           snapshot_every=snapshot_every, fault_plan=fault)
        driver = SyncDriver(eng, metrics=registry)
        front = driver

    eng.warmup()
    if use_async:
        eng.start()

    limits = GatewayLimits(
        rate_per_s=gateway_rate, burst=gateway_burst,
        request_deadline_s=gateway_deadline_ms / 1e3,
        max_connections=gateway_max_conns,
        max_sessions_per_tenant=gateway_tenant_sessions)
    gw = Gateway(front, cfg, sys_.task_w, limits=limits,
                 host=gateway_host, port=gateway_port,
                 metrics=registry, flight=flight)
    if server is not None and sup is None:
        server.set_ready(gw._front_health)
    return GatewayStack(cfg=cfg, world=world, sys_=sys_, gw=gw,
                        limits=limits, eng=eng,
                        sup=sup, driver=driver, registry=registry,
                        flight=flight, server=server, store=store,
                        metrics_json=metrics_json, flight_jsonl=flight_jsonl,
                        trace_json=trace_json)


def run_torr_gateway(run_seconds: float = 0.0, gateway_host: str = "127.0.0.1",
                     **kwargs):
    """Serve the TorR engine behind the network gateway until SIGTERM.

    The same engine stack as :func:`run_torr_streams` — config
    (``deployment_name``), synthetic TOOD world, observability tier, state
    store, chaos plan, supervisor — but instead of driving synthetic
    streams in-process, the :class:`repro.serving.gateway.Gateway` listens
    on ``gateway_host:gateway_port`` (0 = ephemeral, printed as a
    ``listening`` line that ``benchmarks/loadgen.py --spawn`` parses) and
    clients open tenant sessions over real sockets. SIGINT/SIGTERM
    triggers the graceful drain: stop accepting, flush in-flight
    requests, close the engine, write every armed artifact, exit 0.
    ``kwargs`` are :func:`build_torr_gateway`'s.

    ``run_seconds > 0`` bounds the serve window (tests); 0 serves until
    a signal arrives.
    """
    stack = build_torr_gateway(gateway_host=gateway_host, **kwargs)
    interrupted = False
    prev_handlers = None
    try:
        prev_handlers = _install_signal_handlers()
        stack.gw.start()
        # the loadgen --spawn handshake line: printed only once the
        # socket accepts (flush so a pipe reader sees it immediately)
        print(f"[serve/gateway] listening on "
              f"http://{gateway_host}:{stack.gw.port} "
              f"(SIGINT/SIGTERM drains and flushes artifacts)", flush=True)
        t_end = None if run_seconds <= 0 else time.time() + run_seconds
        while t_end is None or time.time() < t_end:
            time.sleep(0.2)
    except KeyboardInterrupt:
        interrupted = True
        print("[serve/gateway] signal received — draining", flush=True)
    finally:
        if prev_handlers is not None:
            _restore_signal_handlers(prev_handlers)
    return stack.close(interrupted)


def _write_output(f, sid, seq, wout) -> None:
    """Append one resolved window's output record (fsync'd: the SIGKILL
    recovery test diffs these ledgers across runs, so a record must never
    be half-written)."""
    import hashlib
    import json
    import os

    scores = np.ascontiguousarray(np.asarray(wout.scores))
    rec = {"stream": sid, "seq": int(seq),
           "best": np.asarray(wout.best).tolist(),
           "scores_sha256": hashlib.sha256(scores.tobytes()).hexdigest()}
    f.write(json.dumps(rec) + "\n")
    f.flush()
    os.fsync(f.fileno())


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="musicgen-large")
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--gen", type=int, default=32)
    ap.add_argument("--rerank", action="store_true")
    ap.add_argument("--temperature", type=float, default=0.8)
    ap.add_argument("--torr-streams", type=int, default=0,
                    help="serve N synthetic TOOD streams through the "
                         "multi-stream window engine and exit")
    ap.add_argument("--torr-frames", type=int, default=30)
    ap.add_argument("--torr-slots", type=int, default=0,
                    help="stream slots (defaults to --torr-streams)")
    ap.add_argument("--torr-serial", action="store_true",
                    help="lax.map lowering (scalar branching; CPU-friendly) "
                         "instead of vmap lanes")
    ap.add_argument("--torr-fused", default="", metavar="MODE",
                    choices=["", "switch", "prefix", "compact", "auto",
                             "off"],
                    help="full-path kernel dispatch: switch | prefix | "
                         "compact (reuse-aware compact-then-compute) | "
                         "auto (load-aware: the engine picks compact vs "
                         "hoisted per step from the telemetry path-mix "
                         "EWMA) | off (oracle); default picks per "
                         "lowering — see repro.core.pipeline."
                         "torr_window_step")
    ap.add_argument("--async", dest="use_async", action="store_true",
                    help="dispatch/collect split: overlap host window "
                         "assembly with device steps (AsyncStreamEngine)")
    ap.add_argument("--mesh", type=int, default=0, metavar="N",
                    help="shard stream slots over N devices, -1 = all "
                         "available (implies --async; default 0 = no "
                         "sharding)")
    ap.add_argument("--rt", default="", choices=["", "RT-30", "RT-60"],
                    help="arm RT-deadline admission control at this "
                         "operating point (implies --async)")
    ap.add_argument("--governor", action="store_true",
                    help="close the QoS loop: slack-driven bank/precision "
                         "gating with the energy governor (implies --async; "
                         "defaults --rt to RT-60; see module docstring for "
                         "TORR_GOV_* env overrides)")
    ap.add_argument("--metrics-port", type=int, default=None, metavar="PORT",
                    help="serve Prometheus text on 127.0.0.1:PORT/metrics "
                         "for the duration of the run (0 = ephemeral port, "
                         "printed at startup); metric catalog in "
                         "docs/observability.md")
    ap.add_argument("--metrics-json", default="", metavar="PATH",
                    help="dump the final metrics registry snapshot as JSON "
                         "(the CI bench-smoke artifact shape)")
    ap.add_argument("--flight-jsonl", default="", metavar="PATH",
                    help="spill the flight recorder (one structured record "
                         "per dispatched step) to JSONL; replay offline "
                         "with repro.obs.flight.replay")
    ap.add_argument("--trace-json", default="", metavar="PATH",
                    help="arm per-window causal tracing and write a Chrome "
                         "trace-event JSON (open in chrome://tracing or "
                         "ui.perfetto.dev); see docs/observability.md")
    ap.add_argument("--supervise", action="store_true",
                    help="wrap the engine in a ServeSupervisor: worker "
                         "death restarts the engine, re-admits streams "
                         "warm from the state store and replays in-flight "
                         "windows (implies --async; see docs/robustness.md)")
    ap.add_argument("--state-store", default="", metavar="PATH",
                    help="file-backed JSONL session store (a SIGKILLed run "
                         "resumes from it); default with --supervise is "
                         "in-memory")
    ap.add_argument("--snapshot-every", type=int, default=1, metavar="N",
                    help="write-through a stream's session snapshot every "
                         "N served windows (default 1)")
    ap.add_argument("--fault-at", type=int, default=None, metavar="STEP",
                    help="chaos harness: kill the engine worker at this "
                         "dispatched-step index (implies --supervise)")
    ap.add_argument("--fault-kind", default="dispatcher",
                    choices=["dispatcher", "collector"],
                    help="which worker thread the injected fault kills "
                         "(default dispatcher)")
    ap.add_argument("--outputs-jsonl", default="", metavar="PATH",
                    help="stream one fsync'd record per resolved window "
                         "(stream, seq, best classes, scores digest) — the "
                         "recovery tests' bit-match ledger")
    ap.add_argument("--gateway-port", type=int, default=None, metavar="PORT",
                    help="serve the network event gateway on this port "
                         "(0 = ephemeral, printed at startup) instead of "
                         "driving synthetic streams in-process; runs until "
                         "SIGTERM, then drains gracefully "
                         "(docs/gateway.md)")
    ap.add_argument("--gateway-host", default="127.0.0.1")
    ap.add_argument("--gateway-rate", type=float, default=200.0,
                    metavar="N", help="per-tenant token-bucket refill "
                    "rate, windows/s (default 200)")
    ap.add_argument("--gateway-burst", type=int, default=100, metavar="N",
                    help="per-tenant token-bucket depth (default 100)")
    ap.add_argument("--gateway-deadline-ms", type=float, default=2000.0,
                    metavar="MS", help="default per-request wait budget "
                    "before a window parks with 503 (default 2000)")
    ap.add_argument("--gateway-max-conns", type=int, default=64, metavar="N")
    ap.add_argument("--gateway-tenant-sessions", type=int, default=8,
                    metavar="N", help="per-tenant session quota (fair "
                    "slot admission; default 8)")
    ap.add_argument("--gateway-seconds", type=float, default=0.0,
                    metavar="S", help="bound the serve window (0 = until "
                    "signal)")
    ap.add_argument("--gateway-sync", action="store_true",
                    help="drive the sync StreamEngine through the "
                         "SyncDriver adapter instead of the async runtime "
                         "(incompatible with --rt/--governor/--supervise)")
    ap.add_argument("--deployment", default="toy",
                    choices=sorted(DEPLOYMENTS),
                    help="served TorrConfig for --torr-streams and "
                         "--gateway-port: toy (default; D=2048, M=64) or "
                         "torr_edge (the paper's D=8192, M=1024, K=8, "
                         "N_max=128)")
    args = ap.parse_args()

    from ..runtime.compile_cache import enable_compile_cache
    enable_compile_cache()

    if args.gateway_port is not None:
        run_torr_gateway(
            n_slots=args.torr_slots or 8, serial=args.torr_serial,
            rt=args.rt, governor=args.governor,
            fused=args.torr_fused or None,
            metrics_port=args.metrics_port, metrics_json=args.metrics_json,
            flight_jsonl=args.flight_jsonl, trace_json=args.trace_json,
            supervise=args.supervise, state_store=args.state_store,
            snapshot_every=args.snapshot_every, fault_at=args.fault_at,
            fault_kind=args.fault_kind, gateway_port=args.gateway_port,
            gateway_host=args.gateway_host, gateway_rate=args.gateway_rate,
            gateway_burst=args.gateway_burst,
            gateway_deadline_ms=args.gateway_deadline_ms,
            gateway_max_conns=args.gateway_max_conns,
            gateway_tenant_sessions=args.gateway_tenant_sessions,
            run_seconds=args.gateway_seconds,
            use_async=not args.gateway_sync,
            deployment_name=args.deployment)
        return

    if args.torr_streams > 0:
        run_torr_streams(args.torr_streams, args.torr_frames,
                         args.torr_slots, serial=args.torr_serial,
                         use_async=(args.use_async or args.mesh != 0
                                    or bool(args.rt) or args.governor),
                         mesh_devices=args.mesh, rt=args.rt,
                         governor=args.governor,
                         fused=args.torr_fused or None,
                         metrics_port=args.metrics_port,
                         metrics_json=args.metrics_json,
                         flight_jsonl=args.flight_jsonl,
                         trace_json=args.trace_json,
                         supervise=args.supervise,
                         state_store=args.state_store,
                         snapshot_every=args.snapshot_every,
                         fault_at=args.fault_at,
                         fault_kind=args.fault_kind,
                         outputs_jsonl=args.outputs_jsonl,
                         deployment_name=args.deployment)
        return

    cfg = get_smoke(args.arch) if args.smoke else get(args.arch)
    key = jax.random.PRNGKey(0)
    params = tf.init_params(key, cfg)

    B, S = args.batch, args.prompt_len
    rng = np.random.default_rng(0)
    if cfg.family == "audio":
        tokens = rng.integers(0, cfg.vocab, (B, S, cfg.n_codebooks))
    else:
        tokens = rng.integers(0, cfg.vocab, (B, S))
    batch = {"tokens": jnp.asarray(tokens, jnp.int32)}
    if cfg.family == "vlm":
        batch["vision"] = jnp.asarray(
            rng.standard_normal((B, cfg.n_vision_tokens, cfg.vision_dim)),
            jnp.bfloat16)

    prefill = jax.jit(tf.prefill, static_argnames="cfg")
    decode = jax.jit(tf.decode_step, static_argnames=("cfg", "return_hidden"))

    t0 = time.time()
    cache, logits = prefill(params, batch, cfg)
    t_prefill = time.time() - t0

    rcfg, rparams, rim, rstate = None, None, None, None
    rstep = None
    if args.rerank:
        rcfg = TorrConfig(D=2048, B=8, M=min(cfg.vocab, 256), K=8,
                          N_max=B, feat_dim=cfg.d_model)
        rparams, rim = rr.init_reranker(jax.random.PRNGKey(7), rcfg,
                                        cfg.d_model, cfg.vocab, alpha=0.5)
        rstate = rr.init_state(rcfg, B)
        rstep = jax.jit(rr.rerank_step, static_argnames=("cfg",))

    sample_key = jax.random.PRNGKey(1)
    generated = []
    bypassed_frac = []
    hidden = None
    t0 = time.time()
    for i in range(args.gen):
        if args.rerank and hidden is not None and cfg.family != "audio":
            logits, rstate, tel = rstep(rparams, rstate, rim,
                                        hidden, logits, rcfg)
            bypassed_frac.append(float(jnp.mean(tel["bypassed"])))
        if cfg.family == "audio":
            lf = logits.reshape(B, cfg.n_codebooks, cfg.vocab)
            sample_key, k = jax.random.split(sample_key)
            nxt = jax.random.categorical(k, lf / args.temperature, axis=-1)
        else:
            sample_key, k = jax.random.split(sample_key)
            nxt = jax.random.categorical(k, logits / args.temperature, axis=-1)
        generated.append(np.asarray(nxt))
        cache, logits, hidden = decode(params, cache, nxt, cfg,
                                       return_hidden=True)
    t_decode = time.time() - t0

    print(f"[serve] arch={cfg.name} batch={B} prompt={S} gen={args.gen}")
    print(f"[serve] prefill {t_prefill*1e3:.1f} ms; decode "
          f"{t_decode/args.gen*1e3:.1f} ms/token "
          f"({B*args.gen/t_decode:.1f} tok/s)")
    if bypassed_frac:
        print(f"[serve] reranker bypass rate: {np.mean(bypassed_frac):.2f}")
    out = np.stack(generated, axis=1)
    print(f"[serve] generated shape {out.shape}, sample: {out[0].ravel()[:16]}")


if __name__ == "__main__":
    main()
