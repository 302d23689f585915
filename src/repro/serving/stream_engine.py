"""Multi-stream batched window engine: slot scheduler over the vmapped step.

The single-stream serving loop (``tood_pipelines.run_torr``) dispatches one
``torr_window_step`` per frame and leaves the accelerator idle between
windows. This engine serves S independent camera/DVS streams through *one*
compiled ``torr_multi_stream_step``: streams are admitted into fixed stream
slots, each slot owns a stacked row of ``TorrState`` (its query cache, task
weights and backlog), and every ``step()`` drains one window per busy slot
as a padded :class:`repro.core.types.StreamBatch`.

Scheduling contract:

  * ``admit(stream_id, task_w)`` binds a stream to a free slot and resets
    that slot's cache (no cross-stream reuse leaks).
  * ``submit(stream_id, q_packed, valid, boxes)`` enqueues one window.
  * ``step()`` pops the head window of every busy slot, pads idle slots
    (valid all-False -> the pipeline's pad branch leaves their cache
    untouched), and returns {stream_id: (WindowOutput, WindowTelemetry)}.
    A stream's ``queue_depth`` is its remaining backlog after the pop, so
    Alg. 1's per-stream load gating (H, D') sees true per-stream pressure.
  * ``retire(stream_id)`` drops the stream's remaining backlog and frees
    the slot; admission asserts the recycled slot's queue is empty.

Because the batched step is an exact vmap of the window FSM, results are
bit-identical to running each stream alone (tests/test_multistream.py).

``fused="auto"`` arms the load-aware kernel dispatch: every step the
engine folds the previous step's full-path fraction into an EWMA and picks
between the hoisted lowering default and the reuse-aware compact dispatch
(``fused="compact"`` with a ``core.policy.bucket_ladder`` tier sized to the
predicted miss count) — reuse-heavy traffic stops paying the full
XNOR-popcount scan over lanes that resolve via bypass/delta. Every choice
is bit-identical (compact overflow falls back exactly), so auto is purely
a scheduling knob.
"""
from __future__ import annotations

import collections
import dataclasses
import functools
import time
from typing import Dict

import jax
import jax.numpy as jnp
import numpy as np

from ..core import pipeline, policy, query_cache
from ..core.item_memory import ItemMemory
from ..core.pipeline import TorrState, WindowOutput
from ..core.types import PATH_FULL, StreamBatch, TorrConfig, WindowTelemetry
from ..obs.bridge import StepObserver, telemetry_digest
from ..obs.phases import phase_table
from ..obs.spans import NULL_SPAN, span
from ..obs.trace import now_us, trace_scope

# admission-gate verdicts for `_assemble(gate=...)`; values align with
# `repro.serving.deadline.Decision` (an IntEnum) so trackers can be used
# as gates without this module importing the deadline layer
GATE_ADMIT, GATE_ESCALATE, GATE_SHED = 0, 1, 2

# load-aware fused="auto" dispatch: EWMA weight of the newest step's
# full-path fraction, and the headroom multiplier the predicted full count
# is padded by before rounding up to a bucket-ladder tier (a mispredict is
# never wrong — the compact dispatch falls back exactly on overflow — but
# the fallback rescans every row, so headroom is cheap insurance)
AUTO_ALPHA = 0.3
AUTO_HEADROOM = 2.0


@dataclasses.dataclass
class EngineStats:
    """Counters for the batched engine (host-side, cheap)."""

    steps: int = 0
    windows: int = 0          # non-pad windows processed
    pad_slots: int = 0        # idle slot-steps (wasted lanes)
    admitted: int = 0
    retired: int = 0
    dropped: int = 0          # backlog windows discarded by retire()
    shed: int = 0             # windows shed by RT admission control
    telemetry_dropped: int = 0  # observed windows lost before the fold
                                # (collector drain on worker death, futures
                                # cancelled mid-flight) — the silent-loss
                                # audit counter

    @property
    def occupancy(self) -> float:
        total = self.windows + self.pad_slots
        return self.windows / total if total else 0.0


class StreamEngine:
    """Fixed-slot scheduler feeding ``torr_multi_stream_step``."""

    # engine family stamped into minted trace contexts (async overrides)
    _ENGINE = "sync"

    def __init__(
        self,
        cfg: TorrConfig,
        im: ItemMemory,
        n_slots: int = 16,
        jit: bool = True,
        serial: bool = False,
        fused: str | None = None,
        bucket_cap: int | None = None,
        decide: str | None = None,
        metrics=None,
        flight=None,
        tracer=None,
        store=None,
        snapshot_every: int = 1,
        fault_plan=None,
    ):
        self.cfg = cfg
        self.im = im
        self.n_slots = n_slots
        self._state: TorrState = pipeline.init_multi_stream_state(
            cfg, jnp.zeros((n_slots, cfg.M), jnp.float32)
        )
        self._pending = [collections.deque() for _ in range(n_slots)]
        self._slot_of: Dict[object, int] = {}
        self._free = list(range(n_slots - 1, -1, -1))
        # `serial` picks the lowering (vmap lanes vs on-device lax.map); both
        # are bit-identical — see pipeline.torr_multi_stream_step. Jit the
        # module-level function (not a per-engine partial) so engines with
        # the same cfg share one compiled executable.
        self._serial = serial
        # `fused` picks the full path's kernel dispatch (None = the
        # lowering-appropriate fused default; "off" = the jnp-oracle
        # reference step). Static, like `serial`. "auto" arms the
        # load-aware dispatcher: each step picks compact-vs-hoisted (and
        # the compact bucket tier) from the telemetry path-mix EWMA.
        self._auto = fused == "auto"
        self._fused = None if self._auto else fused
        self._bucket_cap = bucket_cap
        # `decide` picks the compact dispatch's decide-pass lowering
        # (None = "batched"; "scan" pins the sequential reference oracle).
        # Static like `fused`; auto-picked compact steps ride it too.
        self._decide = decide
        # full-path fraction EWMA; starts pessimistic (a cold cache makes
        # every proposal a miss), so auto begins on the hoisted lowering.
        # The backlog holds telemetry of in-flight steps; only entries at
        # least one dispatch old are folded (see _fold_telemetry).
        self._full_ewma = 1.0
        self._tel_backlog: collections.deque = collections.deque()
        # The QoS control plane's latched knob plan: a static jit argument,
        # so each distinct plan dispatches its own specialized executable
        # (the window-latched register analogue). None = uncontrolled step.
        self._plan = None
        step = pipeline.torr_stream_batch_step
        self._step = (
            jax.jit(step, static_argnames=("cfg", "serial", "plan", "fused",
                                           "bucket_cap", "decide"))
            if jit else step
        )
        self.stats = EngineStats()
        # observability (repro.obs): a MetricsRegistry and/or FlightRecorder
        # attach a StepObserver; without either the engine pays nothing but
        # NULL_SPAN's empty context managers. The telemetry backlog rides
        # the same deferred-fold path the auto dispatcher uses, so obs never
        # blocks the host on an in-flight device step either.
        self._obs = (StepObserver(metrics, flight)
                     if metrics is not None or flight is not None else None)
        # causal tracing (repro.obs.trace): when a Tracer is armed, submit()
        # mints a per-window TraceContext that rides the pending tuple, the
        # step's spans stamp phase intervals onto it via trace_scope, and
        # the telemetry fold completes it with the resolved plan/lowering.
        # Spans are armed for a tracer even without a registry (span(name,
        # None) records no histogram but still feeds record_span).
        self._tracer = tracer
        self._step_ctxs = None  # live ctx list while a traced step assembles
        sp = (lambda name: span(name, metrics)) \
            if metrics is not None or tracer is not None \
            else (lambda name: NULL_SPAN)
        self._sp_assemble = sp("host_assemble")
        self._sp_dispatch = sp("dispatch_enqueue")
        self._sp_observe = sp("host_observe")
        self._last_resolved = (self._fused, self._bucket_cap, self._decide)
        # externalized session state (repro.serving.state_store): with a
        # store attached, every stream's cache rows + task weights write
        # through every `snapshot_every` served windows — sliced lazily at
        # dispatch, materialized on the deferred telemetry fold (sync) or
        # the collector (async), so the hot path never blocks on it
        self._store = store
        self._snapshot_every = max(1, int(snapshot_every))
        self._served_count: Dict[object, int] = {}
        # deterministic chaos injection (runtime.fault.FaultPlan): fired at
        # the engine's step boundaries; exercises the EngineDead + recovery
        # machinery end-to-end
        self._fault = fault_plan
        # reusable host-side pad buffers for batch assembly
        self._q0 = np.zeros((cfg.N_max, cfg.words), np.uint32)
        self._v0 = np.zeros((cfg.N_max,), bool)
        self._b0 = np.zeros((cfg.N_max, 4), np.float32)

    # -- admission control --------------------------------------------------

    def admit(self, stream_id, task_w, snapshot=None) -> int:
        """Bind a stream to a free slot; returns the slot index.

        ``snapshot`` (a :class:`repro.serving.state_store.StreamSnapshot`,
        or None) warm-starts the slot: the snapshot's cache rows (packed
        prototypes, accumulators, ``acc_tag``s, age/validity) and
        task-weight row overwrite the freshly-reset slot, and the
        stream's served-window count resumes from ``snapshot.window_seq``
        — a re-admitted stream keeps the reuse state that makes
        partial-similarity paths pay, instead of recomputing it cold.
        """
        if stream_id in self._slot_of:
            raise ValueError(f"stream {stream_id!r} already admitted")
        if not self._free:
            raise RuntimeError("no free stream slots; retire a stream first")
        slot = self._free.pop()
        # retire() drops a stream's un-popped backlog with the slot, so a
        # recycled slot must come back empty — anything else is a
        # cross-stream backlog leak.
        assert not self._pending[slot], (
            f"slot {slot} re-admitted with {len(self._pending[slot])} leaked "
            "backlog windows; retire() must drop them")
        self._slot_of[stream_id] = slot
        self._state = TorrState(
            cache=query_cache.reset_slot(self._state.cache, self.cfg, slot),
            task_weights=self._state.task_weights.at[slot].set(
                jnp.asarray(task_w, jnp.float32)
            ),
        )
        if snapshot is not None:
            from . import state_store as ss
            self._state = ss.restore_slot(self._state, self.cfg, slot,
                                          snapshot)
            self._served_count[stream_id] = int(snapshot.window_seq)
        else:
            self._served_count[stream_id] = 0
        self.stats.admitted += 1
        if self._obs is not None:
            self._obs.on_admit()
        return slot

    def retire(self, stream_id) -> None:
        """Release a stream's slot, dropping any un-popped backlog.

        The slot's cache is reset on the next admit; the backlog must be
        dropped *here* so a recycled slot can never serve a window (or leak
        queue-depth pressure) belonging to the retired stream."""
        slot = self._slot_of.pop(stream_id)
        n_dropped = len(self._pending[slot])
        self.stats.dropped += n_dropped
        self._pending[slot].clear()
        self._free.append(slot)
        self.stats.retired += 1
        self._served_count.pop(stream_id, None)
        if self._store is not None:
            self._store.delete(stream_id)
        if self._obs is not None:
            self._obs.on_retire(n_dropped)

    # -- window flow --------------------------------------------------------

    def submit(self, stream_id, q_packed, valid, boxes) -> None:
        """Enqueue one window (packed queries, validity, boxes) for a stream.

        The window's trailing payload is ``(future, arrival, ctx)``, as in
        the async engine: no future here, the arrival time on the
        monotonic clock, and with a tracer armed a per-window
        :class:`TraceContext` minted here (the window's admission
        timestamp), else None."""
        slot = self._slot_of[stream_id]
        ctx = (self._tracer.mint(stream_id, self._ENGINE)
               if self._tracer is not None else None)
        self._pending[slot].append((np.asarray(q_packed, np.uint32),
                                    np.asarray(valid, bool),
                                    np.asarray(boxes, np.float32),
                                    None, time.monotonic(), ctx))

    def backlog(self, stream_id) -> int:
        return len(self._pending[self._slot_of[stream_id]])

    @property
    def busy(self) -> bool:
        return any(self._pending[s] for s in self._slot_of.values())

    def _assemble(self, gate=None):
        """Pop the head window of every busy slot into padded host buffers.

        Returns ``(q, v, b, qd, served)`` where served is a list of
        ``(stream_id, slot, extra)`` — ``extra`` is the ``(future, arrival,
        ctx)`` payload ``submit`` queued alongside the window arrays. Idle
        slots stay all-pad; ``qd`` is each served slot's *remaining*
        backlog after the pop, so Alg. 1's load gate sees true per-stream
        pressure.

        ``gate(stream_id, backlog_after_pop, extra) -> GATE_*`` is the
        optional admission hook (the async engine's RT-deadline controller):
        GATE_SHED drops the head (the gate owns failing its future) and the
        next queued window is offered in its place; GATE_ESCALATE serves the
        window with its queue-depth lane floored to ``cfg.q_hi`` so Alg. 1's
        ``H(N, q)`` goes high. With ``gate=None`` every window is admitted —
        the batch composition the bit-equivalence tests pin down."""
        S = self.n_slots
        q = np.broadcast_to(self._q0, (S,) + self._q0.shape).copy()
        v = np.broadcast_to(self._v0, (S,) + self._v0.shape).copy()
        b = np.broadcast_to(self._b0, (S,) + self._b0.shape).copy()
        qd = np.zeros((S,), np.int32)
        served = []  # (stream_id, slot, extra) of non-pad lanes this step
        for stream_id, slot in self._slot_of.items():
            dq = self._pending[slot]
            while dq:
                qw, vw, bw, *extra = dq[0]
                decision = GATE_ADMIT if gate is None else \
                    gate(stream_id, len(dq) - 1, extra)
                dq.popleft()
                if decision == GATE_SHED:
                    continue    # offer this slot's next queued window
                q[slot], v[slot], b[slot] = qw, vw, bw
                qd[slot] = len(dq)
                if decision == GATE_ESCALATE:
                    qd[slot] = max(qd[slot], self.cfg.q_hi)
                served.append((stream_id, slot, extra))
                ctx = extra[2]
                if ctx is not None:
                    ctx.slot = slot
                    if ctx.decision is None:  # a gate may have stamped it
                        ctx.decision = ("admit", "escalate",
                                        "shed")[decision]
                    if self._step_ctxs is not None:
                        self._step_ctxs.append(ctx)
                break
        return q, v, b, qd, served

    @property
    def state(self) -> TorrState:
        """The stacked per-slot state (placed over the mesh when the engine
        shards its slots)."""
        return self._state

    def set_plan(self, plan) -> None:
        """Latch a knob plan (``repro.control.plan.KnobPlan`` or None) for
        subsequent steps. Host-side only: takes effect on the next dispatch."""
        if plan is not None:
            plan.validate(self.cfg)
        self._plan = plan

    @property
    def plan(self):
        return self._plan

    # -- load-aware fused="auto" dispatch ------------------------------------

    def _observe_path_mix(self, path, n_valid) -> None:
        """Fold one (host-resident) step's full-path fraction into the EWMA.

        ``path`` is the step's [S, N_max] path trace, ``n_valid`` the [S]
        valid counts; pad lanes report bypass, so the full count needs no
        masking. Called by :meth:`_fold_telemetry` (sync engine) or the
        async collector, whichever owns host-side telemetry."""
        nv = int(np.sum(n_valid))
        if nv:
            f = float(np.sum(np.asarray(path) == PATH_FULL)) / nv
            self._full_ewma += AUTO_ALPHA * (f - self._full_ewma)

    # -- externalized session state (write-through snapshots) ----------------

    def _snap_meta(self) -> dict:
        """Host-side metadata stamped into every snapshot: the engine
        family, the auto dispatcher's path-mix EWMA (so a warm-started
        engine resumes load-aware dispatch where the dead one left off),
        and the latched knob plan, if any."""
        meta = {"engine": self._ENGINE, "full_ewma": float(self._full_ewma)}
        if self._plan is not None:
            meta["plan"] = {"banks": int(self._plan.banks),
                            "planes": int(self._plan.planes)}
        return meta

    def _collect_snaps(self, served):
        """Advance served-window counts and slice snapshot rows for streams
        that hit the ``snapshot_every`` cadence this step.

        Called right after ``_dispatch`` (the state already points at the
        post-step arrays), under the async engine's lock. The slices are
        *lazy device views* — materialization to host (and the store write)
        happens on the deferred telemetry fold (sync) or in the collector
        after ``block_until_ready`` (async), so the dispatcher never blocks
        on a snapshot."""
        from . import state_store as ss
        snaps = []
        for stream_id, slot, _extra in served:
            n = self._served_count.get(stream_id, 0) + 1
            self._served_count[stream_id] = n
            if n % self._snapshot_every == 0:
                snaps.append(ss.snapshot_rows(
                    self._state, slot, stream_id, n, self._snap_meta()))
        return snaps

    def _fold_one(self, tel, rec, ctxs=None, snaps=None) -> None:
        """Move one backlogged step's telemetry to host and consume it:
        the auto dispatcher's path-mix EWMA, the observer's metric digest +
        flight-record completion (``rec`` is the step's open flight record,
        or None), when the step was traced — completing its windows'
        contexts with the resolved plan/lowering off the same digest — and
        any pending state-store snapshots (materialized + written here,
        off the dispatch path)."""
        tel_h = jax.tree_util.tree_map(np.asarray, tel)
        if self._auto:
            self._observe_path_mix(tel_h.path, tel_h.n_valid)
        digest = None
        if self._obs is not None:
            digest = self._obs.observe_step(tel_h, rec)
        if ctxs:
            if digest is None:
                digest = telemetry_digest(tel_h)
            self._trace_finish(ctxs, rec, digest)
        if snaps:
            from . import state_store as ss
            memo = {}  # one host transfer per stacked leaf per fold batch
            for pending in snaps:
                self._store.put(ss.materialize_snapshot(pending, memo))

    def _trace_finish(self, ctxs, rec, digest) -> None:
        """Complete one step's trace contexts: stamp the resolved plan and
        lowering (read back off the step's telemetry digest — the same
        source the flight replay bit-matches against the governor's plan
        log), link the flight step index, embed the per-window dicts into
        the flight record under ``"trace"``, and retire the contexts into
        the tracer ring."""
        plan = {"banks": digest.get("banks"), "planes": digest.get("planes")}
        if rec is not None:
            gov = rec.get("governor") or {}
            if gov.get("level") is not None:
                plan["level"] = gov["level"]
        lowering = {"fused": digest.get("fused"),
                    "decide": digest.get("decide"),
                    "bucket_tier": digest.get("bucket_tier")}
        step = rec.get("step") if rec is not None else None
        for ctx in ctxs:
            ctx.step = step
            ctx.plan = plan
            ctx.lowering = lowering
            self._tracer.complete(ctx)
        if rec is not None:
            rec["trace"] = [ctx.to_dict() for ctx in ctxs]

    def _fold_telemetry(self) -> None:
        """Sync-engine EWMA feed: fold telemetry of steps that are at
        least one dispatch old. The newest entry stays in the backlog —
        reading it here would block on the step that may still be running
        on-device, serializing the host against the device every step;
        leaving one in flight preserves the dispatch/compute overlap
        (double buffering). The async engine overrides this with a no-op —
        its collector thread feeds :meth:`_observe_path_mix` from already
        host-resident traces without ever touching the dispatcher."""
        while len(self._tel_backlog) > 1:
            self._fold_one(*self._tel_backlog.popleft())

    def flush_telemetry(self) -> None:
        """Fold *every* backlogged step, including the newest (blocks on
        any step still executing). Call before reading summaries or
        spilling the flight recorder — otherwise up to one step's
        telemetry is still deferred by the double-buffering contract."""
        while self._tel_backlog:
            self._fold_one(*self._tel_backlog.popleft())

    def _resolve_fused(self):
        """(fused, bucket_cap, decide) for the next dispatch.

        Pinned modes pass straight through. In auto mode the predicted
        full-path rows (path-mix EWMA x total lanes, padded by
        ``AUTO_HEADROOM``) round up to a ``core.policy.bucket_ladder``
        tier: a tier below full capacity dispatches the compact lowering,
        full capacity falls back to the lowering-appropriate hoisted
        default (compaction would save nothing). The executable family
        stays bounded at ladder x plan — the recompile-guard test pins it.
        The engine's ``decide`` knob rides along unchanged: whichever
        decide-pass lowering was pinned at construction (None = batched)
        is what an auto-picked compact step runs with.
        """
        if not self._auto:
            return self._fused, self._bucket_cap, self._decide
        self._fold_telemetry()
        n_rows = self.n_slots * self.cfg.N_max
        want = int(np.ceil(self._full_ewma * n_rows * AUTO_HEADROOM))
        tier = policy.bucket_tier(n_rows, want)
        if tier >= n_rows:
            return None, None, self._decide  # hoisted default, no decide pass
        return "compact", tier, self._decide

    @property
    def full_path_ewma(self) -> float:
        """The auto dispatcher's current full-path-fraction estimate."""
        return self._full_ewma

    def _batch(self, q, v, b, qd) -> StreamBatch:
        """One step's host buffers as the step's device batch."""
        return StreamBatch(
            q_packed=jnp.asarray(q), valid=jnp.asarray(v),
            boxes=jnp.asarray(b), queue_depth=jnp.asarray(qd),
        )

    def _step_call(self, batch, resolved):
        """``(args, kwargs)`` of the step over ``batch`` with the resolved
        ``(fused, bucket_cap, decide)``."""
        fused, bucket_cap, decide = resolved
        return ((self._state, self.im, batch, self.cfg),
                dict(serial=self._serial, plan=self._plan, fused=fused,
                     bucket_cap=bucket_cap, decide=decide))

    def _dispatch(self, q, v, b, qd):
        """Launch one batched step (asynchronously) and advance the state."""
        batch = self._batch(q, v, b, qd)
        self._last_resolved = self._resolve_fused()
        args, kw = self._step_call(batch, self._last_resolved)
        self._state, out, tel = self._step(*args, **kw)
        return out, tel

    @staticmethod
    def _queue_waits(served, now):
        """Each served window's seconds in the queue at dispatch time."""
        return [now - extra[1] for _sid, _slot, extra in served]

    def step(self) -> Dict[object, tuple[WindowOutput, WindowTelemetry]]:
        """Drain one window per busy slot through the batched step."""
        # traced steps open a trace_scope around the assemble/dispatch
        # spans: _assemble populates step_ctxs as it admits windows, and
        # each span stamps its interval onto them at exit
        # chaos injection: the sync engine plays both worker roles inside
        # step() — "dispatcher" fires before assemble, "collector" after
        # the telemetry fold (mirroring where the async threads would die)
        if self._fault is not None:
            self._fault.maybe_fire("dispatcher", self.stats.steps)
        step_ctxs = None
        scope = NULL_SPAN
        if self._tracer is not None:
            step_ctxs = self._step_ctxs = []
            scope = trace_scope(step_ctxs)
        try:
            with scope:
                with self._sp_assemble:
                    q, v, b, qd, served = self._assemble()
                if not served:  # idle engine: skip the no-op device step
                    return {}
                with self._sp_dispatch:
                    t_dispatch = time.monotonic()
                    out, tel = self._dispatch(q, v, b, qd)
        finally:
            self._step_ctxs = None
        self.stats.steps += 1
        self.stats.windows += len(served)
        self.stats.pad_slots += self.n_slots - len(served)
        snaps = self._collect_snaps(served) \
            if self._store is not None else None

        if self._auto or self._obs is not None or self._tracer is not None \
                or self._store is not None:
            rec = None
            if self._obs is not None:
                rec = self._obs.on_dispatch(
                    len(served), self.n_slots - len(served),
                    requested=self._last_resolved, plan=self._plan,
                    full_ewma=self._full_ewma if self._auto else None,
                    waits=self._queue_waits(served, t_dispatch))
                if rec is not None and self._tracer is not None:
                    rec["ts_us"] = now_us()
                    rec["queue_depth"] = int(qd.max())
            # deferred fold: this step's telemetry enters the backlog, and
            # only entries at least one dispatch old are consumed now
            self._tel_backlog.append((tel, rec, step_ctxs, snaps))
            with self._sp_observe:
                self._fold_telemetry()

        if self._fault is not None:
            self._fault.maybe_fire("collector", self.stats.steps)

        results = {}
        for stream_id, slot, _extra in served:
            results[stream_id] = (
                jax.tree_util.tree_map(lambda x: x[slot], out),
                jax.tree_util.tree_map(lambda x: x[slot], tel),
            )
        return results

    def drain(self) -> Dict[object, list]:
        """Step until every backlog is empty; per-stream result lists."""
        acc: Dict[object, list] = {sid: [] for sid in self._slot_of}
        while self.busy:
            for sid, res in self.step().items():
                acc[sid].append(res)
        return acc

    def sync(self) -> None:
        """Block until all dispatched steps have executed on the device.

        Step results are dispatched asynchronously; timing code must call
        this before reading the clock."""
        jax.block_until_ready(self._state.cache.age)

    def summary(self) -> Dict[str, float]:
        """Engine counters as a flat dict (flushes deferred telemetry so
        the observer's numbers cover every dispatched step)."""
        self.flush_telemetry()
        s = dataclasses.asdict(self.stats)
        s["occupancy"] = self.stats.occupancy
        if self._auto:
            s["full_path_ewma"] = self._full_ewma
        return s

    def warmup(self) -> None:
        """Compile the batched step outside any timed region.

        Runs one all-pad step (a state no-op: every lane takes the pad
        branch) and discards the result; stats are not touched. With a
        registry, then publishes the phase table of the executable that
        step ran (:meth:`_publish_phases`)."""
        S = self.n_slots
        zero = self._batch(np.broadcast_to(self._q0, (S,) + self._q0.shape),
                           np.broadcast_to(self._v0, (S,) + self._v0.shape),
                           np.broadcast_to(self._b0, (S,) + self._b0.shape),
                           np.zeros((S,), np.int32))
        args, kw = self._step_call(zero, self._resolve_fused())
        out = self._step(*args, **kw)
        jax.block_until_ready(out[1].scores)
        self._publish_phases(args, kw)

    def _publish_phases(self, args, kw) -> None:
        """Publish the served step's phase table (``repro.obs.phases``):
        the step lowered again with the arguments the warm-up ran, which
        JAX's compile caches serve, so the table names the ops of the very
        executable that serves. Only with a registry and a jitted step;
        skipped where the backend keeps no HLO text."""
        if self._obs is None or self._obs.registry is None:
            return
        step = self._step
        if isinstance(step, functools.partial):   # the meshed step
            step, kw = step.func, {**kw, **step.keywords}
        if not hasattr(step, "lower"):             # jit=False
            return
        try:
            text = step.lower(*args, **kw).compile().as_text()
        except NotImplementedError:
            return
        self._obs.publish_phases(phase_table(text))
