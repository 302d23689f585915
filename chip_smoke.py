#!/usr/bin/env python3
"""Chip smoke test: the paper's edge deployment served on a TPU.

One process, at ``torr_edge`` widths (D=8192 in 8 banks, M=1024 concepts,
K=8, N_max=128, delta budget 2048), with weights and traffic made from a
fixed seed:

  (a) gateway   — the stack ``serve.py --gateway-port 0 --async
                  --deployment torr_edge`` builds, on an ephemeral port. A
                  few tenant/stream sessions send windows over HTTP; every
                  window must answer 200 with ``best`` and the scores digest
                  bit-identical to the oracle step (``fused="off"``,
                  ``decide="scan"``) run here on the same chip.
  (b) lowerings — ``StreamEngine`` under ``prefix``, ``compact`` (batched
                  decide) and ``auto`` over a reuse-controlled trace (valid
                  proposals <= K, so bypass and delta fire), each
                  bit-identical to the oracle.

``--chips 4`` runs only the stream-sharded async engine (``serve.py
--mesh 4``) over a 4-device stream mesh, compares it with the same streams
on one device, and checks that the slot state spans the 4 devices.

Without a TPU it exits 2 and says so: it never falls back to the CPU. A
failed phase exits 1. The last line of its output is
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": N}}``.

    python chip_smoke.py            # one chip
    python chip_smoke.py --chips 4  # a four-chip host
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time
from concurrent.futures import ThreadPoolExecutor

ROOT = os.path.dirname(os.path.abspath(__file__))
sys.path[:0] = [os.path.join(ROOT, "src"), ROOT]

DEPLOYMENT = "torr_edge"
STREAMS = 8        # engine slots in every phase
SESSIONS = 4       # gateway sessions (2 tenants x 2 streams)
GW_WINDOWS = 6     # windows per gateway session
TRACE_WINDOWS = 10  # reuse-trace windows per stream after the cold one
MIX = 0.9          # intended bypass+delta share of the reuse trace
SEED = 0


class PhaseFailed(Exception):
    pass


def check(cond: bool, msg: str) -> None:
    if not cond:
        raise PhaseFailed(msg)


def log(msg: str) -> None:
    print(f"[chip_smoke] {msg}", flush=True)


def _host(tree):
    import jax
    import numpy as np
    return jax.tree.map(np.asarray, tree)


def _timed(fn):
    t0 = time.perf_counter()
    out = fn()
    return out, time.perf_counter() - t0


def _drive_engine(eng, task_w, trace):
    """Admit one stream per slot, queue every window, drain: per-stream
    lists of host (WindowOutput, WindowTelemetry)."""
    S = len(task_w)
    for s in range(S):
        eng.admit(s, task_w[s])
    for q, v, b, _qd in trace:
        for s in range(S):
            eng.submit(s, q[s], v[s], b[s])
    res = eng.drain()
    return {s: [_host(r) for r in res[s]] for s in range(S)}


def _compare(label, got, want) -> int:
    """Bit-exact scores, best and path per stream window; returns the
    number of windows compared."""
    import numpy as np
    n = 0
    for s, wins in want.items():
        check(len(got[s]) == len(wins),
              f"{label}: stream {s} served {len(got[s])} of {len(wins)}")
        for t, ((gout, gtel), (wout, wtel)) in enumerate(zip(got[s], wins)):
            check(np.array_equal(gout.scores, wout.scores)
                  and np.array_equal(gout.best, wout.best)
                  and np.array_equal(gtel.path, wtel.path),
                  f"{label}: stream {s} window {t} differs from the oracle")
            n += 1
    return n


def _path_mix(res) -> dict:
    import numpy as np
    # the reuse trace's valid proposals lead each window
    paths = np.concatenate([tel.path[:int(tel.n_valid)]
                            for wins in res.values() for _, tel in wins])
    return {name: float(np.mean(paths == p))
            for p, name in enumerate(("bypass", "delta", "full"))}


def _reuse_trace(cfg, seed):
    from benchmarks.micro_aligner import _mix_trace
    return _mix_trace(cfg, MIX, STREAMS, TRACE_WINDOWS, seed=seed,
                      numpy=True, n_valid=cfg.K)


def gateway_phase(seed: int, compile_s: dict):
    """(a): HTTP windows through the served gateway vs the oracle step.
    Returns the gateway's (cfg, synthetic system) for the next phase."""
    import jax.numpy as jnp
    import numpy as np
    from benchmarks.loadgen import _b64, _Client
    from repro.core import hdc
    from repro.data import tood_synth as ts
    from repro.launch.serve import build_torr_gateway
    from repro.serving import protocol
    from repro.serving.stream_engine import StreamEngine

    stack, compile_s["gateway_build"] = _timed(lambda: build_torr_gateway(
        n_slots=STREAMS, gateway_port=0, deployment_name=DEPLOYMENT))
    cfg, sys_ = stack.cfg, stack.sys_
    n_tasks = sys_.task_w.shape[0]
    sessions = [(f"tenant{i % 2}", f"cam{i}", i % n_tasks)
                for i in range(SESSIONS)]
    R = jnp.asarray(sys_.R)
    windows = []
    for i, (_tenant, _stream, task) in enumerate(sessions):
        frames = ts.simulate_sequence(stack.world, task, GW_WINDOWS,
                                      seed=seed + i, n_max=cfg.N_max)
        windows.append([
            (np.asarray(hdc.pack_bits(hdc.sign_project(
                jnp.asarray(f.feats), R))),
             np.asarray(f.valid, bool), np.asarray(f.boxes, np.float32))
            for f in frames])

    def drive(i):
        tenant, stream, task = sessions[i]
        cli = _Client("127.0.0.1", stack.gw.port, timeout_s=600.0)
        try:
            status, _h, body = cli.request("POST", "/v1/session", {
                "tenant": tenant, "stream": stream, "task": task})
            check(status == 200, f"session {tenant}/{stream}: {status} "
                  f"{body}")
            replies = []
            for seq, (q, v, b) in enumerate(windows[i]):
                status, _h, body = cli.request("POST", "/v1/window", {
                    "session": f"{tenant}/{stream}", "seq": seq,
                    "q": _b64(q), "valid": _b64(v), "boxes": _b64(b),
                    "deadline_ms": 600_000})
                replies.append((status, body))
            cli.request("DELETE", f"/v1/session/{tenant}/{stream}")
            return replies
        finally:
            cli.close()

    stack.gw.start()
    try:
        with ThreadPoolExecutor(len(sessions)) as ex:
            served = list(ex.map(drive, range(len(sessions))))
    finally:
        stack.close()

    oracle = StreamEngine(cfg, sys_.im, n_slots=STREAMS, fused="off",
                          decide="scan")
    _, compile_s["oracle"] = _timed(oracle.warmup)
    for i, (_t, _s, task) in enumerate(sessions):
        oracle.admit(i, sys_.task_w[task])
    n_ok = 0
    for seq in range(GW_WINDOWS):
        for i in range(len(sessions)):
            oracle.submit(i, *windows[i][seq])
        res = oracle.step()
        for i in range(len(sessions)):
            status, body = served[i][seq]
            check(status == 200,
                  f"gateway window {sessions[i][:2]} seq {seq}: {status} "
                  f"{body}")
            want = protocol.window_result_body(seq, res[i][0])
            check(body == want, f"gateway window {sessions[i][:2]} seq "
                  f"{seq}: best/scores digest differ from the oracle")
            n_ok += 1
    log(f"gateway: {len(sessions)} sessions x {GW_WINDOWS} windows, "
        f"{n_ok}/{n_ok} answered 200, best + scores_sha256 bit-identical "
        "to the oracle step")
    return cfg, sys_


def lowerings_phase(cfg, sys_, seed: int, compile_s: dict) -> None:
    """(b): prefix / compact (batched decide) / auto vs the oracle on a
    reuse-controlled trace."""
    import numpy as np
    from repro.core.types import FUSED_NAMES
    from repro.serving.stream_engine import StreamEngine

    trace = _reuse_trace(cfg, seed)
    task_w = sys_.task_w[np.arange(STREAMS) % sys_.task_w.shape[0]]
    runs = {}
    for label, kw in (("oracle", dict(fused="off", decide="scan")),
                      ("prefix", dict(fused="prefix")),
                      ("compact", dict(fused="compact", decide="batched")),
                      ("auto", dict(fused="auto"))):
        eng = StreamEngine(cfg, sys_.im, n_slots=STREAMS, **kw)
        _, compile_s[label] = _timed(eng.warmup)
        runs[label], run_s = _timed(lambda: _drive_engine(eng, task_w,
                                                          trace))
        log(f"{label}: {STREAMS * len(trace)} windows in {run_s:.3f} s "
            "(host clock, compiles of new bucket tiers included)")
    oracle = runs.pop("oracle")
    mix = _path_mix(oracle)
    log("achieved path mix (valid lanes, oracle): " + " ".join(
        f"{k}={v:.4f}" for k, v in mix.items()))
    check(mix["bypass"] > 0 and mix["delta"] > 0,
          f"reuse trace never took bypass and delta: {mix}")
    for label, res in runs.items():
        n = _compare(label, res, oracle)
        used = sorted({FUSED_NAMES[int(tel.fused_mode)]
                       for wins in res.values() for _, tel in wins})
        log(f"{label}: {n} windows bit-identical to the oracle "
            f"(lowerings run: {','.join(used)})")


def sharded_phase(n_chips: int, seed: int, compile_s: dict) -> None:
    """--chips 4: the stream-sharded async engine vs one device."""
    import jax
    import numpy as np
    from repro.configs import deployment
    from repro.data import tood_synth as ts
    from repro.runtime import sharding as shd
    from repro.serving import tood_pipelines as tp
    from repro.serving.async_engine import AsyncStreamEngine
    from repro.serving.stream_engine import StreamEngine

    cfg = deployment(DEPLOYMENT)
    sys_ = tp.build_system(ts.make_world(seed=seed, M=cfg.M, d=cfg.feat_dim),
                           cfg, seed=seed)
    trace = _reuse_trace(cfg, seed)
    task_w = sys_.task_w[np.arange(STREAMS) % sys_.task_w.shape[0]]

    eng = AsyncStreamEngine(cfg, sys_.im, n_slots=STREAMS,
                            mesh=shd.stream_mesh(n_chips), paused=True)
    _, compile_s["sharded"] = _timed(eng.warmup)
    futs = {s: [] for s in range(STREAMS)}
    for s in range(STREAMS):
        eng.admit(s, task_w[s])
    for q, v, b, _qd in trace:
        for s in range(STREAMS):
            futs[s].append(eng.submit(s, q[s], v[s], b[s]))
    t0 = time.perf_counter()
    eng.start()
    try:
        eng.flush(timeout=600)
        got = {s: [_host(f.result(timeout=60)) for f in fs]
               for s, fs in futs.items()}
        run_s = time.perf_counter() - t0
        span = eng.state.cache.acc.sharding.device_set
    finally:
        eng.close()
    check(len(span) == n_chips,
          f"slot state spans {len(span)} devices, not {n_chips}")
    log(f"sharded: slot state spans {len(span)} devices "
        f"({sorted(d.id for d in span)}); {STREAMS * len(trace)} windows "
        f"in {run_s:.3f} s (host clock)")

    ref = StreamEngine(cfg, sys_.im, n_slots=STREAMS)
    _, compile_s["one_device"] = _timed(ref.warmup)
    want = _drive_engine(ref, task_w, trace)
    n = _compare("sharded", got, want)
    log(f"sharded: {n} windows bit-identical to the same streams on "
        f"device {jax.devices()[0].id}")


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4),
                    help="1: gateway + lowering phases on one chip; 4: "
                         "only the stream-sharded engine over 4 chips")
    args = ap.parse_args()

    from repro.runtime.compile_cache import enable_compile_cache
    cache_dir = enable_compile_cache()
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu":
        print(f"[chip_smoke] no TPU found: JAX's devices are "
              f"{devs[0].platform} ({len(devs)}); this smoke test runs "
              "only on a TPU and does not fall back", file=sys.stderr)
        return 2
    if len(devs) < args.chips:
        print(f"[chip_smoke] --chips {args.chips} needs {args.chips} TPU "
              f"devices; found {len(devs)}", file=sys.stderr)
        return 2
    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": len(devs)}
    log(f"device: platform={device['platform']} kind={device['kind']} "
        f"count={device['count']}; compile cache {cache_dir}")

    from repro.configs import deployment
    cfg = deployment(DEPLOYMENT)
    log(f"deployment {DEPLOYMENT}: D={cfg.D} B={cfg.B} M={cfg.M} K={cfg.K} "
        f"N_max={cfg.N_max} delta_budget={cfg.delta_budget} "
        f"feat_dim={cfg.feat_dim}; slots={STREAMS}")

    compile_s: dict = {}
    try:
        if args.chips == 1:
            cfg, sys_ = gateway_phase(SEED, compile_s)
            lowerings_phase(cfg, sys_, SEED, compile_s)
        else:
            sharded_phase(args.chips, SEED, compile_s)
    except PhaseFailed as e:
        log(f"FAILED: {e}")
        return 1
    log("compile seconds (engine warm-ups, host clock): " + " ".join(
        f"{k}={v:.3f}" for k, v in compile_s.items())
        + f" total={sum(compile_s.values()):.3f}")
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
