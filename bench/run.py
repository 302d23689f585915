#!/usr/bin/env python3
"""TorR's chip benchmark: one cell, one process that holds the chip.

    python3 bench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

The cell (a ``workloads`` entry of ``BENCHMARK.json``) names a
configuration, ``bench/configs/<config>.json``, and a traffic mix,
``bench/traffic/<traffic>.json``; every metric is a reader
``bench/metrics/<metric>.py``. All are found by name. The configuration
names its input module (``bench/inputs``: what its windows are, the data
and stack they are served by, and the reference that replays them), or
gets the default one, packed queries.

A run makes the data and builds the served stack from the seed through the
input module, starts the clients in a child process that never imports JAX
(``bench/client.py``), lets every stream's warm windows through, then
measures for ``--seconds``.
``setup_s`` is everything from JAX's devices in hand to the window's open.
With ``--trace 1`` the
profiler records a slice in the middle of the window and the run reports
the cell's per-layer metrics instead of its end-to-end ones.

Once the window has closed and the stack is down, the replies are checked
against the input module's plain reference: every window of every stream
answer for answer, and the path counters against the reference's
decisions, one stream to a spawned worker process. Each number
compared is printed beside its limit, on standard error and under the
result line's last key, ``checks``.

The last line of standard output is the result. Without a TPU, or with
fewer chips than the cell asks for, the run exits 2 and prints no result.
``--control planes`` latches the program's reduced-precision plan (one
bit-slice plane fewer) to show the checks fail on it; no benchmark run
passes it.
"""
from __future__ import annotations

import argparse
import contextlib
import gc
import importlib.util
import json
import multiprocessing
import os
import shutil
import subprocess
import sys
import tempfile
import threading
import time

import numpy as np

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
HERE = os.path.join(ROOT, "bench")
TRACE_MAX_S = 5.0            # longest profiled slice of the window
CLIENT_GRACE_S = 60.0        # wait for replies in flight at the close
# the engine's span objects a traced run also writes as profiler
# annotations, with the names of their spans
ENGINE_SPANS = {"_sp_decide": "host_decide", "_sp_assemble": "host_assemble",
                "_sp_dispatch": "dispatch_enqueue",
                "_sp_drain": "collector_drain", "_sp_observe": "host_observe"}
# the gateway's protocol calls a traced run annotates
GATEWAY_SPANS = {"parse_json_body": "gateway_decode",
                 "validate_window": "gateway_decode",
                 "window_result_body": "gateway_encode"}


def log(msg: str) -> None:
    print(f"[bench] {msg}", file=sys.stderr, flush=True)


class Spec:
    """One cell of ``BENCHMARK.json`` with its configuration, traffic and
    metric entries, read from a checkout root."""

    def __init__(self, root: str, workload: str):
        from bench import inputs

        self.root = root
        with open(os.path.join(root, "BENCHMARK.json")) as f:
            self.bench = json.load(f)
        cells = {w["name"]: w for w in self.bench["workloads"]}
        if workload not in cells:
            raise SystemExit(f"unknown workload {workload!r}; cells: "
                             f"{sorted(cells)}")
        self.cell = cells[workload]
        configs = {c["name"]: c for c in self.bench["configs"]}
        with open(os.path.join(root, configs[self.cell["config"]]["file"])) as f:
            self.config = json.load(f)
        self.input = inputs.path_of(root, self.config)
        with open(os.path.join(root, "bench", "traffic",
                               self.cell["traffic"] + ".json")) as f:
            self.traffic = json.load(f)
        self.end_to_end = [m for m in self.bench["end_to_end"]
                           if self._applies(m)]
        e2e = {m["name"] for m in self.end_to_end}
        self.per_layer = [m for m in self.bench["per_layer"]
                          if self._applies(m, e2e)]

    def _applies(self, metric: dict, e2e=None) -> bool:
        if "workloads" in metric:
            return self.cell["name"] in metric["workloads"]
        return e2e is None or metric["moves"] in e2e

    def reader(self, name: str):
        path = os.path.join(self.root, "bench", "metrics", name + ".py")
        mod_spec = importlib.util.spec_from_file_location(
            "bench_metric_" + name.replace(".", "_"), path)
        mod = importlib.util.module_from_spec(mod_spec)
        mod_spec.loader.exec_module(mod)
        return mod.read


class Context:
    """What a metric reader may read: the client's records, the registry
    over the measured (or traced) interval, the trace reduction, the
    configuration and the peaks. A roofline reader records in
    ``roofline_bound`` whether operations or bytes bound its metric; the
    traced result line carries it."""

    def __init__(self, **kw):
        self.roofline_bound = {}
        self.__dict__.update(kw)

    def counter(self, name: str, **labels) -> float:
        return _series_delta(self.snaps, name, labels, "value")

    def hist(self, name: str, **labels) -> tuple:
        return (_series_delta(self.snaps, name, labels, "sum"),
                _series_delta(self.snaps, name, labels, "count"))


def _series_value(snap, name, labels, field="value") -> float:
    fam = snap.get(name)
    if fam is None:
        return 0.0
    return float(sum(s[field] for s in fam["series"]
                     if all(s["labels"].get(k) == v
                            for k, v in labels.items())))


def _series_delta(snaps, name, labels, field) -> float:
    return (_series_value(snaps[1], name, labels, field)
            - _series_value(snaps[0], name, labels, field))


class _Annotated:
    """An engine span that also writes a profiler annotation (traced runs
    only): host phases on the trace's clock, to label idle gaps."""

    def __init__(self, inner, name: str):
        import jax

        self._inner, self._name = inner, name
        self._ann = jax.profiler.TraceAnnotation
        self._tls = threading.local()

    def __enter__(self):
        ann = self._ann(self._name)
        ann.__enter__()
        self._tls.__dict__.setdefault("stack", []).append(ann)
        return self._inner.__enter__()

    def __exit__(self, *exc):
        try:
            return self._inner.__exit__(*exc)
        finally:
            self._tls.stack.pop().__exit__(None, None, None)


def _annotate(stack):
    """Write the host phases into the profiler's trace: the engine's spans
    and the gateway's decode and encode. Returns ``(labels, restore)``."""
    import jax

    from repro.serving import protocol

    for attr, name in ENGINE_SPANS.items():
        setattr(stack.eng, attr, _Annotated(getattr(stack.eng, attr), name))
    saved = {fn: getattr(protocol, fn) for fn in GATEWAY_SPANS}

    def wrap(fn, name):
        def annotated(*a, **k):
            with jax.profiler.TraceAnnotation(name):
                return fn(*a, **k)
        return annotated
    for fn, name in GATEWAY_SPANS.items():
        setattr(protocol, fn, wrap(saved[fn], name))

    def restore():
        for fn, f in saved.items():
            setattr(protocol, fn, f)
    labels = set(ENGINE_SPANS.values()) | set(GATEWAY_SPANS.values())
    return tuple(sorted(labels)), restore


def _window_latencies(records, unsent, loop, t_open, t_close):
    """Latency (ms) of every window of the measured window, and the
    windows answered 200 within it.

    Closed loop: the windows answered within the window, from send to
    reply. Open loop: the windows scheduled within the window, from the
    scheduled send to the reply; one unanswered (or unsent) at the close
    counts with its age then."""
    lat, answered = [], 0
    for r in records:
        _s, _q, t_sched, t_send, t_reply, status = r[:6]
        if loop == "closed":
            if t_open <= t_reply <= t_close:
                lat.append((t_reply - t_send) * 1e3)
                answered += status == 200
        elif t_open <= t_sched < t_close:
            lat.append((min(t_reply, t_close) - t_sched) * 1e3)
            answered += status == 200 and t_reply <= t_close
    for _s, t_sched in unsent:
        lat.append((t_close - t_sched) * 1e3)
    return lat, answered


def check_stream(job: tuple) -> tuple:
    """Replay one stream through its input module's reference and compare
    every window answered 200: ``(failed, wrong, compared, paths)``.
    ``job`` is ``(input path, config, traffic, seed, stream, data as numpy
    arrays, the stream's records in order of seq)``."""
    from bench import inputs

    path, config, traffic, seed, stream, data, recs = job
    mod = inputs.load(path)
    # a session or a window went missing
    failed = int(len(recs) < traffic["warm"]
                 or [r[1] for r in recs] != list(range(len(recs))))
    replay = mod.reference(config, data, stream)
    wins = mod.windows(traffic, config, seed, stream)
    wrong = compared = 0
    paths = np.zeros(3, np.int64)
    for r in recs:
        scores, p = replay(next(wins))
        paths += p
        if r[5] == 200:
            best, digest = mod.answer(scores)
            compared += 1
            wrong += best != r[6] or digest != r[7]
    return failed, wrong, compared, paths.tolist()


@contextlib.contextmanager
def _environ(**env):
    saved = {k: os.environ.get(k) for k in env}
    os.environ.update(env)
    try:
        yield
    finally:
        for k, v in saved.items():
            if v is None:
                os.environ.pop(k, None)
            else:
                os.environ[k] = v


def check(spec: Spec, data: dict, records, server_paths, seed,
          workers: int = 0):
    """The comparison with the plain reference, every window of every
    stream: ``{name: (value, limit)}``, the number of windows compared and
    the reference's path counts. Each stream is replayed on its own, in
    ``workers`` spawned processes, or here one after another where
    ``workers`` is 0 (the serial form); the sums are the same."""
    n_streams = spec.traffic["streams"]
    by_stream = {i: [] for i in range(n_streams)}
    failed = 0
    for r in records:
        by_stream[r[0]].append(r)
        failed += r[5] != 200
    jobs = [(spec.input, spec.config, spec.traffic, seed, i, data,
             sorted(by_stream[i], key=lambda r: r[1]))
            for i in range(n_streams)]
    if workers:
        # spawned, never forked from the process that holds the chip; one
        # BLAS thread a worker, since the workers fill the cores
        with _environ(OPENBLAS_NUM_THREADS="1", OMP_NUM_THREADS="1",
                      MKL_NUM_THREADS="1"):
            pool = multiprocessing.get_context("spawn").Pool(workers)
        try:
            results = pool.map(check_stream, jobs, chunksize=1)
        finally:
            pool.terminate()
            pool.join()
    else:
        results = map(check_stream, jobs)
    wrong = compared = 0
    paths = np.zeros(3, np.int64)
    for f, w, c, p in results:
        failed += f
        wrong += w
        compared += c
        paths += p
    gap = int(np.abs(paths - np.asarray(server_paths)).sum())
    checks = {"failed_windows": (failed, 0), "wrong_answers": (wrong, 0),
              "path_count_gap": (gap, 0)}
    return checks, compared, paths.tolist()


def run(argv=None, root: str = ROOT, require_chip: bool = True) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, default=0, choices=(0, 1))
    ap.add_argument("--control", default="", choices=("", "planes"),
                    help="planes: the program's one-plane-fewer precision "
                         "(the control that the checks must fail)")
    args = ap.parse_args(argv)
    spec = Spec(root, args.workload)

    import jax

    devs = jax.devices()
    chips = int(spec.cell["chips"])
    if require_chip and (devs[0].platform != "tpu" or len(devs) < chips):
        print(f"[bench] no TPU found: JAX's devices are {devs[0].platform} "
              f"x{len(devs)}, the cell asks for {chips} TPU chip(s); the "
              "benchmark runs only on a TPU and does not fall back",
              file=sys.stderr)
        return 2
    t_start = time.monotonic()
    compiles = [0]

    def on_event(event, *_a, **_k):
        if "backend_compile" in event or "cache_retrieval" in event:
            compiles[0] += 1
    jax.monitoring.register_event_duration_secs_listener(on_event)

    from bench import inputs
    from bench import trace_reduce as trr
    from bench import work

    config, traffic = spec.config, spec.traffic
    mod = inputs.load(spec.input)
    data = mod.make_data(config, args.seed)
    data_h = {k: np.asarray(v) for k, v in data.items()}
    t_data = time.monotonic()
    plan = None
    if args.control == "planes":
        from repro.control.plan import KnobPlan
        t = config["torr"]
        plan = KnobPlan(banks=t["B"], planes=t["bit_planes"] - 1,
                        plane_total=t["bit_planes"])
    stack = mod.build(config, data, plan=plan)
    t_stack = time.monotonic()
    del data
    labels, restore = _annotate(stack) if args.trace else ((), None)
    stack.gw.start()
    job = {"host": "127.0.0.1", "port": stack.gw.port, "traffic": traffic,
           "input": spec.input, "config": config,
           "seed": args.seed, "streams": traffic["streams"],
           "tenants": traffic["tenants"], "tasks": config["tasks"],
           "warm": traffic["warm"], "seconds": args.seconds,
           "deadline_ms": config["gateway"]["request_deadline_ms"],
           "timeout_s": args.seconds + CLIENT_GRACE_S + 60.0}
    child = subprocess.Popen(
        [sys.executable, os.path.join(HERE, "client.py"), json.dumps(job)],
        stdin=subprocess.PIPE, stdout=subprocess.PIPE, text=True)
    log_dir = None
    try:
        line = child.stdout.readline()
        if line.strip() != "ready":
            raise RuntimeError(f"client did not get ready: {line!r}")
        setup_s = time.monotonic() - t_start
        log(f"set-up {setup_s:.3f} s: data {t_data - t_start:.3f}, stack "
            f"{t_stack - t_data:.3f}, warm windows "
            f"{t_start + setup_s - t_stack:.3f}; {compiles[0]} programs "
            "compiled or loaded")
        n_compiles0 = compiles[0]
        snap_open = stack.registry.snapshot()
        t_open = time.monotonic()
        child.stdin.write(f"go {t_open!r}\n")
        child.stdin.flush()
        t_close = t_open + args.seconds
        traced = None
        if args.trace:
            trace_s = min(TRACE_MAX_S, args.seconds / 2)
            time.sleep(max(0.0, t_open + (args.seconds - trace_s) / 2
                           - time.monotonic()))
            log_dir = tempfile.mkdtemp(prefix="bench_trace_")
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            jax.profiler.start_trace(log_dir, profiler_options=opts)
            with jax.profiler.TraceAnnotation(trr.WINDOW):
                snap_a, t_a = stack.registry.snapshot(), time.monotonic()
                time.sleep(trace_s)
                snap_b, t_b = stack.registry.snapshot(), time.monotonic()
            jax.profiler.stop_trace()
            traced = (snap_a, snap_b, t_a, t_b)
        time.sleep(max(0.0, t_close - time.monotonic()))
        snap_close = stack.registry.snapshot()
        n_compiles = compiles[0] - n_compiles0
        out, _ = child.communicate(timeout=args.seconds + CLIENT_GRACE_S
                                   + 120.0)
    finally:
        if child.poll() is None:
            child.kill()
            child.wait()
    result = json.loads(out.strip().splitlines()[-1])
    records, unsent = result["records"], result["unsent"]
    snap_end = stack.registry.snapshot()
    peak_bytes = max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
                     for d in devs[:chips])
    stack.close()
    if restore is not None:
        restore()
    tcfg = config["torr"]
    del stack
    gc.collect()
    log(f"compilations inside the window: {n_compiles}")

    loop = traffic["loop"]
    lat, answered = _window_latencies(records, unsent, loop, t_open, t_close)
    attempted = sum(1 for r in records if (
        t_open <= (r[4] if loop == "closed" else r[2]) <= t_close)) \
        + len(unsent)
    failed_in_window = sum(1 for r in records if r[5] != 200 and (
        t_open <= (r[4] if loop == "closed" else r[2]) <= t_close))

    red = None
    if traced is not None:
        red = trr.reduce_file(trr.find_xplane(log_dir), labels)
        shutil.rmtree(log_dir, ignore_errors=True)
        snaps = traced[:2]
        span_s = traced[3] - traced[2]
    else:
        snaps, span_s = (snap_open, snap_close), args.seconds
    kind = devs[0].device_kind
    peak = work.peaks(kind) if devs[0].platform == "tpu" else None
    ctx = Context(spec=spec, cfg=tcfg, records=records, unsent=unsent,
                  loop=loop, t_open=t_open, t_close=t_close,
                  seconds=args.seconds, latencies_ms=lat, answered=answered,
                  setup_s=setup_s, snaps=snaps, span_s=span_s, trace=red,
                  peak=peak, work=work)
    if lat:
        q = np.percentile(lat, [5, 25, 50, 75, 90, 95, 99, 100])
        log(f"latency ms over {len(lat)} windows, p5/25/50/75/90/95/99/max: "
            + " ".join(f"{v:.1f}" for v in q))
    wanted = spec.per_layer if args.trace else spec.end_to_end
    metrics = {}
    for m in wanted:
        value = spec.reader(m["name"])(ctx)
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}

    server_paths = [_series_value(snap_end, "torr_path_total", {"path": p})
                    for p in ("bypass", "delta", "full")]
    t_ref = time.monotonic()
    # one worker a stream: each replays in order, and the cores share them
    n_workers = traffic["streams"]
    checks, compared, ref_paths = check(spec, data_h, records, server_paths,
                                        args.seed, workers=n_workers)
    log(f"reference: {compared} windows compared in "
        f"{time.monotonic() - t_ref:.3f} s by {n_workers} worker processes"
        f" on {len(os.sched_getaffinity(0))} cores;"
        f" paths server {server_paths} reference {ref_paths}")
    correct = compared > 0 and all(v <= lim for v, lim in checks.values())

    device = {"platform": devs[0].platform, "kind": kind, "count": chips,
              "memory_peak_bytes": peak_bytes}
    if red is not None:
        device["busy_s"] = red["busy_s"]
        device["window_s"] = red["window_s"]
    line = {"correct": correct, "attempted": attempted,
            "failed": failed_in_window, "metrics": metrics, "device": device}
    if red is not None:
        line["breakdown"] = trr.breakdown(red)
    if ctx.roofline_bound:
        line["roofline_bound"] = ctx.roofline_bound
    line["checks"] = {k: {"value": v, "limit": lim}
                      for k, (v, lim) in checks.items()}
    for k, (v, lim) in checks.items():
        print(f"[bench] check {k}: {v} (limit {lim})", file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(line), flush=True)
    return 0


def main() -> int:
    sys.path[:0] = [os.path.join(ROOT, "src"), ROOT]
    import jax

    from repro.runtime.compile_cache import enable_compile_cache

    enable_compile_cache()
    # every program in the cache, however quick its compile, so that no
    # run after a cell's first compiles anything in its set-up
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    # no size cap: the cap's LRU bookkeeping races between the threads
    # that compile at set-up, and the cell's few programs are small
    jax.config.update("jax_compilation_cache_max_size", -1)
    return run()


if __name__ == "__main__":
    sys.exit(main())
