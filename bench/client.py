"""The benchmark's clients: one process, one thread and one connection per
stream, sending ``POST /v1/window`` to the gateway over loopback.

Never imports JAX: the server process holds the chip. Started by
``bench/run.py`` with a JSON job as its one argument; it

1. opens every stream's session and sends its ``warm`` windows, each one
   after the reply to the last; a window's fields come from the
   configuration's input module (``bench/inputs``), from the seed;
2. prints ``ready`` and waits for ``go <t_open>`` on its standard input
   (``t_open`` on ``time.monotonic``, which both processes share);
3. sends windows until ``t_open + seconds``: in a closed loop the next one
   as soon as the reply is in, in an open loop each at its scheduled time
   (or as soon as the stream's last reply is in, if that is later);
4. waits for the replies still in flight and prints one JSON line: every
   window's timing, status, ``best`` and ``scores_sha256``, and the
   windows that were due in the window but never sent.
"""
from __future__ import annotations

import json
import os
import sys
import threading
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from bench import inputs  # noqa: E402
from bench import traffic as tr  # noqa: E402
from bench.wire import Client  # noqa: E402


def _session(job: dict, i: int) -> tuple[str, str, int]:
    return (f"t{i % job['tenants']}", f"c{i}", i % job["tasks"])


def window_body(sid: str, seq: int, deadline_ms: float,
                fields: dict) -> dict:
    """The ``POST /v1/window`` body: the session's keys, then the
    window's fields."""
    return {"session": sid, "seq": seq, "deadline_ms": deadline_ms, **fields}


def _stream(job: dict, mod, i: int, go: threading.Event, start: dict,
            ready: threading.Barrier, out: dict) -> None:
    tenant, stream, task = _session(job, i)
    sid = f"{tenant}/{stream}"
    cli = Client(job["host"], job["port"], job["timeout_s"])
    wins = mod.windows(job["traffic"], job["config"], job["seed"], i)
    recs, unsent = [], []
    out[i] = (recs, unsent)
    seq = 0

    def send(t_sched: float) -> float:
        nonlocal seq
        body = window_body(sid, seq, job["deadline_ms"],
                           mod.fields(next(wins)))
        t_send = time.monotonic()
        try:
            status, reply = cli.request("POST", "/v1/window", body)
        except Exception as e:  # noqa: BLE001 — a transport failure is data
            status, reply = -1, {"error": f"{type(e).__name__}: {e}"}
        t_reply = time.monotonic()
        ok = status == 200 and isinstance(reply, dict)
        recs.append([i, seq, t_sched, t_send, t_reply, status,
                     reply.get("best") if ok else None,
                     reply.get("scores_sha256") if ok else
                     str(reply)[:200]])
        seq += 1
        return t_reply

    try:
        status, reply = cli.request("POST", "/v1/session", {
            "tenant": tenant, "stream": stream, "task": task})
        if status != 200:
            raise RuntimeError(f"session {sid}: {status} {reply}")
        for _ in range(job["warm"]):
            send(time.monotonic())
    finally:
        ready.wait()
    go.wait()
    t_open = start["t_open"]
    t_close = t_open + job["seconds"]
    if job["traffic"]["loop"] == "closed":
        while time.monotonic() < t_close:
            send(time.monotonic())
    else:
        for off in start["arrivals"][i]:
            t_sched = t_open + float(off)
            now = time.monotonic()
            if now >= t_close:
                unsent.append([i, t_sched])
                continue
            if t_sched > now:
                time.sleep(t_sched - now)
            send(t_sched)
    cli.close()


def main() -> int:
    job = json.loads(sys.argv[1])
    mod = inputs.load(job["input"])
    n = job["streams"]
    go, start, out = threading.Event(), {}, {}
    ready = threading.Barrier(n + 1)
    if job["traffic"]["loop"] == "open":
        start["arrivals"] = tr.schedule(job["traffic"], n, job["seconds"],
                                        job["seed"])
    threads = [threading.Thread(target=_stream, name=f"client-{i}",
                                args=(job, mod, i, go, start, ready, out),
                                daemon=True) for i in range(n)]
    for th in threads:
        th.start()
    ready.wait()
    print("ready", flush=True)
    line = sys.stdin.readline().split()
    if not line or line[0] != "go":
        return 2
    start["t_open"] = float(line[1])
    go.set()
    for th in threads:
        th.join()
    records = [r for i in range(n) for r in out.get(i, ([], []))[0]]
    unsent = [u for i in range(n) for u in out.get(i, ([], []))[1]]
    print(json.dumps({"records": records, "unsent": unsent}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
