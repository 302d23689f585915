"""The numpy traffic generator: its bit packing is the program's, its
mixes give the intended valid counts, and a seed fixes every window."""
from __future__ import annotations

import json
import os
import subprocess
import sys

import jax.numpy as jnp
import numpy as np
import pytest

from bench import traffic as tr
from bench_toy import ROOT, TOY_TORR
from repro.core import hdc


def _windows(mix, n_max, D, seed, stream, n):
    gen = tr.StreamGen(mix, n_max, D, seed, stream)
    return [gen.next() for _ in range(n)]


def _mix(name: str) -> dict:
    with open(os.path.join(ROOT, "bench", "traffic", name + ".json")) as f:
        return json.load(f)


def test_pack_bits_matches_the_program():
    rng = np.random.default_rng(0)
    bip = np.where(rng.random((3, 5, 256)) < 0.5, 1, -1).astype(np.int8)
    want = np.asarray(hdc.pack_bits(jnp.asarray(bip)))
    got = tr.pack_bits(bip)
    assert got.dtype == np.uint32 and np.array_equal(got, want)
    assert np.array_equal(tr.unpack_bits(got, 256), bip)


@pytest.mark.parametrize("name", ["coherent_closed16", "crowded_closed16"])
def test_mix_valid_counts_and_seed_determinism(name):
    mix = _mix(name)
    n_max, D = TOY_TORR["N_max"] * 8, TOY_TORR["D"]
    wins = _windows(mix, n_max, D, seed=2**31 + 11, stream=3, n=40)
    again = _windows(mix, n_max, D, seed=2**31 + 11, stream=3, n=40)
    other = _windows(mix, n_max, D, seed=2**31 + 12, stream=3, n=40)
    for (q, v, b), (q2, v2, b2) in zip(wins, again):
        assert np.array_equal(q, q2) and np.array_equal(v, v2)
        assert np.array_equal(b, b2)
    assert not np.array_equal(wins[1][0], other[1][0])
    counts = np.array([v.sum() for _q, v, _b in wins])
    if "first" in mix["valid"]:
        assert (counts == mix["valid"]["first"]).all()
        assert all(v[:mix["valid"]["first"]].all() for _q, v, _b in wins)
    else:
        assert abs(counts.mean() / n_max - mix["valid"]["p"]) < 0.03


def test_coherent_mix_reuses_rows():
    mix = _mix("coherent_closed16")
    D = TOY_TORR["D"]
    wins = _windows(mix, 128, D, seed=5, stream=0, n=60)
    kept = flipped = fresh = 0
    for (q0, v, _), (q1, _v, _) in zip(wins, wins[1:]):
        ham = np.bitwise_count(q0 ^ q1).sum(axis=1)[v]
        kept += int((ham == 0).sum())
        flipped += int((ham == round(mix["content"]["flip_frac"] * D)).sum())
        fresh += int((ham > D // 4).sum())
    total = kept + flipped + fresh
    assert total == 59 * mix["valid"]["first"]
    assert abs(kept / total - mix["content"]["keep"]) < 0.08
    assert abs(flipped / total - mix["content"]["flip"]) < 0.08


def test_schedule_is_the_same_set_for_every_seed():
    mix = dict(_mix("coherent_closed16"), loop="open", rate_per_s=64.0,
               schedule_seed=0)
    a = tr.schedule(mix, 16, 10.0, seed=3)
    b = tr.schedule(mix, 16, 10.0, seed=2**31 + 4)
    assert sorted(map(len, a)) == sorted(map(len, b))
    assert abs(sum(map(len, a)) / 10.0 - 64.0) < 10.0
    assert all((x >= 0).all() and (x < 10.0).all() for x in a)


def test_client_never_imports_jax():
    code = ("import sys; sys.path.insert(0, %r); import bench.client; "
            "assert 'jax' not in sys.modules, 'client imported jax'" % ROOT)
    subprocess.run([sys.executable, "-c", code], check=True)


def test_client_resends_once_the_server_closed_an_idle_connection():
    import socket
    import threading

    from bench.wire import Client

    srv = socket.socket()
    srv.bind(("127.0.0.1", 0))
    srv.listen(2)

    def serve():
        for n in (1, 2):                  # one request per connection,
            conn, _ = srv.accept()        # then the server closes it
            buf = b""
            while b"\r\n\r\n" not in buf:
                buf += conn.recv(4096)
            body = b'{"n": %d}' % n
            conn.sendall(b"HTTP/1.1 200 OK\r\nContent-Type: application/json"
                         b"\r\nContent-Length: %d\r\n\r\n" % len(body) + body)
            conn.close()
    th = threading.Thread(target=serve, daemon=True)
    th.start()
    cli = Client("127.0.0.1", srv.getsockname()[1], 5.0)
    assert cli.request("GET", "/") == (200, {"n": 1})
    th.join(0.2)                          # the close is in before the resend
    assert cli.request("GET", "/") == (200, {"n": 2})
    cli.close()
    th.join(5.0)
    srv.close()
