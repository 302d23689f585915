"""Proposal features in: the chip encodes q = sign(R z) (paper Sec. 3.2)
inside the served step (see ``bench/inputs/__init__.py`` for the input
module contract).

Each window is ``(z int8 [N_max, d], valid bool [N_max], boxes f32
[N_max, 4])``: every valid proposal's d-wide detector embedding,
quantized symmetrically to int8 per row with its scale dropped
(sign(R c z) = sign(R z) for any c > 0), zero rows elsewhere. The data
adds to the concept codes and the task bank (``bench/stack.py``'s, as
the packed module has them) the projection R, ±1 int8 ``[D, d]`` (the
Rademacher form of sign random projection), which the engine keeps on
the device. Every projection is then an exact integer,
|y| <= d * 127 < 2**24, so the program's int8 matmul with int32
accumulation and the reference's f32 matmul agree on every bit; y = 0
maps to +1.

The configuration's ``features`` block: ``dim`` (d, equal to the
``torr`` block's ``feat_dim``), ``dtype`` ``"int8"`` and ``projection``
``"rademacher_int8"``.

The traffic file's keys (this module alone reads them, beside the
harness's ``loop``, ``streams``, ``tenants``, ``warm``, ``rate_per_s`` and
``schedule_seed``, which ``bench/traffic.py`` documents):

``valid``    ``{"first": n}``: proposal rows 0..n-1 of every window are
             valid (n tracked objects).
``content``  ``{"keep": a, "drift": b, "drift_rel": r, "fresh": c}``, with
             a + b + c = 1: from a stream's second window each valid row's
             float feature stays the same with probability a, moves by
             Gaussian noise of norm r times its own with probability b (an
             angle of about r rad, so about r / pi of its D bits flip),
             else is drawn fresh; each row is then quantized.

The content of window t of stream s depends only on (seed, s, t).
"""
from __future__ import annotations

import numpy as np

from bench import reference as ref
from bench.traffic import pack_bits
from bench.wire import encode

answer = ref.answer
_SALT = 16          # the stream generator's, apart from bench/traffic.py's


def _quantize(x: np.ndarray) -> np.ndarray:
    """Symmetric int8 per row, the scale dropped; a zero row stays zero."""
    peak = np.abs(x).max(axis=-1, keepdims=True)
    scale = np.divide(127.0, peak, out=np.zeros_like(peak), where=peak > 0)
    return np.clip(np.rint(x * scale), -127, 127).astype(np.int8)


def windows(traffic: dict, config: dict, seed: int, stream: int):
    n_max, d = config["torr"]["N_max"], config["features"]["dim"]
    c = traffic["content"]
    keep = float(c["keep"])
    drift = keep + float(c["drift"])
    if abs(drift + float(c["fresh"]) - 1.0) > 1e-9:
        raise ValueError(f"keep + drift + fresh must be 1: {c}")
    rel = float(c["drift_rel"])
    rng = np.random.default_rng([int(seed), int(stream), _SALT])
    n_valid = int(traffic["valid"]["first"])
    valid = np.arange(n_max) < n_valid
    x = rng.standard_normal((n_valid, d))
    boxes = rng.random((n_max, 4), dtype=np.float32)
    z = np.zeros((n_max, d), np.int8)
    t = 0
    while True:
        if t:
            r = rng.random(n_valid)
            noise = rng.standard_normal((n_valid, d))
            noise *= rel * np.linalg.norm(x, axis=1, keepdims=True) \
                / np.linalg.norm(noise, axis=1, keepdims=True)
            moved = (r >= keep) & (r < drift)
            x[moved] += noise[moved]
            fresh = r >= drift
            x[fresh] = rng.standard_normal((int(fresh.sum()), d))
        z[:n_valid] = _quantize(x)
        t += 1
        yield z.copy(), valid, boxes


def fields(window) -> dict:
    z, valid, boxes = window
    return {"z": encode(z), "valid": encode(valid), "boxes": encode(boxes)}


def make_data(config: dict, seed: int) -> dict:
    """Codes and task bank as ``bench/stack.make_data`` makes them, and R
    ±1 int8 [D, d] from a key of its own, on the device."""
    import jax
    import jax.numpy as jnp

    from bench import stack

    t, f = config["torr"], config["features"]
    if (f["dtype"], f["projection"]) != ("int8", "rademacher_int8") \
            or f["dim"] != t["feat_dim"]:
        raise ValueError(f"features block not served here: {f}")
    codes, task_bank = stack.make_data(config, seed)

    @jax.jit
    def make(key):
        return jnp.where(jax.random.bernoulli(key, 0.5, (t["D"], f["dim"])),
                         jnp.int8(1), jnp.int8(-1))

    key_bits = np.random.SeedSequence([int(seed), _SALT]).generate_state(1)[0]
    return {"codes": codes, "task_bank": task_bank,
            "projection": make(jax.random.PRNGKey(int(key_bits)))}


def build(config: dict, data: dict, plan=None):
    """The stack as ``bench/stack.build`` builds it, with the projection
    passed to the engine, which then takes feature windows."""
    import jax

    from bench import stack
    from repro.core.item_memory import build_item_memory
    from repro.obs import MetricsRegistry
    from repro.serving.async_engine import AsyncStreamEngine
    from repro.serving.gateway import Gateway, GatewayLimits

    tcfg = stack.torr_config(config)
    im = jax.jit(build_item_memory, static_argnames="plane_total")(
        data["codes"], plane_total=tcfg.bit_planes)
    registry = MetricsRegistry()
    eng = AsyncStreamEngine(tcfg, im, n_slots=config["slots"], paused=True,
                            metrics=registry, projection=data["projection"])
    if plan is not None:
        eng.set_plan(plan)
    eng.warmup()
    eng.start()
    g = config["gateway"]
    limits = GatewayLimits(
        rate_per_s=g["rate_per_s"], burst=g["burst"],
        request_deadline_s=g["request_deadline_ms"] / 1e3,
        max_connections=g["max_connections"],
        max_sessions_per_tenant=g["max_sessions_per_tenant"])
    gw = Gateway(eng, tcfg, np.asarray(data["task_bank"]), limits=limits,
                 host="127.0.0.1", port=0, metrics=registry)
    return stack.Stack(cfg=tcfg, eng=eng, gw=gw, registry=registry)


def encode_packed(z: np.ndarray, r_t: np.ndarray) -> np.ndarray:
    """Packed words uint32 [n, D/32] of int8 features ``z`` [n, d] against
    ``r_t`` = R.T as f32 [d, D]: exact, since every product and sum is an
    integer below 2**24."""
    y = z.astype(np.float32) @ r_t
    return pack_bits(np.where(y >= 0, 1, -1))


def reference(config: dict, data: dict, stream: int):
    t = config["torr"]
    codes = data["codes"]
    replay = ref.Stream(t, codes, data["task_bank"][stream % config["tasks"]],
                        codes.astype(np.float32))
    r_t = np.ascontiguousarray(data["projection"].astype(np.float32).T)

    def window(w):
        z, valid, _boxes = w
        rows = np.flatnonzero(valid)
        q = np.zeros((t["N_max"], t["D"] // 32), np.uint32)
        q[rows] = encode_packed(z[rows], r_t)
        return replay.window(q, valid)
    return window
