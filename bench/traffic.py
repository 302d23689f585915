"""Window contents and arrival schedules, from a traffic file and a seed.

numpy only: the client process that sends the windows imports this module
and never imports JAX. One general generator reads every mix; a mix is a
data file ``bench/traffic/<name>.json`` with these keys:

``loop``       ``"closed"`` (each stream sends its next window when the
               reply to the last one is in) or ``"open"`` (each stream
               sends on its own Poisson schedule).
``valid``      which of the N_max proposal rows of a window are valid:
               ``{"first": n}`` (rows 0..n-1) or ``{"p": x}`` (each row
               with probability x, at least one).
``content``    how a stream's rows change from one window to the next:
               ``{"keep": a, "flip": b, "flip_frac": f}`` (each valid row
               keeps its query with probability a, flips ``f * D`` distinct
               dimensions with probability b, else is drawn fresh: the
               reuse mix of ``benchmarks/micro_aligner.py::_mix_trace``) or
               ``{"bit_flips": n}`` (n single-bit flips at random rows, as
               ``benchmarks/loadgen.py::_FrameGen`` does).
``warm``       windows per stream answered before the measured window
               opens (the first is the cold window).
``rate_per_s`` open loop only: the aggregate arrival rate.
``schedule_seed`` open loop only: the arrivals are drawn once from this
               seed, and a run's seed only rotates them over the streams,
               so every seed offers the same set of arrivals.

The content of window t of stream s depends only on (seed, s, t), never on
timing, so the reference replays exactly what was sent.
"""
from __future__ import annotations

import numpy as np


def pack_bits(bipolar: np.ndarray) -> np.ndarray:
    """Bipolar int8 [..., D] -> uint32 [..., D // 32]; bit d % 32 of word
    d // 32 is 1 where dimension d is +1."""
    bits = np.asarray(bipolar) > 0
    packed = np.packbits(bits, axis=-1, bitorder="little")
    return np.ascontiguousarray(packed).view("<u4").astype(np.uint32)


def unpack_bits(packed: np.ndarray, D: int) -> np.ndarray:
    """Inverse of :func:`pack_bits`: uint32 [..., D // 32] -> int8 ±1."""
    raw = np.ascontiguousarray(packed.astype("<u4")).view(np.uint8)
    bits = np.unpackbits(raw, axis=-1, bitorder="little")[..., :D]
    return np.where(bits == 1, 1, -1).astype(np.int8)


def _stream_rng(seed: int, stream: int, salt: int) -> np.random.Generator:
    return np.random.default_rng([int(seed), int(stream), int(salt)])


class StreamGen:
    """The windows of one client stream, in order."""

    def __init__(self, traffic: dict, n_max: int, D: int, seed: int,
                 stream: int):
        self.n_max, self.D, self.words = n_max, D, D // 32
        self.valid_spec = traffic["valid"]
        self.content = traffic["content"]
        self.rng = _stream_rng(seed, stream, 0)
        self.q = self.rng.integers(0, 1 << 32, (n_max, self.words),
                                   dtype=np.uint32)
        self.boxes = self.rng.random((n_max, 4), dtype=np.float32)
        self.t = 0

    def _valid(self) -> np.ndarray:
        spec = self.valid_spec
        if "first" in spec:
            valid = np.zeros(self.n_max, bool)
            valid[:int(spec["first"])] = True
            return valid
        valid = self.rng.random(self.n_max) < float(spec["p"])
        if not valid.any():
            valid[0] = True
        return valid

    def _flip_dims(self, row: int, n: int) -> None:
        dims = self.rng.choice(self.D, n, replace=False)
        np.bitwise_xor.at(self.q[row], dims // 32,
                          (np.uint32(1) << (dims % 32).astype(np.uint32)))

    def next(self) -> tuple[np.ndarray, np.ndarray, np.ndarray]:
        """``(q uint32 [N_max, D/32], valid bool [N_max], boxes f32
        [N_max, 4])`` of the stream's next window."""
        valid = self._valid()
        c = self.content
        if self.t and "keep" in c:
            rows = np.flatnonzero(valid)
            r = self.rng.random(rows.size)
            n_flip = max(1, int(round(float(c["flip_frac"]) * self.D)))
            keep, flip = float(c["keep"]), float(c["keep"]) + float(c["flip"])
            for row, x in zip(rows, r):
                if x < keep:
                    continue
                if x < flip:
                    self._flip_dims(row, n_flip)
                else:
                    self.q[row] = self.rng.integers(
                        0, 1 << 32, self.words, dtype=np.uint32)
        elif self.t and "bit_flips" in c:
            n = int(c["bit_flips"])
            rows = self.rng.integers(0, self.n_max, n)
            dims = self.rng.integers(0, self.D, n)
            for row, d in zip(rows, dims):
                self.q[row, d // 32] ^= np.uint32(1) << np.uint32(d % 32)
        self.t += 1
        return self.q.copy(), valid, self.boxes


def schedule(traffic: dict, n_streams: int, seconds: float,
             seed: int) -> list[np.ndarray]:
    """Open loop: per-stream arrival offsets in [0, seconds), from the
    mix's fixed ``schedule_seed``; ``seed`` rotates them over the streams."""
    rng = np.random.default_rng(int(traffic["schedule_seed"]))
    per_stream = float(traffic["rate_per_s"]) / n_streams
    arrivals = []
    for _ in range(n_streams):
        gaps = rng.exponential(1.0 / per_stream,
                               int(per_stream * seconds * 2 + 64))
        t = np.cumsum(gaps)
        while t[-1] < seconds:      # a long draw: extend, same stream
            t = np.concatenate([t, t[-1] + np.cumsum(
                rng.exponential(1.0 / per_stream, 64))])
        arrivals.append(t[t < seconds])
    shift = int(seed) % n_streams
    return arrivals[shift:] + arrivals[:shift]
