"""Plain reference of what the served path answers: numpy, one stream and
one proposal at a time, importing nothing of the program.

It follows the paper's window FSM (Fig. 3/4, Alg. 1, Eq. 5/6) as the
program states it for the uncontrolled plan (all banks Alg. 1 allows, all
bit-slice planes):

* Alg. 1 picks the banks from the window's valid count and queue depth
  (the worst case, all proposals full, must fit the FPS cycle budget);
  D' = banks * D / B, and the enabled dimensions are a prefix of words.
* Each valid proposal, in order, finds its nearest valid cache entry by
  Hamming distance over D' (rho = 1 - 2 ham / D', Eq. 5; first entry on
  ties). Bypass if rho >= tau_byp under high load H(N, q); delta if
  rho >= tau_q, |Delta| <= the delta budget and the entry's accumulator
  was made under the same plan; else full.
* Bypass answers the entry's cached output and refreshes its age. Delta
  corrects the entry's accumulator by Eq. 6 over the flipped dimensions
  and rewrites that entry; full takes the dot product of the query with
  every concept over D' and writes the LRU slot (first invalid, else
  oldest). Every write ages the other entries.
* Scores are accumulator / D'. The reasoner is gated: when the top-k class
  indices (lower index first on ties) equal the nearest entry's and the
  top-1/top-2 margin is within ``margin_eps`` of its margin, the nearest
  entry's cached output is answered; otherwise scores * task weights.
* Invalid proposals answer a zero row; ``best`` is the first argmax.
"""
from __future__ import annotations

import hashlib

import numpy as np

from .traffic import unpack_bits

BYPASS, DELTA, FULL = 0, 1, 2
_AGE0 = np.iinfo(np.int32).max // 2


def select_banks(cfg: dict, n_valid: int, queue_depth: int) -> int:
    """Alg. 1's bank count: the largest whose all-full worst case fits the
    per-window cycle budget (f32 compare, as the controller computes it)."""
    mw = -(-cfg["M"] // cfg["W"])
    budget = np.float32(cfg["clock_hz"] / cfg["fps_target"]) / \
        np.float32(1.0 + queue_depth)
    n = max(n_valid, 1)
    best = 1
    for b in range(1, cfg["B"] + 1):
        worst = n * b * (cfg["D"] // cfg["B"]) * mw + n * (mw + 64)
        if np.float32(worst) <= budget:
            best = b
    return best


def topk_stable(s: np.ndarray, k: int) -> np.ndarray:
    """The first k of a stable descending sort: the k largest, lower index
    first on ties. Only the values at or above the k-th largest are
    sorted."""
    if s.size <= k:
        return np.argsort(-s, kind="stable")[:k]
    kth = np.partition(s, s.size - k)[s.size - k]
    top = np.flatnonzero(s >= kth)
    return top[np.argsort(-s[top], kind="stable")[:k]]


class Stream:
    """One stream's query cache and the windows it has answered."""

    def __init__(self, cfg: dict, codes: np.ndarray, task_w: np.ndarray,
                 codes_f: np.ndarray | None = None):
        self.cfg = cfg
        K, M, W = cfg["K"], cfg["M"], cfg["D"] // 32
        self.codes = codes                       # int8 [M, D]
        self.codes_t = np.ascontiguousarray(codes.T)     # int8 [D, M]
        # f32 holds every ±1 dot product exactly (|dot| <= D < 2**24);
        # streams of one run may share it
        self.codes_f = codes.astype(np.float32) if codes_f is None \
            else codes_f
        self.w = task_w.astype(np.float32)
        self.packed = np.zeros((K, W), np.uint32)
        self.tag = np.full(K, -1, np.int64)
        self.age = np.full(K, _AGE0, np.int64)
        self.valid = np.zeros(K, bool)
        self.acc = np.zeros((K, M), np.int64)
        self.out = np.zeros((K, M), np.float32)
        self.key = np.full((K, cfg["top_k"]), -1, np.int64)
        self.margin = np.zeros(K, np.float32)
        # each proposal row's last full dot products, with the query and
        # the number of words they were taken over (0: none yet)
        self.dot = np.zeros((cfg["N_max"], M), np.int64)
        self.dot_q = np.zeros((cfg["N_max"], W), np.uint32)
        self.dot_words = np.zeros(cfg["N_max"], np.int64)

    def _flip_sum(self, flipped: np.ndarray, sign: np.ndarray) -> np.ndarray:
        """Sum over the ``flipped`` dimensions of twice the new ``sign``
        times the concepts' column there: what Eq. 6 adds to every
        concept's dot product (int64 [M]; f32 holds it exactly, since
        its magnitude is at most 2 D < 2**24)."""
        return np.rint((2 * sign).astype(np.float32)
                       @ self.codes_t[flipped].astype(np.float32)
                       ).astype(np.int64)

    def _full_dots(self, q: np.ndarray, rows: np.ndarray,
                   words: int) -> np.ndarray:
        """The dot products over the first ``words * 32`` dimensions of
        each of ``rows``' queries with every concept (int64 [N_max, M],
        by row). A row's last products are corrected over the dimensions
        flipped since they were taken, each flip adding twice the new
        sign times the concepts' column there; where many flipped, or
        D' changed, they are taken afresh in one f32 matmul. Both are
        exact on ±1 integers."""
        dims = words * 32
        diff = q[rows, :words] ^ self.dot_q[rows, :words]
        n_flip = np.bitwise_count(diff).sum(axis=1, dtype=np.int64)
        afresh = (self.dot_words[rows] != words) | (n_flip > dims // 16)
        new = rows[afresh]
        if new.size:
            q_bip = unpack_bits(q[new, :words], dims)
            self.dot[new] = np.rint(
                q_bip.astype(np.float32) @ self.codes_f[:, :dims].T
            ).astype(np.int64)
        moved = ~afresh & (n_flip > 0)
        for i, d in zip(rows[moved], diff[moved]):
            flipped = np.flatnonzero(unpack_bits(d, dims) > 0)
            self.dot[i] += self._flip_sum(
                flipped, unpack_bits(q[i, :words], dims)[flipped])
        self.dot_q[rows] = q[rows]
        self.dot_words[rows] = words
        return self.dot

    def _write(self, slot: int, q, acc, out, key, margin, tag) -> None:
        self.age += 1
        self.age[slot] = 0
        self.valid[slot] = True
        self.packed[slot], self.tag[slot] = q, tag
        self.acc[slot], self.out[slot] = acc, out
        self.key[slot], self.margin[slot] = key, margin

    def window(self, q: np.ndarray, valid: np.ndarray,
               queue_depth: int = 0):
        """Answer one window: ``(scores f32 [N_max, M], paths)``
        where ``paths`` counts the valid proposals per path."""
        cfg = self.cfg
        n_valid = int(valid.sum())
        banks = select_banks(cfg, n_valid, queue_depth)
        words = banks * cfg["D"] // cfg["B"] // 32
        d_eff = np.float32(banks * cfg["D"] // cfg["B"])
        high = n_valid >= cfg["N_hi"] or queue_depth >= cfg["q_hi"]
        tau_byp, tau_q = np.float32(cfg["tau_byp"]), np.float32(cfg["tau_q"])
        eps = np.float32(cfg["margin_eps"])
        tag = banks                  # every plan here keeps all planes
        scores = np.zeros((cfg["N_max"], cfg["M"]), np.float32)
        paths = [0, 0, 0]
        rows = np.flatnonzero(valid)
        dims = words * 32
        full_dot = self._full_dots(q, rows, words)
        for i in rows:
            qi = q[i]
            ham = np.bitwise_count(self.packed[:, :words] ^ qi[:words]) \
                .sum(axis=1, dtype=np.int64)
            rho = np.float32(1.0) - np.float32(2.0) * \
                ham.astype(np.float32) / d_eff
            rho = np.where(self.valid, rho, np.float32(-np.inf))
            idx = int(np.argmax(rho))
            if rho[idx] >= tau_byp and high:
                path = BYPASS
            elif (rho[idx] >= tau_q and ham[idx] <= cfg["delta_budget"]
                  and self.tag[idx] == tag):
                path = DELTA
            else:
                path = FULL
            paths[path] += 1
            if path == BYPASS:
                self.age += 1
                self.age[idx] = 0
                scores[i] = self.out[idx]
                continue
            if path == DELTA:
                new = unpack_bits(qi, cfg["D"])[:dims]
                old = unpack_bits(self.packed[idx], cfg["D"])[:dims]
                flipped = np.flatnonzero(new != old)
                acc = self.acc[idx] + self._flip_sum(flipped, new[flipped])
            else:
                acc = full_dot[i]
            s = acc.astype(np.float32) / d_eff
            key = topk_stable(s, cfg["top_k"])
            margin = s[key[0]] - s[key[1]]
            match = (np.array_equal(key, self.key[idx])
                     and abs(margin - self.margin[idx]) <= eps)
            out = self.out[idx].copy() if match else s * self.w
            scores[i] = out
            slot = idx if path == DELTA else int(np.argmax(
                np.where(self.valid, self.age, np.iinfo(np.int64).max)))
            self._write(slot, qi, acc, out, key, margin, tag)
        return scores, paths


def answer(scores: np.ndarray) -> tuple[list, str]:
    """What the gateway replies for a window: ``best`` and the digest."""
    scores = np.ascontiguousarray(scores, np.float32)
    return (np.argmax(scores, axis=-1).tolist(),
            hashlib.sha256(scores.tobytes()).hexdigest())
