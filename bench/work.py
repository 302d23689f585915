"""The work a window needs, and the chip's peaks: the yardstick of the
utilisation and roofline metrics. It is computed from the path counters,
so the roofline reads the same work whatever kernel implements it.

* A full-path proposal needs 2 * M * D' int8 operations: Hamming distance
  against every concept in its ±1 matmul form, the fastest known form of
  it on this chip.
* A delta-path proposal needs 2 * |Delta| * M (Eq. 6 over its flipped
  dimensions).
* The compulsory bytes of the full-path scan: the packed item memory at D'
  read once per step, each full-path query once, and one f32 score row per
  full-path proposal.
"""
from __future__ import annotations

import json
import os

PEAKS = os.path.join(os.path.dirname(os.path.abspath(__file__)), "peaks.json")


def peaks(device_kind: str, path: str = PEAKS) -> dict:
    """The peaks of one ``device_kind``; an unknown kind is an error."""
    with open(path) as f:
        table = json.load(f)["devices"]
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r}; known: "
                       f"{sorted(table)}")
    return table[device_kind]


def ops(M: int, d_eff: int, n_full: float, delta_dims: float) -> float:
    """int8 operations the windows needed: full scans plus Eq. 6."""
    return 2.0 * M * d_eff * n_full + 2.0 * M * delta_dims


def scan_bytes(M: int, d_eff: int, n_full: float, steps: float) -> float:
    """Compulsory HBM bytes of the full-path scans."""
    return steps * M * d_eff / 8.0 + n_full * (d_eff / 8.0 + 4.0 * M)


def roofline_s(n_ops: float, n_bytes: float, peak: dict) -> tuple:
    """``(seconds, bound)``: the least time the chip could take, and
    whether operations or bytes bound it."""
    t_ops = n_ops / peak["int8_ops_per_s"]
    t_bytes = n_bytes / peak["hbm_bytes_per_s"]
    return (t_ops, "ops") if t_ops >= t_bytes else (t_bytes, "bytes")
