"""Autotune the batched XNOR-popcount kernel's TQ/TM block shapes.

Times the batched ``packed_hamming_batched`` kernel on a
multi-stream-shaped workload for each (tq, tm) candidate — all in this one
process, each candidate passed to the kernel explicitly (one process keeps
one claim on the device; the kernel clips each candidate to a TPU-legal
tile, and candidates that clip to the same tiles are timed once) — and
emits the winning shapes as a JSON artifact::

    {"best": {"tq": .., "tm": ..}, "grid": [{"tq":..,"tm":..,"us":..}, ..],
     "workload": {"N": .., "M": .., "D": ..},
     "device": {"platform": .., "kind": .., "count": ..}}

Artifact path: ``TORR_AUTOTUNE_OUT`` env var, default
``autotune_blocks.json`` in the working directory. Point ``TORR_TUNE_FILE``
at the written artifact and every kernel consumer (the direct defaults,
``kernels.ops``'s tile caps and the fused family) loads the swept winner at
import — no hand-exported ``TORR_TQ``/``TORR_TM`` needed; explicit env vars
still win (precedence table in ``kernels.xnor_popcount_sim``). A winner is
a statement about the device in ``device``: on a TPU sweep a denser grid
(the module docstring of ``xnor_popcount_sim`` suggests TQ in {8,16,32} x
TM in {128,256,512}); the defaults here are kept small so a CPU run (the
interpret-mode grid) stays fast.

Rows: ``autotune/tq<tq>_tm<tm>, <us>, us`` per candidate plus
``autotune/best, <us>, tq=..|tm=..|platform=..``.
"""
from __future__ import annotations

import json
import os
import time

import jax

from repro.core import hdc
from repro.kernels.xnor_popcount_sim import (lane_tile,
                                             packed_hamming_batched,
                                             sublane_tile)


def _time_combo(q, h, tq: int, tm: int, iters: int) -> float:
    """Per-call latency (us) of the kernel at explicit block shapes."""
    fn = lambda: packed_hamming_batched(q, h, tq=tq, tm=tm)  # noqa: E731
    jax.block_until_ready(fn())              # compile
    t0 = time.perf_counter()
    for _ in range(iters):
        out = fn()
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / iters * 1e6


def run(tq_grid=(8, 16), tm_grid=(128, 256), N: int = 16, M: int = 256,
        D: int = 4096, iters: int = 3) -> list[tuple]:
    """Sweep the grid, report each candidate, persist the best as JSON."""
    q = hdc.pack_bits(hdc.random_hv(jax.random.PRNGKey(0), (N, D)))
    h = hdc.pack_bits(hdc.random_hv(jax.random.PRNGKey(1), (M, D)))
    grid, seen = [], set()
    for tq in tq_grid:
        for tm in tm_grid:
            tiles = (sublane_tile(N, tq), lane_tile(M, tm))
            if tiles in seen:
                continue
            seen.add(tiles)
            grid.append({"tq": tiles[0], "tm": tiles[1],
                         "us": _time_combo(q, h, *tiles, iters)})
    best = min(grid, key=lambda r: r["us"])

    dev = jax.devices()[0]
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(jax.devices())}
    artifact = {
        "best": {"tq": best["tq"], "tm": best["tm"]},
        "grid": grid,
        "workload": {"N": N, "M": M, "D": D, "iters": iters},
        "device": device,
    }
    out_path = os.environ.get("TORR_AUTOTUNE_OUT", "autotune_blocks.json")
    with open(out_path, "w") as f:
        json.dump(artifact, f, indent=1, sort_keys=True)

    rows = [(f"autotune/tq{r['tq']}_tm{r['tm']}", round(r["us"], 1), "us")
            for r in grid]
    rows.append(("autotune/best", round(best["us"], 1),
                 f"tq={best['tq']}|tm={best['tm']}|json={out_path}"
                 f"|platform={device['platform']}|kind={device['kind']}"
                 "|apply_via=TORR_TUNE_FILE"))
    return rows


if __name__ == "__main__":
    for row in run():
        print(",".join(str(x) for x in row))
