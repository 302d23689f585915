"""XNOR-popcount associative similarity kernel (paper Sec. 4.2/4.3, full path).

TPU adaptation of the ASIC's shared bipolar-cosine micro-kernel: hypervectors
are packed 32 dims/word; XOR + population_count on the VPU gives the hamming
distance, and dot = d_eff - 2*hamming. Bank gating (D') is realized by
*static word-count specialization* — the wrapper slices the enabled prefix of
words, so each D' compiles to a kernel that genuinely reads less memory
(the TPU analogue of SRAM bank enables).

Grid: (query-tiles, class-tiles, word-tiles), word dim fastest so each
(n, m) output block accumulates hamming counts across word tiles in VMEM.
Each program processes a TQ x TM block of the output — a *block of queries*
per program rather than one row — which is what lets the multi-stream
engine amortize the item-memory tile across S stream slots' proposals
(S * N_max query rows per window batch).

Block shapes: item-memory tile (TM, TW) uint32 in VMEM; TW is a multiple of
128 (lane width), TM a multiple of 8 (sublane), TQ a small sublane-multiple
(8 by default) so the TQ x TM x TW xor intermediate stays VMEM-resident.
The TPU compiler accepts a block only where its last two dimensions divide
by 8 and 128 or equal the array's: :func:`sublane_tile`/:func:`lane_tile`
clip every tile of this package to that rule (a dimension that has no such
divisor is taken whole), so interpret mode runs the exact grid the chip
compiles.
The M x TW tile is broadcast against TQ query rows — the analogue of the
ASIC's column broadcast to W class lanes, repeated over a query block.

TPU autotuning without code edits: the ``tq``/``tm`` defaults are
overridable through environment variables and/or the autotune sweep's JSON
artifact, all read once at import. Precedence (highest first):

    knob | source              | default | constraint
    ---- | ------------------- | ------- | ---------------------------------
    tq   | ``TORR_TQ`` env     |       8 | query-block rows; sublane
         | ``TORR_TUNE_FILE``  |         | multiple (8) preferred, clipped
         | artifact ``best.tq``|         | to divide N
    tm   | ``TORR_TM`` env     |     128 | class-tile rows; multiple of 8,
         | ``TORR_TUNE_FILE``  |         | clipped to divide M
         | artifact ``best.tm``|         |
    tw   | (fixed)             |     128 | word-tile = lane width; not
         |                     |         | tunable (whole row if W % 128)

``TORR_TUNE_FILE`` points at the JSON artifact written by
``benchmarks/autotune_blocks.py`` (``{"best": {"tq": .., "tm": ..}, ...}``),
so a sweep's winner applies fleet-wide without hand-exported shape vars;
an explicit ``TORR_TQ``/``TORR_TM`` still wins over the file, and a
missing/corrupt file named by the env var is an error, not a silent
fallback. The built-in defaults are interpret-mode safe and
VMEM-conservative (TQ*TM*TW*4B = 512 KiB intermediate at 8x128x128); on
real TPU sweep ``TORR_TQ in {8, 16, 32}`` x ``TORR_TM in {128, 256, 512}``
against ``benchmarks/micro_aligner.py`` — the direct kernel defaults, the
tile caps used by ``kernels.ops`` and the fused family in
``kernels.fused_window`` all honor the overrides, so no call site changes.
"""
from __future__ import annotations

import functools
import json
import os

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _tuned_tiles() -> dict:
    """Block shapes from the ``TORR_TUNE_FILE`` autotune artifact (the JSON
    written by ``benchmarks/autotune_blocks.py``); {} when unset."""
    path = os.environ.get("TORR_TUNE_FILE", "")
    if not path:
        return {}
    try:
        with open(path) as f:
            artifact = json.load(f)
        best = artifact["best"]
        return {"tq": int(best["tq"]), "tm": int(best["tm"])}
    except (OSError, ValueError, KeyError, TypeError) as e:
        raise ValueError(
            f"TORR_TUNE_FILE={path!r} is not a readable autotune artifact "
            f"({{'best': {{'tq': .., 'tm': ..}}}}): {e}") from None


def _env_tile(name: str, default: int, tuned: int | None = None) -> int:
    """Block-shape override: env var wins, then the tune-file artifact,
    then the built-in default (bad values rejected)."""
    raw = os.environ.get(name, "")
    if not raw:
        val = default if tuned is None else tuned
    else:
        try:
            val = int(raw)
        except ValueError:
            raise ValueError(f"{name}={raw!r} is not an integer") from None
    if val <= 0:
        raise ValueError(f"{name}={val} must be positive")
    return val


_TUNED = _tuned_tiles()
TQ_DEFAULT = _env_tile("TORR_TQ", 8, _TUNED.get("tq"))
TM_DEFAULT = _env_tile("TORR_TM", 128, _TUNED.get("tm"))
TW = 128   # lane width; fixed


def fit_tile(n: int, cap: int) -> int:
    """Largest divisor of ``n`` that is <= ``cap`` (>= 1).

    Decrements (trace-time only; n is at most a few thousand) rather than
    halving so a non-power-of-two cap — e.g. TORR_TM=192 against M=1024 —
    lands on the biggest usable divisor (128) instead of degenerating to 1.
    Shared by this module's block-shape clipping and ``kernels.ops``'s tile
    caps."""
    t = max(1, min(cap, n))
    while n % t:
        t -= 1
    return t


def sublane_tile(n: int, cap: int) -> int:
    """Row tile for a block's second-to-last dimension: the largest
    multiple of 8 dividing ``n`` that is <= max(cap, 8), or ``n`` itself
    when ``n`` is not a multiple of 8 (a whole-dimension block)."""
    if n % 8:
        return n
    return 8 * fit_tile(n // 8, max(cap, 8) // 8)


def lane_tile(n: int, cap: int) -> int:
    """Tile for a block's last dimension: the largest multiple of the
    128-lane width dividing ``n`` that is <= max(cap, 128), or ``n`` itself
    when ``n`` is not lane-aligned (a whole-dimension block)."""
    if n % TW:
        return n
    return TW * fit_tile(n // TW, max(cap, TW) // TW)


def resolve_interpret(interpret: bool | None) -> bool:
    """None -> interpret off-TPU only (the BlockSpecs are TPU-shaped)."""
    if interpret is None:
        return jax.default_backend() != "tpu"
    return interpret


def _kernel(q_ref, im_ref, ham_ref):
    w = pl.program_id(2)

    @pl.when(w == 0)
    def _init():
        ham_ref[...] = jnp.zeros_like(ham_ref)

    q = q_ref[...]                                              # [TQ, TW]
    im = im_ref[...]                                            # [TM, TW]
    x = jnp.bitwise_xor(q[:, None, :], im[None, :, :])          # [TQ, TM, TW]
    pc = jax.lax.population_count(x).astype(jnp.int32)
    ham_ref[...] += jnp.sum(pc, axis=-1)                        # [TQ, TM]


@functools.partial(jax.jit, static_argnames=("tq", "tm", "tw", "interpret"))
def packed_hamming_batched(
    q_packed: jax.Array,    # uint32 [N, W_eff]  (already sliced to enabled words)
    im_packed: jax.Array,   # uint32 [M, W_eff]
    *,
    tq: int | None = None,
    tm: int | None = None,
    tw: int = TW,
    interpret: bool | None = None,
) -> jax.Array:
    """Hamming distance of every query to every class: int32 [N, M].

    One grid program covers a (tq, tm) output block, so a batch of queries
    (e.g. all proposals of all admitted streams in one multi-stream window)
    reuses each item-memory tile tq times from VMEM. Used by both the
    full-path scan and the cache-nearest lookup (`ops.cache_nearest`), which
    is just this kernel with the query cache as the "item memory".

    ``tq``/``tm`` default to the ``TORR_TQ``/``TORR_TM`` environment
    overrides (module docstring has the defaults table).
    """
    N, W = q_packed.shape
    M, W2 = im_packed.shape
    assert W == W2, (W, W2)
    # clip the requested (or env-default) block shapes to TPU-legal
    # divisors, so any TORR_TQ/TORR_TM sweep value yields a runnable grid
    tq = sublane_tile(N, TQ_DEFAULT if tq is None else tq)
    tm = lane_tile(M, TM_DEFAULT if tm is None else tm)
    tw = lane_tile(W, tw)

    grid = (N // tq, M // tm, W // tw)
    return pl.pallas_call(
        _kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((tq, tw), lambda n, m, w: (n, w)),
            pl.BlockSpec((tm, tw), lambda n, m, w: (m, w)),
        ],
        out_specs=pl.BlockSpec((tq, tm), lambda n, m, w: (n, m)),
        out_shape=jax.ShapeDtypeStruct((N, M), jnp.int32),
        interpret=resolve_interpret(interpret),
    )(q_packed, im_packed)

