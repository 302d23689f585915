"""Jit'd public wrappers around the Pallas kernels.

These are the entry points the rest of the framework uses. Each chooses the
kernel when shapes are kernel-friendly and transparently falls back to the
oracle otherwise (ragged shapes, tiny trailing dims), so callers never see a
shape constraint. ``interpret=None`` (the default) resolves by backend:
compiled on TPU, interpret mode elsewhere; ``True``/``False`` force one.

Bank gating contract: ``banks`` is a *static* int here. The controller's
per-window bank choice is latched on the host (exactly like the ASIC's
window-latched registers, Sec. 4.6) and dispatches one of <= B specialized
executables. Fully-jitted pipelines, where the per-window bank choice is a
*traced* value, instead go through ``repro.core.aligner.full_scores_all`` —
the ``lax.switch`` / bank-prefix dispatch over the same kernel family in
``kernels.fused_window`` — or, when the path mix is known first, the
compacted-bucket dispatch ``repro.core.aligner.compact_full_scores``
(see ``kernels/README.md`` for the three contracts and when to use which).

Precision gating rides the same contract: ``planes`` (of ``plane_total``
bit-slice planes, ``core.item_memory``'s plane striping) is a static knob
from the latched QoS plan. With all planes kept, the enabled words are the
bank prefix and the original fast path runs unchanged; with planes dropped,
the wrappers select the enabled words *plane-major* — a contiguous
per-plane-block prefix of the item memory's ``pmajor`` view when the caller
provides it, a static column gather otherwise — so the XNOR-popcount scan
genuinely reads fewer words, the TPU analogue of not reading the low-order
bit-slice SRAMs.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from ..core.item_memory import plane_sel
from . import fused_window, ref
from .sign_project import sign_project as _sign_kernel
from .xnor_popcount_sim import packed_hamming_batched as _ham_kernel


def _batched_hamming(
    q: jax.Array,           # uint32 [N, W_eff]
    h: jax.Array,           # uint32 [M, W_eff]
    *,
    interpret: bool | None,
    use_kernel: bool,
) -> jax.Array:
    """Shared dispatch for every packed-hamming consumer (full-path scans
    and cache-nearest lookups): the batched kernel when M tiles, the jnp
    oracle otherwise. The kernel clips its tiles to TPU-legal blocks (a
    sub-lane-width D' — small-D configs, deep reduced plans — is read as
    one whole-row word tile), honoring the TORR_TQ/TORR_TM overrides."""
    if use_kernel and h.shape[0] % 8 == 0:
        return _ham_kernel(q, h, interpret=interpret)
    return ref.packed_hamming_ref(q, h)


def _plan_columns(
    arrays: tuple[jax.Array, ...],
    banks: int,
    bank_words: int,
    planes: int | None,
    plane_total: int,
    pmajor: jax.Array | None = None,
) -> tuple[tuple[jax.Array, ...], int]:
    """Restrict packed-word arrays to a (banks, planes) plan's enabled words.

    Returns the restricted arrays (all in the *same* column order — hamming
    sums over columns, so any shared order is exact) and the effective
    dimension. Full precision keeps the original contiguous bank-prefix
    slice; reduced precision selects plane-major columns — via a contiguous
    per-plane-block prefix of ``pmajor`` for the array it replaces (the
    item memory, pre-permuted once at build), a static gather otherwise.
    """
    words_eff = banks * bank_words
    if planes is None or planes >= plane_total:
        return tuple(a[:, :words_eff] for a in arrays), 32 * words_eff
    sel = plane_sel(words_eff, planes, plane_total)
    out = []
    for i, a in enumerate(arrays):
        if i == len(arrays) - 1 and pmajor is not None:
            # pmajor's plane blocks span all words; the plan's enabled
            # prefix of plane block p starts at p * (total_words / P)
            wpb = pmajor.shape[1] // plane_total
            keep = words_eff // plane_total
            out.append(jnp.concatenate(
                [pmajor[:, p * wpb: p * wpb + keep] for p in range(planes)],
                axis=1))
        else:
            out.append(a[:, sel])
    return tuple(out), 32 * sel.size


def packed_similarity(
    q_packed: jax.Array,     # uint32 [N, W_total]
    im_packed: jax.Array,    # uint32 [M, W_total]
    *,
    banks: int,
    bank_words: int,
    planes: int | None = None,
    plane_total: int = 4,
    pmajor: jax.Array | None = None,
    interpret: bool | None = None,
    use_kernel: bool = True,
) -> tuple[jax.Array, jax.Array]:
    """Full-scan scores under the (banks, planes) plan's enabled dims.

    D' = 32 * banks * bank_words * planes / plane_total. Returns
    (acc int32 [N, M], cosine f32 [N, M]). N may be the flattened proposal
    batch of many streams; the kernel processes a block of queries per
    program, so each item-memory tile is read once per block. ``planes``
    (static, from the latched QoS plan; None = all) drops low-order
    bit-slice planes; pass ``pmajor`` (``ItemMemory.pmajor``) to read them
    as contiguous plane-block prefixes instead of gathered columns.
    """
    (q, h), d_eff = _plan_columns(
        (q_packed, im_packed), banks, bank_words, planes, plane_total,
        pmajor=pmajor)
    ham = _batched_hamming(q, h, interpret=interpret, use_kernel=use_kernel)
    acc = d_eff - 2 * ham
    return acc, acc.astype(jnp.float32) / d_eff


def fused_similarity(
    q_packed: jax.Array,     # uint32 [N, W_total]
    im_packed: jax.Array,    # uint32 [M, W_total]
    *,
    banks: int,
    bank_words: int,
    planes: int | None = None,
    plane_total: int = 4,
    pmajor: jax.Array | None = None,
    interpret: bool | None = None,
    use_kernel: bool = True,
) -> tuple[jax.Array, jax.Array, jax.Array, jax.Array]:
    """Host-latched entry to the *fused* window-step kernel
    (``kernels.fused_window.fused_scores``): one grid fuses the gated
    XNOR-popcount scan, the integer accumulation and the argmax/top-2
    readout, so neither the ``[N, M, W]`` xor nor a separate readout pass
    materializes. Same static ``(banks, planes)`` contract as
    :func:`packed_similarity`. Returns (acc int32 [N, M], cosine f32 [N, M],
    best int32 [N] — ``argmax(acc)``, top2 int32 [N, 2] — the two highest
    accumulators; ``top2[:, 0] - top2[:, 1]`` is the integer margin).
    """
    (q, h), d_eff = _plan_columns(
        (q_packed, im_packed), banks, bank_words, planes, plane_total,
        pmajor=pmajor)
    acc, best, top2 = fused_window.fused_scores_any(
        q, h, d_eff=d_eff, interpret=interpret, use_kernel=use_kernel)
    return acc, acc.astype(jnp.float32) / d_eff, best, top2


def encode_packed(
    z: jax.Array,   # f32 [N, d] encoder features, or int8 with an int8 R
    R: jax.Array,   # f32 [D, d] projection, or int8
    *,
    interpret: bool | None = None,
    use_kernel: bool = True,
) -> jax.Array:
    """Fused encode front-end: uint32 [N, D//32] = pack(sign(z @ R.T)).

    f32 operands, or int8 features against an int8 projection (int32
    accumulation: exact). On the Pallas lowering one kernel
    (``fused_window.sign_project_pack``) keeps the projection *and* the
    int8 bipolar code in VMEM; only the packed words are written. Off-TPU
    (and off-tile) the jnp form runs — XLA fuses the sign into the matmul
    there, and the pack is cheap."""
    N, _ = z.shape
    D, _ = R.shape
    lowering = fused_window._pallas_lowering(interpret)
    if use_kernel and lowering is not None and D % 32 == 0:
        return fused_window.sign_project_pack(z, R, interpret=lowering)
    return _encode_packed_jnp(z, R)


_encode_packed_jnp = jax.jit(ref.sign_project_pack_ref)


def cache_nearest(
    q_packed: jax.Array,      # uint32 [N, W_total] query batch
    cache_packed: jax.Array,  # uint32 [K, W_total] cached queries
    cache_valid: jax.Array,   # bool [K]
    *,
    banks: int,
    bank_words: int,
    planes: int | None = None,
    plane_total: int = 4,
    interpret: bool | None = None,
    use_kernel: bool = True,
) -> tuple[jax.Array, jax.Array, jax.Array]:
    """Batched PSU nearest-match: every query vs every cache entry.

    Same micro-kernel as the full-path scan — the cache's packed queries
    stand in for the item memory — so full-path and cache-nearest lookups
    share one specialized executable per (D', planes) plan. Returns
    (idx int32 [N], rho f32 [N] per Eq. 5, hamming int32 [N]); invalid
    entries are pushed to rho = -inf as in ``core.query_cache.nearest``.
    """
    (q, c), d_eff = _plan_columns(
        (q_packed, cache_packed), banks, bank_words, planes, plane_total)
    ham = _batched_hamming(q, c, interpret=interpret, use_kernel=use_kernel)
    rho = 1.0 - 2.0 * ham.astype(jnp.float32) / float(d_eff)
    rho = jnp.where(cache_valid[None, :], rho, -jnp.inf)
    idx = jnp.argmax(rho, axis=-1).astype(jnp.int32)
    n = jnp.arange(idx.shape[0])
    return idx, rho[n, idx], ham[n, idx]


def masked_hamming_all(
    q_packed: jax.Array,      # uint32 [N, W_total] query batch
    e_packed: jax.Array,      # uint32 [K, W_total] lookup entries
    wmask: jax.Array,         # bool [W_total] plan-enabled words (may be traced)
    *,
    interpret: bool | None = None,
    use_kernel: bool = True,
) -> jax.Array:
    """Plan-gated hamming lookup table: int32 [N, K], every query row vs
    every entry row, counted over the words ``wmask`` enables.

    The batched form of the per-proposal masked popcount inside
    ``core.query_cache.nearest`` — the one-wide-similarity-pass PSU shape
    the batched decide pass (``core.pipeline._decide_pass_batched``) runs
    over the window-entry cache snapshot and the proposal batch itself.
    Unlike the static-plan wrappers above, ``wmask`` may be a *traced*
    value (Alg. 1's per-window bank choice): both operands are pre-masked
    (disabled words zeroed on both sides, so their xor contributes zero
    popcount), which makes the plain packed-hamming kernel family compute
    the gated sum unchanged — bit-identical to masking the popcounts.

    Lowering selection follows the fused-family contract
    (``fused_window._pallas_lowering``): compiled Pallas on TPU, the jnp
    oracle elsewhere (the [N, K, W] xor is cache-depth-sized, where plain
    XLA beats interpret-mode grid machinery), ``TORR_FUSED_PALLAS=1``
    forces the interpret-mode grid; off-tile shapes fall back to the
    oracle in any mode.
    """
    wmask = wmask[None, :]
    q = jnp.where(wmask, q_packed, jnp.uint32(0))
    e = jnp.where(wmask, e_packed, jnp.uint32(0))
    lowering = fused_window._pallas_lowering(interpret)
    if lowering is None or not use_kernel:
        return ref.packed_hamming_ref(q, e)
    return _batched_hamming(q, e, interpret=lowering, use_kernel=use_kernel)


def delta_update(
    acc: jax.Array,       # int32 [M]
    dmajor: jax.Array,    # int8 [D, M]
    idx: jax.Array,       # int32 [budget]
    weight: jax.Array,    # int32 [budget]
    *,
    interpret: bool | None = None,
    use_kernel: bool = True,
) -> jax.Array:
    """Sparse Eq. 6 correction under the family's lowering-selection
    contract (``fused_window.delta_apply``): the scalar-prefetch kernel on
    the Pallas lowering, the vectorized O(|Delta| * M) gather-einsum
    elsewhere, the oracle off-tile. ``interpret=True`` forces the
    interpret-mode kernel grid (tests)."""
    return fused_window.delta_apply(acc, dmajor, idx, weight,
                                    interpret=interpret,
                                    use_kernel=use_kernel)


def sign_project(
    z: jax.Array,   # f32 [N, d]
    R: jax.Array,   # f32 [D, d]
    *,
    interpret: bool | None = None,
    use_kernel: bool = True,
) -> jax.Array:
    """Fused bipolar projection; falls back to the oracle off-tile."""
    N, _ = z.shape
    D, _ = R.shape
    if use_kernel and D % 128 == 0 and N % 8 == 0:
        td = 256 if D % 256 == 0 else 128
        return _sign_kernel(z, R, tn=8, td=td, interpret=interpret)
    return ref.sign_project_ref(z, R)
