"""Fused window-step kernels: the jitted full path's Pallas family.

The paper's throughput claim (Sec. 4.2/4.3) is a *memory-traffic* claim: the
bit-sliced XNOR-popcount item memory reads only the enabled banks/planes.
``repro.core.aligner``'s jnp oracle simulates that with masks — it still
reads (and, batched, materializes) the full ``[N, M, W]`` xor. This module
is the co-designed kernel family the fully-jitted pipeline dispatches
instead (``core.aligner.full_scores_all`` is the traced-banks shim):

  * :func:`fused_scores` — one grid fusing the plane/bank-gated
    XNOR-popcount scan, the per-class integer accumulation
    (``acc = D' - 2*hamming``) **and** the argmax / top-2 readout. The
    item-memory tile streams through VMEM (Pallas pipelines input blocks
    with automatic double buffering over the word grid axis), so the
    ``[TQ, TM, TW]`` xor lives only in registers/VMEM and nothing
    ``[N, M, W]``-shaped ever reaches HBM. Static ``(banks, planes)``
    specialization: callers pre-slice the enabled words, so each plan
    compiles to a kernel that genuinely reads less memory.
  * :func:`bank_prefix_hamming` — the traced-banks family member: one pass
    over the (static) plan-capped word prefix emitting the hamming count at
    *every* bank boundary ``[N, cap, M]``. A traced ``banks`` then selects
    its prefix with one gather — the vmap-safe dispatch the multi-stream
    engine uses, where ``lax.switch`` would execute every branch per batch.
    The reuse-aware compact dispatch (``core.aligner.compact_full_scores``,
    the third contract in ``README.md``) runs this same kernel over a
    *bucket* of only the full-path proposals, so ``N`` shrinks with the
    cache hit rate instead of staying pinned at the batch size.
  * :func:`delta_apply` — the delta path's scatter-accumulate (Eq. 6),
    dispatching to the scalar-prefetch ``delta_update`` kernel so the
    bypass/delta/full trio all avoid the jnp oracle inside the jitted step.
  * :func:`sign_project_pack` — encode front-end: sign-projection fused
    with bit-packing, writing uint32 words directly (neither the f32
    projection nor the int8 bipolar code round-trips HBM).

Every kernel keeps the oracle fallback contract of ``kernels.ops``: ragged
shapes transparently use the jnp reference, so callers never see a shape
constraint.

Lowering selection (the ``interpret`` knob of the ``*_any`` dispatchers):

  * ``None`` (default) — Pallas compiled on TPU; on other backends a
    *blocked-jnp* lowering with the identical tiling (a lax.scan over
    query blocks, tile-sized xor) runs instead, because the interpret-mode
    grid machinery loses to plain XLA there. ``TORR_FUSED_PALLAS=1``
    forces interpret-mode Pallas anywhere (how CI validates the kernel
    grids bit-exactly without a TPU).
  * ``True`` — interpret-mode Pallas (explicit; kernel-grid tests).
  * ``False`` — compiled Pallas (explicit TPU request).

Both lowerings are bit-identical (integer hamming sums are order-invariant)
and neither ever materializes an ``[N, M, W]``-shaped intermediate.
"""
from __future__ import annotations

import functools
import os

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from . import ref
from .delta_update import delta_update as _delta_kernel
from .xnor_popcount_sim import (TM_DEFAULT, TQ_DEFAULT, TW, fit_tile,
                                lane_tile, resolve_interpret, sublane_tile)

_I32_MIN = -(2 ** 31)
_I32_MAX = 2 ** 31 - 1

# query-block rows of the blocked-jnp lowering: 8 keeps the [TQ, TM, TW]
# xor tile L2-resident on CPU (measured best in {4..128} at serving shapes)
TQ_BLOCKED = 8


def _pallas_lowering(interpret: bool | None) -> bool | None:
    """Resolve the dispatch knob: the pallas interpret flag to use, or
    None meaning 'take the blocked-jnp lowering'."""
    if interpret is not None:
        return interpret
    if jax.default_backend() == "tpu":
        return False
    if os.environ.get("TORR_FUSED_PALLAS", ""):
        return True
    return None


# ---------------------------------------------------------------------------
# fused scan -> acc -> argmax/top-2 readout (static-plan specialization)
# ---------------------------------------------------------------------------

def _fused_kernel(q_ref, im_ref, acc_ref, best_ref, top2_ref,
                  *, d_eff: int, nw: int, tm: int):
    """Grid (query-tiles, class-tiles, word-tiles), word dim fastest.

    The hamming count accumulates in the ``acc_ref`` VMEM block across word
    tiles and is finalized to ``d_eff - 2*ham`` at the last tile; the
    argmax/top-2 state lives in the ``best``/``top2`` output blocks, whose
    index_map ignores (m, w) — for a fixed query tile they stay VMEM-resident
    across the whole class/word walk, giving a running readout for free.
    Tie-breaking matches ``jnp.argmax``/``lax.top_k``: strictly-greater to
    update plus lowest-index-first within a tile keeps the earliest class.
    """
    m, w = pl.program_id(1), pl.program_id(2)

    @pl.when(w == 0)
    def _init_ham():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    @pl.when(jnp.logical_and(m == 0, w == 0))
    def _init_readout():
        best_ref[...] = jnp.zeros_like(best_ref)
        top2_ref[...] = jnp.full_like(top2_ref, _I32_MIN)

    x = jnp.bitwise_xor(q_ref[...][:, None, :], im_ref[...][None, :, :])
    acc_ref[...] += jnp.sum(jax.lax.population_count(x).astype(jnp.int32), -1)

    @pl.when(w == nw - 1)
    def _finalize():
        blk = d_eff - 2 * acc_ref[...]                       # [TQ, TM] acc
        acc_ref[...] = blk
        bmax = jnp.max(blk, axis=1)
        iota = jax.lax.broadcasted_iota(jnp.int32, blk.shape, 1) + m * tm
        barg = jnp.min(jnp.where(blk == bmax[:, None], iota, _I32_MAX), axis=1)
        b2 = jnp.max(jnp.where(iota == barg[:, None], _I32_MIN, blk), axis=1)
        v1, v2 = top2_ref[:, 0], top2_ref[:, 1]
        upd = bmax > v1
        best_ref[:, 0] = jnp.where(upd, barg, best_ref[:, 0])
        top2_ref[:, 0] = jnp.where(upd, bmax, v1)
        top2_ref[:, 1] = jnp.maximum(jnp.minimum(bmax, v1),
                                     jnp.maximum(b2, v2))


@functools.partial(jax.jit,
                   static_argnames=("d_eff", "tq", "tm", "tw", "interpret"))
def fused_scores(
    q_packed: jax.Array,    # uint32 [N, W_eff] (pre-sliced enabled words)
    im_packed: jax.Array,   # uint32 [M, W_eff] (same column order as q)
    *,
    d_eff: int,
    tq: int | None = None,
    tm: int | None = None,
    tw: int = TW,
    interpret: bool | None = None,
) -> tuple[jax.Array, jax.Array, jax.Array]:
    """(acc int32 [N, M], best int32 [N], top2 int32 [N, 2]) in one grid.

    ``best``/``top2`` are the argmax index and the two highest accumulator
    values (``top2[:, 0] - top2[:, 1]`` is the integer margin), bit-identical
    to ``jnp.argmax(acc)`` / ``lax.top_k(acc, 2)[0]``. ``tq``/``tm`` default
    to the ``TORR_TQ``/``TORR_TM`` overrides (see the knob table in
    ``kernels.xnor_popcount_sim``), clipped to divisors.
    """
    N, W = q_packed.shape
    M, W2 = im_packed.shape
    assert W == W2, (W, W2)
    tq = sublane_tile(N, TQ_DEFAULT if tq is None else tq)
    tm = lane_tile(M, TM_DEFAULT if tm is None else tm)
    tw = lane_tile(W, tw)     # a reduced plan's unaligned W is taken whole
    nw = W // tw
    kern = functools.partial(_fused_kernel, d_eff=d_eff, nw=nw, tm=tm)
    acc, best, top2 = pl.pallas_call(
        kern,
        grid=(N // tq, M // tm, nw),
        name="fused_scores",
        in_specs=[
            pl.BlockSpec((tq, tw), lambda n, m, w: (n, w)),
            pl.BlockSpec((tm, tw), lambda n, m, w: (m, w)),
        ],
        out_specs=[
            pl.BlockSpec((tq, tm), lambda n, m, w: (n, m)),
            pl.BlockSpec((tq, 1), lambda n, m, w: (n, 0)),
            pl.BlockSpec((tq, 2), lambda n, m, w: (n, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((N, M), jnp.int32),
            jax.ShapeDtypeStruct((N, 1), jnp.int32),
            jax.ShapeDtypeStruct((N, 2), jnp.int32),
        ],
        interpret=resolve_interpret(interpret),
    )(q_packed, im_packed)
    return acc, best[:, 0], top2


def _blocked_scores(
    q_packed: jax.Array, im_packed: jax.Array, *, d_eff: int,
) -> tuple[jax.Array, jax.Array, jax.Array]:
    """Blocked-jnp lowering of :func:`fused_scores`: the same query-block
    tiling as the kernel grid, expressed as a lax.scan so XLA vectorizes
    each tile — the xor intermediate is [TQ, M, W]-tile-sized, never
    [N, M, W]. Bit-identical (integer sums; argmax/top-2 on the acc)."""
    N, W = q_packed.shape
    M = im_packed.shape[0]
    tq = fit_tile(N, TQ_BLOCKED)
    qt = q_packed.reshape(N // tq, tq, W)

    def body(carry, qb):
        x = jnp.bitwise_xor(qb[:, None, :], im_packed[None, :, :])
        ham = jnp.sum(jax.lax.population_count(x).astype(jnp.int32), -1)
        return carry, d_eff - 2 * ham

    _, acc = jax.lax.scan(body, jnp.int32(0), qt)
    acc = acc.reshape(N, M)
    best = jnp.argmax(acc, axis=-1).astype(jnp.int32)
    if M < 2:
        top2 = jnp.concatenate([acc, jnp.full_like(acc, _I32_MIN)], axis=-1)
    else:
        top2 = jax.lax.top_k(acc, 2)[0]
    return acc, best, top2


def fused_scores_any(
    q_packed: jax.Array, im_packed: jax.Array, *, d_eff: int,
    interpret: bool | None = None, use_kernel: bool = True,
) -> tuple[jax.Array, jax.Array, jax.Array]:
    """:func:`fused_scores` under the lowering-selection contract (module
    docstring), with the transparent oracle fallback for ragged M."""
    if not (use_kernel and im_packed.shape[0] % 8 == 0):
        return ref.fused_scores_ref(q_packed, im_packed, d_eff=d_eff)
    lowering = _pallas_lowering(interpret)
    if lowering is None:
        return _blocked_scores(q_packed, im_packed, d_eff=d_eff)
    return fused_scores(q_packed, im_packed, d_eff=d_eff, interpret=lowering)


# ---------------------------------------------------------------------------
# bank-prefix hamming (traced-banks family member)
# ---------------------------------------------------------------------------

def _prefix_kernel(q_ref, im_ref, out_ref, *, cap: int, epw: int):
    """One (query-tile, class-tile) block per program: each query row's xor
    against the item-memory tile stays in VMEM, and one small matmul with a
    0/1 bank-prefix matrix ``tri[b, w] = (w < (b + 1) * epw)`` turns the
    per-word popcounts into every bank boundary's running count, written
    class-minor as ``[TQ, cap, TM]``. Neither a per-bank reduce that splits
    the lane dimension nor a cumsum is needed, and bank boundaries never
    constrain the tiling. Exact: popcounts (<= 32) and 0/1 are exact in
    bf16, and the f32 accumulation of sums <= 32 * W < 2**24 is exact."""
    w = im_ref.shape[1]
    word = jax.lax.broadcasted_iota(jnp.int32, (cap, w), 1)
    bank = jax.lax.broadcasted_iota(jnp.int32, (cap, w), 0)
    tri = (word < (bank + 1) * epw).astype(jnp.bfloat16)    # [cap, W]
    im = im_ref[...]                                        # [TM, W]
    for i in range(q_ref.shape[0]):
        x = jnp.bitwise_xor(q_ref[i:i + 1, :], im)          # [TM, W]
        pc = jax.lax.population_count(x).astype(jnp.int32)
        counts = jax.lax.dot_general(
            tri, pc.astype(jnp.float32).astype(jnp.bfloat16),
            (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)              # [cap, TM]
        out_ref[i] = counts.astype(jnp.int32)


@functools.partial(jax.jit,
                   static_argnames=("cap", "tq", "tm", "interpret"))
def bank_prefix_hamming(
    q_packed: jax.Array,    # uint32 [N, cap * epw] (plan-capped enabled words)
    im_packed: jax.Array,   # uint32 [M, cap * epw] (same column order)
    *,
    cap: int,
    tq: int | None = None,
    tm: int | None = None,
    interpret: bool | None = None,
) -> jax.Array:
    """Hamming over the first 1..cap banks' enabled words: int32 [N, cap, M].

    One pass over the plan-capped prefix (bytes read scale with the *static*
    cap x planes, never the full width); a traced per-window bank choice
    selects its row afterwards with one gather over the ``cap`` axis, which
    is what keeps the jitted multi-stream path exact without executing a
    ``lax.switch`` branch per bank per batch. ``N`` is typically the
    *flattened* proposal batch of a whole multi-stream step (S x N_max
    rows) — the batched engines hoist this single call out of their vmap,
    so each item-memory tile is read once per query block instead of once
    per stream. The class axis is last so the output is lane-dense.

    A word count that is not a multiple of the 128-lane width is zero-padded
    to one (zero words xor to zero and add nothing).
    """
    N, W = q_packed.shape
    M, W2 = im_packed.shape
    assert W == W2 and W % cap == 0, (W, W2, cap)
    epw = W // cap                      # enabled words per bank
    pad = -W % TW
    if pad:
        q_packed = jnp.pad(q_packed, ((0, 0), (0, pad)))
        im_packed = jnp.pad(im_packed, ((0, 0), (0, pad)))
    tq = sublane_tile(N, TQ_DEFAULT if tq is None else tq)
    tm = lane_tile(M, TM_DEFAULT if tm is None else tm)
    kern = functools.partial(_prefix_kernel, cap=cap, epw=epw)
    return pl.pallas_call(
        kern,
        grid=(N // tq, M // tm),
        name="bank_prefix_hamming",
        in_specs=[
            pl.BlockSpec((tq, W + pad), lambda n, m: (n, 0)),
            pl.BlockSpec((tm, W + pad), lambda n, m: (m, 0)),
        ],
        out_specs=pl.BlockSpec((tq, cap, tm), lambda n, m: (n, 0, m)),
        out_shape=jax.ShapeDtypeStruct((N, cap, M), jnp.int32),
        interpret=resolve_interpret(interpret),
    )(q_packed, im_packed)


def _blocked_prefix(
    q_packed: jax.Array, im_packed: jax.Array, *, cap: int,
) -> jax.Array:
    """Blocked-jnp lowering of :func:`bank_prefix_hamming` (same tiling
    story as :func:`_blocked_scores`)."""
    N, W = q_packed.shape
    M = im_packed.shape[0]
    epw = W // cap
    tq = fit_tile(N, TQ_BLOCKED)
    qt = q_packed.reshape(N // tq, tq, W)

    def body(carry, qb):
        x = jnp.bitwise_xor(qb[:, None, :], im_packed[None, :, :])
        pc = jax.lax.population_count(x).astype(jnp.int32)
        per_bank = jnp.sum(pc.reshape(tq, M, cap, epw), -1)
        return carry, jnp.cumsum(per_bank, -1)       # [tq, M, cap]

    _, hp = jax.lax.scan(body, jnp.int32(0), qt)
    return jnp.swapaxes(hp.reshape(N, M, cap), 1, 2)


def bank_prefix_hamming_any(
    q_packed: jax.Array, im_packed: jax.Array, *, cap: int,
    interpret: bool | None = None, use_kernel: bool = True,
) -> jax.Array:
    """:func:`bank_prefix_hamming` under the lowering-selection contract,
    with the oracle fallback for ragged M."""
    if not (use_kernel and im_packed.shape[0] % 8 == 0):
        return ref.bank_prefix_hamming_ref(q_packed, im_packed, cap=cap)
    lowering = _pallas_lowering(interpret)
    if lowering is None:
        return _blocked_prefix(q_packed, im_packed, cap=cap)
    return bank_prefix_hamming(q_packed, im_packed, cap=cap,
                               interpret=lowering)


# ---------------------------------------------------------------------------
# delta path (Eq. 6) — same module so bypass/delta/full all avoid the oracle
# ---------------------------------------------------------------------------

def delta_apply(
    acc: jax.Array,       # int32 [M]
    dmajor: jax.Array,    # int8 [D, M]
    idx: jax.Array,       # int32 [budget] flipped dims (0-padded)
    weight: jax.Array,    # int32 [budget] in {-2, 0, +2}
    *,
    interpret: bool | None = None,
    use_kernel: bool = True,
) -> jax.Array:
    """Sparse scatter-accumulate ``acc += sum_k w[k] * dmajor[idx[k], :]``.

    Dispatches to the scalar-prefetch ``delta_update`` kernel (the
    Delta-FIFO's TPU analogue: only O(|Delta| * M) bytes move) under the
    Pallas lowering; elsewhere the vectorized gather-einsum *is* already
    the right O(|Delta| * M) form, so it is used directly. Safe under
    scan/switch/vmap — the jitted pipeline's delta branch calls this.
    """
    M = acc.shape[0]
    lowering = _pallas_lowering(interpret)
    if use_kernel and M % 8 == 0 and lowering is not None:
        return _delta_kernel(acc, dmajor, idx, weight, interpret=lowering)
    return ref.delta_update_ref(acc, dmajor, idx, weight)


# ---------------------------------------------------------------------------
# encode front-end: sign-projection fused with bit-packing
# ---------------------------------------------------------------------------

def _pack_kernel(r_ref, z_ref, out_ref):
    """Dims on sublanes, queries on lanes: ``y^T = R_tile z^T`` is [TD, TN];
    each group of 32 consecutive dims (a sublane-aligned split) folds into
    one word by shifting each sign bit to its position and summing the
    disjoint bits — the int32 sum of disjoint powers of two is their OR,
    bit 31 included."""
    y = jax.lax.dot_general(r_ref[...], z_ref[...], (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32)  # [TD, TN]
    td, tn = y.shape
    bits = (y >= 0.0).astype(jnp.int32).reshape(td // 32, 32, tn)
    shifts = jax.lax.broadcasted_iota(jnp.int32, (td // 32, 32, tn), 1)
    words = jnp.sum(jnp.left_shift(bits, shifts), axis=1)       # [TD/32, TN]
    out_ref[...] = jax.lax.bitcast_convert_type(words, jnp.uint32)


@functools.partial(jax.jit, static_argnames=("interpret",))
def sign_project_pack(
    z: jax.Array,    # f32 [N, d]
    R: jax.Array,    # f32 [D, d]
    *,
    interpret: bool | None = None,
) -> jax.Array:
    """Packed query words uint32 [N, D//32] = pack(sign(z @ R.T)).

    Extends the ``sign_project`` kernel one stage further: the f32
    projection *and* the bipolar code both stay in VMEM; only the
    1-bit/dim packed words are written back (a 32x cut on the
    encoder->aligner hand-off, previously left to XLA as a separate pass).
    The kernel writes the words transposed, ``[D//32, N]``, so a block is
    8 words (256 dims; all D/32 where D is not a multiple of 256) on
    sublanes by 128 queries (or all of them) on lanes; the wrapper
    transposes the small packed result back.
    """
    N, d = z.shape
    D, d2 = R.shape
    assert d == d2 and D % 32 == 0
    td = 256 if D % 256 == 0 else D
    tn = lane_tile(N, TW)
    words_t = pl.pallas_call(
        _pack_kernel,
        grid=(D // td, N // tn),
        name="sign_project_pack",
        in_specs=[
            pl.BlockSpec((td, d), lambda dd, n: (dd, 0)),
            pl.BlockSpec((tn, d), lambda dd, n: (n, 0)),
        ],
        out_specs=pl.BlockSpec((td // 32, tn), lambda dd, n: (dd, n)),
        out_shape=jax.ShapeDtypeStruct((D // 32, N), jnp.uint32),
        interpret=resolve_interpret(interpret),
    )(R, z)
    return words_t.T
