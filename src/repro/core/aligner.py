"""Associative cosine aligner: full-scan, delta-update and score readout.

Functional (masked) reference implementations of the two hardware access
patterns (paper Sec. 4.2/4.3). The Pallas kernels in ``repro.kernels`` are
drop-in accelerated versions validated against these.

Accumulators are *integer dot products* over the enabled dimensions; cosine
is applied only at readout (the ASIC's "normalization shift by log2 D'").
This makes Eq. 6's delta corrections exact in the integer domain.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from ..obs.phases import phase
from . import hdc
from .item_memory import (ItemMemory, bank_plane_sel, pmajor_bank_blocks,
                          word_mask)
from .types import TorrConfig


def full_dot(q_packed: jax.Array, im: ItemMemory, wmask: jax.Array) -> jax.Array:
    """Integer dot <q, h_j> over enabled words for all M classes.

    q_packed: uint32 [W]; im.packed: uint32 [M, W]; wmask: bool [W].
    dot = d_eff - 2 * hamming, with hamming counted on enabled words only.
    """
    x = jnp.bitwise_xor(q_packed[None, :], im.packed)          # [M, W]
    pc = jax.lax.population_count(x).astype(jnp.int32)         # [M, W]
    pc = jnp.where(wmask[None, :], pc, 0)
    d_eff = 32 * jnp.sum(wmask.astype(jnp.int32))
    return d_eff - 2 * jnp.sum(pc, axis=-1)                    # [M]


def delta_indices(
    q_new_packed: jax.Array,
    q_old_packed: jax.Array,
    wmask: jax.Array,
    budget: int,
    D: int,
) -> tuple[jax.Array, jax.Array, jax.Array]:
    """PSU (Sec. 4.4): flipped dims between queries, within the delta budget.

    Returns (idx [budget] int32, weight [budget] int32 in {-2,0,+2},
    count [] int32 = true |Delta| over enabled words). Padding entries have
    weight 0 and idx 0; if count > budget the caller must escalate to full
    (TorR-on-TPU adaptation: static budget instead of a data-dependent FIFO).

    The index list feeds the lowerings whose Eq. 6 reads only the flipped
    rows: the ``switch`` step's ``delta_update`` kernel, the ``off``
    oracle's :func:`delta_correct` and the ``compact`` decide passes. The
    vmapped ``prefix`` step takes :func:`delta_count` and
    :func:`delta_dense` instead.
    """
    xor = jnp.bitwise_xor(q_new_packed, q_old_packed)
    xor = jnp.where(wmask, xor, jnp.uint32(0))
    count = jnp.sum(jax.lax.population_count(xor).astype(jnp.int32))
    shifts = jnp.arange(32, dtype=jnp.uint32)
    flip_bits = ((xor[:, None] >> shifts) & jnp.uint32(1)).reshape(D)   # [D] 0/1
    # first `budget` flipped dims in ascending order, 0-padded — exactly
    # jnp.nonzero(size=budget, fill_value=0), but as a binary search over
    # the flip-rank cumsum: the k-th flipped dim is the smallest d whose
    # cumulative flip count reaches k+1. Sized-nonzero lowers to a full
    # [D] sort and a scatter formulation hits XLA-CPU's scalar scatter
    # loop; at one call per proposal per window either dominated the whole
    # scan (~0.2 ms/call on CPU — ~8x the searchsorted form).
    cum = jnp.cumsum(flip_bits)
    k = jnp.arange(budget, dtype=jnp.int32)
    in_budget = k < count
    idx = jnp.where(
        in_budget,
        jnp.searchsorted(cum, k + 1, side="left").astype(jnp.int32), 0)
    # q_new bit at flipped idx: +1 bit -> new value +1 -> correction +2.
    new_bits = (q_new_packed[idx // 32] >> (idx % 32).astype(jnp.uint32)) & jnp.uint32(1)
    weight = jnp.where(new_bits == 1, 2, -2).astype(jnp.int32)
    weight = jnp.where(in_budget, weight, 0)
    return idx, weight, count


def delta_count(q_new_packed: jax.Array, q_old_packed: jax.Array,
                wmask: jax.Array) -> jax.Array:
    """|Delta| over enabled words, int32 []: the count of
    :func:`delta_indices` without its index list."""
    xor = jnp.where(wmask, jnp.bitwise_xor(q_new_packed, q_old_packed),
                    jnp.uint32(0))
    return jnp.sum(jax.lax.population_count(xor).astype(jnp.int32))


def delta_dense(q_new_packed: jax.Array, q_old_packed: jax.Array,
                wmask: jax.Array, im: ItemMemory, D: int) -> jax.Array:
    """Eq. 6's correction term as one dense masked matvec: int32 [M] with
    ``corr[m] = sum_d mask_d * (q_new_d - q_old_d) * dmajor[d, m]``.

    Every dim is read, flipped or not, so no index list is built: under
    ``vmap`` the lanes' corrections are one [lanes, D] x [D, M] int8
    product against the unbatched item memory. Equal to the correction of
    :func:`delta_correct` over :func:`delta_indices` whenever
    ``count <= budget`` (the only case Alg. 1 lets the delta path use):
    the list then holds every flipped dim, and both sum the same integer
    terms, each in {-2, 0, +2}, in int32."""
    dmask = jnp.repeat(wmask, 32)
    diff = jnp.where(dmask, hdc.unpack_bits(q_new_packed, D)
                     - hdc.unpack_bits(q_old_packed, D), 0).astype(jnp.int8)
    return jax.lax.dot_general(diff, im.dmajor, (((0,), (0,)), ((), ())),
                               preferred_element_type=jnp.int32)


def delta_correct(
    acc: jax.Array, im: ItemMemory, idx: jax.Array, weight: jax.Array
) -> jax.Array:
    """Eq. 6: acc_j += sum_{i in Delta} (q_i^t - q_i^{t-1}) h_{j,i}.

    acc: int32 [M]; gathers rows of the D-major item memory.
    """
    rows = im.dmajor[idx, :].astype(jnp.int32)                 # [budget, M]
    return acc + jnp.einsum("k,km->m", weight, rows)


def delta_corrections(
    d_idx: jax.Array,     # int32 [L, budget] flipped dims (0-padded)
    d_weight: jax.Array,  # int32 [L, budget] in {-2, 0, +2} (0 = padding)
    im: ItemMemory,
    D: int,
) -> jax.Array:
    """Eq. 6 correction *terms* for a whole proposal batch: int32 [L, M]
    with ``corr[l] = sum_k d_weight[l, k] * dmajor[d_idx[l, k]]``.

    The correction is independent of the accumulator it lands on
    (:func:`delta_correct` is ``acc + corr``), so the batched apply pass
    hoists it out of the per-proposal scan. Lowered as a dense f32 matmul:
    the sparse per-row weights scatter into a [L, D] vector and one
    GEMM against the D-major item memory reads every matrix once —
    instead of gathering ``budget`` [M] rows per lane (~4x the bytes at
    serving shapes). Bit-identical to the int32 gather-einsum: weights are
    in {-2, 0, +2}, dmajor entries in {-1, +1} and each row has at most
    ``budget`` nonzero terms, so every f32 partial sum is an integer of
    magnitude <= 2*budget << 2^24 — exact under any accumulation order.
    Padding entries scatter weight 0 onto dim 0, contributing nothing even
    when dim 0 is a genuine flip."""
    L = d_idx.shape[0]
    wvec = jnp.zeros((L, D), jnp.float32).at[
        jnp.arange(L)[:, None], d_idx].add(d_weight.astype(jnp.float32))
    return jnp.round(wvec @ im.dmajor.astype(jnp.float32)).astype(jnp.int32)


def readout(acc: jax.Array, d_eff: jax.Array | int) -> jax.Array:
    """Cosine scores from integer accumulators (normalization 'shift')."""
    return acc.astype(jnp.float32) / jnp.asarray(d_eff, jnp.float32)


def full_scores(
    q_packed: jax.Array, im: ItemMemory, cfg: TorrConfig, banks: jax.Array | int
) -> tuple[jax.Array, jax.Array]:
    """Convenience: (acc int32 [M], cosine f32 [M]) for a full scan."""
    wmask = word_mask(cfg, banks)
    acc = full_dot(q_packed, im, wmask)
    d_eff = jnp.asarray(banks, jnp.int32) * cfg.bank_dims
    return acc, readout(acc, d_eff)


# ---------------------------------------------------------------------------
# Fused-kernel dispatch shim (traced banks, static plan cap)
# ---------------------------------------------------------------------------

def _plan_columns_bank_major(
    q_packed_all: jax.Array, im: ItemMemory, banks: int, planes: int,
    cfg: TorrConfig,
) -> tuple[jax.Array, jax.Array]:
    """(q_sel, im_sel) restricted to a *static* (banks, planes) plan's
    enabled words, in the shared bank-major column order of
    ``item_memory.bank_plane_sel`` (bank boundaries stay word prefixes, the
    bank-prefix kernel's contract). Full precision keeps the original
    contiguous bank prefix of ``packed``; reduced precision assembles
    static contiguous slices of ``pmajor`` for the item memory and a static
    gather for the (tiny) query batch."""
    if planes >= cfg.bit_planes:
        we = banks * cfg.bank_words
        return q_packed_all[:, :we], im.packed[:, :we]
    sel = bank_plane_sel(cfg, banks, planes)
    return (q_packed_all[:, sel],
            pmajor_bank_blocks(im.pmajor, cfg, banks, planes))


def plan_prefix_hamming(
    q_packed: jax.Array,       # uint32 [N, D//32] (N may be S*N_max flattened)
    im: ItemMemory,
    cfg: TorrConfig,
    *,
    planes: int,
    cap: int,
    interpret: bool | None = None,
    use_kernel: bool = True,
) -> jax.Array:
    """Bank-prefix hamming over a (cap, planes) plan's enabled words:
    int32 [N, cap, M]. Column selection + the ``bank_prefix_hamming``
    kernel, the step's ``prefix_scan`` phase; the batched multi-stream
    step hoists this single call over its flattened S x N_max proposal
    batch (one kernel pass per step — a per-stream call under vmap would
    re-enter the grid once per stream)."""
    from ..kernels import fused_window as fw

    with phase("prefix_scan"):
        q_sel, im_sel = _plan_columns_bank_major(q_packed, im, cap, planes,
                                                 cfg)
        return fw.bank_prefix_hamming_any(q_sel, im_sel, cap=cap,
                                          interpret=interpret,
                                          use_kernel=use_kernel)


def full_scores_all(
    q_packed_all: jax.Array,   # uint32 [N, D//32] all proposals of a window
    im: ItemMemory,
    banks: jax.Array,          # traced int32 [] — Alg. 1's per-window choice
    cfg: TorrConfig,
    *,
    planes: int,               # static (latched plan)
    cap: int,                  # static plan cap on banks (cfg.B uncontrolled)
    mode: str = "switch",
    ham_prefix: jax.Array | None = None,  # precomputed [N, cap, M] (hoisted)
    interpret: bool | None = None,
    use_kernel: bool = True,
) -> jax.Array:
    """Full-path integer accumulators for *all* proposals: int32 [N, M].

    The traced-banks dispatch shim over ``kernels.fused_window``: the whole
    window's proposal batch goes through one fused XNOR-popcount scan (the
    item-memory tile is read once per query block and streamed through
    VMEM), instead of one masked full-width ``[M, W]`` xor per proposal
    inside the scan — bit-identical to :func:`full_dot` under the same
    ``(banks, planes)``, because integer hamming sums are order-invariant
    and the readout formula is shared.

    Two lowerings of the traced ``banks``, both a bounded family of
    <= B x P specialized executables keyed by the static ``(cap, planes)``:

      * ``mode="switch"`` — ``lax.switch`` over the <= cap bank branches;
        only the selected branch executes (reads exactly ``banks`` banks'
        enabled words), the right trade wherever branches stay scalar
        (single-stream jit, the lax.map serial lowering).
      * ``mode="prefix"`` — one ``bank_prefix_hamming`` pass over the
        plan-capped prefix emitting every bank boundary's count, then a
        traced gather selects ``banks``. Under vmap a switch would execute
        *every* branch on the whole batch; the prefix pass reads the capped
        width once. The batched multi-stream step additionally hoists the
        kernel call itself over the flattened S x N_max proposal batch and
        passes the per-stream slice in as ``ham_prefix``.
    """
    from ..kernels import fused_window as fw

    banks = jnp.clip(jnp.asarray(banks, jnp.int32), 1, cap)
    if mode == "prefix":
        ham_p = ham_prefix
        if ham_p is None:
            ham_p = plan_prefix_hamming(
                q_packed_all, im, cfg, planes=planes, cap=cap,
                interpret=interpret, use_kernel=use_kernel)  # [N, cap, M]
        ham = ham_p[:, banks - 1, :]
        d_eff = cfg.d_eff_planned(banks, planes)
        return d_eff - 2 * ham
    if mode != "switch":
        raise ValueError(f"unknown fused dispatch mode {mode!r}")

    def make_branch(b: int):
        def branch(q):
            q_sel, im_sel = _plan_columns_bank_major(q, im, b, planes, cfg)
            acc, _best, _top2 = fw.fused_scores_any(
                q_sel, im_sel, d_eff=int(cfg.d_eff_planned(b, planes)),
                interpret=interpret, use_kernel=use_kernel)
            return acc
        return branch

    return jax.lax.switch(
        banks - 1, [make_branch(b) for b in range(1, cap + 1)], q_packed_all)


def prefix_select(
    ham_prefix: jax.Array,     # int32 [..., cap, M] bank-boundary counts
    banks: jax.Array,          # int32 [...] traced per-row bank choice
    planes: int,
    cfg: TorrConfig,
) -> jax.Array:
    """Accumulators from bank-prefix hamming counts: each row selects its
    traced bank boundary and normalizes by its own D'. int32 [..., M]."""
    ham = jnp.take_along_axis(
        ham_prefix, (banks - 1)[..., None, None], axis=-2)[..., 0, :]
    d_eff = cfg.d_eff_planned(banks, planes)
    return d_eff[..., None] - 2 * ham


def compact_full_scores(
    q_flat: jax.Array,         # uint32 [R, D//32] flattened proposal batch
    full_mask: jax.Array,      # bool [R] rows whose window FSM chose FULL
    banks_flat: jax.Array,     # int32 [R] each row's window's bank choice
    im: ItemMemory,
    cfg: TorrConfig,
    *,
    planes: int,               # static (latched plan)
    cap: int,                  # static plan cap on banks
    bucket_cap: int,           # static bucket capacity (the ladder tier)
    interpret: bool | None = None,
    use_kernel: bool = True,
) -> jax.Array:
    """Compact-then-compute full-path accumulators: int32 [R, M], exact on
    every ``full_mask`` row (other rows are zero — the apply pass never
    reads them).

    The third dispatch contract (``kernels/README.md``): the decide pass
    already produced the path vector, so the fused XNOR-popcount scan runs
    **only over the full-path rows**, compacted by a sized ``nonzero``
    gather into a dense bucket padded to the *static* ``bucket_cap`` (a
    ``core.policy.bucket_ladder`` tier — the executable family stays
    bounded at ladder x plan). Each bucket row selects its own window's
    traced bank boundary from the prefix counts, and the results scatter
    back to their flat positions. If the window mix overflows the latched
    tier (``n_full > bucket_cap``) a *scalar* ``lax.cond`` falls back to
    the hoisted all-rows prefix pass — bit-exact always, merely slower, so
    an engine's tier mispredict can never corrupt results.
    """
    R = q_flat.shape[0]
    bucket_cap = min(int(bucket_cap), R)
    banks_flat = jnp.clip(jnp.asarray(banks_flat, jnp.int32), 1, cap)
    n_full = jnp.sum(full_mask.astype(jnp.int32))

    def from_bucket():
        (rows,) = jnp.nonzero(full_mask, size=bucket_cap, fill_value=R)
        safe = jnp.minimum(rows, R - 1)
        ham_b = plan_prefix_hamming(
            q_flat[safe], im, cfg, planes=planes, cap=cap,
            interpret=interpret, use_kernel=use_kernel)     # [cap_b, cap, M]
        acc_b = prefix_select(ham_b, banks_flat[safe], planes, cfg)
        return jnp.zeros((R, cfg.M), jnp.int32).at[rows].set(
            acc_b, mode="drop")

    def hoisted():
        ham = plan_prefix_hamming(
            q_flat, im, cfg, planes=planes, cap=cap,
            interpret=interpret, use_kernel=use_kernel)     # [R, cap, M]
        acc = prefix_select(ham, banks_flat, planes, cfg)
        return jnp.where(full_mask[:, None], acc, 0)

    return jax.lax.cond(n_full <= bucket_cap, from_bucket, hoisted)


def lookup_hamming_all(
    q_packed_all: jax.Array,   # uint32 [N, W] query batch
    entries: jax.Array,        # uint32 [K, W] lookup entries
    wmask: jax.Array,          # bool [W] plan-enabled words (may be traced)
    *, interpret: bool | None = None, use_kernel: bool = True,
) -> jax.Array:
    """Batched associative-lookup hamming table: int32 [N, K] masked
    distances of every query against every entry (``ops.masked_hamming_all``
    — the batched decide pass's PSU primitive). ``entries`` may be the
    cache snapshot's packed queries or the proposal batch itself (the
    intra-window writer table); bit-identical to the per-proposal masked
    popcount in ``query_cache.nearest`` because disabled words are zeroed
    on both operands before the plain hamming sum."""
    from ..kernels import ops

    return ops.masked_hamming_all(q_packed_all, entries, wmask,
                                  interpret=interpret, use_kernel=use_kernel)


def delta_apply(
    acc: jax.Array, im: ItemMemory, idx: jax.Array, weight: jax.Array,
    *, interpret: bool | None = None, use_kernel: bool = True,
) -> jax.Array:
    """Eq. 6 through the kernel family (`fused_window.delta_apply`):
    scalar-prefetch row streaming instead of :func:`delta_correct`'s
    [budget, M] gather+einsum. Bit-identical (integer adds)."""
    from ..kernels import fused_window as fw

    return fw.delta_apply(acc, im.dmajor, idx, weight, interpret=interpret,
                          use_kernel=use_kernel)


def full_dot_mxu(q_bipolar: jax.Array, im: ItemMemory,
                 dmask: jax.Array) -> jax.Array:
    """Beyond-paper alternative: bipolar cosine as a bf16 MXU matmul.

    The paper's XNOR-popcount path minimizes *traffic* (1 bit/dim); on TPU
    the MXU's 197 TFLOP/s bf16 can beat the VPU popcount pipeline when the
    item memory already resides in VMEM (compute-bound regime, large M·D).
    Exact for D <= 2^24 (bf16 holds the ±1 products; accumulation is f32 on
    the MXU). q_bipolar: int8 [..., D]; returns int32 dots [..., M].
    """
    q = jnp.where(dmask, q_bipolar, 0).astype(jnp.bfloat16)
    h = im.bipolar.astype(jnp.bfloat16)
    dots = jax.lax.dot_general(
        q, h, (((q.ndim - 1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32)
    return jnp.round(dots).astype(jnp.int32)
