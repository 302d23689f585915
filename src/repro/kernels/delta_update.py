"""Delta-update kernel: sparse accumulator corrections (paper Eq. 6, Sec 4.3).

The ASIC pops flipped-bit indices from a Delta-FIFO and touches only those
item-memory columns. On TPU the FIFO becomes a *scalar-prefetched index
array* (static delta-budget length): the grid's fast dimension walks the
budget, and the index_map uses the prefetched index to fetch the 32-row
group of the D-major item memory that holds the flipped row — an int8
block must be 32 rows tall to fit the chip's (32, 128) int8 tiling — and
the kernel picks the row out of the group. So O(|Delta| * M) bytes move,
never O(D * M); the ascending flip order makes consecutive steps often
share a group, which Pallas then does not fetch again. Padding entries
carry weight 0 (and index 0), preserving exactness.

Grid: (budget,); per step the kernel adds
    weight[k] * dmajor[idx[k], :]
into the persistent accumulator block (all M classes), initialized from
acc_in at k == 0.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .xnor_popcount_sim import resolve_interpret

ROWS = 32   # int8 sublane tile: the row group fetched per budget entry


def _kernel(idx_ref, w_ref, acc_in_ref, dmaj_ref, out_ref):
    k = pl.program_id(0)

    @pl.when(k == 0)
    def _init():
        out_ref[...] = acc_in_ref[...]

    rows = dmaj_ref[...].astype(jnp.int32)                      # [ROWS, M]
    hit = jax.lax.broadcasted_iota(jnp.int32, rows.shape, 0) == (
        idx_ref[k] % ROWS)
    row = jnp.sum(jnp.where(hit, rows, 0), axis=0, keepdims=True)
    out_ref[...] += w_ref[k] * row


@functools.partial(jax.jit, static_argnames=("interpret",))
def delta_update(
    acc: jax.Array,      # int32 [M] persistent per-class accumulators
    dmajor: jax.Array,   # int8  [D, M] D-major item memory
    idx: jax.Array,      # int32 [budget] flipped dims (0-padded)
    weight: jax.Array,   # int32 [budget] in {-2, 0, +2}
    *,
    interpret: bool | None = None,
) -> jax.Array:
    """acc + sum_k weight[k] * dmajor[idx[k], :], via sparse row streaming.

    D must be a multiple of 32 (every ``TorrConfig`` D is)."""
    (M,) = acc.shape
    D = dmajor.shape[0]
    assert D % ROWS == 0, D
    budget = idx.shape[0]

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(budget,),
        in_specs=[
            pl.BlockSpec((1, M), lambda k, idx, w: (0, 0)),
            pl.BlockSpec((ROWS, M), lambda k, idx, w: (idx[k] // ROWS, 0)),
        ],
        out_specs=pl.BlockSpec((1, M), lambda k, idx, w: (0, 0)),
    )
    out = pl.pallas_call(
        _kernel,
        grid_spec=grid_spec,
        name="delta_update",
        out_shape=jax.ShapeDtypeStruct((1, M), jnp.int32),
        interpret=resolve_interpret(interpret),
    )(idx, weight, acc.reshape(1, M), dmajor)
    return out[0]
