"""Fused sign-projection kernel: q = sign(R z) (paper Sec. 3.2).

Fuses the [D, d] x [d] projection with the sign quantization so the f32
intermediate y = R z never round-trips to HBM — only the int8 bipolar code
is written back (a 4x traffic cut on the encoder->aligner hand-off; the
subsequent 32x cut comes from bit-packing, left to XLA as a cheap reshape).

Grid: (batch-tiles, D-tiles); each step computes a (TN, TD) tile of the
matmul on the MXU, applies sign, and writes int8. d (feature dim) is kept
un-tiled: encoder features are small (d <= 1024), so one (TD, d) weight
slab fits VMEM comfortably (TD=256, d=512 f32 -> 512 KiB).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from .xnor_popcount_sim import resolve_interpret


def _kernel(z_ref, r_ref, out_ref):
    y = jnp.dot(
        z_ref[...], r_ref[...].T, preferred_element_type=jnp.float32
    )                                                   # [TN, TD]
    out_ref[...] = jnp.where(y >= 0.0, 1, -1).astype(jnp.int8)


@functools.partial(jax.jit, static_argnames=("tn", "td", "interpret"))
def sign_project(
    z: jax.Array,    # f32 [N, d]
    R: jax.Array,    # f32 [D, d]
    *,
    tn: int = 8,
    td: int = 256,
    interpret: bool | None = None,
) -> jax.Array:
    """Bipolar int8 [N, D] = sign(z @ R.T)."""
    N, d = z.shape
    D, d2 = R.shape
    assert d == d2
    tn = min(tn, N)
    td = min(td, D)
    assert N % tn == 0 and D % td == 0

    return pl.pallas_call(
        _kernel,
        grid=(N // tn, D // td),
        name="sign_project",
        in_specs=[
            pl.BlockSpec((tn, d), lambda n, dd: (n, 0)),
            pl.BlockSpec((td, d), lambda n, dd: (dd, 0)),
        ],
        out_specs=pl.BlockSpec((tn, td), lambda n, dd: (n, dd)),
        out_shape=jax.ShapeDtypeStruct((N, D), jnp.int8),
        interpret=resolve_interpret(interpret),
    )(z, R)
