"""Pure-jnp oracles for every Pallas kernel (shape/dtype-exact)."""
from __future__ import annotations

import jax
import jax.numpy as jnp


def packed_hamming_ref(q_packed: jax.Array, im_packed: jax.Array) -> jax.Array:
    """int32 [N, M] hamming distances from packed uint32 words."""
    x = jnp.bitwise_xor(q_packed[:, None, :], im_packed[None, :, :])
    return jnp.sum(jax.lax.population_count(x).astype(jnp.int32), axis=-1)


def fused_scores_ref(
    q_packed: jax.Array, im_packed: jax.Array, *, d_eff: int
) -> tuple[jax.Array, jax.Array, jax.Array]:
    """(acc [N, M], best [N], top2 [N, 2]) — `fused_window.fused_scores`."""
    acc = d_eff - 2 * packed_hamming_ref(q_packed, im_packed)
    best = jnp.argmax(acc, axis=-1).astype(jnp.int32)
    if acc.shape[-1] < 2:
        top2 = jnp.concatenate(
            [acc, jnp.full_like(acc, -(2 ** 31))], axis=-1)
    else:
        top2 = jax.lax.top_k(acc, 2)[0]
    return acc, best, top2


def bank_prefix_hamming_ref(
    q_packed: jax.Array, im_packed: jax.Array, *, cap: int
) -> jax.Array:
    """int32 [N, cap, M] — `fused_window.bank_prefix_hamming` (materializes
    the [N, M, W] xor; the kernel exists so the jitted path never does)."""
    N, W = q_packed.shape
    M = im_packed.shape[0]
    epw = W // cap
    x = jnp.bitwise_xor(q_packed[:, None, :], im_packed[None, :, :])
    pc = jax.lax.population_count(x).astype(jnp.int32)          # [N, M, W]
    per_bank = pc.reshape(N, M, cap, epw).sum(axis=-1)          # [N, M, cap]
    return jnp.swapaxes(jnp.cumsum(per_bank, axis=-1), 1, 2)


def sign_project_pack_ref(z: jax.Array, R: jax.Array) -> jax.Array:
    """uint32 [N, D//32] — `fused_window.sign_project_pack`, in either
    operand form."""
    from ..core import hdc   # function-level: core imports this package

    return hdc.pack_bits(sign_project_ref(z, R))


def delta_update_ref(
    acc: jax.Array, dmajor: jax.Array, idx: jax.Array, weight: jax.Array
) -> jax.Array:
    """int32 [M]: acc + sum_k weight[k] * dmajor[idx[k], :]."""
    rows = dmajor[idx, :].astype(jnp.int32)
    return acc + jnp.einsum("k,km->m", weight, rows)


def sign_project_ref(z: jax.Array, R: jax.Array) -> jax.Array:
    """int8 [N, D] = sign(z @ R.T), sign(0) -> +1. int8 ``z`` and ``R``
    project with int32 accumulation (exact); any other dtypes in f32."""
    if z.dtype == jnp.int8 and R.dtype == jnp.int8:
        y = jax.lax.dot_general(z, R, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.int32)
    else:
        y = z.astype(jnp.float32) @ R.astype(jnp.float32).T
    return jnp.where(y >= 0, 1, -1).astype(jnp.int8)
