"""Pallas TPU kernels for TorR's compute hot-spots, with jnp oracles.

Kernels (each: <name>.py = pl.pallas_call + BlockSpec; ops.py = jit'd
wrappers; ref.py = pure-jnp oracles; README.md = the two dispatch
contracts):
  * xnor_popcount_sim — full-scan bipolar cosine (bit-packed, VPU popcount)
  * fused_window      — the jitted full path's fused family: gated scan +
                        integer accumulation + argmax/top-2 readout in one
                        grid, the traced-banks bank-prefix variant, the
                        delta scatter-accumulate entry, and the
                        encode->pack front-end
  * delta_update      — Eq. 6 sparse accumulator corrections (scalar-prefetch
                        index streaming = the Delta-FIFO's TPU analogue)
  * sign_project      — fused q = sign(R z) (MXU matmul + int8 quantize)
"""
from . import fused_window, ops, ref
from .delta_update import delta_update
from .fused_window import bank_prefix_hamming, fused_scores, sign_project_pack
from .sign_project import sign_project
from .xnor_popcount_sim import packed_hamming_batched

__all__ = ["fused_window", "ops", "ref", "delta_update", "sign_project",
           "packed_hamming_batched", "fused_scores",
           "bank_prefix_hamming", "sign_project_pack"]
