"""Shared configuration/state types for the TorR core.

Everything here is a static (hashable) config or a JAX pytree. The config
mirrors the paper's deployment-time knobs: dimension D, bank count B (so the
effective dimension D' is a multiple of D/B), similarity thresholds
(tau_byp, tau_q), load thresholds (N_hi, q_hi), the delta budget, lane count
W and clock — the last two parameterize the cycle model of paper Sec. 4.7.
"""
from __future__ import annotations

import dataclasses
from typing import Tuple

import jax
import jax.numpy as jnp


@dataclasses.dataclass(frozen=True)
class TorrConfig:
    """Static TorR configuration (hashable; safe as a jit static arg)."""

    # --- HDC geometry -----------------------------------------------------
    D: int = 8192            # full hypervector dimension
    B: int = 8               # item-memory banks (D' = k * D/B, k in 1..B)
    M: int = 128             # number of concept hypervectors in item memory
    feat_dim: int = 512      # encoder feature dim d (z_e in R^d)

    # --- cache / reuse ----------------------------------------------------
    K: int = 8               # query-cache depth
    N_max: int = 16          # max proposals (queries) per window
    delta_budget: int = 1024 # static |Delta| budget (TPU adaptation; multiple of 128)

    # --- Alg. 1 thresholds --------------------------------------------------
    tau_byp: float = 0.95    # bypass similarity threshold
    tau_q: float = 0.60      # delta-vs-full similarity threshold
    N_hi: int = 8            # high-load object count
    q_hi: int = 4            # high-load queue depth

    # --- reasoner ----------------------------------------------------------
    n_relations: int = 16    # relation hypervectors (used-for, part-of, ...)
    max_hops: int = 3        # max k-hop relation path length
    top_k: int = 5           # top-k key width for reasoner gating
    margin_eps: float = 0.02 # margin tolerance for reasoner gating

    # --- hardware model (paper Sec. 4.3 / 4.7, TSMC 28nm @ 1 GHz) ----------
    W: int = 64              # class lanes in the associative aligner
    clock_hz: float = 1.0e9  # 1 GHz
    accum_bits: int = 8      # accumulator precision knob (int8; int4 has no TPU analogue)
    bit_planes: int = 4      # bit-slice planes per bank (precision gating grain)

    # --- QoS ---------------------------------------------------------------
    fps_target: float = 60.0

    def __post_init__(self):
        if self.D % (self.B * 32) != 0:
            raise ValueError(f"D={self.D} must be divisible by 32*B={32 * self.B}")
        if self.delta_budget % 8 != 0:
            raise ValueError("delta_budget must be a multiple of 8")
        if self.bank_words % self.bit_planes != 0:
            raise ValueError(
                f"bank words D/(32B)={self.bank_words} must be divisible by "
                f"bit_planes={self.bit_planes}")

    @property
    def words(self) -> int:
        """Total packed uint32 words per hypervector."""
        return self.D // 32

    @property
    def bank_dims(self) -> int:
        """Dimensions per bank (D/B)."""
        return self.D // self.B

    @property
    def bank_words(self) -> int:
        return self.bank_dims // 32

    def d_eff(self, banks: jax.Array | int) -> jax.Array | int:
        """Effective dimension D' for a given number of enabled banks."""
        return banks * self.bank_dims

    @property
    def plane_words(self) -> int:
        """Packed words per bit-slice plane within one bank."""
        return self.bank_words // self.bit_planes

    @property
    def plane_dims(self) -> int:
        """Dimensions per bit-slice plane within one bank."""
        return self.bank_dims // self.bit_planes

    def d_eff_planned(
        self, banks: jax.Array | int, planes: int
    ) -> jax.Array | int:
        """Effective dimension under combined bank + bit-plane gating."""
        return banks * (self.plane_dims * planes)

    @property
    def cycles_per_window_budget(self) -> float:
        return self.clock_hz / self.fps_target


# Path encodings shared by the policy, pipeline and cycle model.
PATH_BYPASS = 0
PATH_DELTA = 1
PATH_FULL = 2
PATH_NAMES = ("bypass", "delta", "full")

# Static-lowering encodings recorded in WindowTelemetry so lowering audits
# (flight recorder, cycle model) can read the resolved dispatch straight off
# the trace. Index-aligned name tuples are the shared decode vocabulary —
# ``FUSED_NAMES[int(tel.fused_mode)]`` — used by ``repro.obs`` and
# ``repro.perf.cycle_model``.
FUSED_NAMES = ("off", "switch", "prefix", "compact")
FUSED_IDS = {name: i for i, name in enumerate(FUSED_NAMES)}
DECIDE_NAMES = ("scan", "batched")
DECIDE_IDS = {name: i for i, name in enumerate(DECIDE_NAMES)}
DECIDE_NONE = -1   # non-compact lowerings run no decide pass

# The delta accumulator's exactness tag (Eq. 6): a delta correction is only
# valid against an accumulator computed under the *same* enabled dimensions,
# which under the QoS control plane means the same (banks, bit-planes) pair.
# One int32 packs both so the cache carries a single tag per entry; 0 (the
# init value) can never collide because banks >= 1 for any real scan.
PLAN_TAG_BASE = 256


def plan_tag(banks: jax.Array | int, planes: jax.Array | int):
    """int32 tag for an accumulator computed under (banks, planes)."""
    return banks * PLAN_TAG_BASE + planes


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass
class StreamBatch:
    """One batched multi-stream window step's inputs (S stream slots).

    The leading axis is the stream-slot axis of the engine
    (`repro.serving.stream_engine`): slot s carries stream s's next window.
    Idle slots are padded with ``valid`` all-False and ``queue_depth`` 0 —
    the pipeline's pad branch guarantees they leave that slot's cache
    untouched. ``queue_depth`` is per-stream (each stream has its own
    backlog), which is what lets Alg. 1's load gating stay per-stream
    under batching.
    """

    q_packed: jax.Array     # uint32 [S, N_max, D//32] proposal query HVs
                            # (int8 [S, N_max, feat_dim] features for a
                            # step that encodes them)
    valid: jax.Array        # bool   [S, N_max]
    boxes: jax.Array        # f32    [S, N_max, 4]
    queue_depth: jax.Array  # int32  [S] per-stream backlog

    @property
    def n_streams(self) -> int:
        return self.q_packed.shape[0]

    def tree_flatten(self):
        return ((self.q_packed, self.valid, self.boxes, self.queue_depth),
                None)

    @classmethod
    def tree_unflatten(cls, aux, children):
        del aux
        return cls(*children)


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass
class WindowTelemetry:
    """Per-window execution trace (feeds the cycle-accurate model).

    ``queue_depth`` and ``high_load`` echo the load signals Alg. 1's gate
    H(N, q) actually saw, so host-side controllers (the RT-deadline
    admission control in ``repro.serving.deadline``) and the cycle model can
    attribute path decisions to backlog pressure without re-deriving it.
    ``banks`` and ``planes`` together record the knob plan the window
    actually ran with (the QoS governor's latched D'/precision choice), so
    energy accounting and plan audits read straight off the trace.
    ``fused_mode``/``decide_mode``/``bucket_tier`` record the *resolved*
    static lowering knobs the step actually dispatched with (``FUSED_IDS``/
    ``DECIDE_IDS`` encodings; ``DECIDE_NONE`` and tier 0 for lowerings that
    run no decide pass), so lowering audits never have to re-derive which
    executable a traced window went through.
    """

    path: jax.Array        # [N_max] int32, PATH_* per proposal
    delta_count: jax.Array # [N_max] int32, |Delta| per proposal
    banks: jax.Array       # [] int32, enabled banks this window
    rho: jax.Array         # [N_max] f32, similarity to nearest cached query
    n_valid: jax.Array     # [] int32, actual proposals this window
    reasoner_active: jax.Array  # [N_max] bool, reasoner ran (not gated)
    queue_depth: jax.Array # [] int32, backlog fed to H(N, q) this window
    high_load: jax.Array   # [] bool, H(N, q) as evaluated by Alg. 1
    planes: jax.Array      # [] int32, enabled bit-slice planes this window
    fused_mode: jax.Array  # [] int32, FUSED_IDS[...] the step ran with
    decide_mode: jax.Array # [] int32, DECIDE_IDS[...] or DECIDE_NONE
    bucket_tier: jax.Array # [] int32, compact bucket capacity (0 = n/a)

    def tree_flatten(self):
        return (
            (self.path, self.delta_count, self.banks, self.rho, self.n_valid,
             self.reasoner_active, self.queue_depth, self.high_load,
             self.planes, self.fused_mode, self.decide_mode,
             self.bucket_tier),
            None,
        )

    @classmethod
    def tree_unflatten(cls, aux, children):
        del aux
        return cls(*children)
