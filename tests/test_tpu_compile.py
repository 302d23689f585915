"""The main-path kernels and steps compile for a TPU v5e at paper widths.

Nothing runs: each test compiles for a *described* ``v5e:2x2`` topology
(the TPU compiler is installed; no chip is needed), which refuses what
interpret mode accepts — blocks off the (8, 128) tiling, primitives Mosaic
cannot lower, kernels XLA cannot partition. Widths are ``torr_edge``'s:
D=8192 (256 words), M=1024, K=8, N_max=128, delta budget 2048, with S=8
streams' proposals (1024 rows) in the hoisted passes.

The topology is described inside a fixture (never at import): only one
process may load the TPU library, and under pytest-xdist every worker
imports every test file.
"""
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec, SingleDeviceSharding

from repro.configs import torr_edge
from repro.core import item_memory, pipeline
from repro.core.types import StreamBatch
from repro.kernels import fused_window, xnor_popcount_sim
from repro.kernels.delta_update import delta_update
from repro.kernels.sign_project import sign_project
from repro.obs import phases
from repro.runtime import sharding as shd

CFG = torr_edge()
S = 8                                   # streams per step
ROWS = S * CFG.N_max                    # hoisted proposal rows
PLANS = [(b, p) for b in range(1, CFG.B + 1)
         for p in range(1, CFG.bit_planes + 1)]


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    os.environ.setdefault("TPU_LOG_DIR", "disabled")  # no compiler logs
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any failure means no compiler
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module", autouse=True)
def no_persistent_cache():
    """A compile for a described chip is written to the persistent cache
    but cannot be read back without one: keep the cache off here."""
    from jax.experimental.compilation_cache import compilation_cache as cc
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    cc.reset_cache()


@pytest.fixture
def tpu_lowering(monkeypatch):
    """Take the compiled-Pallas lowering the TPU backend would pick (code
    asking the backend here sees the CPU)."""
    monkeypatch.setattr(fused_window, "_pallas_lowering",
                        lambda interpret: False if interpret is None
                        else interpret)


def _compile(fn, *args):
    compiled = jax.jit(fn).lower(*args).compile()
    return compiled.as_text()


def _spec(sharding, shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


# --- kernels -----------------------------------------------------------------

@pytest.mark.parametrize("n,m", [(ROWS, CFG.M), (CFG.N_max, CFG.K),
                                 (CFG.N_max, CFG.N_max)])
def test_packed_hamming_compiles(one_chip, n, m):
    """Full-width scan and the batched decide's [N, K] / [N, N] tables."""
    w = CFG.words
    text = _compile(
        lambda q, h: xnor_popcount_sim.packed_hamming_batched(
            q, h, interpret=False),
        _spec(one_chip, (n, w), jnp.uint32), _spec(one_chip, (m, w),
                                                  jnp.uint32))
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("words", [8, 24, 160, 256])
def test_fused_scores_compiles(one_chip, words):
    """Full width and reduced plans' unaligned word counts (160 = 5 banks,
    8 = 1 bank at 1 plane)."""
    text = _compile(
        lambda q, h: fused_window.fused_scores(q, h, d_eff=32 * words,
                                               interpret=False),
        _spec(one_chip, (CFG.N_max, words), jnp.uint32),
        _spec(one_chip, (CFG.M, words), jnp.uint32))
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("banks,planes", PLANS)
def test_bank_prefix_hamming_compiles(one_chip, banks, planes):
    """Every (banks, planes) plan of the governor's ladder."""
    words = banks * planes * CFG.plane_words
    text = _compile(
        lambda q, h: fused_window.bank_prefix_hamming(q, h, cap=banks,
                                                      interpret=False),
        _spec(one_chip, (ROWS, words), jnp.uint32),
        _spec(one_chip, (CFG.M, words), jnp.uint32))
    assert "tpu_custom_call" in text


def test_delta_update_compiles(one_chip):
    text = _compile(
        lambda a, d, i, w: delta_update(a, d, i, w, interpret=False),
        _spec(one_chip, (CFG.M,), jnp.int32),
        _spec(one_chip, (CFG.D, CFG.M), jnp.int8),
        _spec(one_chip, (CFG.delta_budget,), jnp.int32),
        _spec(one_chip, (CFG.delta_budget,), jnp.int32))
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("n", [CFG.N_max, 8])
def test_sign_project_kernels_compile(one_chip, n):
    z = _spec(one_chip, (n, CFG.feat_dim), jnp.float32)
    r = _spec(one_chip, (CFG.D, CFG.feat_dim), jnp.float32)
    for fn in (lambda z, r: fused_window.sign_project_pack(z, r,
                                                           interpret=False),
               lambda z, r: sign_project(z, r, interpret=False)):
        assert "tpu_custom_call" in _compile(fn, z, r)


# --- whole steps -------------------------------------------------------------

def _step_args(place):
    """Shapes of one S-stream step's (state, item memory, batch); ``place``
    maps (leaf, has_stream_axis) to a sharding."""
    def put(tree, streamed):
        return jax.tree.map(
            lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype,
                                           sharding=place(a, streamed)),
            tree)
    state = jax.eval_shape(lambda: pipeline.init_multi_stream_state(
        CFG, jnp.zeros((S, CFG.M), jnp.float32)))
    im = jax.eval_shape(lambda: item_memory.random_item_memory(
        jax.random.PRNGKey(0), CFG))
    batch = StreamBatch(
        q_packed=jax.ShapeDtypeStruct((S, CFG.N_max, CFG.words), jnp.uint32),
        valid=jax.ShapeDtypeStruct((S, CFG.N_max), jnp.bool_),
        boxes=jax.ShapeDtypeStruct((S, CFG.N_max, 4), jnp.float32),
        queue_depth=jax.ShapeDtypeStruct((S,), jnp.int32))
    return put(state, True), put(im, False), put(batch, True)


@pytest.mark.parametrize("fused,kw", [
    ("prefix", {}),
    ("compact", {"decide": "batched"}),
    ("switch", {"serial": True}),
])
def test_multi_stream_step_compiles(one_chip, tpu_lowering, fused, kw):
    """Every lowering the engine can pick carries the Pallas kernels."""
    state, im, batch = _step_args(lambda a, streamed: one_chip)
    text = _compile(
        lambda st, m, b: pipeline.torr_stream_batch_step(
            st, m, b, CFG, fused=fused, **kw), state, im, batch)
    assert "tpu_custom_call" in text


_RESULT = re.compile(r"^\s*(?:ROOT )?%?([\w.\-]+) = (.*?) [a-z][\w\-]*\(")


def test_prefix_step_delta_phases_build_no_index_list(one_chip,
                                                     tpu_lowering):
    """The served ``prefix`` step evaluates Eq. 6 as one dense masked
    matvec: no op of its ``delta_search`` or ``delta_apply`` phases has a
    result dimension of ``delta_budget``, the length of the flipped-dim
    list a per-lane index search and row gather would build."""
    state, im, batch = _step_args(lambda a, streamed: one_chip)
    text = _compile(
        lambda st, m, b: pipeline.torr_stream_batch_step(
            st, m, b, CFG, fused="prefix"), state, im, batch)
    results = dict(m.groups() for m in map(_RESULT.match, text.splitlines())
                   if m is not None)
    delta_ops = {name: results[name]
                 for name, ph in phases.phase_table(text).items()
                 if ph in ("delta_search", "delta_apply")}
    assert delta_ops
    for name, shape in delta_ops.items():
        dims = [int(d) for group in re.findall(r"\[([\d,]*)\]", shape)
                for d in group.split(",") if d]
        assert CFG.delta_budget not in dims, (name, shape)


def test_stream_sharded_step_compiles(topo, tpu_lowering):
    """The 4-chip stream-sharded step: each chip runs the kernels on its
    own slots, with no collective in the step."""
    mesh = Mesh(np.asarray(topo.devices[:4]), (shd.STREAM_AXIS,))

    def place(a, streamed):
        spec = (PartitionSpec(shd.STREAM_AXIS, *([None] * (a.ndim - 1)))
                if streamed else PartitionSpec())
        return NamedSharding(mesh, spec)

    state, im, batch = _step_args(place)
    text = shd.stream_sharded_step.lower(state, im, batch, CFG,
                                         mesh=mesh).compile().as_text()
    assert "tpu_custom_call" in text
    for collective in ("all-gather", "all-reduce", "all-to-all"):
        assert collective not in text


@pytest.mark.parametrize("n", [ROWS, CFG.N_max])
def test_int8_sign_project_pack_compiles(one_chip, n):
    """The feature step's encode: int8 features against the int8 ±1
    projection, int32 accumulation on the MXU."""
    text = _compile(
        lambda z, r: fused_window.sign_project_pack(z, r, interpret=False),
        _spec(one_chip, (n, CFG.feat_dim), jnp.int8),
        _spec(one_chip, (CFG.D, CFG.feat_dim), jnp.int8))
    assert "tpu_custom_call" in text


_OP = re.compile(r"^\s*(?:ROOT )?%?([\w.\-]+) = (.*?) ([a-z][\w\-]*)\(")


def _encode_ops(text) -> dict:
    """``{instruction: (result shape, opcode)}`` of the ops in the
    ``encode`` phase."""
    ops = {m.group(1): m.groups()[1:]
           for m in map(_OP.match, text.splitlines()) if m is not None}
    return {name: ops[name]
            for name, ph in phases.phase_table(text).items()
            if ph == "encode"}


def test_only_the_feature_step_encodes(one_chip, tpu_lowering):
    """The served packed step has no ``encode`` op; the feature step's
    ``encode`` phase holds one kernel, ``sign_project_pack``, over all
    S x N_max rows, before the prefix scan."""
    state, im, batch = _step_args(lambda a, streamed: one_chip)
    packed = _compile(
        lambda st, m, b: pipeline.torr_stream_batch_step(
            st, m, b, CFG, fused="prefix"), state, im, batch)
    assert not _encode_ops(packed)
    assert "sign_project_pack" not in packed

    feats = jax.ShapeDtypeStruct((S, CFG.N_max, CFG.feat_dim), jnp.int8,
                                 sharding=one_chip)
    proj = _spec(one_chip, (CFG.D, CFG.feat_dim), jnp.int8)
    text = _compile(
        lambda st, m, b, r: pipeline.torr_stream_batch_step(
            st, m, b, CFG, fused="prefix", projection=r),
        state, im, StreamBatch(q_packed=feats, valid=batch.valid,
                               boxes=batch.boxes,
                               queue_depth=batch.queue_depth), proj)
    encode = _encode_ops(text)
    calls = [name for name, (_shape, op) in encode.items()
             if op == "custom-call"]
    assert len(calls) == 1 and "sign_project_pack" in calls[0], encode
    assert encode[calls[0]][0].startswith(f"u32[{CFG.words},{ROWS}]")
    # the words go on to the bank-prefix scan in the same executable
    assert "bank_prefix_hamming" in text
