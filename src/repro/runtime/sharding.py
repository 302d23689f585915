"""Sharding rules: DP / TP / EP / SP over the production mesh.

Parameters follow Megatron-style column/row parallelism over the 'model'
axis; MoE experts are expert-parallel over 'model'; batch shards over
('pod', 'data'). Decode caches pick, per tensor, the best shardable axis:
KV heads when divisible by the model-axis size, else sequence (flash-decode
style), else head_dim — so every (arch x shape) cell partitions without
padding.

Rules are *name-based on the trailing dims* and padded with leading Nones,
so the same rule covers a flat weight, a layer-stacked weight [L, ...] and a
vmapped group stack.
"""
from __future__ import annotations

import functools
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

# trailing-dims spec per parameter leaf name
_COL = ("_col", (None, "model"))     # [in, out_sharded]
_ROW = ("_row", ("model", None))     # [in_sharded, out]

_PARAM_RULES: dict[str, tuple] = {
    # embeddings
    "embed": ("model", None),        # [V, d] vocab-sharded
    "unembed": (None, "model"),
    # attention & projections (column-parallel)
    "wq": _COL[1], "wk": _COL[1], "wv": _COL[1],
    "wq_a": (None, None), "wq_b": _COL[1],
    "wkv_a": (None, None), "wk_b": _COL[1], "wv_b": _COL[1],
    # row-parallel outputs
    "wo": _ROW[1], "w_down": _ROW[1], "w_out": _ROW[1],
    # MLPs / recurrent branches (column-parallel)
    "w_gate": _COL[1], "w_up": _COL[1], "w_z": _COL[1],
    "w_gate_in": _COL[1], "w_in": _COL[1], "w_ifzo": _COL[1],
    "w_up_gate": _COL[1],
    "shared_gate": _COL[1], "shared_up": _COL[1], "shared_down": _ROW[1],
    # gates / small
    "router": (None, None), "w_if": (None, None), "proj": (None, None),
    "wa": _COL[1], "wx": _COL[1],
    "conv_w": (None, "model"),
    "lam": ("model",), "gn_scale": ("model",),
    "r_ifzo": (None, None, None),
    "head": (None, None), "head_b": (None,),
}

# MoE expert stacks: leading experts dim is expert-parallel
_MOE_EXPERT_RULES = {
    "w_gate": ("model", None, None),
    "w_up": ("model", None, None),
    "w_down": ("model", None, None),
}

_REPLICATED_MARKERS = ("ln", "norm", "b_", "gate", "margin")


def _leaf_name(path) -> str:
    for entry in reversed(path):
        if isinstance(entry, jax.tree_util.DictKey):
            return str(entry.key)
        if isinstance(entry, jax.tree_util.GetAttrKey):
            return entry.name
    return ""


def _in_moe(path) -> bool:
    return any(isinstance(e, jax.tree_util.DictKey) and str(e.key) == "moe"
               for e in path)


def param_spec(path, leaf) -> P:
    name = _leaf_name(path)
    rules = _MOE_EXPERT_RULES if _in_moe(path) and name in _MOE_EXPERT_RULES \
        else _PARAM_RULES
    if name in rules:
        trailing = rules[name]
        pad = leaf.ndim - len(trailing)
        if pad < 0:   # e.g. a 1-D leaf hitting a 2-D rule; replicate
            return P()
        return P(*((None,) * pad + tuple(trailing)))
    if name.startswith(_REPLICATED_MARKERS) or name.endswith("_norm") or \
            "norm" in name:
        return P()
    return P()


def _drop_indivisible(spec: P, leaf, mesh: Mesh) -> P:
    """Replace any sharded dim the leaf's shape can't divide with None."""
    out = []
    for dim, axes in enumerate(spec):
        if axes is None:
            out.append(None)
            continue
        size = 1
        for a in (axes if isinstance(axes, tuple) else (axes,)):
            size *= mesh.shape[a]
        out.append(axes if leaf.shape[dim] % size == 0 else None)
    return P(*out)


def params_pspecs(params, mesh: Mesh | None = None) -> Any:
    if mesh is None:
        return jax.tree_util.tree_map_with_path(param_spec, params)
    return jax.tree_util.tree_map_with_path(
        lambda p, l: _drop_indivisible(param_spec(p, l), l, mesh), params)


def params_sharding(params, mesh: Mesh):
    return jax.tree_util.tree_map_with_path(
        lambda p, l: NamedSharding(mesh, _drop_indivisible(param_spec(p, l), l, mesh)),
        params)


# ---------------------------------------------------------------------------
# Activations / batches / caches
# ---------------------------------------------------------------------------

def batch_axes(mesh: Mesh):
    return ("pod", "data") if "pod" in mesh.axis_names else ("data",)


def _divisible(n: int, mesh: Mesh, axes) -> bool:
    size = 1
    for a in (axes if isinstance(axes, tuple) else (axes,)):
        size *= mesh.shape[a]
    return n % size == 0 and n >= size


def batch_spec(mesh: Mesh, leaf) -> P:
    """Tokens/labels/vision: shard dim0 over (pod, data) when divisible."""
    ba = batch_axes(mesh)
    if _divisible(leaf.shape[0], mesh, ba):
        return P(ba, *([None] * (leaf.ndim - 1)))
    if _divisible(leaf.shape[0], mesh, "data"):
        return P("data", *([None] * (leaf.ndim - 1)))
    return P(*([None] * leaf.ndim))


def batch_sharding(batch, mesh: Mesh):
    return jax.tree.map(lambda l: NamedSharding(mesh, batch_spec(mesh, l)), batch)


def cache_spec(path, leaf, mesh: Mesh) -> P:
    """Decode-cache sharding. Layout conventions (see models.transformer):

    kv        [G(, pos), B, S, Hkv, dh]
    ckv       [G, B, S, r+dr]
    cross_kv  [G, B, Nv, Hkv, dh]
    rec.h     [G, n_rec, B, w]        rec.conv [G, n_rec, B, cw, w]
    mlstm.C   [G, n_m, B, H, dh, dh]  mlstm.n [G, n_m, B, H, dh]
    mlstm.m   [G, n_m, B, H]          mlstm.conv [G, n_m, B, cw, din]
    slstm.*   [G, B, d] / [G, B, H]
    """
    names = [str(e.key) for e in path if isinstance(e, jax.tree_util.DictKey)]
    if not names:
        return P()
    top = names[0]
    nd = leaf.ndim
    spec = [None] * nd
    msize = mesh.shape["model"]

    def shard_batch(dim):
        ba = batch_axes(mesh)
        if _divisible(leaf.shape[dim], mesh, ba):
            spec[dim] = ba
        elif _divisible(leaf.shape[dim], mesh, "data"):
            spec[dim] = "data"

    if top == "pos":
        return P()
    leafname = names[-1]
    if top in ("kv", "cross_kv"):
        if leafname in ("ks", "vs"):      # int8-cache scales: [.., B, S, Hkv]
            b_dim, s_dim, h_dim = nd - 3, nd - 2, nd - 1
            shard_batch(b_dim)
            if leaf.shape[h_dim] % msize == 0:
                spec[h_dim] = "model"
            elif top == "kv" and leaf.shape[s_dim] % msize == 0:
                spec[s_dim] = "model"
            return P(*spec)
        # k/v (or kq/vq) trailing dims: [B, S, Hkv, dh]
        b_dim, s_dim, h_dim, d_dim = nd - 4, nd - 3, nd - 2, nd - 1
        shard_batch(b_dim)
        if leaf.shape[h_dim] % msize == 0:
            spec[h_dim] = "model"
        elif top == "kv" and leaf.shape[s_dim] % msize == 0:
            spec[s_dim] = "model"
        elif leaf.shape[d_dim] % msize == 0:
            spec[d_dim] = "model"
        return P(*spec)
    if top.startswith("ckv"):   # 'ckv' and 'ckv_prefix' (dense-prefix MLA)
        if leafname == "s":               # int8 latent scales [G, B, S]
            b_dim, s_dim = nd - 2, nd - 1
            shard_batch(b_dim)
            if leaf.shape[s_dim] % msize == 0:
                spec[s_dim] = "model"
            return P(*spec)
        b_dim, s_dim = nd - 3, nd - 2
        shard_batch(b_dim)
        if leaf.shape[s_dim] % msize == 0:
            spec[s_dim] = "model"
        return P(*spec)
    if top == "rec":
        shard_batch(nd - 2 if names[-1] == "h" else nd - 3)
        if leaf.shape[nd - 1] % msize == 0:
            spec[nd - 1] = "model"
        return P(*spec)
    if top == "mlstm":
        leafname = names[-1]
        if leafname == "C":
            shard_batch(2)
            if leaf.shape[4] % msize == 0:
                spec[4] = "model"
        elif leafname in ("n", "conv"):
            shard_batch(2)
            if leaf.shape[nd - 1] % msize == 0:
                spec[nd - 1] = "model"
        elif leafname == "m":
            shard_batch(2)
        return P(*spec)
    if top == "slstm":
        shard_batch(1)
        if names[-1] != "m" and leaf.shape[nd - 1] % msize == 0:
            spec[nd - 1] = "model"
        return P(*spec)
    return P()


def cache_pspecs(cache, mesh: Mesh):
    return jax.tree_util.tree_map_with_path(
        lambda p, l: cache_spec(p, l, mesh), cache)


def cache_sharding(cache, mesh: Mesh):
    return jax.tree_util.tree_map_with_path(
        lambda p, l: NamedSharding(mesh, cache_spec(p, l, mesh)), cache)


def abstract_tree(init_fn, *args, **kwargs):
    """eval_shape an init function: ShapeDtypeStruct tree, no allocation."""
    return jax.eval_shape(init_fn, *args, **kwargs)


# ---------------------------------------------------------------------------
# Multi-stream serving: stacked per-stream state over a 1-D stream mesh
# ---------------------------------------------------------------------------
# The multi-stream TorR engine (serving.stream_engine / serving.async_engine)
# stacks every per-stream leaf with a leading stream-slot axis [S, ...].
# Streams are independent (the batched step is an exact vmap of the window
# FSM), so the only sensible partitioning is: shard the leading S axis,
# replicate the shared item memory. These helpers keep that rule in one
# place; the engine pads its slot count to a multiple of the device count so
# the leading axis always divides.

STREAM_AXIS = "stream"


def stream_mesh(n_devices: int | None = None) -> Mesh:
    """1-D mesh over (the first) ``n_devices`` devices for stream sharding."""
    devs = jax.devices()
    n = len(devs) if n_devices in (None, 0) else n_devices
    if n > len(devs):
        raise ValueError(f"requested {n} devices, only {len(devs)} present")
    return Mesh(np.asarray(devs[:n]), (STREAM_AXIS,))


def pad_stream_slots(n_slots: int, mesh: Mesh | None) -> int:
    """Round a slot count up to a multiple of the mesh's stream-axis size."""
    if mesh is None:
        return n_slots
    n_dev = mesh.shape[STREAM_AXIS]
    return -(-n_slots // n_dev) * n_dev


def stream_spec(leaf) -> P:
    """Shard the leading stream-slot axis; everything trailing replicated."""
    return P(STREAM_AXIS, *([None] * (leaf.ndim - 1)))


def stream_sharding(tree, mesh: Mesh):
    """NamedSharding tree for stacked per-stream state / batches.

    Every leaf must carry the leading [S] stream axis with S divisible by
    the mesh (guaranteed by :func:`pad_stream_slots`)."""
    return jax.tree.map(lambda l: NamedSharding(mesh, stream_spec(l)), tree)


def replicated_sharding(tree, mesh: Mesh):
    """Fully-replicated NamedSharding tree (shared item memory)."""
    return jax.tree.map(lambda _: NamedSharding(mesh, P()), tree)


def _stream_sharded_step(state, im, batch, cfg, serial=False, plan=None,
                         fused=None, bucket_cap=None, decide=None, *, mesh):
    """``pipeline.torr_stream_batch_step`` with each device stepping only
    its own stream slots (``shard_map`` over the stream axis; the item
    memory replicated). Streams never interact, so this is the same step
    — and the Pallas kernels inside it, which XLA cannot partition, see
    per-device blocks."""
    from ..core import pipeline

    body = functools.partial(pipeline.torr_stream_batch_step, cfg=cfg,
                             serial=serial, plan=plan, fused=fused,
                             bucket_cap=bucket_cap, decide=decide)
    slots = P(STREAM_AXIS)
    return jax.shard_map(body, mesh=mesh, in_specs=(slots, P(), slots),
                         out_specs=slots, check_vma=False)(state, im, batch)


# module-level jit (mesh static) so engines on the same mesh share one
# compiled executable, like the unsharded engines' step
stream_sharded_step = jax.jit(
    _stream_sharded_step,
    static_argnames=("cfg", "serial", "plan", "fused", "bucket_cap",
                     "decide", "mesh"))
