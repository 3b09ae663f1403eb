"""Partition rules: params / caches / inputs -> PartitionSpec.

Rules are matched on the *trailing* path component names; specs are padded
with leading ``None`` for scan-stacked axes (segment params carry a leading
(reps,) axis). "model" is the tensor/expert-parallel mesh axis; batch is
sharded over ("pod","data") (or ("data",) single-pod); KV-cache sequence dims
shard over "data" for the decode shapes (batch is too small to fill the mesh
at ``long_500k``).

ZeRO-1 (beyond-paper §Perf lever): ``zero1=True`` additionally shards
optimizer-state leaves over the data axis on their largest divisible dim.
"""
from __future__ import annotations

import re
from contextlib import contextmanager
from typing import Any, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

# ---------------------------------------------------------------------------
# ambient-mesh sharding hints
#
# Model code (e.g. the MoE dispatch buffers) sometimes needs explicit
# with_sharding_constraint hints that GSPMD propagation won't find on its
# own. Model layers call ``constrain(x, spec)`` with symbolic axis names;
# outside a mesh context this is a no-op, so CPU tests/benchmarks are
# unaffected. "batch" resolves to every data-like axis present in the mesh.
# ---------------------------------------------------------------------------

_AMBIENT_MESH: list = []


@contextmanager
def ambient_mesh(mesh: Mesh, layout: str = "tp"):
    _AMBIENT_MESH.append((mesh, layout))
    try:
        yield mesh
    finally:
        _AMBIENT_MESH.pop()


def constrain(x: jnp.ndarray, spec: Tuple) -> jnp.ndarray:
    if not _AMBIENT_MESH:
        return x
    mesh, layout = _AMBIENT_MESH[-1]
    explicit = {s for s in spec if isinstance(s, str) and s != "batch"}
    resolved = []
    for s, dim in zip(spec, x.shape):
        if s == "batch":
            # drop axes already claimed by explicit entries of this spec
            s = tuple(a for a in batch_axes(mesh, layout)
                      if a not in explicit)
            if not s:
                resolved.append(None)
                continue
            size = int(np.prod([mesh.shape[a] for a in s]))
        elif s is not None:
            size = mesh.shape[s] if s in mesh.axis_names else None
            if size is None:
                resolved.append(None)
                continue
        if s is not None and (dim < size or dim % size != 0):
            resolved.append(None)
        else:
            resolved.append(s)
    return jax.lax.with_sharding_constraint(
        x, NamedSharding(mesh, P(*resolved)))


# ---------------------------------------------------------------------------
# party-axis helpers (mesh-sharded party engine)
#
# The EASTER protocol is embarrassingly parallel across participants, so the
# party dimension is a first-class mesh axis: core/party_engine.py lays each
# group's stacked params and feature slices out over PARTY_AXIS with
# shard_map and runs embed / decide / assisted-grad steps K-parallel, with
# the blinded all-gather as the only cross-device collective.
# ---------------------------------------------------------------------------

PARTY_AXIS = "party"


def shard_map(fn, mesh: Mesh, in_specs, out_specs):
    """``jax.shard_map`` over the party mesh, with replication checking
    off: every ``P()`` output of the party engine is an all-gather or a
    psum, replicated by construction."""
    return jax.shard_map(fn, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=False)


def party_axis_size(mesh: Optional[Mesh], axis: str = PARTY_AXIS) -> int:
    if mesh is None or axis not in mesh.axis_names:
        return 1
    return mesh.shape[axis]


def party_shardable(mesh: Optional[Mesh], n: int,
                    axis: str = PARTY_AXIS) -> bool:
    """True when a party-stacked leading dim of ``n`` can lay out over the
    party axis (axis present, >1 device, and n divides evenly). The
    engines run any other group replicated: the whole vmapped group is
    computed on every device."""
    size = party_axis_size(mesh, axis)
    return size > 1 and n >= size and n % size == 0


def data_axes(mesh: Mesh) -> Tuple[str, ...]:
    return tuple(a for a in mesh.axis_names if a in ("pod", "data"))


def batch_axes(mesh: Mesh, layout: str = "tp") -> Tuple[str, ...]:
    """Axes the batch dim shards over. layout="zero3" absorbs the model
    axis into the batch (pure data parallelism + fully-sharded params)."""
    if layout == "zero3":
        return tuple(mesh.axis_names)
    return data_axes(mesh)


def model_axis(mesh: Mesh) -> str:
    return "model"


def _msize(mesh: Mesh) -> int:
    return mesh.shape["model"]


# ---------------------------------------------------------------------------
# parameter rules
# ---------------------------------------------------------------------------

def _param_rule(path: Tuple[str, ...], leaf, mesh: Mesh,
                seq_axis: Optional[str] = None) -> P:
    """Decide the spec for one param leaf from its path names."""
    names = [p for p in path]
    name = names[-1] if names else ""
    parent = names[-2] if len(names) > 1 else ""
    gparent = names[-3] if len(names) > 2 else ""
    m = "model"
    msize = _msize(mesh)

    def fits(dim: int) -> bool:
        return dim >= msize and dim % msize == 0

    shape = leaf.shape
    nd = leaf.ndim

    def pad(rule: Tuple) -> P:
        extra = nd - len(rule)
        return P(*([None] * extra + list(rule)))

    # --- embeddings / heads ---
    if name == "table":
        # vocab-sharded embedding (replicate vocab when it doesn't divide —
        # e.g. whisper's 51865 — and shard d_model instead if possible)
        if fits(shape[-2]):
            return pad((m, None))
        return pad((None, m)) if fits(shape[-1]) else pad((None, None))
    if parent == "head" and name == "w":
        return pad((None, m)) if fits(shape[-1]) else pad((None, None))

    # --- MoE ---
    if name in ("w_gate", "w_up", "w_down"):
        E = shape[-3]
        if fits(E):
            return pad((m, None, None))            # expert parallel
        # tensor-parallel experts: shard the ff dim
        return pad((None, None, m)) if name != "w_down" else pad((None, m, None))
    if name == "router":
        return pad((None, None))

    # --- attention ---
    if parent in ("wq", "wk", "wv") and name == "w":
        return pad((None, m)) if fits(shape[-1]) else pad((None, None))
    if parent in ("wq", "wk", "wv") and name == "b":
        return pad((m,)) if fits(shape[-1]) else pad((None,))
    if parent == "wo" and name == "w":
        return pad((m, None)) if fits(shape[-2]) else pad((None, None))

    # --- dense MLP ---
    if parent in ("up", "gate") and name == "w":
        return pad((None, m)) if fits(shape[-1]) else pad((None, None))
    if parent == "down" and name == "w":
        return pad((m, None)) if fits(shape[-2]) else pad((None, None))
    if parent in ("up", "gate") and name == "b":
        return pad((m,)) if fits(shape[-1]) else pad((None,))

    # --- SSD (mamba2) ---
    if name == "in_proj":                          # packed zxbcdt: replicate
        return pad((None, None))
    if name == "out_proj":
        return pad((m, None)) if fits(shape[-2]) else pad((None, None))
    if name in ("A_log", "D", "dt_bias"):
        return pad((m,)) if fits(shape[-1]) else pad((None,))
    if name in ("conv_w", "conv_b"):
        return pad((None,) * nd)

    # --- RG-LRU ---
    if parent in ("in_x", "in_gate") and name == "w":
        return pad((None, m)) if fits(shape[-1]) else pad((None, None))
    if parent in ("w_r", "w_i") and name == "w":
        return pad((None, m)) if fits(shape[-1]) else pad((None, None))
    if parent in ("w_r", "w_i") and name == "b":
        return pad((m,)) if fits(shape[-1]) else pad((None,))
    if name == "lam":
        return pad((m,)) if fits(shape[-1]) else pad((None,))
    if parent == "out" and name == "w":
        return pad((m, None)) if fits(shape[-2]) else pad((None, None))

    # --- EASTER proj / decision head ---
    if parent == "proj" and name == "w":
        return pad((None, None))

    # norms, scalars, everything else: replicate
    return pad((None,) * nd)


def _path_names(keypath) -> Tuple[str, ...]:
    names = []
    for k in keypath:
        if hasattr(k, "key"):
            names.append(str(k.key))
        elif hasattr(k, "idx"):
            names.append(f"i{k.idx}")
        else:
            names.append(str(k))
    return tuple(names)


def _add_fsdp(spec: P, leaf, mesh: Mesh, dax: Optional[Tuple] = None) -> P:
    """FSDP overlay: shard one remaining replicated dim over the data axes.

    Preference order: the scan-stack (layer) axis, then the largest
    divisible dim. Only applied to leaves > 1M elements — biases/norms stay
    replicated.
    """
    if leaf.size < 2 ** 20:
        return spec
    dax = dax or data_axes(mesh)
    dsz = int(np.prod([mesh.shape[a] for a in dax]))
    entries = list(spec) + [None] * (leaf.ndim - len(spec))
    order = list(range(leaf.ndim))
    # try dims largest-first, but prefer the leading stack axis if divisible
    order.sort(key=lambda i: -leaf.shape[i])
    if entries[0] is None and leaf.shape[0] % dsz == 0 and leaf.ndim > 2:
        order = [0] + [i for i in order if i != 0]
    for i in order:
        if entries[i] is None and leaf.shape[i] % dsz == 0 \
                and leaf.shape[i] >= dsz:
            entries[i] = dax
            return P(*entries)
    return spec


def param_specs(params, mesh: Mesh, fsdp: bool = False,
                layout: str = "tp"):
    """Pytree of PartitionSpec matching ``params``.

    layout="tp" (default): 1D tensor parallel over "model" (+ optional FSDP
    overlay over "data"). layout="zero3": no tensor parallelism — params
    fully sharded over ALL mesh axes (ZeRO-3 / pure-FSDP), gathered per
    layer at use; the right layout when activation collectives dominate.
    """
    def rule(kp, leaf):
        if layout == "zero3":
            spec = P(*([None] * leaf.ndim))
            return _add_fsdp(spec, leaf, mesh,
                             dax=tuple(mesh.axis_names))
        spec = _param_rule(_path_names(kp), leaf, mesh)
        if fsdp:
            spec = _add_fsdp(spec, leaf, mesh)
        return spec

    return jax.tree_util.tree_map_with_path(rule, params)


def param_shardings(params, mesh: Mesh, fsdp: bool = False):
    return jax.tree.map(lambda s: NamedSharding(mesh, s),
                        param_specs(params, mesh, fsdp))


# ---------------------------------------------------------------------------
# cache rules
# ---------------------------------------------------------------------------

def _cache_rule(path: Tuple[str, ...], leaf, mesh: Mesh,
                shard_seq: bool) -> P:
    name = path[-1] if path else ""
    nd = leaf.ndim
    dax = data_axes(mesh)
    dsz = int(np.prod([mesh.shape[a] for a in dax]))

    def pad(rule):
        return P(*([None] * (nd - len(rule)) + list(rule)))

    if name in ("k", "v", "k_scale", "v_scale"):
        # (B, T, Hkv, hd|1): batch over data if divisible (else seq over
        # data), AND kv-heads over model if divisible (else seq over model).
        # Without the model-axis entry GSPMD re-gathers the WHOLE cache in
        # f32 every decode step to reconcile the attention compute sharding
        # with a replicated-heads cache layout (§Perf H2, 180 GB/token).
        B, T, H = leaf.shape[-4], leaf.shape[-3], leaf.shape[-2]
        msz = _msize(mesh)
        rule = [None, None, None, None]
        if not shard_seq and B % dsz == 0 and B >= dsz:
            rule[0] = dax
        elif T % dsz == 0 and T >= dsz:
            rule[1] = dax
        if H % msz == 0 and H >= msz:
            rule[2] = "model"
        elif rule[1] is None and T % msz == 0 and T >= msz:
            rule[1] = "model"
        return pad(tuple(rule))
    if name == "state" and nd >= 3:
        # ssm state (B,H,P,N) / lru state (B,W): shard H / W over model
        dim = leaf.shape[-3] if nd >= 4 else leaf.shape[-1]
        if dim % _msize(mesh) == 0 and dim >= _msize(mesh):
            return pad(("model", None, None)) if nd >= 4 else pad(("model",))
        return pad((None,) * nd)
    if name == "conv":
        D = leaf.shape[-1]
        if D % _msize(mesh) == 0:
            return pad((None, "model"))
        return pad((None,) * nd)
    return pad((None,) * nd)


def cache_specs(caches, mesh: Mesh, batch: int):
    dsz = int(np.prod([mesh.shape[a] for a in data_axes(mesh)]))
    shard_seq = batch < dsz
    return jax.tree_util.tree_map_with_path(
        lambda kp, leaf: _cache_rule(_path_names(kp), leaf, mesh, shard_seq),
        caches)


# ---------------------------------------------------------------------------
# input / batch rules
# ---------------------------------------------------------------------------

def batch_specs(batch_tree, mesh: Mesh, layout: str = "tp"):
    dax = batch_axes(mesh, layout)
    dsz = int(np.prod([mesh.shape[a] for a in dax]))

    def rule(leaf):
        B = leaf.shape[0]
        if B % dsz == 0 and B >= dsz:
            return P(dax, *([None] * (leaf.ndim - 1)))
        return P(*([None] * leaf.ndim))

    return jax.tree.map(rule, batch_tree)


# ---------------------------------------------------------------------------
# optimizer-state rules (ZeRO-1 option)
# ---------------------------------------------------------------------------

def opt_state_specs(opt_state, params, mesh: Mesh, zero1: bool = False,
                    fsdp: bool = False, layout: str = "tp"):
    pspecs = param_specs(params, mesh, fsdp, layout)

    def like_param(state_branch):
        # m / v / s trees mirror params
        return jax.tree.map(lambda leaf, sp: sp, state_branch, pspecs)

    def maybe_zero1(spec_tree, state_branch):
        if not zero1:
            return spec_tree
        dax = data_axes(mesh)
        dsz = int(np.prod([mesh.shape[a] for a in dax]))

        def z(leaf, sp: P):
            specs = list(sp) + [None] * (leaf.ndim - len(sp))
            used = set()
            for s in specs:
                for a in (s if isinstance(s, tuple) else (s,)):
                    if a:
                        used.add(a)
            if used & set(dax):
                return P(*specs)     # already data-sharded (fsdp overlay)
            for i, (dim, s) in enumerate(zip(leaf.shape, specs)):
                if s is None and dim % dsz == 0 and dim >= dsz:
                    specs[i] = dax
                    break
            return P(*specs)

        return jax.tree.map(z, state_branch, spec_tree)

    out = {}
    if isinstance(opt_state, dict):
        for k, v in opt_state.items():
            if k in ("m", "v", "s"):
                out[k] = maybe_zero1(like_param(v), v)
            else:
                out[k] = jax.tree.map(lambda l: P(), v) if v is not None else v
        return out
    return jax.tree.map(lambda l: P(), opt_state)
