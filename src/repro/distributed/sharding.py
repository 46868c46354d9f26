"""Sharding rules: parameter/optimizer/activation PartitionSpecs.

2-D sharding on the ("data", "model") mesh axes:
  * "model"  — tensor/expert parallelism: attention head products, FFN hidden,
    expert axis, vocab.
  * "data"   — FSDP: the non-TP dimension of every large matrix is sharded
    over the data axis and all-gathered at use (GSPMD inserts the gathers).
  * "pod"    — pure data parallelism across pods: batch is additionally
    sharded over "pod"; parameters stay replicated across pods (FSDP gathers
    ride the fast intra-pod ICI, gradient all-reduce crosses pods once).

Rules are name-based over the flattened parameter path, right-aligned to the
leaf rank so the same table covers stacked (scan) and unstacked (prefix)
layers.
"""
from __future__ import annotations

from typing import Any, Optional, Tuple

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

__all__ = [
    "param_specs", "batch_specs", "cache_specs", "tree_shardings",
    "DATA_AXIS", "MODEL_AXIS", "POD_AXIS", "dp_axes",
    "PART_AXIS", "relational_mesh", "partition_sharding",
    "available_partitions",
]

DATA_AXIS = "data"
MODEL_AXIS = "model"
POD_AXIS = "pod"

# ---------------------------------------------------------------------------
# Relational partition mesh (sharded fused fragments)
# ---------------------------------------------------------------------------

PART_AXIS = "part"

_MESH_CACHE: dict = {}


def available_partitions() -> int:
    """Device lanes a sharded fragment can fan out over — the device count
    this process sees (the test suite forces 8 CPU devices; see
    ``tests/conftest.py``)."""
    return jax.device_count()


def relational_mesh(num_parts: int) -> Mesh:
    """1-D mesh over the first ``num_parts`` local devices with the single
    named axis ``"part"`` — one hash/radix partition of a fused relational
    fragment per device.  Meshes are cached per partition count so the
    partitioned-column cache and the compiled ``shard_map`` programs agree
    on device placement (a mismatched mesh object would make XLA re-shard
    every input per call)."""
    num_parts = int(num_parts)
    if num_parts < 1:
        raise ValueError(f"num_parts must be >= 1, got {num_parts}")
    devs = jax.devices()
    if num_parts > len(devs):
        raise ValueError(
            f"num_parts={num_parts} exceeds the {len(devs)} "
            f"{devs[0].platform} device(s) this process sees; request at "
            f"most {len(devs)} partitions")
    mesh = _MESH_CACHE.get(num_parts)
    if mesh is None:
        mesh = Mesh(np.array(devs[:num_parts]), (PART_AXIS,))
        _MESH_CACHE[num_parts] = mesh
    return mesh


def partition_sharding(num_parts: int) -> NamedSharding:
    """Sharding for a ``(num_parts, bucket)`` partitioned column: one row
    block per mesh device along the ``"part"`` axis."""
    return NamedSharding(relational_mesh(num_parts), P(PART_AXIS))


def dp_axes(mesh: Mesh):
    """Data-parallel axes: ("pod","data") when the mesh has a pod axis."""
    return tuple(a for a in (POD_AXIS, DATA_AXIS) if a in mesh.axis_names)


# name → trailing-dims spec (right-aligned; missing leading dims → None)
_TRAILING_RULES = {
    # embedding (V, d): shard the EMBED dim, replicate vocab — a gather over a
    # vocab-sharded table triggers XLA SPMD "involuntary full remat" (the
    # [B,S,d] gather output gets replicated); d-sharding keeps the lookup
    # local and the output lands (dp, None, "model") for free.
    "table": (None, "model"),
    "lm_head": ("data", "model"),
    "wq": ("data", "model"),
    "wk": ("data", "model"),
    "wv": ("data", "model"),
    "wi": ("data", "model"),
    "wg": ("data", "model"),
    "wo": ("model", "data"),
    "w_uk": ("data", "model"),
    "w_uv": ("data", "model"),
    "w_dkv": ("data", None),
    "router": ("data", None),
    "wz": ("data", "model"),
    "wx": ("data", "model"),
    "wb": ("data", None),
    "wc": ("data", None),
    "wdt": ("data", None),
    "conv_w": (None, "model"),
    "conv_b": ("model",),
    "bq": ("model",),
    "bk": ("model",),
    "bv": ("model",),
    "proj": ("data", "model"),
}

# expert-stacked leaves (leading E axis → expert parallelism on "model")
_EXPERT_RULES = {
    "wg": ("model", "data", None),
    "wi": ("model", "data", None),
    "wo": ("model", None, "data"),
}


def _path_names(path) -> Tuple[str, ...]:
    names = []
    for part in path:
        if hasattr(part, "key"):
            names.append(str(part.key))
        elif hasattr(part, "idx"):
            names.append(str(part.idx))
    return tuple(names)


def _spec_for(path_names: Tuple[str, ...], shape: Tuple[int, ...],
              num_experts: int) -> P:
    if not path_names:
        return P()
    name = path_names[-1]
    nd = len(shape)
    is_expert = (
        name in _EXPERT_RULES
        and "shared" not in path_names
        and nd >= 3
        and num_experts > 0
        and shape[-3] == num_experts
    )
    rule = _EXPERT_RULES[name] if is_expert else _TRAILING_RULES.get(name)
    if rule is None or nd < len(rule):
        return P()  # small / unknown leaves: replicate
    spec = [None] * (nd - len(rule)) + list(rule)
    return P(*spec)


def param_specs(params_shape: Any, cfg, *, fsdp: bool = True) -> Any:
    """PartitionSpec pytree matching a params (shape-struct) pytree.

    ``fsdp=False`` drops the "data" (FSDP) axis from every rule — pure tensor
    parallelism.  For models whose bf16 params fit HBM/model_parallel this
    removes the per-layer parameter all-gathers entirely (a §Perf lever)."""
    flat, treedef = jax.tree_util.tree_flatten_with_path(params_shape)
    specs = []
    for path, leaf in flat:
        spec = _spec_for(_path_names(path), tuple(leaf.shape), cfg.num_experts)
        if not fsdp:
            spec = P(*[None if e == "data" else e for e in spec])
        specs.append(spec)
    return jax.tree_util.tree_unflatten(treedef, specs)


def batch_specs(batch_shape: Any, mesh: Mesh) -> Any:
    """Shard the batch dim over DP axes (replicate if batch < #dp shards)."""
    dp = dp_axes(mesh)
    dp_size = int(np.prod([mesh.shape[a] for a in dp])) if dp else 1

    def spec(path, leaf):
        names = _path_names(path)
        batch_axis = 1 if names and names[-1] == "positions" else 0
        if leaf.shape[batch_axis] % dp_size != 0 or leaf.shape[batch_axis] < dp_size:
            return P()
        s = [None] * len(leaf.shape)
        s[batch_axis] = dp
        return P(*s)

    flat, treedef = jax.tree_util.tree_flatten_with_path(batch_shape)
    return jax.tree_util.tree_unflatten(treedef, [spec(p, l) for p, l in flat])


def cache_specs(cache_shape: Any, cfg, mesh: Mesh) -> Any:
    """Decode-cache specs.

    Attention KV: batch over DP, kv-head (or MLA latent / conv channels) over
    "model".  SSD state: heads over "model".  The scalar position replicates.
    """
    dp = dp_axes(mesh)
    dp_size = int(np.prod([mesh.shape[a] for a in dp])) if dp else 1

    def spec(path, leaf):
        names = _path_names(path)
        name = names[-1]
        nd = len(leaf.shape)
        if name == "pos" or nd == 0:
            return P()
        # leading period axis present iff under "blocks"
        lead = [None] if names[0] == "blocks" else []
        model_size = mesh.shape[MODEL_AXIS]
        batch = dp if (leaf.shape[len(lead)] % dp_size == 0
                       and leaf.shape[len(lead)] >= dp_size) else None

        def fits(dim_idx):
            d = leaf.shape[len(lead) + dim_idx]
            return d % model_size == 0 and d >= model_size

        if name in ("k", "v"):
            # context-parallel decode: shard the SEQUENCE over "model".  KV
            # heads rarely divide a 16-wide axis, and head_dim sharding made
            # GSPMD re-layout the cache per step; with S sharded, scores stay
            # local and only the softmax stats + (B,H,D) output all-reduce.
            if fits(1):
                return P(*lead, batch, "model", None, None)
            return P(*lead, batch, None, None, None)
        if name == "ckv":
            return P(*lead, batch, "model" if fits(1) else None, None)
        if name == "conv":
            return P(*lead, batch, None, "model" if fits(2) else None)
        if name == "ssd":
            return P(*lead, batch, "model" if fits(1) else None, None, None)
        return P()

    flat, treedef = jax.tree_util.tree_flatten_with_path(cache_shape)
    return jax.tree_util.tree_unflatten(treedef, [spec(p, l) for p, l in flat])


def tree_shardings(mesh: Mesh, spec_tree: Any) -> Any:
    return jax.tree.map(
        lambda s: NamedSharding(mesh, s),
        spec_tree,
        is_leaf=lambda x: isinstance(x, P),
    )
