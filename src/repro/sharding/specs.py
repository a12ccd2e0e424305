"""Parameter / cache PartitionSpecs from leaf-path pattern rules.

Megatron-style tensor parallelism on the 'model' axis:
  * attention: q heads column-parallel, output row-parallel
  * mlp: up/gate column-parallel, down row-parallel
  * moe: expert-parallel (experts sharded, dense within an expert)
  * mamba: d_inner column/row-parallel (the scan is elementwise in
    d_inner, so TP costs one all-reduce at out_proj like an MLP)
  * embeddings / lm head: vocab-parallel

Leaf paths look like "blocks/p0/attn/wq"; block leaves carry a leading
group axis (always unsharded).  Trailing-dims tables keep one rule valid
for both stacked and unstacked layouts.
"""
from __future__ import annotations

import re
from typing import Any, Dict, Optional, Tuple

import jax
from jax.sharding import PartitionSpec as P

from repro.sharding.partition import MeshAxis, resolve

# leaf-name pattern -> logical axes of the TRAILING dims
_PARAM_TABLE: Tuple[Tuple[str, Tuple[Optional[str], ...]], ...] = (
    (r"embed$", ("vocab", "fsdp")),
    (r"head$", ("fsdp", "vocab")),
    (r"attn/wq$", ("fsdp", "heads")),
    (r"attn/wk$", ("fsdp", "kv_heads")),
    (r"attn/wv$", ("fsdp", "kv_heads")),
    (r"attn/wo$", ("heads", "fsdp")),
    (r"attn/bq$", ("heads",)),
    (r"attn/bk$", ("kv_heads",)),
    (r"attn/bv$", ("kv_heads",)),
    (r"attn/q_down$", ("fsdp", None)),
    (r"attn/kv_down$", ("fsdp", None)),
    (r"attn/q_up$", (None, "heads")),
    (r"attn/k_up$", (None, "heads")),
    (r"attn/v_up$", (None, "heads")),
    # expert weights are already (experts x expert_mlp) = data x model
    # sharded — adding fsdp would duplicate the 'data' axis
    (r"moe/(up|gate)$", ("experts", None, "expert_mlp")),
    (r"moe/down$", ("experts", "expert_mlp", None)),
    (r"moe/shared/(up|gate)$", ("fsdp", "mlp")),
    (r"moe/shared/down$", ("mlp", "fsdp")),
    (r"moe/router$", ("fsdp", None)),
    (r"mlp/(up|gate)$", ("fsdp", "mlp")),
    (r"mlp/down$", ("mlp", "fsdp")),
    (r"mixer/in_proj$", ("fsdp", "d_inner")),
    (r"mixer/out_proj$", ("d_inner", "fsdp")),
    (r"mixer/conv_w$", (None, "d_inner")),
    (r"mixer/(conv_b|dt_bias|D)$", ("d_inner",)),
    (r"mixer/x_proj$", ("d_inner", None)),
    (r"mixer/dt_proj$", (None, "d_inner")),
    (r"mixer/A_log$", ("d_inner", None)),
)

_CACHE_TABLE: Tuple[Tuple[str, Tuple[Optional[str], ...]], ...] = (
    (r"/k$", ("batch", "cache_seq", "kv_heads", None)),
    (r"/v$", ("batch", "cache_seq", "kv_heads", None)),
    (r"/(k_scale|v_scale)$", ("batch", "cache_seq", "kv_heads")),
    (r"/pos$", (None,)),
    (r"/conv$", ("batch", None, "d_inner")),
    (r"/h$", ("batch", "d_inner", None)),
)

# the paged pool is shared by every request: no batch or sequence axis,
# pages (P, K, ps, hd) and int8 scales (P, K, 1, ps) split over kv heads
_PAGED_CACHE_TABLE: Tuple[Tuple[str, Tuple[Optional[str], ...]], ...] = (
    (r"/(k|v|k_scale|v_scale)$", (None, "kv_heads", None, None)),
)


def _leaf_path(path) -> str:
    parts = []
    for k in path:
        if isinstance(k, jax.tree_util.DictKey):
            parts.append(str(k.key))
        elif isinstance(k, jax.tree_util.SequenceKey):
            parts.append(str(k.idx))
        elif isinstance(k, jax.tree_util.GetAttrKey):
            parts.append(k.name)
        else:
            parts.append(str(k))
    return "/".join(parts)


def _axis_size(mesh, entry: MeshAxis) -> int:
    if mesh is None or entry is None:
        return 1
    sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
    axes = entry if isinstance(entry, tuple) else (entry,)
    n = 1
    for a in axes:
        n *= sizes.get(a, 1)
    return n


def _spec_for(path: str, shape, table, rules: Dict[str, MeshAxis],
              mesh=None) -> P:
    ndim = len(shape)
    for pat, logical in table:
        if re.search(pat, path):
            trailing = [rules.get(a) if a else None for a in logical]
            if ndim < len(trailing):
                trailing = trailing[-ndim:]      # align to the last dims
            entries = [None] * (ndim - len(trailing)) + trailing
            # jit argument shardings require exact divisibility (unlike
            # with_sharding_constraint): drop sharding on uneven dims,
            # e.g. minicpm3's vocab=73448 or the 1601 image-token axis
            entries = [e if dim % _axis_size(mesh, e) == 0 else None
                       for e, dim in zip(entries, shape)]
            return P(*entries)
    return P(*([None] * ndim))


def param_specs(params: Any, rules: Dict[str, MeshAxis], mesh=None) -> Any:
    """PartitionSpec pytree matching ``params`` (works on ShapeDtypeStructs)."""
    if mesh is not None:
        rules = resolve(rules, mesh)

    def f(path, leaf):
        return _spec_for(_leaf_path(path), leaf.shape, _PARAM_TABLE, rules,
                         mesh)

    return jax.tree_util.tree_map_with_path(f, params)


def cache_specs(caches: Any, rules: Dict[str, MeshAxis], mesh=None, *,
                paged: bool = False) -> Any:
    """PartitionSpec pytree for ring caches, or for a paged pool
    (``paged=True``) whose leaves carry the same names."""
    if mesh is not None:
        rules = resolve(rules, mesh)
    table = _PAGED_CACHE_TABLE if paged else _CACHE_TABLE

    def f(path, leaf):
        return _spec_for(_leaf_path(path), leaf.shape, table, rules, mesh)

    return jax.tree_util.tree_map_with_path(f, caches)


def sharded_bytes(abstract_tree: Any, spec_tree: Any, mesh) -> int:
    """Exact per-device bytes of a pytree under its PartitionSpecs."""
    sizes = dict(zip(mesh.axis_names, mesh.devices.shape))

    def one(leaf, spec):
        denom = 1
        for entry in spec:
            if entry is None:
                continue
            axes = entry if isinstance(entry, tuple) else (entry,)
            for a in axes:
                denom *= sizes.get(a, 1)
        import numpy as _np
        return int(_np.prod(leaf.shape, dtype=_np.int64)
                   * _np.dtype(leaf.dtype).itemsize) // max(denom, 1)

    leaves = jax.tree.leaves(abstract_tree)
    specs = jax.tree.leaves(spec_tree, is_leaf=lambda x: isinstance(x, P))
    return sum(one(l, s) for l, s in zip(leaves, specs))


def batch_specs(batch: Any, rules: Dict[str, MeshAxis], mesh=None) -> Any:
    """Inputs: shard dim 0 by 'batch', replicate the rest."""
    if mesh is not None:
        rules = resolve(rules, mesh)
    ax = rules.get("batch")

    def f(leaf):
        if leaf.ndim == 0:
            return P()
        return P(*([ax] + [None] * (leaf.ndim - 1)))

    return jax.tree.map(f, batch)
