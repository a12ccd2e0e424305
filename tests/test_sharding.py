"""Sharding rules / specs unit tests (no multi-device mesh needed)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from repro.configs import get_config, get_smoke_config, list_architectures
from repro.launch import steps as steps_mod
from repro.models import transformer as tf
from repro.sharding import specs as sp
from repro.sharding.partition import (decode_rules, prefill_rules, resolve,
                                      train_rules)


class FakeMesh:
    axis_names = ("data", "model")

    class _Dev:
        shape = (16, 16)

    devices = _Dev()


def test_param_specs_cover_every_leaf():
    """Every 2D+ weight in every arch gets a spec with at least one
    sharded dim (except tiny norms/scalars)."""
    rules = train_rules(True, fsdp=True)
    for arch in list_architectures():
        cfg = get_smoke_config(arch)
        params = tf.abstract_params(cfg)
        spec_tree = sp.param_specs(params, rules)
        flat = jax.tree_util.tree_flatten_with_path(params)[0]
        specs = jax.tree.leaves(spec_tree, is_leaf=lambda x: isinstance(x, P))
        assert len(flat) == len(specs)
        for (path, leaf), spec in zip(flat, specs):
            assert len(spec) <= leaf.ndim
            if leaf.ndim >= 2 and leaf.size > 1_000_000:
                assert any(a is not None for a in spec), \
                    f"{arch}: big leaf unsharded: {path}"


def test_full_config_divisibility_model_axis():
    """Sharded dims of every FULL config divide the 16-way model axis,
    except documented uneven cases handled by GSPMD padding:
    minicpm3's vocab (73448 = 8*9181) and llama4's 40 heads."""
    rules = resolve(train_rules(True), FakeMesh())
    known_uneven = {73448}                  # minicpm3 vocab, 8-divisible only
    for arch in list_architectures():
        cfg = get_config(arch)
        params = tf.abstract_params(cfg)
        spec_tree = sp.param_specs(params, rules)
        flat = jax.tree_util.tree_flatten_with_path(params)[0]
        specs = jax.tree.leaves(spec_tree, is_leaf=lambda x: isinstance(x, P))
        for (path, leaf), spec in zip(flat, specs):
            for dim, ax in zip(leaf.shape, spec):
                if ax == "model" and dim % 16 != 0:
                    assert dim in known_uneven, \
                        f"{arch} {sp._leaf_path(path)}: dim {dim} not 16-divisible"


def test_kv_shardable_logic():
    assert steps_mod.kv_shardable(get_config("codeqwen1.5-7b"))      # kv=32
    assert steps_mod.kv_shardable(get_config("gemma2-27b"))          # kv=16
    assert not steps_mod.kv_shardable(get_config("jamba-v0.1-52b"))  # kv=8
    assert not steps_mod.kv_shardable(get_config("minicpm3-4b"))     # MLA
    assert steps_mod.kv_shardable(get_config("falcon-mamba-7b"))     # no attn


def test_rules_no_duplicate_axes_possible():
    """cache_seq and kv_heads never map to the same mesh axis."""
    for kvs in (True, False):
        for bs in (True, False):
            r = decode_rules(kvs, bs)
            cs, kh = r["cache_seq"], r["kv_heads"]
            cs_axes = set(cs if isinstance(cs, tuple) else [cs]) - {None}
            kh_axes = set(kh if isinstance(kh, tuple) else [kh]) - {None}
            assert not (cs_axes & kh_axes)


def test_resolve_drops_missing_axes():
    r = resolve(train_rules(True), FakeMesh())
    assert r["batch"] == ("data",)          # 'pod' dropped on single pod


def test_sharded_bytes_math():
    tree = {"a": jax.ShapeDtypeStruct((32, 64), jnp.float32)}
    spec = {"a": P("data", "model")}
    got = sp.sharded_bytes(tree, spec, FakeMesh())
    assert got == 32 * 64 * 4 // 256
    spec2 = {"a": P(None, ("data", "model"))}
    assert sp.sharded_bytes(tree, spec2, FakeMesh()) == 32 * 64 * 4 // 256
    spec3 = {"a": P()}
    assert sp.sharded_bytes(tree, spec3, FakeMesh()) == 32 * 64 * 4


def test_cache_specs_shape_alignment():
    cfg = get_smoke_config("jamba-v0.1-52b")
    caches = tf.abstract_caches(cfg, 4, 64)
    rules = decode_rules(False, True)
    spec_tree = sp.cache_specs(caches, rules)
    flat = jax.tree_util.tree_flatten_with_path(caches)[0]
    specs = jax.tree.leaves(spec_tree, is_leaf=lambda x: isinstance(x, P))
    for (path, leaf), spec in zip(flat, specs):
        assert len(spec) <= leaf.ndim, (path, leaf.shape, spec)


@pytest.mark.parametrize("dtype", ["bfloat16", "int8"])
def test_paged_cache_specs_split_kv_heads(dtype):
    """A paged pool leaf (G, P, K, ps, hd) — or its int8 scales
    (G, P, K, 1, ps) — shards only its kv-head axis: the pool has no
    batch or sequence axis for the ring rules to land on."""
    cfg = get_smoke_config("olmo-1b")
    caches = tf.abstract_caches(cfg, 0, 0, dtype, num_pages=5, page_size=8)
    rules = decode_rules(True, True)
    spec_tree = sp.cache_specs(caches, rules, paged=True)
    specs = jax.tree.leaves(spec_tree, is_leaf=lambda x: isinstance(x, P))
    leaves = jax.tree.leaves(caches)
    assert len(specs) == len(leaves) == (4 if dtype == "int8" else 2)
    for leaf, spec in zip(leaves, specs):
        assert leaf.shape[2] == cfg.num_kv_heads
        assert tuple(spec) == (None, None, rules["kv_heads"], None, None)
