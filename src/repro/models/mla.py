"""Multi-head Latent Attention (MiniCPM3 / DeepSeek-V2 style).

Prefill/train: the compressed KV latent is expanded to per-head K/V and
fed through the shared blocked flash path (MLA is MHA after expansion).

Decode: the *absorbed* formulation — queries are projected into latent
space (q_nope @ W_uk) so attention runs directly against the cached
latent as MQA with head_dim = kv_lora + d_rope.  The cache stores only
the latent + shared rope key: (kv_lora + d_rope) per token per layer,
which is MLA's entire point.
"""
from __future__ import annotations

import math
from typing import Any, Dict

import jax
import jax.numpy as jnp

from repro.models.attention import (blocked_attention,
                                    build_decode_prefetch, cache_insert,
                                    cache_prefill, decode_attention,
                                    gather_pages, masked_causal_attention,
                                    masked_decode_attention,
                                    paged_cache_insert, paged_cache_prefill)
from repro.models.layers import apply_norm, apply_rope, dense_init, init_norm
from repro.sharding.partition import shard

Params = Dict[str, Any]


def init_mla(key, *, d_model: int, num_heads: int, q_lora: int, kv_lora: int,
             d_nope: int, d_rope: int, v_head_dim: int, dtype=jnp.float32) -> Params:
    ks = jax.random.split(key, 9)
    h = num_heads
    return {
        "q_down": dense_init(ks[0], d_model, q_lora, dtype),
        "q_norm": init_norm(ks[1], q_lora, "rmsnorm", dtype),
        "q_up": dense_init(ks[2], q_lora, h * (d_nope + d_rope), dtype),
        "kv_down": dense_init(ks[3], d_model, kv_lora + d_rope, dtype),
        "kv_norm": init_norm(ks[4], kv_lora, "rmsnorm", dtype),
        "k_up": dense_init(ks[5], kv_lora, h * d_nope, dtype),
        "v_up": dense_init(ks[6], kv_lora, h * v_head_dim, dtype),
        "wo": dense_init(ks[7], h * v_head_dim, d_model, dtype),
    }


def _project_latent(params: Params, x, *, kv_lora: int, d_rope: int, positions,
                    rope_theta: float):
    """x (B,S,D) -> normalised latent (B,S,kv_lora), roped k_rope (B,S,d_rope)."""
    ckv = x @ params["kv_down"].astype(x.dtype)
    c_kv, k_rope = ckv[..., :kv_lora], ckv[..., kv_lora:]
    c_kv = apply_norm(params["kv_norm"], c_kv, "rmsnorm")
    k_rope = apply_rope(k_rope[:, :, None, :], positions, theta=rope_theta)[:, :, 0]
    return c_kv, k_rope


def _project_q(params: Params, x, *, num_heads: int, d_nope: int, d_rope: int,
               positions, rope_theta: float):
    b, s, _ = x.shape
    q = x @ params["q_down"].astype(x.dtype)
    q = apply_norm(params["q_norm"], q, "rmsnorm")
    q = (q @ params["q_up"].astype(x.dtype)).reshape(b, s, num_heads, d_nope + d_rope)
    q_nope, q_rope = q[..., :d_nope], q[..., d_nope:]
    q_rope = apply_rope(q_rope, positions, theta=rope_theta)
    return q_nope, q_rope


def mla_prefill(params: Params, x, *, num_heads: int, q_lora: int, kv_lora: int,
                d_nope: int, d_rope: int, v_head_dim: int, rope_theta: float,
                positions, cache: Params = None, inner_remat: bool = False,
                block_tables=None, q_offset=None, insert_from=None,
                layer=None):
    """Training / prefill forward.  Returns (out (B,S,D), new_cache).

    ``q_offset`` (traced ok) switches to the shared-prefix *tail* path:
    the tail's latent is written into the paged pool at absolute
    positions q_offset.., then attention runs over the block-table
    gather of the pool (resident prefix latent + the tail), expanded to
    per-head K/V.  ``insert_from`` keeps writes off resident shared
    pages (see attention.paged_cache_prefill).  ``layer`` addresses the
    layer of a stacked paged pool.
    """
    del q_lora
    b, s, _ = x.shape
    h = num_heads
    q_nope, q_rope = _project_q(params, x, num_heads=h, d_nope=d_nope,
                                d_rope=d_rope, positions=positions,
                                rope_theta=rope_theta)
    c_kv, k_rope = _project_latent(params, x, kv_lora=kv_lora, d_rope=d_rope,
                                   positions=positions, rope_theta=rope_theta)
    q = jnp.concatenate([q_nope, q_rope], axis=-1)
    k_up = params["k_up"].astype(x.dtype)
    v_up = params["v_up"].astype(x.dtype)

    if block_tables is not None and q_offset is not None:
        latent = jnp.concatenate([c_kv, k_rope], axis=-1)[:, :, None, :]
        new_cache = paged_cache_prefill(cache, latent, latent[..., :1],
                                        block_tables, start=q_offset,
                                        insert_from=insert_from, layer=layer)
        lat = gather_pages(new_cache["k"], block_tables,
                           layer)[:, :, 0]                      # (B,T,L)
        t = lat.shape[1]
        c_g, kr_g = lat[..., :kv_lora], lat[..., kv_lora:]
        k_g = jnp.concatenate(
            [(c_g @ k_up).reshape(b, t, h, d_nope),
             jnp.broadcast_to(kr_g[:, :, None, :], (b, t, h, d_rope))],
            axis=-1)
        v_g = (c_g @ v_up).reshape(b, t, h, v_head_dim)
        q_pos = (jnp.asarray(q_offset, jnp.int32).reshape((-1, 1))
                 + jnp.arange(s, dtype=jnp.int32)[None])        # (1|B, S)
        out = masked_causal_attention(
            q, k_g, v_g, jnp.arange(t, dtype=jnp.int32), q_pos,
            scale=1.0 / math.sqrt(d_nope + d_rope))
        out = out.reshape(b, s, h * v_head_dim) @ params["wo"].astype(x.dtype)
        return out, new_cache

    # expand latent to per-head K/V (MHA after expansion)
    k_nope = (c_kv @ k_up).reshape(b, s, h, d_nope)
    v = (c_kv @ v_up).reshape(b, s, h, v_head_dim)
    k = jnp.concatenate([k_nope, jnp.broadcast_to(k_rope[:, :, None, :],
                                                  (b, s, h, d_rope))], axis=-1)
    out = blocked_attention(q, k, v, causal=True,
                            scale=1.0 / math.sqrt(d_nope + d_rope),
                            inner_remat=inner_remat)
    out = out.reshape(b, s, h * v_head_dim) @ params["wo"].astype(x.dtype)
    new_cache = None
    if cache is not None:
        latent = jnp.concatenate([c_kv, k_rope], axis=-1)[:, :, None, :]
        if block_tables is not None:
            new_cache = paged_cache_prefill(cache, latent, latent[..., :1],
                                            block_tables, start=0,
                                            layer=layer)
        else:
            new_cache = cache_prefill(cache, latent, latent[..., :1], start=0)
            new_cache = {"k": new_cache["k"], "v": new_cache["v"],
                         "pos": new_cache["pos"]}
    return out, new_cache


def mla_decode(params: Params, x, cache: Params, pos, *, num_heads: int,
               kv_lora: int, d_nope: int, d_rope: int, v_head_dim: int,
               rope_theta: float, block_tables=None, prefetch=None,
               layer=None):
    """Absorbed single-token decode.  cache['k']: (B, cap, 1, kv_lora+d_rope)
    (ring), or with ``block_tables`` (B, M) a paged latent pool
    ([G,] P, 1, page_size, kv_lora+d_rope) read and written at layer
    ``layer``, with per-row positions ``pos`` (B,).

    Returns (out (B,1,D), new_cache).
    """
    b, one, _ = x.shape
    h = num_heads
    positions = jnp.broadcast_to(
        jnp.asarray(pos, jnp.int32).reshape((-1, 1)), (b, 1))
    q_nope, q_rope = _project_q(params, x, num_heads=h, d_nope=d_nope,
                                d_rope=d_rope, positions=positions,
                                rope_theta=rope_theta)
    c_kv, k_rope = _project_latent(params, x, kv_lora=kv_lora, d_rope=d_rope,
                                   positions=positions, rope_theta=rope_theta)
    latent = jnp.concatenate([c_kv, k_rope], axis=-1)[:, :, None, :]
    if block_tables is not None:
        cache = paged_cache_insert(cache, latent, latent[..., :1],
                                   block_tables, pos, layer)
    else:
        cache = cache_insert(cache, latent, latent[..., :1], pos)

    # absorb W_uk into q:  (B,1,H,d_nope) x (kv_lora, H, d_nope) -> latent space
    k_up = params["k_up"].astype(x.dtype).reshape(kv_lora, h, d_nope)
    q_abs = jnp.einsum("bshd,lhd->bshl", q_nope, k_up)
    q_cat = jnp.concatenate([q_abs, q_rope], axis=-1)      # (B,1,H,kv_lora+d_rope)

    # MQA over the latent cache; v = the latent's c_kv slice
    if block_tables is not None:
        from repro.kernels import ops as kops
        if kops.use_pallas():
            # v rides as the leading kv_lora features of the same
            # latent slab (v_dim), so the kernel DMAs each page once
            if prefetch is None:
                prefetch = build_decode_prefetch(block_tables, pos)
            out_lat = kops.paged_attention(
                q_cat[:, 0], cache["k"], cache["k"], prefetch, layer,
                scale=1.0 / math.sqrt(d_nope + d_rope),
                v_dim=kv_lora)[:, None]
        else:
            lat = gather_pages(cache["k"], block_tables, layer)  # (B,T,1,L)
            out_lat = masked_decode_attention(
                q_cat, lat, lat[..., :kv_lora],
                jnp.arange(lat.shape[1], dtype=jnp.int32), pos,
                scale=1.0 / math.sqrt(d_nope + d_rope))
    else:
        latent_cache = {"k": cache["k"], "v": cache["k"][..., :kv_lora],
                        "pos": cache["pos"]}
        out_lat = decode_attention(q_cat, latent_cache, pos,
                                   scale=1.0 / math.sqrt(d_nope + d_rope))
    # un-absorb W_uv:  (B,1,H,kv_lora) x (kv_lora, H, v_hd)
    v_up = params["v_up"].astype(x.dtype).reshape(kv_lora, h, v_head_dim)
    out = jnp.einsum("bshl,lhv->bshv", out_lat, v_up)
    out = out.reshape(b, 1, h * v_head_dim) @ params["wo"].astype(x.dtype)
    return out, cache
