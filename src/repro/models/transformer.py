"""Generic pattern-tiled decoder LM covering all assigned architectures.

The depth is tiled by ``cfg.pattern`` (P layer specs) repeated G times.
Parameters for pattern position i are stacked over the G groups and the
forward pass is a single ``lax.scan`` over groups, so HLO size and
compile time are O(P), not O(num_layers) — essential for the 46-64 layer
configs on the dry-run path.

Entry points:
  * ``forward``        (train; full sequence, no cache)
  * ``prefill``        (full sequence, writes KV/SSM caches)
  * ``prefill_paged``  (full sequence into a shared paged pool)
  * ``decode_step``    (one token; ring caches with a shared scalar
    position, or — via ``block_tables`` — a paged pool with per-row
    positions so one batch mixes requests at different lengths)
"""
from __future__ import annotations

import functools
import math
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import LayerSpec, ModelConfig
from repro.models import attention as attn
from repro.models import mamba as ssm
from repro.models import mla as mla_mod
from repro.models import moe as moe_mod
from repro.models.layers import (apply_mlp, apply_norm, apply_rope,
                                 dense_init, embed_init, init_mlp, init_norm,
                                 sinusoidal_positions, softcap)
from repro.sharding.partition import shard

Params = Dict[str, Any]


# ===========================================================================
# Init
# ===========================================================================

def _init_layer(key, spec: LayerSpec, cfg: ModelConfig) -> Params:
    ks = jax.random.split(key, 8)
    dt = cfg.pdtype
    p: Params = {"norm1": init_norm(ks[0], cfg.d_model, cfg.norm, dt)}
    if cfg.use_post_norm:
        p["norm1_post"] = init_norm(ks[1], cfg.d_model, cfg.norm, dt)

    if spec.mixer == "attn":
        p["attn"] = attn.init_attention(
            ks[2], d_model=cfg.d_model, num_heads=cfg.num_heads,
            num_kv_heads=cfg.num_kv_heads, head_dim=cfg.head_dim,
            qkv_bias=cfg.qkv_bias, qk_norm=cfg.qk_norm,
            v_head_dim=cfg.v_head_dim, dtype=dt)
    elif spec.mixer == "cross_attn":
        p["attn"] = attn.init_attention(
            ks[2], d_model=cfg.d_model, num_heads=cfg.num_heads,
            num_kv_heads=cfg.num_kv_heads, head_dim=cfg.head_dim,
            qk_norm=cfg.qk_norm, dtype=dt)
        p["gate_attn"] = jnp.zeros((), dt)
        p["gate_mlp"] = jnp.zeros((), dt)
    elif spec.mixer == "mla":
        p["attn"] = mla_mod.init_mla(
            ks[2], d_model=cfg.d_model, num_heads=cfg.num_heads,
            q_lora=cfg.q_lora, kv_lora=cfg.kv_lora, d_nope=cfg.d_nope,
            d_rope=cfg.d_rope, v_head_dim=cfg.v_head_dim or cfg.head_dim,
            dtype=dt)
    elif spec.mixer == "mamba":
        p["mixer"] = ssm.init_mamba(
            ks[2], d_model=cfg.d_model, d_inner=cfg.d_inner,
            ssm_state=cfg.ssm_state, d_conv=cfg.d_conv, dt_rank=cfg.dt_rank,
            dtype=dt)
    else:
        raise ValueError(spec.mixer)

    if spec.mlp == "dense":
        p["norm2"] = init_norm(ks[3], cfg.d_model, cfg.norm, dt)
        if cfg.use_post_norm:
            p["norm2_post"] = init_norm(ks[4], cfg.d_model, cfg.norm, dt)
        p["mlp"] = init_mlp(ks[5], cfg.d_model, cfg.d_ff, gated=cfg.gated_mlp,
                            act=cfg.act, dtype=dt)
    elif spec.mlp == "moe":
        p["norm2"] = init_norm(ks[3], cfg.d_model, cfg.norm, dt)
        if cfg.use_post_norm:
            p["norm2_post"] = init_norm(ks[4], cfg.d_model, cfg.norm, dt)
        p["moe"] = moe_mod.init_moe(
            ks[5], d_model=cfg.d_model, num_experts=cfg.num_experts,
            moe_d_ff=cfg.moe_d_ff, shared_d_ff=cfg.shared_expert_d_ff or None,
            gated=cfg.gated_mlp, dtype=dt)
    return p


def init_params(cfg: ModelConfig, key) -> Params:
    keys = jax.random.split(key, 4 + len(cfg.pattern))
    dt = cfg.pdtype
    g = cfg.num_groups
    params: Params = {}
    if cfg.num_codebooks:
        params["embed"] = jax.vmap(
            lambda k: embed_init(k, cfg.vocab_size, cfg.d_model, dt))(
                jax.random.split(keys[0], cfg.num_codebooks))
    else:
        params["embed"] = embed_init(keys[0], cfg.vocab_size, cfg.d_model, dt)

    blocks: Params = {}
    for i, spec in enumerate(cfg.pattern):
        blocks[f"p{i}"] = jax.vmap(
            lambda k, spec=spec: _init_layer(k, spec, cfg))(
                jax.random.split(keys[2 + i], g))
    params["blocks"] = blocks
    params["final_norm"] = init_norm(keys[1], cfg.d_model, cfg.norm, dt)
    if not cfg.tie_embeddings:
        if cfg.num_codebooks:
            params["head"] = jax.vmap(
                lambda k: dense_init(k, cfg.d_model, cfg.vocab_size, dt))(
                    jax.random.split(keys[-1], cfg.num_codebooks))
        else:
            params["head"] = dense_init(keys[-1], cfg.d_model, cfg.vocab_size, dt)
    return params


def abstract_params(cfg: ModelConfig, dtype: Optional[str] = None):
    """Shape-only params for AOT lowering (never allocates)."""
    out = jax.eval_shape(functools.partial(init_params, cfg),
                         jax.random.key(0))
    if dtype is not None:
        out = jax.tree.map(
            lambda x: jax.ShapeDtypeStruct(x.shape, jnp.dtype(dtype)), out)
    return out


# ===========================================================================
# Caches
# ===========================================================================

def init_layer_cache(spec: LayerSpec, cfg: ModelConfig, batch: int,
                     seq_len: int, dtype=None, *,
                     num_pages: Optional[int] = None,
                     page_size: Optional[int] = None) -> Optional[Params]:
    """Ring-buffer layer cache by default; pass ``num_pages``/
    ``page_size`` for the paged-pool variant (pages shared across
    requests, addressed via block tables — ``batch``/``seq_len`` are
    then ignored; positions beyond a row's pages are masked, so window/
    chunked layers use the same pool geometry as full layers)."""
    if dtype is None:
        dtype = jnp.dtype(cfg.kv_cache_dtype)
    paged = page_size is not None
    if paged and spec.mixer not in ("attn", "mla"):
        raise NotImplementedError(
            f"paged KV cache supports attn/mla mixers, got {spec.mixer!r} "
            f"(mamba state and cross-attention context are per-request, "
            f"not token-paged)")
    if spec.mixer == "attn":
        if paged:
            return attn.init_paged_kv_cache(
                num_pages, page_size, cfg.num_kv_heads, cfg.head_dim,
                v_head_dim=cfg.v_head_dim, dtype=dtype)
        cap = attn.attention_span(spec.attn_kind, seq_len, window=cfg.window,
                                  chunk=cfg.chunk)
        return attn.init_kv_cache(batch, cap, cfg.num_kv_heads, cfg.head_dim,
                                  v_head_dim=cfg.v_head_dim, dtype=dtype)
    if spec.mixer == "mla":
        # latent-cache quantization unsupported: keep bf16 for MLA
        mla_dtype = jnp.bfloat16 if dtype == jnp.int8 else dtype
        if paged:
            return attn.init_paged_kv_cache(
                num_pages, page_size, 1, cfg.kv_lora + cfg.d_rope,
                v_head_dim=1, dtype=mla_dtype)
        return attn.init_kv_cache(batch, seq_len, 1, cfg.kv_lora + cfg.d_rope,
                                  v_head_dim=1, dtype=mla_dtype)
    if spec.mixer == "mamba":
        return ssm.init_mamba_cache(batch, d_inner=cfg.d_inner,
                                    ssm_state=cfg.ssm_state, d_conv=cfg.d_conv,
                                    dtype=dtype)
    if spec.mixer == "cross_attn":
        return {
            "k": jnp.zeros((batch, cfg.num_image_tokens, cfg.num_kv_heads,
                            cfg.head_dim), dtype),
            "v": jnp.zeros((batch, cfg.num_image_tokens, cfg.num_kv_heads,
                            cfg.head_dim), dtype),
        }
    raise ValueError(spec.mixer)


def init_caches(cfg: ModelConfig, batch: int, seq_len: int,
                dtype=None, *, num_pages: Optional[int] = None,
                page_size: Optional[int] = None) -> Params:
    g = cfg.num_groups
    caches: Params = {}
    for i, spec in enumerate(cfg.pattern):
        one = init_layer_cache(spec, cfg, batch, seq_len, dtype,
                               num_pages=num_pages, page_size=page_size)
        caches[f"p{i}"] = jax.tree.map(
            lambda x: jnp.broadcast_to(x[None], (g,) + x.shape).copy(), one)
    return caches


def abstract_caches(cfg: ModelConfig, batch: int, seq_len: int,
                    dtype=None, *, num_pages: Optional[int] = None,
                    page_size: Optional[int] = None):
    return jax.eval_shape(
        functools.partial(init_caches, cfg, batch, seq_len, dtype,
                          num_pages=num_pages, page_size=page_size))


# ===========================================================================
# Forward
# ===========================================================================

def _attn_scale(cfg: ModelConfig) -> float:
    if cfg.query_scale is not None:
        return cfg.query_scale
    return 1.0 / math.sqrt(cfg.head_dim)


def _mixer_forward(p: Params, spec: LayerSpec, cfg: ModelConfig, x,
                   *, positions, mode: str, pos=None, cache=None,
                   image_embeds=None, block_tables=None, q_offset=None,
                   insert_from=None, prefetch=None, layer=None):
    """Returns (out, new_cache).  ``block_tables`` (B, M) switches the
    cache path to the paged pool — the whole stacked pool, read and
    written at layer group ``layer``; in decode mode ``pos`` is then a
    per-row (B,) vector rather than a shared scalar.  ``q_offset``
    (prefill mode, traced ok) is the shared-prefix tail path: K/V is
    written at absolute positions q_offset.. and attention runs over
    the gathered pool view (resident prefix + tail) instead of the
    in-sequence blocked path; ``insert_from`` keeps tail writes off
    resident shared pages."""
    b, s, _ = x.shape
    inner_remat = cfg.remat == "full_inner" and mode == "train"
    if spec.mixer == "mamba":
        kw = dict(d_inner=cfg.d_inner, ssm_state=cfg.ssm_state,
                  d_conv=cfg.d_conv, dt_rank=cfg.dt_rank,
                  norm_bc_dt=cfg.mamba_norm)
        if mode == "decode":
            return ssm.mamba_decode(p["mixer"], x, cache, **kw)
        return ssm.mamba_forward(p["mixer"], x, cache=cache,
                                 inner_remat=inner_remat, **kw)

    if spec.mixer == "mla":
        kw = dict(num_heads=cfg.num_heads, kv_lora=cfg.kv_lora,
                  d_nope=cfg.d_nope, d_rope=cfg.d_rope,
                  v_head_dim=cfg.v_head_dim or cfg.head_dim,
                  rope_theta=cfg.rope_theta)
        if mode == "decode":
            return mla_mod.mla_decode(p["attn"], x, cache, pos,
                                      block_tables=block_tables,
                                      prefetch=prefetch, layer=layer, **kw)
        return mla_mod.mla_prefill(p["attn"], x, q_lora=cfg.q_lora,
                                   positions=positions, cache=cache,
                                   inner_remat=inner_remat,
                                   block_tables=block_tables,
                                   q_offset=q_offset,
                                   insert_from=insert_from, layer=layer,
                                   **kw)

    if spec.mixer == "cross_attn":
        ap = p["attn"]
        q = (x @ ap["wq"].astype(x.dtype)).reshape(
            b, s, cfg.num_heads, cfg.head_dim)
        if cfg.qk_norm:
            q = apply_norm(ap["q_norm"], q, "rmsnorm")
        if mode == "decode":
            k = cache["k"].astype(x.dtype)
            v = cache["v"].astype(x.dtype)
            new_cache = cache
        else:
            img = image_embeds.astype(x.dtype)
            bi, n, _ = img.shape
            k = (img @ ap["wk"].astype(x.dtype)).reshape(bi, n, cfg.num_kv_heads, cfg.head_dim)
            v = (img @ ap["wv"].astype(x.dtype)).reshape(bi, n, cfg.num_kv_heads, cfg.head_dim)
            new_cache = None
            if cache is not None:
                new_cache = {"k": k.astype(cache["k"].dtype),
                             "v": v.astype(cache["v"].dtype)}
        out = attn.cross_attention(q, k, v, scale=_attn_scale(cfg))
        out = attn.out_project(ap, out)
        return out, new_cache

    # self-attention
    ap = p["attn"]
    q, k, v = attn.qkv_project(ap, x, num_heads=cfg.num_heads,
                               num_kv_heads=cfg.num_kv_heads,
                               head_dim=cfg.head_dim,
                               v_head_dim=cfg.v_head_dim,
                               qk_norm=cfg.qk_norm)
    if spec.rope and cfg.pos_embed == "rope":
        if mode == "decode":
            rp = jnp.broadcast_to(
                jnp.asarray(pos, jnp.int32).reshape((-1, 1)), (b, 1))
        else:
            rp = positions
        q = apply_rope(q, rp, theta=cfg.rope_theta)
        k = apply_rope(k, rp, theta=cfg.rope_theta)
    q = shard(q, "batch", None, "heads", None)
    k = shard(k, "batch", None, "kv_heads", None)
    v = shard(v, "batch", None, "kv_heads", None)
    window = cfg.window if spec.attn_kind == "swa" else None
    chunk = cfg.chunk if spec.attn_kind == "chunked" else None
    if mode == "decode":
        if block_tables is not None:
            cache = attn.paged_cache_insert(cache, k, v, block_tables, pos,
                                            layer)
            out = attn.paged_decode_attention(
                q, cache, block_tables, pos, window=window, chunk=chunk,
                scale=_attn_scale(cfg), logit_cap=cfg.attn_logit_cap,
                prefetch=prefetch, layer=layer)
        else:
            cache = attn.cache_insert(cache, k, v, pos)
            out = attn.decode_attention(q, cache, pos, window=window,
                                        chunk=chunk, scale=_attn_scale(cfg),
                                        logit_cap=cfg.attn_logit_cap)
        new_cache = cache
    elif block_tables is not None and q_offset is not None:
        # shared-prefix tail prefill: write the tail's K/V into the
        # pool first, then attend over the block-table gather — the
        # resident prefix pages this request mapped plus its own tail
        new_cache = attn.paged_cache_prefill(cache, k, v, block_tables,
                                             start=q_offset,
                                             insert_from=insert_from,
                                             layer=layer)
        out = attn.paged_prefill_attention(
            q, new_cache, block_tables, q_offset, window=window, chunk=chunk,
            scale=_attn_scale(cfg), logit_cap=cfg.attn_logit_cap,
            layer=layer)
    else:
        out = attn.blocked_attention(q, k, v, causal=True, window=window,
                                     chunk=chunk, scale=_attn_scale(cfg),
                                     logit_cap=cfg.attn_logit_cap,
                                     inner_remat=inner_remat)
        new_cache = None
        if cache is not None:
            if block_tables is not None:
                new_cache = attn.paged_cache_prefill(cache, k, v,
                                                     block_tables, start=0,
                                                     layer=layer)
            else:
                new_cache = attn.cache_prefill(cache, k, v, start=0)
    return attn.out_project(ap, out), new_cache


def _block_forward(p: Params, spec: LayerSpec, cfg: ModelConfig, h,
                   *, positions, mode: str, pos=None, cache=None,
                   image_embeds=None, block_tables=None, q_offset=None,
                   insert_from=None, prefetch=None, layer=None):
    """One transformer block.  Returns (h, new_cache, aux_loss)."""
    gated_residual = spec.mixer == "cross_attn"
    mix_in = apply_norm(p["norm1"], h, cfg.norm, cfg.norm_eps)
    out, new_cache = _mixer_forward(p, spec, cfg, mix_in, positions=positions,
                                    mode=mode, pos=pos, cache=cache,
                                    image_embeds=image_embeds,
                                    block_tables=block_tables,
                                    q_offset=q_offset, insert_from=insert_from,
                                    prefetch=prefetch, layer=layer)
    # Megatron-SP: constrain the row-parallel output to the seq-sharded
    # layout BEFORE the residual add so XLA emits a reduce-scatter
    # instead of all-reduce + reshard (2x+ the link bytes); §Perf iter
    out = shard(out, "batch", "seq", "embed")
    if cfg.use_post_norm:
        out = apply_norm(p["norm1_post"], out, cfg.norm, cfg.norm_eps)
    if cfg.residual_scale is not None:
        out = out * cfg.residual_scale
    if gated_residual:
        out = out * jnp.tanh(p["gate_attn"].astype(out.dtype))
    h = h + out
    aux = jnp.zeros((), jnp.float32)

    if spec.mlp != "none":
        y = apply_norm(p["norm2"], h, cfg.norm, cfg.norm_eps)
        if spec.mlp == "moe":
            y, aux = moe_mod.moe_ffn(
                p["moe"], y, num_experts=cfg.num_experts,
                top_k=cfg.num_experts_per_tok, router_act=cfg.router_act,
                capacity_factor=cfg.capacity_factor, act=cfg.act,
                gated=cfg.gated_mlp, dropless=(mode == "decode"),
                group_tokens=cfg.moe_group_tokens if mode == "train" else 0)
        else:
            y = apply_mlp(p["mlp"], y, gated=cfg.gated_mlp, act=cfg.act)
        y = shard(y, "batch", "seq", "embed")    # reduce-scatter (see above)
        if cfg.use_post_norm:
            y = apply_norm(p["norm2_post"], y, cfg.norm, cfg.norm_eps)
        if cfg.residual_scale is not None:
            y = y * cfg.residual_scale
        if gated_residual:
            y = y * jnp.tanh(p["gate_mlp"].astype(y.dtype))
        h = h + y
    h = shard(h, "batch", "seq", "embed")
    return h, new_cache, aux


def embed_tokens(params: Params, cfg: ModelConfig, tokens):
    """tokens: (B,S) int32 or (B,S,K) for multi-codebook audio."""
    emb = params["embed"]
    if cfg.num_codebooks:
        h = sum(emb[k].astype(cfg.cdtype)[tokens[..., k]]
                for k in range(cfg.num_codebooks))
    else:
        h = emb.astype(cfg.cdtype)[tokens]
    if cfg.embed_scale is not None:
        h = h * jnp.asarray(cfg.embed_scale, cfg.cdtype)
    return shard(h, "batch", "seq", "embed")


def unembed(params: Params, cfg: ModelConfig, h):
    """h (B,S,D) -> logits (B,S,V) (or (B,S,K,V) multi-codebook)."""
    if cfg.tie_embeddings:
        w = params["embed"].astype(h.dtype)
        logits = jnp.einsum("bsd,vd->bsv", h, w)
    elif cfg.num_codebooks:
        w = params["head"].astype(h.dtype)                      # (K, D, V)
        logits = jnp.einsum("bsd,kdv->bskv", h, w)
    else:
        logits = h @ params["head"].astype(h.dtype)
    if cfg.final_logit_cap is not None:
        logits = softcap(logits, cfg.final_logit_cap)
    return shard(logits, "batch", None, None, "vocab") if cfg.num_codebooks \
        else shard(logits, "batch", None, "vocab")


def _scan_blocks(params: Params, cfg: ModelConfig, h, *, positions, mode: str,
                 pos=None, caches=None, image_embeds=None, block_tables=None,
                 q_offset=None, insert_from=None, prefetch=None):
    """Scan over the G pattern groups.  Returns (h, new_caches, aux_sum).

    Ring caches (and training, with none) ride the scan's ``xs``/``ys``
    one group slice at a time.  A paged pool (``block_tables`` given)
    rides the carry whole instead, with the group index in ``xs``: each
    layer writes and reads its pages at that index, so the pool is
    updated in place and never sliced out or restacked."""
    specs = cfg.pattern

    def blocks(block_params, hh, group_caches, layer):
        aux_g = jnp.zeros((), jnp.float32)
        new_caches = {}
        for i, spec in enumerate(specs):
            c = None if group_caches is None else group_caches.get(f"p{i}")
            hh, nc, aux = _block_forward(
                block_params[f"p{i}"], spec, cfg, hh, positions=positions,
                mode=mode, pos=pos, cache=c, image_embeds=image_embeds,
                block_tables=block_tables, q_offset=q_offset,
                insert_from=insert_from, prefetch=prefetch, layer=layer)
            aux_g = aux_g + aux
            if nc is not None:
                new_caches[f"p{i}"] = nc
        return hh, aux_g, new_caches

    aux0 = jnp.zeros((), jnp.float32)
    if block_tables is not None and caches is not None:
        def layer_fn(carry, xs):
            hh, aux_acc, pool = carry
            block_params, g = xs
            hh, aux_g, pool = blocks(block_params, hh, pool, g)
            return (hh, aux_acc + aux_g, pool), None

        xs = (params["blocks"], jnp.arange(cfg.num_groups, dtype=jnp.int32))
        (h, aux, caches), _ = jax.lax.scan(layer_fn, (h, aux0, caches), xs)
        return h, caches, aux

    def group_fn(carry, xs):
        hh, aux_acc = carry
        block_params, group_caches = xs

        def body(hh):
            return blocks(block_params, hh, group_caches, None)

        if cfg.remat in ("full", "full_inner") and mode == "train":
            body = jax.checkpoint(body)
        hh, aux_g, new_caches = body(hh)
        return (hh, aux_acc + aux_g), (new_caches or None)

    xs = (params["blocks"], caches)
    (h, aux), out_caches = jax.lax.scan(group_fn, (h, aux0), xs)
    return h, out_caches, aux


def forward(params: Params, cfg: ModelConfig, tokens, *, image_embeds=None,
            mode: str = "train", caches=None, pos=None, block_tables=None,
            q_offset=None, insert_from=None, prefetch=None):
    """Main entry.  mode: train | prefill | decode.

    ``block_tables`` (B, M) routes the cache path through the paged
    pool; decode ``pos`` is then per-row (B,).  Prefill ``q_offset``
    (traced ok) shifts the sequence to absolute positions q_offset..
    — the shared-prefix tail path, where the resident prefix KV is
    read back from the pool instead of recomputed; ``insert_from``
    bounds which of those positions write the pool.  ``prefetch`` is
    the combined decode-step scalar-prefetch operand
    (attention.build_decode_prefetch), shared by every layer.
    Returns (hidden (B,S,D) post-final-norm, new_caches, aux_loss).
    """
    if mode == "decode":
        positions = None
    else:
        positions = jnp.broadcast_to(jnp.arange(tokens.shape[1])[None],
                                     tokens.shape[:2])
        if q_offset is not None:
            off = jnp.asarray(q_offset, jnp.int32)
            positions = positions + (off[:, None] if off.ndim == 1 else off)
    h = embed_tokens(params, cfg, tokens)
    if cfg.pos_embed == "sinusoidal":
        p = (jnp.broadcast_to(jnp.asarray(pos, jnp.int32).reshape((-1, 1)),
                              (tokens.shape[0], 1))
             if mode == "decode" else positions)
        h = h + sinusoidal_positions(p, cfg.d_model).astype(h.dtype)
    h, new_caches, aux = _scan_blocks(params, cfg, h, positions=positions,
                                      mode=mode, pos=pos, caches=caches,
                                      image_embeds=image_embeds,
                                      block_tables=block_tables,
                                      q_offset=q_offset,
                                      insert_from=insert_from,
                                      prefetch=prefetch)
    h = apply_norm(params["final_norm"], h, cfg.norm, cfg.norm_eps)
    return h, new_caches, aux


# ===========================================================================
# Losses / steps
# ===========================================================================

def _ce(logits, labels):
    """fp32 cross-entropy; logits (..., V), labels (...) int32."""
    logits = logits.astype(jnp.float32)
    lse = jax.nn.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, labels[..., None], axis=-1)[..., 0]
    return lse - gold


def lm_loss(params: Params, cfg: ModelConfig, batch: Dict[str, Any]):
    """Next-token CE (+ MoE aux).  batch: tokens, labels, [image_embeds]."""
    tokens, labels = batch["tokens"], batch["labels"]
    h, _, aux = forward(params, cfg, tokens,
                        image_embeds=batch.get("image_embeds"), mode="train")

    if cfg.logits_chunk and not cfg.num_codebooks:
        c = cfg.logits_chunk
        b, s, d = h.shape
        assert s % c == 0, (s, c)
        hc = h.reshape(b, s // c, c, d).transpose(1, 0, 2, 3)
        lc = labels.reshape(b, s // c, c).transpose(1, 0, 2)

        @jax.checkpoint
        def chunk_loss(carry, xs):
            hh, ll = xs
            logits = unembed(params, cfg, hh)
            return carry + _ce(logits, ll).sum(), None

        total, _ = jax.lax.scan(chunk_loss, jnp.zeros((), jnp.float32), (hc, lc))
        loss = total / labels.size
    else:
        logits = unembed(params, cfg, h)
        loss = _ce(logits, labels).mean()
    n_moe = cfg.num_groups * sum(s.mlp == "moe" for s in cfg.pattern)
    return loss + cfg.aux_loss_coef * aux / max(n_moe, 1), {
        "ce": loss, "aux": aux}


def prefill(params: Params, cfg: ModelConfig, tokens, *, image_embeds=None,
            cache_len: Optional[int] = None, cache_dtype=jnp.bfloat16):
    """Process a prompt, returning (next_token_logits, caches)."""
    b, s = tokens.shape[:2]
    caches = init_caches(cfg, b, cache_len or s, cache_dtype)
    h, caches, _ = forward(params, cfg, tokens, image_embeds=image_embeds,
                           mode="prefill", caches=caches)
    logits = unembed(params, cfg, h[:, -1:])
    return logits, caches


def prefill_paged(params: Params, cfg: ModelConfig, tokens, caches,
                  block_tables, last_index=None, *, q_offset=None,
                  insert_from=None):
    """Prefill a prompt (or a shared-prefix tail) into pages of a
    shared pool.

    tokens: (B, S) — S may include right padding (padded slots hold
    garbage K/V but sit at positions > the live query and are
    overwritten by decode inserts before ever becoming visible).
    caches: paged pool from ``init_caches(..., num_pages=, page_size=)``
    (shared across requests; donate it through jit).
    block_tables: (B, M) page ids for these rows.
    last_index: index of the last real token *within ``tokens``*
    (traced ok); defaults to S - 1.
    q_offset (traced ok): absolute position of tokens[:, 0] — the
    shared-prefix tail path, where positions < q_offset are resident
    pages mapped from another sequence and are read, not recomputed.
    insert_from (traced ok): absolute position below which the tail
    does not write the pool (those slots belong to shared pages).
    Returns (next-token logits (B, 1, V), caches).
    """
    h, caches, _ = forward(params, cfg, tokens, mode="prefill", caches=caches,
                           block_tables=block_tables, q_offset=q_offset,
                           insert_from=insert_from)
    if last_index is None:
        h_last = h[:, -1:]
    else:
        h_last = jax.lax.dynamic_slice_in_dim(
            h, jnp.asarray(last_index, jnp.int32), 1, axis=1)
    logits = unembed(params, cfg, h_last)
    return logits, caches


def verify_paged(params: Params, cfg: ModelConfig, tokens, caches,
                 block_tables, q_offset, *, insert_from=None):
    """Speculative-decoding verify step: run S = k+1 tokens per row at
    per-row absolute positions ``q_offset`` (B,) through the paged
    prefill path and return logits for EVERY position, (B, S, V) — the
    verifier's greedy picks at offsets 0..k decide how many draft
    tokens commit.  Rows sit at different decode positions, hence the
    per-row q_offset; ``insert_from`` (scalar or (B,)) routes writes
    below it to scratch.  K/V written above a row's finally-committed
    position is garbage but masked (kv_pos > q_pos) and overwritten by
    later inserts before ever becoming visible — rollback on the
    verifier side is purely positional.
    """
    h, caches, _ = forward(params, cfg, tokens, mode="prefill", caches=caches,
                           block_tables=block_tables, q_offset=q_offset,
                           insert_from=insert_from)
    logits = unembed(params, cfg, h)
    return logits, caches


def decode_step(params: Params, cfg: ModelConfig, token, caches, pos, *,
                block_tables=None):
    """One decode step.  token (B,1) (or (B,1,K)); pos = its position —
    a shared scalar on the ring path, or per-row (B,) when
    ``block_tables`` routes through the paged pool (token-level
    continuous batching: rows may sit at different positions).

    Returns (logits for the next token, updated caches).
    """
    prefetch = None
    if block_tables is not None:
        # one combined block-table + lengths scalar-prefetch operand for
        # the whole stack — every layer's paged kernel shares it instead
        # of staging two scalar operands per layer
        prefetch = attn.build_decode_prefetch(block_tables, pos)
    h, caches, _ = forward(params, cfg, token, mode="decode", caches=caches,
                           pos=pos, block_tables=block_tables,
                           prefetch=prefetch)
    logits = unembed(params, cfg, h)
    return logits, caches
