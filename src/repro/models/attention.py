"""Attention: blocked (flash-style) prefill/train path + ring-buffer decode.

Variants covered (all assigned archs):
  * full causal                         (olmo, codeqwen, musicgen, olmoe, ...)
  * sliding-window (gemma2 local)       window=4096
  * chunked-local  (llama4 iRoPE)       chunk=8192
  * GQA (any kv_heads <= heads), MQA, logit soft-capping, qk-norm, qkv bias
  * cross-attention over a static context (llama3.2-vision image layers)

The prefill/train path never materialises the S x S score matrix: it
scans KV blocks with an online-softmax accumulator (full-causal) or
scans Q blocks against a banded KV slice (windowed/chunked), so the HLO
the dry-run analyses has flash-equivalent memory *and* FLOPs.

Decode uses one of two cache layouts behind the same masking core
(``masked_decode_attention``):
  * ring buffer of capacity = attention span, one slab per batch slot;
    each slot remembers the absolute position it holds (``pos_buf``)
  * paged pool — head-major (num_pages, K, page_size, hd) slabs shared
    by all requests, addressed through per-row block tables, with
    per-row query positions so a decode batch can mix requests at
    different lengths (token-level continuous batching; see
    repro.serving.kv_cache).
"""
from __future__ import annotations

import math
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp

from repro.models.layers import apply_rope, dense_init, init_norm, apply_norm, softcap
from repro.sharding.partition import shard

Params = Dict[str, Any]

NEG_INF = -1e30


# ---------------------------------------------------------------------------
# Parameters
# ---------------------------------------------------------------------------

def init_attention(key, *, d_model: int, num_heads: int, num_kv_heads: int,
                   head_dim: int, qkv_bias: bool = False, qk_norm: bool = False,
                   v_head_dim: Optional[int] = None, dtype=jnp.float32) -> Params:
    v_hd = v_head_dim or head_dim
    ks = jax.random.split(key, 6)
    p: Params = {
        "wq": dense_init(ks[0], d_model, num_heads * head_dim, dtype),
        "wk": dense_init(ks[1], d_model, num_kv_heads * head_dim, dtype),
        "wv": dense_init(ks[2], d_model, num_kv_heads * v_hd, dtype),
        "wo": dense_init(ks[3], num_heads * v_hd, d_model, dtype),
    }
    if qkv_bias:
        p["bq"] = jnp.zeros((num_heads * head_dim,), dtype)
        p["bk"] = jnp.zeros((num_kv_heads * head_dim,), dtype)
        p["bv"] = jnp.zeros((num_kv_heads * v_hd,), dtype)
    if qk_norm:
        p["q_norm"] = init_norm(ks[4], head_dim, "rmsnorm", dtype)
        p["k_norm"] = init_norm(ks[5], head_dim, "rmsnorm", dtype)
    return p


def qkv_project(params: Params, x, *, num_heads: int, num_kv_heads: int,
                head_dim: int, v_head_dim: Optional[int] = None,
                qk_norm: bool = False):
    """x: (B, S, D) -> q (B,S,H,hd), k (B,S,K,hd), v (B,S,K,vhd)."""
    b, s, _ = x.shape
    v_hd = v_head_dim or head_dim
    q = x @ params["wq"].astype(x.dtype)
    k = x @ params["wk"].astype(x.dtype)
    v = x @ params["wv"].astype(x.dtype)
    if "bq" in params:
        q = q + params["bq"].astype(x.dtype)
        k = k + params["bk"].astype(x.dtype)
        v = v + params["bv"].astype(x.dtype)
    q = q.reshape(b, s, num_heads, head_dim)
    k = k.reshape(b, s, num_kv_heads, head_dim)
    v = v.reshape(b, s, num_kv_heads, v_hd)
    if qk_norm:
        q = apply_norm(params["q_norm"], q, "rmsnorm")
        k = apply_norm(params["k_norm"], k, "rmsnorm")
    return q, k, v


def out_project(params: Params, o):
    b, s, h, v_hd = o.shape
    return o.reshape(b, s, h * v_hd) @ params["wo"].astype(o.dtype)


# ---------------------------------------------------------------------------
# Prefill / train attention (flash-style, no S x S materialisation)
# ---------------------------------------------------------------------------

def _gqa_scores(q, k):
    """q: (B,S,K,G,hd)  k: (B,T,K,hd) -> scores (B,K,G,S,T)."""
    return jnp.einsum("bskgd,btkd->bkgst", q, k, preferred_element_type=jnp.float32)


def _gqa_out(p, v):
    """p: (B,K,G,S,T)  v: (B,T,K,vd) -> (B,S,K,G,vd)."""
    return jnp.einsum("bkgst,btkv->bskgv", p, v)


def blocked_attention(q, k, v, *, causal: bool = True, window: Optional[int] = None,
                      chunk: Optional[int] = None, scale: Optional[float] = None,
                      logit_cap: Optional[float] = None, kv_block: int = 512,
                      q_block: int = 512, q_offset: int = 0,
                      inner_remat: bool = False) -> jnp.ndarray:
    """Causal (optionally windowed/chunked) attention.

    q: (B, S, H, hd); k: (B, T, K, hd); v: (B, T, K, vd); H % K == 0.
    ``q_offset`` is the absolute position of q[.,0] (k/v start at 0).
    ``inner_remat`` checkpoints each KV-block step so the backward pass
    recomputes the block's probabilities instead of storing them stacked
    over all blocks (the dominant train-memory term at 4k+; §Perf).
    Returns (B, S, H, vd).
    """
    b, s, h, hd = q.shape
    t = k.shape[1]
    kk = k.shape[2]
    g = h // kk
    scale = scale if scale is not None else 1.0 / math.sqrt(hd)
    qr = (q * scale).reshape(b, s, kk, g, hd)

    if window is not None or chunk is not None:
        return _banded_attention(qr, k, v, window=window, chunk=chunk,
                                 logit_cap=logit_cap, q_block=q_block,
                                 q_offset=q_offset, inner_remat=inner_remat)

    # Full causal: python-unrolled outer loop over Q blocks; inner
    # lax.scan over exactly the (i+1) causally-live KV blocks.  This is
    # the flash-attention tiling: the online-softmax accumulator is
    # per-Q-block (stays on-chip on TPU; tiny scan carry in the HLO), so
    # the HLO's FLOPs *and* HBM traffic match the Pallas kernel —
    # including the ~2x FLOP saving from skipping above-diagonal blocks.
    vd = v.shape[-1]
    bq = min(q_block, s)
    nq = -(-s // bq)
    pad_s = nq * bq
    if pad_s != s:
        qr = jnp.pad(qr, ((0, 0), (0, pad_s - s), (0, 0), (0, 0), (0, 0)))
    nblk = -(-t // kv_block)
    pad_t = nblk * kv_block
    if pad_t != t:
        k = jnp.pad(k, ((0, 0), (0, pad_t - t), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad_t - t), (0, 0), (0, 0)))
    kb = k.reshape(b, nblk, kv_block, kk, hd).transpose(1, 0, 2, 3, 4)
    vb = v.reshape(b, nblk, kv_block, kk, vd).transpose(1, 0, 2, 3, 4)

    outs = []
    for i in range(nq):
        q_blk = qr[:, i * bq:(i + 1) * bq]                 # (B,bq,K,G,hd)
        q_pos = q_offset + i * bq + jnp.arange(bq)
        # causally-live kv blocks for this q block (static count)
        hi = nblk if not causal else min(
            nblk, -(-(q_offset + (i + 1) * bq) // kv_block))

        def step(carry, inp, q_blk=q_blk, q_pos=q_pos):
            m, l, acc = carry
            blk_idx, k_blk, v_blk = inp
            kv_pos = blk_idx * kv_block + jnp.arange(kv_block)
            sc = _gqa_scores(q_blk, k_blk)                 # (B,K,G,bq,Bk)
            if logit_cap is not None:
                sc = softcap(sc, logit_cap)
            mask = jnp.broadcast_to(kv_pos[None, :] < t, (bq, kv_block))
            if causal:
                mask = mask & (kv_pos[None, :] <= q_pos[:, None])
            sc = jnp.where(mask[None, None, None], sc, NEG_INF)
            m_new = jnp.maximum(m, sc.max(axis=-1))
            p = jnp.exp(sc - m_new[..., None])
            corr = jnp.exp(m - m_new)
            l_new = l * corr + p.sum(axis=-1)
            acc_new = acc * corr[..., None] + jnp.einsum(
                "bkgst,btkv->bkgsv", p, v_blk)
            return (m_new, l_new, acc_new), None

        m0 = jnp.full((b, kk, g, bq), NEG_INF, jnp.float32)
        l0 = jnp.zeros((b, kk, g, bq), jnp.float32)
        acc0 = jnp.zeros((b, kk, g, bq, vd), jnp.float32)
        if inner_remat:
            step = jax.checkpoint(step)
        (m, l, acc), _ = jax.lax.scan(
            step, (m0, l0, acc0),
            (jnp.arange(hi), kb[:hi], vb[:hi]))
        o = acc / jnp.maximum(l, 1e-30)[..., None]         # (B,K,G,bq,vd)
        outs.append(o.transpose(0, 3, 1, 2, 4).reshape(b, bq, h, vd))
    out = jnp.concatenate(outs, axis=1)[:, :s]
    return out.astype(q.dtype)


def _banded_attention(qr, k, v, *, window: Optional[int], chunk: Optional[int],
                      logit_cap: Optional[float], q_block: int, q_offset: int,
                      inner_remat: bool = False):
    """Windowed/chunked causal attention via Q-block scan over a KV band.

    qr: (B,S,K,G,hd) pre-scaled.  Each q block of size Bq reads a KV band
    of static width (window + Bq, window-aligned) so the HLO FLOPs match
    the true sub-quadratic cost.
    """
    b, s, kk, g, hd = qr.shape
    t = k.shape[1]
    vd = v.shape[-1]
    bq = min(q_block, s)
    nq = -(-s // bq)
    pad_s = nq * bq
    if pad_s != s:
        qr = jnp.pad(qr, ((0, 0), (0, pad_s - s), (0, 0), (0, 0), (0, 0)))
    span = window if window is not None else chunk
    # band width: enough to cover [lo(q_first), q_last] for any alignment
    band = int(min(t, span + bq))
    # pad kv on the right so the dynamic slice never clamps
    k = jnp.pad(k, ((0, 0), (0, band), (0, 0), (0, 0)))
    v = jnp.pad(v, ((0, 0), (0, band), (0, 0), (0, 0)))

    qb = qr.reshape(b, nq, bq, kk, g, hd).transpose(1, 0, 2, 3, 4, 5)

    def step(_, inp):
        i, q_blk = inp
        q_first = q_offset + i * bq
        if window is not None:
            lo = jnp.maximum(q_first - span + 1, 0)
        else:  # chunked: band starts at the chunk boundary of the first query
            lo = (q_first // span) * span
        k_band = jax.lax.dynamic_slice_in_dim(k, lo, band, axis=1)
        v_band = jax.lax.dynamic_slice_in_dim(v, lo, band, axis=1)
        kv_pos = lo + jnp.arange(band)  # absolute pos of band slots
        q_pos = q_first + jnp.arange(bq)
        sc = jnp.einsum("bskgd,btkd->bkgst", q_blk, k_band,
                        preferred_element_type=jnp.float32)
        if logit_cap is not None:
            sc = softcap(sc, logit_cap)
        mask = kv_pos[None, :] <= q_pos[:, None]
        mask &= kv_pos[None, :] >= 0
        mask &= kv_pos[None, :] < t
        if window is not None:
            mask &= kv_pos[None, :] > q_pos[:, None] - span
        else:
            mask &= kv_pos[None, :] >= (q_pos[:, None] // span) * span
        sc = jnp.where(mask[None, None, None], sc, NEG_INF)
        p = jax.nn.softmax(sc, axis=-1)
        out = jnp.einsum("bkgst,btkv->bskgv", p, v_band)
        return None, out.astype(qr.dtype)

    if inner_remat:
        step = jax.checkpoint(step)
    _, outs = jax.lax.scan(step, None, (jnp.arange(nq), qb))
    out = outs.transpose(1, 0, 2, 3, 4, 5).reshape(b, pad_s, kk * g, vd)
    return out[:, :s]


def cross_attention(q, k, v, *, scale: Optional[float] = None):
    """Non-causal attention over a static context (image tokens)."""
    b, s, h, hd = q.shape
    kk = k.shape[2]
    g = h // kk
    scale = scale if scale is not None else 1.0 / math.sqrt(hd)
    qr = (q * scale).reshape(b, s, kk, g, hd)
    sc = _gqa_scores(qr, k)
    p = jax.nn.softmax(sc, axis=-1)
    out = _gqa_out(p, v).reshape(b, s, h, v.shape[-1])
    return out.astype(q.dtype)


# ---------------------------------------------------------------------------
# Ring-buffer KV cache + decode attention
# ---------------------------------------------------------------------------

def init_kv_cache(batch: int, capacity: int, num_kv_heads: int, head_dim: int,
                  *, v_head_dim: Optional[int] = None, dtype=jnp.bfloat16) -> Params:
    """dtype=int8 stores quantized k/v with per-(token, head) max-abs
    scales — halves decode HBM traffic vs bf16 (§Perf, gemma2 decode)."""
    v_hd = v_head_dim or head_dim
    if isinstance(dtype, str):
        dtype = jnp.dtype(dtype)
    cache = {
        "k": jnp.zeros((batch, capacity, num_kv_heads, head_dim), dtype),
        "v": jnp.zeros((batch, capacity, num_kv_heads, v_hd), dtype),
        "pos": jnp.full((capacity,), -1, jnp.int32),
    }
    if dtype == jnp.int8:
        cache["k_scale"] = jnp.zeros((batch, capacity, num_kv_heads),
                                     jnp.bfloat16)
        cache["v_scale"] = jnp.zeros((batch, capacity, num_kv_heads),
                                     jnp.bfloat16)
    return cache


def _quantize(x, dtype):
    """x (..., hd) -> (int8 values, bf16 scales over the last dim)."""
    amax = jnp.max(jnp.abs(x.astype(jnp.float32)), axis=-1)
    scale = jnp.maximum(amax / 127.0, 1e-8)
    q = jnp.clip(jnp.round(x.astype(jnp.float32) / scale[..., None]),
                 -127, 127).astype(dtype)
    return q, scale.astype(jnp.bfloat16)


def _dequant_kv(cache: Params):
    """Returns (k, v) in compute precision (dequantized if int8)."""
    k, v = cache["k"], cache["v"]
    if k.dtype == jnp.int8:
        k = k.astype(jnp.bfloat16) * cache["k_scale"][..., None]
        v = v.astype(jnp.bfloat16) * cache["v_scale"][..., None]
    return k, v


def cache_insert(cache: Params, k_new, v_new, pos) -> Params:
    """Insert one token's k/v (B,1,K,hd) at ring slot pos % capacity."""
    cap = cache["k"].shape[1]
    slot = jnp.asarray(pos, jnp.int32) % cap
    out = dict(cache)
    if cache["k"].dtype == jnp.int8:
        kq, ks = _quantize(k_new, jnp.int8)
        vq, vs = _quantize(v_new, jnp.int8)
        out["k_scale"] = jax.lax.dynamic_update_slice_in_dim(
            cache["k_scale"], ks, slot, axis=1)
        out["v_scale"] = jax.lax.dynamic_update_slice_in_dim(
            cache["v_scale"], vs, slot, axis=1)
        k_new, v_new = kq, vq
    out["k"] = jax.lax.dynamic_update_slice_in_dim(
        cache["k"], k_new.astype(cache["k"].dtype), slot, axis=1)
    out["v"] = jax.lax.dynamic_update_slice_in_dim(
        cache["v"], v_new.astype(cache["v"].dtype), slot, axis=1)
    out["pos"] = jax.lax.dynamic_update_slice_in_dim(
        cache["pos"], jnp.asarray(pos, jnp.int32)[None], slot, axis=0)
    return out


def cache_prefill(cache: Params, k, v, start: int = 0) -> Params:
    """Write S tokens (B,S,K,hd) starting at absolute position ``start``.

    Requires start % capacity + ... handled via modular scatter; for the
    common S <= capacity case this is a single scatter.
    """
    cap = cache["k"].shape[1]
    s = k.shape[1]
    if s > cap:  # only the trailing `cap` tokens survive a ring overwrite
        k, v = k[:, -cap:], v[:, -cap:]
        start, s = start + (s - cap), cap
    positions = (start + jnp.arange(s)).astype(jnp.int32)
    out = dict(cache)
    scales = None
    if cache["k"].dtype == jnp.int8:
        k, ks = _quantize(k, jnp.int8)
        v, vs = _quantize(v, jnp.int8)
        scales = (ks, vs)
    if isinstance(start, int) and start == 0:
        out["k"] = jax.lax.dynamic_update_slice_in_dim(
            cache["k"], k.astype(cache["k"].dtype), 0, axis=1)
        out["v"] = jax.lax.dynamic_update_slice_in_dim(
            cache["v"], v.astype(cache["v"].dtype), 0, axis=1)
        out["pos"] = jax.lax.dynamic_update_slice_in_dim(
            cache["pos"], positions, 0, axis=0)
        if scales:
            out["k_scale"] = jax.lax.dynamic_update_slice_in_dim(
                cache["k_scale"], scales[0], 0, axis=1)
            out["v_scale"] = jax.lax.dynamic_update_slice_in_dim(
                cache["v_scale"], scales[1], 0, axis=1)
    else:
        slots = positions % cap
        out["k"] = cache["k"].at[:, slots].set(k.astype(cache["k"].dtype))
        out["v"] = cache["v"].at[:, slots].set(v.astype(cache["v"].dtype))
        out["pos"] = cache["pos"].at[slots].set(positions)
        if scales:
            out["k_scale"] = cache["k_scale"].at[:, slots].set(scales[0])
            out["v_scale"] = cache["v_scale"].at[:, slots].set(scales[1])
    return out


def masked_decode_attention(q, k, v, kv_pos, pos, *,
                            window: Optional[int] = None,
                            chunk: Optional[int] = None,
                            scale: Optional[float] = None,
                            logit_cap: Optional[float] = None) -> jnp.ndarray:
    """Single-token attention over an explicit KV view — the one mask
    every decode variant (ring or paged; full/window/chunked/GQA/MLA)
    routes through.

    q: (B, 1, H, hd); k: (B, T, K, hd); v: (B, T, K, vd).
    kv_pos: absolute position held by each KV slot, (T,) shared or
    (B, T) per row; -1 marks an empty slot.
    pos: query position(s) — scalar (whole batch at one position, the
    ring path) or (B,) (token-level continuous batching, the paged
    path).  Returns (B, 1, H, vd).
    """
    b, one, h, hd = q.shape
    kk = k.shape[2]
    g = h // kk
    scale = scale if scale is not None else 1.0 / math.sqrt(hd)
    qr = (q * scale).reshape(b, kk, g, hd)
    sc = jnp.einsum("bkgd,btkd->bkgt", qr, k,
                    preferred_element_type=jnp.float32)
    if logit_cap is not None:
        sc = softcap(sc, logit_cap)
    pos = jnp.asarray(pos, jnp.int32)
    pos_b = jnp.broadcast_to(pos.reshape((-1,)), (b,))          # (B,)
    kv_pos = jnp.asarray(kv_pos, jnp.int32)
    if kv_pos.ndim == 1:
        kv_pos = kv_pos[None]                                   # (1|B, T)
    lower = jnp.zeros((b,), jnp.int32)
    if window is not None:
        lower = pos_b - window + 1
    if chunk is not None:
        lower = (pos_b // chunk) * chunk
    mask = ((kv_pos >= 0) & (kv_pos <= pos_b[:, None])
            & (kv_pos >= lower[:, None]))                       # (B, T)
    sc = jnp.where(mask[:, None, None], sc, NEG_INF)
    p = jax.nn.softmax(sc, axis=-1)
    out = jnp.einsum("bkgt,btkv->bkgv", p, v)
    return out.reshape(b, 1, h, v.shape[-1]).astype(q.dtype)


def masked_causal_attention(q, k, v, kv_pos, q_pos, *,
                            window: Optional[int] = None,
                            chunk: Optional[int] = None,
                            scale: Optional[float] = None,
                            logit_cap: Optional[float] = None) -> jnp.ndarray:
    """Multi-token causal attention over an explicit KV view at
    absolute positions — the S > 1 generalisation of
    ``masked_decode_attention``, used by the shared-prefix tail
    prefill: the queries attend KV this call did not compute (the
    resident prefix pages) plus their own just-inserted tail.

    q: (B, S, H, hd); k: (B, T, K, hd); v: (B, T, K, vd).
    kv_pos: absolute position held by each KV slot, (T,) shared or
    (B, T) per row; -1 marks an empty slot.
    q_pos: absolute query positions, (S,) shared or (B, S) per row
    (traced offsets are fine) — the per-row form is the speculative
    verify step, where rows sit at different decode positions.
    Materialises the S x T score block — tails are short by
    construction; full prompts stay on the blocked flash path.
    Returns (B, S, H, vd).
    """
    b, s, h, hd = q.shape
    kk = k.shape[2]
    g = h // kk
    scale = scale if scale is not None else 1.0 / math.sqrt(hd)
    qr = (q * scale).reshape(b, s, kk, g, hd)
    sc = jnp.einsum("bskgd,btkd->bkgst", qr, k,
                    preferred_element_type=jnp.float32)
    if logit_cap is not None:
        sc = softcap(sc, logit_cap)
    q_pos = jnp.asarray(q_pos, jnp.int32)
    if q_pos.ndim == 1:
        q_pos = q_pos[None]                                     # (1|B, S)
    kv_pos = jnp.asarray(kv_pos, jnp.int32)
    if kv_pos.ndim == 1:
        kv_pos = kv_pos[None]                                   # (1|B, T)
    lower = jnp.zeros_like(q_pos)
    if window is not None:
        lower = q_pos - window + 1
    if chunk is not None:
        lower = (q_pos // chunk) * chunk
    mask = ((kv_pos[:, None, :] >= 0)
            & (kv_pos[:, None, :] <= q_pos[:, :, None])
            & (kv_pos[:, None, :] >= lower[:, :, None]))        # (1|B, S, T)
    sc = jnp.where(mask[:, None, None], sc, NEG_INF)
    p = jax.nn.softmax(sc, axis=-1)
    out = jnp.einsum("bkgst,btkv->bskgv", p, v)
    return out.reshape(b, s, h, v.shape[-1]).astype(q.dtype)


def paged_prefill_attention(q, cache: Params, block_tables, q_offset, *,
                            window: Optional[int] = None,
                            chunk: Optional[int] = None,
                            scale: Optional[float] = None,
                            logit_cap: Optional[float] = None) -> jnp.ndarray:
    """Tail-prefill attention over the paged pool: queries at absolute
    positions q_offset + arange(S) attend the block-table gather of the
    pool — the resident shared-prefix pages plus the tail K/V this
    prefill just wrote.  q: (B, S, H, hd); q_offset is a shared scalar
    or per-row (B,) (speculative verify), traced ok."""
    k, v = paged_gather_kv(cache, block_tables)
    k = shard(k, "batch", None, "kv_heads", None)
    v = shard(v, "batch", None, "kv_heads", None)
    kv_pos = jnp.arange(k.shape[1], dtype=jnp.int32)
    q_pos = jnp.asarray(q_offset, jnp.int32).reshape((-1, 1)) + jnp.arange(
        q.shape[1], dtype=jnp.int32)[None]                      # (1|B, S)
    return masked_causal_attention(q, k, v, kv_pos, q_pos, window=window,
                                   chunk=chunk, scale=scale,
                                   logit_cap=logit_cap)


def decode_attention(q, cache: Params, pos, *, window: Optional[int] = None,
                     chunk: Optional[int] = None, scale: Optional[float] = None,
                     logit_cap: Optional[float] = None) -> jnp.ndarray:
    """Single-token attention over the ring cache.

    q: (B, 1, H, hd); pos: absolute position of the query token (the
    cache must already contain the query token's own k/v).
    Returns (B, 1, H, vd).
    """
    k, v = _dequant_kv(cache)
    k = shard(k, "batch", "cache_seq", "kv_heads", None)
    v = shard(v, "batch", "cache_seq", "kv_heads", None)
    return masked_decode_attention(q, k, v, cache["pos"], pos, window=window,
                                   chunk=chunk, scale=scale,
                                   logit_cap=logit_cap)


# ---------------------------------------------------------------------------
# Paged KV cache + decode attention
# ---------------------------------------------------------------------------
#
# Pages are pool-wide, NOT per batch row: cache["k"] is head-major,
# (num_pages, K, page_size, hd), so the paged kernel's block for one
# (page, kv head) is a whole (page_size, hd) tile.  A request owns an
# ordered list of pages recorded in its block-table row.  Logical
# token j of a request lives in page block_table[j // page_size] at
# slot j % page_size, so a gathered view is position-ordered and the
# mask is simply kv_pos = arange(T) against the per-row query position
# — the same masked_decode_attention core the ring path uses.  Page 0
# is reserved as a scratch page: padding block-table entries and
# inactive batch rows point at it, and everything they write there is
# masked out.  int8 pools keep per-(slot, head) scales as
# (num_pages, K, 1, page_size): one lane row per (page, kv head).

SCRATCH_PAGE = 0


def init_paged_kv_cache(num_pages: int, page_size: int, num_kv_heads: int,
                        head_dim: int, *, v_head_dim: Optional[int] = None,
                        dtype=jnp.bfloat16) -> Params:
    """Pool-wide paged KV store.  dtype=int8 stores quantized k/v with
    per-(slot, head) max-abs scales, mirroring the ring cache."""
    v_hd = v_head_dim or head_dim
    if isinstance(dtype, str):
        dtype = jnp.dtype(dtype)
    cache = {
        "k": jnp.zeros((num_pages, num_kv_heads, page_size, head_dim), dtype),
        "v": jnp.zeros((num_pages, num_kv_heads, page_size, v_hd), dtype),
    }
    if dtype == jnp.int8:
        for name in ("k_scale", "v_scale"):
            cache[name] = jnp.zeros((num_pages, num_kv_heads, 1, page_size),
                                    jnp.bfloat16)
    return cache


def _paged_write(cache: Params, k, v, page, slot) -> Params:
    """Scatter token K/V (..., K, hd) into the pool at (page, slot)
    index arrays of the same leading shape, quantizing for int8 pools.
    The page and slot indices sit on either side of the head axis, so
    the indexed view is (..., K, hd) — the tokens' own layout."""
    out = dict(cache)
    if cache["k"].dtype == jnp.int8:
        kq, ks = _quantize(k, jnp.int8)
        vq, vs = _quantize(v, jnp.int8)
        out["k_scale"] = cache["k_scale"].at[page, :, 0, slot].set(ks)
        out["v_scale"] = cache["v_scale"].at[page, :, 0, slot].set(vs)
        k, v = kq, vq
    out["k"] = cache["k"].at[page, :, slot].set(k.astype(cache["k"].dtype))
    out["v"] = cache["v"].at[page, :, slot].set(v.astype(cache["v"].dtype))
    return out


def paged_cache_insert(cache: Params, k_new, v_new, block_tables,
                       pos) -> Params:
    """Insert one token per row: k/v (B, 1, K, hd) at per-row position
    ``pos`` (B,) via ``block_tables`` (B, M).  Inactive rows should
    point at SCRATCH_PAGE; colliding scratch writes are harmless."""
    ps = cache["k"].shape[2]
    pos = jnp.asarray(pos, jnp.int32).reshape((-1,))
    page = jnp.take_along_axis(block_tables, (pos // ps)[:, None],
                               axis=1)[:, 0]                    # (B,)
    return _paged_write(cache, k_new[:, 0], v_new[:, 0], page, pos % ps)


def paged_cache_prefill(cache: Params, k, v, block_tables,
                        start: int = 0, *, insert_from=None) -> Params:
    """Write S tokens (B, S, K, hd) at positions start..start+S-1 of
    each row's block-table mapping (prefill into pages).

    ``start`` may be a traced scalar (shared-prefix tail prefill) or
    per-row (B,) (speculative verify: rows at different positions).
    ``insert_from`` (absolute position, scalar or (B,), traced ok)
    redirects writes *below* it to the scratch page: a tail recomputes
    those positions for the forward pass but must not touch resident
    shared pages that already hold their K/V.  Positions whose page
    index falls past the block-table width also land on scratch
    (right-padding of a page-rounded tail near max_len)."""
    ps = cache["k"].shape[2]
    s = k.shape[1]
    m = block_tables.shape[1]
    positions = (jnp.asarray(start, jnp.int32).reshape((-1, 1))
                 + jnp.arange(s, dtype=jnp.int32)[None])        # (1|B, S)
    idx = positions // ps                                       # (1|B, S)
    page = jnp.take_along_axis(block_tables, jnp.minimum(idx, m - 1),
                               axis=1)                          # (B, S)
    page = jnp.where(idx >= m, SCRATCH_PAGE, page)
    if insert_from is not None:
        ins = jnp.asarray(insert_from, jnp.int32).reshape((-1, 1))
        page = jnp.where(positions >= ins, page, SCRATCH_PAGE)
    slot = jnp.broadcast_to(positions % ps, page.shape)
    return _paged_write(cache, k, v, page, slot)


def gather_pages(pages, block_tables):
    """Head-major pages (P, K, ps, ...) gathered to a position-ordered
    per-row view (B, M * ps, K, ...)."""
    g = jnp.swapaxes(pages[block_tables], 2, 3)   # (B, M, ps, K, ...)
    return g.reshape((g.shape[0], g.shape[1] * g.shape[2]) + g.shape[3:])


def paged_gather_kv(cache: Params, block_tables):
    """Block-table gather of a paged cache -> (k, v) in compute
    precision, (B, T, K, hd) with T = M * page_size (dequantized when
    the pool stores int8)."""
    k = gather_pages(cache["k"], block_tables)
    v = gather_pages(cache["v"], block_tables)
    if k.dtype == jnp.int8:
        # scales (P, K, 1, ps) gather like (P, K, ps, 1) pages
        def scales(name):
            return gather_pages(jnp.swapaxes(cache[name], 2, 3), block_tables)
        k = k.astype(jnp.bfloat16) * scales("k_scale")
        v = v.astype(jnp.bfloat16) * scales("v_scale")
    return k, v


def build_decode_prefetch(block_tables, pos):
    """Pack a decode step's (B, M) block tables and per-row positions
    into the combined (B, M+1) scalar-prefetch operand the paged kernel
    accepts (lengths = pos + 1 ride in the last column).  Build it ONCE
    per decode step and pass it to every layer via
    ``paged_decode_attention(..., prefetch=...)`` — the per-layer
    scalar-prefetch staging then amortizes over the stack."""
    from repro.kernels.paged_attention import decode_prefetch
    lengths = jnp.asarray(pos, jnp.int32).reshape((-1,)) + 1
    return decode_prefetch(block_tables, lengths)


def paged_decode_attention(q, cache: Params, block_tables, pos, *,
                           window: Optional[int] = None,
                           chunk: Optional[int] = None,
                           scale: Optional[float] = None,
                           logit_cap: Optional[float] = None,
                           prefetch=None) -> jnp.ndarray:
    """Single-token attention over a paged pool via per-row block tables.

    q: (B, 1, H, hd); block_tables: (B, M) int32 page ids; pos: (B,)
    per-row query positions (each row's k/v already inserted).
    On TPU this lowers to the Pallas paged-attention kernel (block
    table scalar-prefetched, pages gathered page-by-page); elsewhere it
    runs the gather + shared-mask jnp path.  Returns (B, 1, H, vd).
    """
    from repro.kernels import ops as kops
    if kops.use_pallas():
        lengths = jnp.asarray(pos, jnp.int32).reshape((-1,)) + 1
        out = kops.paged_attention(
            q[:, 0], cache["k"], cache["v"], block_tables, lengths,
            window=window, chunk=chunk, scale=scale, logit_cap=logit_cap,
            k_scales=cache.get("k_scale"), v_scales=cache.get("v_scale"),
            prefetch=prefetch)
        return out[:, None]
    k, v = paged_gather_kv(cache, block_tables)
    k = shard(k, "batch", None, "kv_heads", None)
    v = shard(v, "batch", None, "kv_heads", None)
    kv_pos = jnp.arange(k.shape[1], dtype=jnp.int32)
    return masked_decode_attention(q, k, v, kv_pos, pos, window=window,
                                   chunk=chunk, scale=scale,
                                   logit_cap=logit_cap)


def attention_span(kind: str, seq_len: int, *, window: Optional[int] = None,
                   chunk: Optional[int] = None) -> int:
    """Ring-cache capacity needed by a layer kind at a given seq length."""
    if kind == "swa" and window is not None:
        return min(window, seq_len)
    if kind == "chunked" and chunk is not None:
        return min(chunk, seq_len)
    return seq_len
