"""Pallas TPU paged-attention decode kernel (block-table gather, GQA,
sliding-window / chunked masks, logit soft-capping, int8 pages).

One query token per batch row attends over that row's KV pages.  Pages
are pool-wide head-major slabs shared by every request, so one (page,
kv head) block is a whole (page_size, hd) tile, as the TPU's (8, 128)
tiling requires of a block's two minor dimensions.  The model's pool
stacks every layer, (L, num_pages, K, page_size, hd), and is carried
whole through the layer scan: the kernel takes the stacked pool plus
the layer index ``layer`` and reads that layer's pages in place, so no
layer slice is ever cut out of the stack.  Each row's ordered page
list and its length arrive in one (B, M+1) operand built by
:func:`decode_prefetch` once per decode step and shared by every
layer; it is **scalar-prefetched** (pltpu.PrefetchScalarGridSpec), as
is the layer index, so the BlockSpec index_map can steer the K/V DMA
to the right (layer, page) before the kernel body runs — the gather
never materialises a contiguous per-row KV copy in HBM.

Grid: (batch, num_pages_per_row).  One step moves one page of every kv
head: a (1, K, page_size, hd) block of K and one of V, a contiguous
slab of the head-major pool, against a (1, K, g, hd) query block, where
g = q_heads // kv_heads is the GQA group size.  Each page is DMA'd
**once per row** for all K heads and all g group members, and a layer
takes batch x pages steps: at olmo-1b's decode shape (32 rows, 32
pages, 16 heads) 1 024 steps of 512 KiB of K+V, so a step's fixed cost
does not set the kernel's time.  The step body runs the per-(head,
page) arithmetic for each of the K heads in turn (a static loop; f32
casts, (g, page_size) score and PV matmuls, masks and online softmax).
The trailing (page) grid dimension is sequential on TPU, so the
online-softmax running state (m, l, acc) of every head of the block
lives in VMEM scratch, (K, g) and (K, g, vd), and is carried across a
row's pages, exactly like the flash kernel carries it across KV blocks.

Pages past a row's length (and outside its window/chunk span) are dead:
pl.when on the *dynamic* per-row length skips their compute, and the
K/V index maps clamp each page step into the row's live span
(:func:`_live_span`), so a dead step names the same page as its live
neighbour and the pipeline, which fetches a block only when its index
changes, issues no DMA for it.  Reserved but unwritten pages at the end
of a block-table row, the pages before a window, and every page of a
padded row but its first are never read — short rows in a mixed-length
decode batch move proportionally fewer bytes, which is the point of
paging.

When the pool stores int8, per-(slot, head) bf16 scales ride along as
two more page slabs of shape (L, num_pages, K, 1, page_size) — one
lane row per (page, kv head).  A scale is constant along a slot's
features, so it factors out of both matmuls and is applied along the
key axis of the (G, page_size) score / probability tiles:
q·(k∘s_k) = (q·k)∘s_k and p·(v∘s_v) = (p∘s_v)·v.  No lane-to-sublane
reshape of the scale row is needed, and HBM traffic stays at the
quantized width.
"""
from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

NEG_INF = -1e30


def _live_span(length, *, page_size: int, window: Optional[int],
               chunk: Optional[int], last_step: int):
    """First and last page step of a row's live span: the pages the
    liveness test of :func:`_paged_attn_kernel` keeps, for a row with
    ``length`` visible tokens.  A row with no live page (length 0) gets
    the span (0, 0).  Scalar arithmetic on traced int32s, shared by the
    K/V index maps."""
    q_pos = jnp.maximum(length - 1, 0)
    last = jnp.minimum(q_pos // page_size, last_step)
    first = jnp.zeros_like(last)
    if window is not None:      # first visible position q_pos - window + 1
        first = jnp.maximum(first,
                            jnp.maximum(q_pos - window + 1, 0) // page_size)
    if chunk is not None:       # the chunk floor (q_pos // chunk) * chunk
        first = jnp.maximum(first, (q_pos // chunk) * chunk // page_size)
    return jnp.minimum(first, last), last


def _paged_attn_kernel(pf_ref, layer_ref, q_ref, k_ref, v_ref, *rest,
                       scale: float, window: Optional[int],
                       chunk: Optional[int], logit_cap: Optional[float],
                       page_size: int, quantized: bool):
    # pf_ref: the (B, M+1) prefetch, page ids then the row's length;
    # layer_ref: the layer index, read only by the index maps
    if quantized:
        ks_ref, vs_ref, o_ref, m_scr, l_scr, acc_scr = rest
    else:
        o_ref, m_scr, l_scr, acc_scr = rest
        ks_ref = vs_ref = None
    b = pl.program_id(0)
    i = pl.program_id(1)
    nm = pl.num_programs(1)
    length_col = pf_ref.shape[1] - 1

    @pl.when(i == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    length = pf_ref[b, length_col]
    q_pos = length - 1
    k_first = i * page_size
    k_last = k_first + page_size - 1

    # dynamic per-row liveness: skip pages past the row's length and
    # outside its window/chunk span
    live = k_first < length
    if window is not None:
        live &= k_last > q_pos - window
    if chunk is not None:
        live &= k_last >= (q_pos // chunk) * chunk

    def attend(h):
        """One kv head's (G, page_size) online-softmax update."""
        q = q_ref[0, h].astype(jnp.float32) * scale         # (G, hd)
        k = k_ref[0, h].astype(jnp.float32)                 # (ps, hd)
        v = v_ref[0, h].astype(jnp.float32)                 # (ps, vd)
        sc = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        if quantized:                                       # (1, ps) rows
            sc = sc * ks_ref[0, h].astype(jnp.float32)
        if logit_cap is not None:
            sc = jnp.tanh(sc / logit_cap) * logit_cap
        kv_pos = k_first + jax.lax.broadcasted_iota(jnp.int32, sc.shape, 1)
        mask = kv_pos < length
        if window is not None:
            mask &= kv_pos > q_pos - window
        if chunk is not None:
            mask &= kv_pos >= (q_pos // chunk) * chunk
        sc = jnp.where(mask, sc, NEG_INF)

        m_prev = m_scr[h]
        m_new = jnp.maximum(m_prev, sc.max(axis=1))
        p = jnp.exp(sc - m_new[:, None])
        corr = jnp.exp(m_prev - m_new)
        l_scr[h] = l_scr[h] * corr + p.sum(axis=1)
        pv = p * vs_ref[0, h].astype(jnp.float32) if quantized else p
        acc_scr[h] = (acc_scr[h] * corr[:, None]
                      + jax.lax.dot_general(pv, v, (((1,), (0,)), ((), ())),
                                            preferred_element_type=jnp.float32))
        m_scr[h] = m_new

    @pl.when(live)
    def _compute():
        for h in range(q_ref.shape[1]):     # every kv head of the page
            attend(h)

    @pl.when(i == nm - 1)
    def _finalize():
        l = jnp.maximum(l_scr[...], 1e-30)
        # a row with length == 0 never enters _compute: acc / clamped-l
        # is not attention over anything — the contract is exact zeros
        out = jnp.where(length > 0, acc_scr[...] / l[..., None], 0.0)
        o_ref[0] = out.astype(o_ref.dtype)


def decode_prefetch(block_tables, lengths):
    """Pack a decode step's block tables (B, M) and per-row lengths (B,)
    into ONE (B, M+1) int32 scalar-prefetch operand: columns 0..M-1 are
    page ids, column M is the row's length.  Built once per decode step
    and shared by every layer of the stack, so the per-layer scalar-
    prefetch staging amortizes instead of re-packing two operands per
    layer.  Pass it as ``paged_attention``'s ``prefetch``.
    """
    bt = jnp.asarray(block_tables, jnp.int32)
    ln = jnp.asarray(lengths, jnp.int32).reshape(bt.shape[0], 1)
    return jnp.concatenate([bt, ln], axis=1)


def paged_attention(q, k_pages, v_pages, prefetch, layer, *,
                    window: Optional[int] = None,
                    chunk: Optional[int] = None,
                    logit_cap: Optional[float] = None,
                    scale: Optional[float] = None,
                    k_scales=None, v_scales=None,
                    v_dim: Optional[int] = None,
                    interpret: bool = False):
    """q: (B, H, hd); k_pages/v_pages: a stacked pool (L, P, K,
    page_size, hd|vd) read at layer ``layer`` (int32 scalar, traced
    ok); prefetch: the (B, M+1) int32 operand of :func:`decode_prefetch`
    (M block-table columns, then the row's visible tokens — the query
    sits at length - 1).  k_scales/v_scales: (L, P, K, 1, page_size)
    bf16 when the pages are int8.  ``v_dim`` reads only the leading
    v_dim features of each v page — with v_pages=k_pages that serves
    absorbed-MLA decode, where v is the latent's first kv_lora features
    of the same slab, without a second page store.
    Returns (B, H, vd) in q.dtype; rows with length 0 are exact zeros.
    """
    from jax.experimental.pallas import tpu as pltpu

    b, h, hd = q.shape
    if prefetch.shape[0] != b:
        raise ValueError(f"prefetch shape {prefetch.shape}: {b} rows wanted")
    m = prefetch.shape[1] - 1
    kk, ps = k_pages.shape[-3:-1]
    vd = v_dim if v_dim is not None else v_pages.shape[-1]
    g = h // kk
    scale_ = scale if scale is not None else 1.0 / math.sqrt(hd)
    quantized = k_pages.dtype == jnp.int8

    # every kv head of a page in one (1, K, ps, hd) block and the g
    # query heads of each kv head in a (1, K, g, hd) query block, so a
    # page is DMA'd once for all of them
    qg = q.reshape(b, kk, g, hd)        # head h <-> (h // g, h % g)
    scalars = [prefetch.astype(jnp.int32),
               jnp.asarray(layer, jnp.int32).reshape((1,))]

    # index maps see the grid indices (row, page step), then the scalar
    # refs: the prefetch, whose leading M columns are the block table
    # and whose last is the length, and the layer index
    def q_idx(b_, i, pf, layer_):
        return (b_, 0, 0, 0)

    def kv_idx(b_, i, pf, layer_):
        # a step outside the row's live span names the span's nearest
        # page: consecutive dead steps then repeat one block index and
        # the pipeline issues no DMA for them
        first, last = _live_span(pf[b_, m], page_size=ps, window=window,
                                 chunk=chunk, last_step=m - 1)
        page = pf[b_, jnp.clip(i, first, last)]
        return (layer_[0], page, 0, 0, 0)

    kernel = functools.partial(
        _paged_attn_kernel, scale=scale_, window=window, chunk=chunk,
        logit_cap=logit_cap, page_size=ps, quantized=quantized)

    # the (layer, page) id for (row b, step i) steers the K/V (and
    # scale) DMAs.  Every block's two minor dims are (ps, hd|vd) or
    # (1, ps): whole tiles of the head-major pool, as the TPU's tiling
    # requires; the layer dim is squeezed out of the block
    def kv_spec(*tile):
        return pl.BlockSpec((pl.Squeezed(), 1, kk) + tile, kv_idx)

    in_specs = [pl.BlockSpec((1, kk, g, hd), q_idx),
                kv_spec(ps, hd), kv_spec(ps, vd)]
    args = [qg, k_pages, v_pages]
    if quantized:
        in_specs += [kv_spec(1, ps), kv_spec(1, ps)]
        args += [k_scales, v_scales]

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=len(scalars),
        grid=(b, m),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, kk, g, vd), q_idx),
        scratch_shapes=[
            pltpu.VMEM((kk, g), jnp.float32),
            pltpu.VMEM((kk, g), jnp.float32),
            pltpu.VMEM((kk, g, vd), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, kk, g, vd), q.dtype),
        interpret=interpret,
    )(*scalars, *args)
    return out.reshape(b, h, vd)
