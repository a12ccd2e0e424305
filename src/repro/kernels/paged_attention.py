"""Pallas TPU paged-attention decode kernel (block-table gather, GQA,
sliding-window / chunked masks, logit soft-capping, int8 pages).

One query token per batch row attends over that row's KV pages.  Pages
are pool-wide head-major slabs (num_pages, K, page_size, hd) shared by
every request, so one (page, kv head) block is a whole (page_size, hd)
tile, as the TPU's (8, 128) tiling requires of a block's two minor
dimensions.  Each row's ordered page list arrives as a block-table row
that is **scalar-prefetched** (pltpu.PrefetchScalarGridSpec) so the
BlockSpec index_map can steer the K/V DMA to the right page before the
kernel body runs — the gather never materialises a contiguous per-row
KV copy in HBM.

Grid: (batch, kv_heads, num_pages_per_row) with a (g, hd) query block,
where g = q_heads // kv_heads is the GQA group size.  Each K/V page is
DMA'd **once per group** and the score / PV matmuls are (g, page_size)-
shaped — decode HBM traffic, the thing decode is bound on, is cut g-fold
versus gridding over query heads (``grouped=False`` keeps the per-head
grid as a measurable baseline; there every group member re-fetches the
same page).  The trailing grid dimension is sequential on TPU, so the
online-softmax running state (m, l, acc) lives in VMEM scratch and is
carried across a row's pages, exactly like the flash kernel carries it
across KV blocks.  Pages past a row's length (and outside its
window/chunk span) are skipped with pl.when on the *dynamic* per-row
length — short rows in a mixed-length decode batch do proportionally
less work, which is the point of paging.

When the pool stores int8, per-(slot, head) bf16 scales ride along as
two more page slabs of shape (num_pages, K, 1, page_size) — one lane
row per (page, kv head).  A scale is constant along a slot's features,
so it factors out of both matmuls and is applied along the key axis of
the (G, page_size) score / probability tiles: q·(k∘s_k) = (q·k)∘s_k and
p·(v∘s_v) = (p∘s_v)·v.  No lane-to-sublane reshape of the scale row is
needed, and HBM traffic stays at the quantized width.

:func:`decode_prefetch` packs block tables and lengths into ONE
(B, M+1) int32 scalar operand that the caller builds once per decode
step and shares across every layer, so the per-layer scalar-prefetch
setup amortizes over the stack instead of re-staging two operands per
layer.  :func:`decode_hbm_bytes` is the analytic mirror of the grid —
the deterministic K/V byte count benchmarks and the roofline report
use, so the g-fold claim is measured, not asserted.
"""
from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl

NEG_INF = -1e30


def _paged_attn_kernel(*refs, scale: float, window: Optional[int],
                       chunk: Optional[int], logit_cap: Optional[float],
                       page_size: int, quantized: bool,
                       length_col: Optional[int]):
    if length_col is None:
        bt_ref, len_ref, q_ref, k_ref, v_ref, *rest = refs
    else:                       # combined (B, M+1) prefetch: lengths ride
        bt_ref, q_ref, k_ref, v_ref, *rest = refs  # in the last column
        len_ref = None
    if quantized:
        ks_ref, vs_ref, o_ref, m_scr, l_scr, acc_scr = rest
    else:
        o_ref, m_scr, l_scr, acc_scr = rest
        ks_ref = vs_ref = None
    b = pl.program_id(0)
    i = pl.program_id(2)
    nm = pl.num_programs(2)

    @pl.when(i == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    length = len_ref[b] if length_col is None else bt_ref[b, length_col]
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

    @pl.when(live)
    def _compute():
        q = q_ref[0, 0].astype(jnp.float32) * scale         # (G, hd)
        k = k_ref[0, 0].astype(jnp.float32)                 # (ps, hd)
        v = v_ref[0, 0].astype(jnp.float32)                 # (ps, vd)
        sc = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        if quantized:                                       # (1, ps) rows
            sc = sc * ks_ref[0, 0].astype(jnp.float32)
        if logit_cap is not None:
            sc = jnp.tanh(sc / logit_cap) * logit_cap
        kv_pos = k_first + jax.lax.broadcasted_iota(jnp.int32, sc.shape, 1)
        mask = kv_pos < length
        if window is not None:
            mask &= kv_pos > q_pos - window
        if chunk is not None:
            mask &= kv_pos >= (q_pos // chunk) * chunk
        sc = jnp.where(mask, sc, NEG_INF)

        m_prev = m_scr[...]
        m_new = jnp.maximum(m_prev, sc.max(axis=1))
        p = jnp.exp(sc - m_new[:, None])
        corr = jnp.exp(m_prev - m_new)
        l_scr[...] = l_scr[...] * corr + p.sum(axis=1)
        pv = p * vs_ref[0, 0].astype(jnp.float32) if quantized else p
        acc_scr[...] = (acc_scr[...] * corr[:, None]
                        + jax.lax.dot_general(pv, v, (((1,), (0,)), ((), ())),
                                              preferred_element_type=jnp.float32))
        m_scr[...] = m_new

    @pl.when(i == nm - 1)
    def _finalize():
        l = jnp.maximum(l_scr[...], 1e-30)
        # a row with length == 0 never enters _compute: acc / clamped-l
        # is not attention over anything — the contract is exact zeros
        out = jnp.where(length > 0, acc_scr[...] / l[:, None], 0.0)
        o_ref[0, 0] = out.astype(o_ref.dtype)


def decode_prefetch(block_tables, lengths):
    """Pack a decode step's block tables (B, M) and per-row lengths (B,)
    into ONE (B, M+1) int32 scalar-prefetch operand: columns 0..M-1 are
    page ids, column M is the row's length.  Built once per decode step
    and shared by every layer of the stack, so the per-layer scalar-
    prefetch staging amortizes instead of re-packing two operands per
    layer.  Pass it as ``paged_attention(..., prefetch=...)``.
    """
    bt = jnp.asarray(block_tables, jnp.int32)
    ln = jnp.asarray(lengths, jnp.int32).reshape(bt.shape[0], 1)
    return jnp.concatenate([bt, ln], axis=1)


def decode_hbm_bytes(k_pages, v_pages, block_tables, lengths, *,
                     num_q_heads: int,
                     window: Optional[int] = None,
                     chunk: Optional[int] = None,
                     v_dim: Optional[int] = None,
                     quantized: Optional[bool] = None,
                     grouped: bool = True) -> int:
    """Analytic K/V HBM bytes one :func:`paged_attention` call DMAs —
    a deterministic host-side mirror of the kernel's grid and per-page
    liveness test (length / window / chunk), counting only page (and
    scale-slab) traffic, the term decode is bandwidth-bound on.

    grouped=True counts one K/V fetch per (row, kv_head, live page);
    grouped=False counts one per (row, q_head, live page) — the exact
    g-fold difference the re-grid removes.
    """
    kk = int(k_pages.shape[1])
    ps = int(k_pages.shape[2])
    hd = int(k_pages.shape[3])
    vd = int(v_dim) if v_dim is not None else int(v_pages.shape[-1])
    if quantized is None:
        quantized = k_pages.dtype == jnp.int8
    heads = kk if grouped else int(num_q_heads)
    visit = (ps * hd * jnp.dtype(k_pages.dtype).itemsize
             + ps * vd * jnp.dtype(v_pages.dtype).itemsize)
    if quantized:                       # two bf16 (slot, head) scale rows
        visit += 2 * ps * 2
    m = int(np.asarray(block_tables).shape[1])
    live_pages = 0
    for length in np.asarray(lengths).reshape(-1).tolist():
        q_pos = length - 1
        for i in range(m):
            k_first, k_last = i * ps, i * ps + ps - 1
            live = k_first < length
            if window is not None:
                live &= k_last > q_pos - window
            if chunk is not None:
                live &= k_last >= (q_pos // chunk) * chunk
            live_pages += bool(live)
    return live_pages * heads * visit


def paged_attention(q, k_pages, v_pages, block_tables, lengths, *,
                    window: Optional[int] = None,
                    chunk: Optional[int] = None,
                    logit_cap: Optional[float] = None,
                    scale: Optional[float] = None,
                    k_scales=None, v_scales=None,
                    v_dim: Optional[int] = None,
                    grouped: bool = True,
                    prefetch=None,
                    interpret: bool = False):
    """q: (B, H, hd); k_pages/v_pages: (P, K, page_size, hd|vd);
    block_tables: (B, M) int32; lengths: (B,) int32 visible tokens per
    row (query at lengths - 1).  k_scales/v_scales: (P, K, 1, page_size)
    bf16 when the pages are int8.  ``v_dim`` reads only the leading
    v_dim features of each v page — with v_pages=k_pages that serves
    absorbed-MLA decode, where v is the latent's first kv_lora features
    of the same slab, without a second page store.

    ``grouped`` grids over KV heads with a (g, hd) query block (each
    page fetched once per GQA group); False keeps the per-head grid as
    the bandwidth baseline.  ``prefetch`` accepts the combined
    (B, M+1) operand from :func:`decode_prefetch`, replacing the
    separate block-table + lengths scalar operands.
    Returns (B, H, vd) in q.dtype; rows with length 0 are exact zeros.
    """
    from jax.experimental.pallas import tpu as pltpu

    b, h, hd = q.shape
    num_pages, kk, ps, _ = k_pages.shape
    vd = v_dim if v_dim is not None else v_pages.shape[-1]
    m = block_tables.shape[1]
    g = h // kk
    scale_ = scale if scale is not None else 1.0 / math.sqrt(hd)
    quantized = k_pages.dtype == jnp.int8

    # grouped: grid over KV heads, the g query heads of the group ride in
    # one (1, 1, g, hd) block and the page is DMA'd once for all of them;
    # per-head: grid over q heads (G=1), each group member re-fetches it
    G = g if grouped else 1
    nh = kk if grouped else h
    qg = q.reshape(b, nh, G, hd)        # head h <-> (h // g, h % g)
    if grouped:
        def kv_head(h_):
            return h_
    else:
        def kv_head(h_):
            return h_ // g

    if prefetch is not None:
        if prefetch.shape != (b, m + 1):
            raise ValueError(f"prefetch shape {prefetch.shape} != ({b}, {m + 1})")
        length_col = m
        nsp = 1
        scalars = (prefetch.astype(jnp.int32),)

        def q_idx(b_, h_, i, pf):
            return (b_, h_, 0, 0)

        def kv_idx(b_, h_, i, pf):
            return (pf[b_, i], kv_head(h_), 0, 0)
    else:
        length_col = None
        nsp = 2
        scalars = (block_tables.astype(jnp.int32), lengths.astype(jnp.int32))

        def q_idx(b_, h_, i, bt, ln):
            return (b_, h_, 0, 0)

        def kv_idx(b_, h_, i, bt, ln):
            return (bt[b_, i], kv_head(h_), 0, 0)

    kernel = functools.partial(
        _paged_attn_kernel, scale=scale_, window=window, chunk=chunk,
        logit_cap=logit_cap, page_size=ps, quantized=quantized,
        length_col=length_col)

    # index maps see the grid indices then the scalar-prefetch ref(s);
    # the page id for (row b, step i) steers the K/V (and scale) DMAs.
    # Every block's two minor dims are (ps, hd|vd) or (1, ps): whole
    # tiles of the head-major pool, as the TPU's tiling requires
    in_specs = [
        pl.BlockSpec((1, 1, G, hd), q_idx),
        pl.BlockSpec((1, 1, ps, hd), kv_idx),
        pl.BlockSpec((1, 1, ps, vd), kv_idx),
    ]
    args = [qg, k_pages, v_pages]
    if quantized:
        in_specs += [pl.BlockSpec((1, 1, 1, ps), kv_idx),
                     pl.BlockSpec((1, 1, 1, ps), kv_idx)]
        args += [k_scales, v_scales]

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=nsp,
        grid=(b, nh, m),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, 1, G, vd), q_idx),
        scratch_shapes=[
            pltpu.VMEM((G,), jnp.float32),
            pltpu.VMEM((G,), jnp.float32),
            pltpu.VMEM((G, vd), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, nh, G, vd), q.dtype),
        interpret=interpret,
    )(*scalars, *args)
    return out.reshape(b, h, vd)
