"""Pallas TPU paged-attention decode kernel (block-table gather, GQA,
sliding-window / chunked masks, logit soft-capping, int8 pages).

One query token per batch row attends over that row's KV pages.  Pages
are pool-wide head-major slabs shared by every request, so one (page,
kv head) block is a whole (page_size, hd) tile, as the TPU's (8, 128)
tiling requires of a block's two minor dimensions.  The model's pool
stacks every layer, (G, num_pages, K, page_size, hd), and is carried
whole through the layer scan: the kernel takes the stacked pool plus
the layer index ``layer`` and reads that layer's pages in place, so no
layer slice is ever cut out of the stack.  A single layer's pool
(num_pages, K, page_size, hd) with ``layer=None`` is accepted too.
Each row's ordered page list arrives as a block-table row that is
**scalar-prefetched** (pltpu.PrefetchScalarGridSpec), as is the layer
index, so the BlockSpec index_map can steer the K/V DMA to the right
(layer, page) before the kernel body runs — the gather never
materialises a contiguous per-row KV copy in HBM.

Grid: (batch, num_pages_per_row).  One step moves one page of every kv
head: a (1, K, page_size, hd) block of K and one of V, a contiguous
slab of the head-major pool, against a (1, K, g, hd) query block, where
g = q_heads // kv_heads is the GQA group size.  Each page is DMA'd
**once per row** for all K heads and all g group members, and a layer
takes batch x pages steps, not batch x K x pages: at olmo-1b's decode
shape (32 rows, 32 pages, 16 heads) 1 024 steps of 512 KiB of K+V
instead of 16 384 of 32 KiB, so a step's fixed cost stops setting the
kernel's time.  The step body runs the per-(head, page) arithmetic for
each of the K heads in turn (a static loop; the same f32 casts, (g,
page_size) score and PV matmuls, masks and online softmax), so the
output is bitwise that of one head per step.  ``grouped=False`` keeps
a per-head grid, (batch, q_heads, num_pages_per_row) with one head per
block, as a measurable baseline: there every group member re-fetches
the same page.  The trailing (page) grid dimension is sequential on
TPU, so the online-softmax running state (m, l, acc) of every head of
the block lives in VMEM scratch, (K, g) and (K, g, vd), and is carried
across a row's pages, exactly like the flash kernel carries it across
KV blocks.

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
two more page slabs of shape ([G,] num_pages, K, 1, page_size) — one
lane row per (page, kv head).  A scale is constant along a slot's
features, so it factors out of both matmuls and is applied along the
key axis of the (G, page_size) score / probability tiles:
q·(k∘s_k) = (q·k)∘s_k and p·(v∘s_v) = (p∘s_v)·v.  No lane-to-sublane
reshape of the scale row is needed, and HBM traffic stays at the
quantized width.

:func:`decode_prefetch` packs block tables and lengths into ONE
(B, M+1) int32 scalar operand that the caller builds once per decode
step and shares across every layer, so the per-layer scalar-prefetch
setup amortizes over the stack instead of re-staging two operands per
layer.  :func:`decode_hbm_bytes` counts the K/V bytes the grid moves —
the deterministic count benchmarks and the roofline report use, so the
g-fold claim is measured, not asserted.
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


def _paged_attn_kernel(*refs, scale: float, window: Optional[int],
                       chunk: Optional[int], logit_cap: Optional[float],
                       page_size: int, quantized: bool,
                       length_col: Optional[int], num_scalars: int,
                       heads: int, page_axis: int):
    # scalar refs: block tables, then lengths unless they ride in the
    # combined prefetch's last column, then the layer index if stacked
    bt_ref = refs[0]
    len_ref = refs[1] if length_col is None else None
    q_ref, k_ref, v_ref, *rest = refs[num_scalars:]
    if quantized:
        ks_ref, vs_ref, o_ref, m_scr, l_scr, acc_scr = rest
    else:
        o_ref, m_scr, l_scr, acc_scr = rest
        ks_ref = vs_ref = None
    b = pl.program_id(0)
    i = pl.program_id(page_axis)
    nm = pl.num_programs(page_axis)

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
        for h in range(heads):
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
    a deterministic host-side count of the live pages (the kernel's
    per-page liveness test: length / window / chunk) times the page
    bytes of K and V (and of the scale slabs), the term decode is
    bandwidth-bound on.  The index maps clamp every dead step onto a
    live neighbour, so the grid fetches each live page once and no
    dead one; the count leaves out only the one page a row with no
    live page (length 0) still names, fetched when that row's block
    index differs from the previous row's.

    grouped=True counts one K/V fetch per (row, kv_head, live page);
    grouped=False counts one per (row, q_head, live page) — the exact
    g-fold difference the re-grid removes.
    """
    kk, ps, hd = (int(d) for d in k_pages.shape[-3:])
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
                    layer=None,
                    interpret: bool = False):
    """q: (B, H, hd); k_pages/v_pages: a stacked pool (L, P, K,
    page_size, hd|vd) read at layer ``layer`` (int32 scalar, traced
    ok), or with ``layer=None`` one layer's (P, K, page_size, hd|vd);
    block_tables: (B, M) int32; lengths: (B,) int32 visible tokens per
    row (query at lengths - 1).  k_scales/v_scales: ([L,] P, K, 1,
    page_size) bf16 when the pages are int8.  ``v_dim`` reads only the
    leading v_dim features of each v page — with v_pages=k_pages that
    serves absorbed-MLA decode, where v is the latent's first kv_lora
    features of the same slab, without a second page store.

    ``grouped`` grids over (row, page) with every kv head's page in one
    block and a (K, g, hd) query block (each page fetched once per row);
    False keeps the per-head grid, one head per step, as the bandwidth
    baseline.  ``prefetch`` accepts the combined
    (B, M+1) operand from :func:`decode_prefetch`, replacing the
    separate block-table + lengths scalar operands.
    Returns (B, H, vd) in q.dtype; rows with length 0 are exact zeros.
    """
    from jax.experimental.pallas import tpu as pltpu

    b, h, hd = q.shape
    stacked = k_pages.ndim == 5
    if stacked != (layer is not None):
        raise ValueError(f"a {k_pages.ndim}-D pool with layer={layer!r}: "
                         "a stacked (L, P, K, ps, hd) pool needs a layer "
                         "index, a (P, K, ps, hd) pool takes none")
    kk, ps = k_pages.shape[-3:-1]
    vd = v_dim if v_dim is not None else v_pages.shape[-1]
    m = block_tables.shape[1]
    g = h // kk
    scale_ = scale if scale is not None else 1.0 / math.sqrt(hd)
    quantized = k_pages.dtype == jnp.int8

    # grouped: grid (row, page), every kv head of the page in one
    # (1, K, ps, hd) block and the g query heads of each kv head in a
    # (1, K, g, hd) query block, so a page is DMA'd once for all of them;
    # per-head: grid (row, q head, page), one head per block (G=1), each
    # group member re-fetching the page
    G = g if grouped else 1
    nh = kk if grouped else h
    heads = kk if grouped else 1
    qg = q.reshape(b, nh, G, hd)        # head h <-> (h // g, h % g)

    if prefetch is not None:
        if prefetch.shape != (b, m + 1):
            raise ValueError(f"prefetch shape {prefetch.shape} != ({b}, {m + 1})")
        length_col = m
        scalars = [prefetch.astype(jnp.int32)]
    else:
        length_col = None
        scalars = [block_tables.astype(jnp.int32), lengths.astype(jnp.int32)]
    if stacked:
        scalars.append(jnp.asarray(layer, jnp.int32).reshape((1,)))

    # index maps see the grid indices, (row, [head,] page step), then the
    # scalar refs: the block table (or the combined prefetch, whose
    # leading M columns are the block table and whose last is the
    # length), the lengths if separate, and a stacked pool's layer index
    ns = len(scalars)

    def q_idx(*args):
        grid = args[:-ns]
        return (grid[0], 0 if grouped else grid[1], 0, 0)

    def kv_idx(*args):
        grid, refs = args[:-ns], args[-ns:]
        b_, i = grid[0], grid[-1]
        length = refs[1][b_] if length_col is None else refs[0][b_, length_col]
        # a step outside the row's live span names the span's nearest
        # page: consecutive dead steps then repeat one block index and
        # the pipeline issues no DMA for them
        first, last = _live_span(length, page_size=ps, window=window,
                                 chunk=chunk, last_step=m - 1)
        at = (refs[0][b_, jnp.clip(i, first, last)],
              0 if grouped else grid[1] // g, 0, 0)
        return (refs[-1][0],) + at if stacked else at

    kernel = functools.partial(
        _paged_attn_kernel, scale=scale_, window=window, chunk=chunk,
        logit_cap=logit_cap, page_size=ps, quantized=quantized,
        length_col=length_col, num_scalars=ns, heads=heads,
        page_axis=1 if grouped else 2)

    # the (layer,) page id for (row b, step i) steers the K/V (and
    # scale) DMAs.  Every block's two minor dims are (ps, hd|vd) or
    # (1, ps): whole tiles of the head-major pool, as the TPU's tiling
    # requires; a stacked pool's layer dim is squeezed out of the block
    lead = (pl.Squeezed(),) if stacked else ()

    def kv_spec(*tile):
        return pl.BlockSpec(lead + (1, heads) + tile, kv_idx)

    in_specs = [pl.BlockSpec((1, heads, G, hd), q_idx),
                kv_spec(ps, hd), kv_spec(ps, vd)]
    args = [qg, k_pages, v_pages]
    if quantized:
        in_specs += [kv_spec(1, ps), kv_spec(1, ps)]
        args += [k_scales, v_scales]

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=len(scalars),
        grid=(b, m) if grouped else (b, h, m),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, heads, G, vd), q_idx),
        scratch_shapes=[
            pltpu.VMEM((heads, G), jnp.float32),
            pltpu.VMEM((heads, G), jnp.float32),
            pltpu.VMEM((heads, G, vd), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, nh, G, vd), q.dtype),
        interpret=interpret,
    )(*scalars, *args)
    return out.reshape(b, h, vd)
