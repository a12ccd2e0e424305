"""Pure-jnp oracles for every Pallas kernel.

These are the *source of truth* for kernel correctness tests
(assert_allclose sweeps in tests/test_kernels.py) and the lowering path
used on non-TPU backends (the dry-run analyses this HLO).
"""
from __future__ import annotations

import math
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

NEG_INF = -1e30


def flash_attention_ref(q, k, v, *, causal: bool = True,
                        window: Optional[int] = None,
                        chunk: Optional[int] = None,
                        logit_cap: Optional[float] = None,
                        scale: Optional[float] = None) -> jnp.ndarray:
    """Naive full-materialisation attention.

    q: (B, S, H, hd); k/v: (B, T, K, hd|vd); H % K == 0.
    Returns (B, S, H, vd) in q.dtype.
    """
    b, s, h, hd = q.shape
    t, kk = k.shape[1], k.shape[2]
    g = h // kk
    scale = scale if scale is not None else 1.0 / math.sqrt(hd)
    qr = (q * scale).reshape(b, s, kk, g, hd)
    sc = jnp.einsum("bskgd,btkd->bkgst", qr, k,
                    preferred_element_type=jnp.float32)
    if logit_cap is not None:
        sc = jnp.tanh(sc / logit_cap) * logit_cap
    q_pos = jnp.arange(s)[:, None]
    kv_pos = jnp.arange(t)[None, :]
    mask = jnp.ones((s, t), bool)
    if causal:
        mask &= kv_pos <= q_pos
    if window is not None:
        mask &= kv_pos > q_pos - window
    if chunk is not None:
        mask &= kv_pos >= (q_pos // chunk) * chunk
    sc = jnp.where(mask[None, None, None], sc, NEG_INF)
    p = jax.nn.softmax(sc, axis=-1)
    out = jnp.einsum("bkgst,btkv->bskgv", p, v.astype(p.dtype))
    return out.reshape(b, s, h, v.shape[-1]).astype(q.dtype)


def paged_attention_ref(q, k_pages, v_pages, block_tables, lengths, *,
                        window: Optional[int] = None,
                        chunk: Optional[int] = None,
                        scale: Optional[float] = None,
                        logit_cap: Optional[float] = None) -> jnp.ndarray:
    """Naive paged decode attention: gather pages via block table, mask
    by per-row length, full-materialisation softmax.

    q: (B, H, hd) one query token per row; k_pages/v_pages:
    (P, K, page_size, hd|vd) head-major pool-wide page slabs;
    block_tables: (B, M) int32 page ids ordered by logical position;
    lengths: (B,) visible tokens per row (the query sits at lengths - 1).
    Returns (B, H, vd) in q.dtype.
    """
    b, h, hd = q.shape
    kk = k_pages.shape[1]
    g = h // kk
    scale = scale if scale is not None else 1.0 / math.sqrt(hd)

    def gather(pages):                      # (B, M, K, ps, d) -> (B, T, K, d)
        x = jnp.swapaxes(pages[block_tables], 2, 3)
        return x.reshape(b, -1, kk, pages.shape[-1])

    k = gather(k_pages)
    v = gather(v_pages)
    t = k.shape[1]
    qr = (q * scale).reshape(b, kk, g, hd)
    sc = jnp.einsum("bkgd,btkd->bkgt", qr, k,
                    preferred_element_type=jnp.float32)
    if logit_cap is not None:
        sc = jnp.tanh(sc / logit_cap) * logit_cap
    kv_pos = jnp.arange(t)[None, :]                         # (1, T)
    q_pos = lengths[:, None] - 1                            # (B, 1)
    mask = kv_pos < lengths[:, None]
    if window is not None:
        mask &= kv_pos > q_pos - window
    if chunk is not None:
        mask &= kv_pos >= (q_pos // chunk) * chunk
    sc = jnp.where(mask[:, None, None], sc, NEG_INF)
    p = jax.nn.softmax(sc, axis=-1)
    out = jnp.einsum("bkgt,btkv->bkgv", p, v.astype(p.dtype))
    out = out.reshape(b, h, v.shape[-1])
    # a row with length 0 has an all-masked softmax (NaN); the kernel
    # contract for such rows is exact zeros
    out = jnp.where(lengths[:, None, None] > 0, out, 0)
    return out.astype(q.dtype)


def selective_scan_ref(x, dt, b_mat, c_mat, a_mat, d_vec
                       ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Sequential-in-time Mamba-1 recurrence (fp32).

    x/dt: (B, S, d_in); b_mat/c_mat: (B, S, n); a_mat: (d_in, n);
    d_vec: (d_in,).
    Returns (y (B, S, d_in) fp32, h_final (B, d_in, n) fp32).
    """
    xf = x.astype(jnp.float32)
    dtf = dt.astype(jnp.float32)
    bf = b_mat.astype(jnp.float32)
    cf = c_mat.astype(jnp.float32)
    af = a_mat.astype(jnp.float32)

    def step(h, inp):
        x_t, dt_t, b_t, c_t = inp
        decay = jnp.exp(dt_t[:, :, None] * af[None])          # (B,d_in,n)
        h = decay * h + (dt_t * x_t)[:, :, None] * b_t[:, None, :]
        y = jnp.einsum("bdn,bn->bd", h, c_t)
        return h, y

    b, s, d_in = x.shape
    h0 = jnp.zeros((b, d_in, a_mat.shape[1]), jnp.float32)
    hf, ys = jax.lax.scan(step, h0, (xf.transpose(1, 0, 2),
                                     dtf.transpose(1, 0, 2),
                                     bf.transpose(1, 0, 2),
                                     cf.transpose(1, 0, 2)))
    y = ys.transpose(1, 0, 2) + xf * d_vec.astype(jnp.float32)[None, None]
    return y, hf


def mux_score_ref(meta, v, cost, *, normalize: bool = True) -> jnp.ndarray:
    """Fused multiplexer head (paper Eq. 5-6).

    meta: (B, M) raw meta-features; v: (N, M); cost: (N,) relative FLOPs.
    Returns softmax_i((v_i . normalize(m)) / c_i): (B, N) fp32.
    """
    m = meta.astype(jnp.float32)
    if normalize:
        m = m / jnp.maximum(jnp.linalg.norm(m, axis=-1, keepdims=True), 1e-6)
    logits = m @ v.astype(jnp.float32).T / cost.astype(jnp.float32)[None, :]
    return jax.nn.softmax(logits, axis=-1)
