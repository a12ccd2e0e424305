"""Dispatching wrappers: Pallas on TPU, pure-jnp oracle elsewhere.

``use_pallas()`` is True on real TPU backends.  Off the TPU the kernels
run only when a test sets ``_FORCE``, and then in interpret mode
(the kernel body executes in Python); on a TPU they always compile.
The jnp fallbacks are not toys — they are the blocked/flash-equivalent
implementations in repro.models.* whose HLO the dry-run analyses.
"""
from __future__ import annotations

from typing import Optional

import jax

from repro.kernels import ref
from repro.kernels.flash_attention import flash_attention as _flash_pallas
from repro.kernels.mux_score import mux_score as _mux_pallas
from repro.kernels.paged_attention import paged_attention as _paged_pallas
from repro.kernels.selective_scan import selective_scan as _scan_pallas

_FORCE = False   # tests set True to run the kernels on the CPU (interpret)


def use_pallas() -> bool:
    return _FORCE or jax.default_backend() == "tpu"


def _interpret() -> bool:
    return jax.default_backend() != "tpu"


def attention(q, k, v, *, causal: bool = True, window: Optional[int] = None,
              chunk: Optional[int] = None, logit_cap: Optional[float] = None,
              scale: Optional[float] = None):
    """Flash attention: Pallas kernel on TPU, blocked-jnp elsewhere."""
    if use_pallas():
        return _flash_pallas(q, k, v, causal=causal, window=window,
                             chunk=chunk, logit_cap=logit_cap, scale=scale,
                             interpret=_interpret())
    from repro.models.attention import blocked_attention
    return blocked_attention(q, k, v, causal=causal, window=window,
                             chunk=chunk, scale=scale, logit_cap=logit_cap)


def paged_attention(q, k_pages, v_pages, prefetch, layer, *,
                    window: Optional[int] = None,
                    chunk: Optional[int] = None,
                    logit_cap: Optional[float] = None,
                    scale: Optional[float] = None,
                    k_scales=None, v_scales=None,
                    v_dim: Optional[int] = None):
    """Paged decode attention through the Pallas kernel (one grid step
    per (row, page) covering every kv head, block table and lengths
    scalar-prefetched, int8 dequant in-kernel), for callers that
    checked ``use_pallas()``; their jnp path is the gather in
    attention.paged_decode_attention.  q: (B, H, hd) one token per row;
    k_pages/v_pages: the stacked (L, P, K, ps, hd) pool read at
    ``layer``; ``prefetch``: the (B, M+1) operand from
    :func:`repro.kernels.paged_attention.decode_prefetch`, built once
    per decode step and shared across layers."""
    return _paged_pallas(q, k_pages, v_pages, prefetch, layer,
                         window=window, chunk=chunk, logit_cap=logit_cap,
                         scale=scale, k_scales=k_scales, v_scales=v_scales,
                         v_dim=v_dim, interpret=_interpret())


def selective_scan(x, dt, b_mat, c_mat, a_mat, d_vec):
    """Mamba-1 scan: Pallas kernel on TPU, lax.scan reference elsewhere."""
    if use_pallas():
        return _scan_pallas(x, dt, b_mat, c_mat, a_mat, d_vec,
                            interpret=_interpret())
    y, _ = ref.selective_scan_ref(x, dt, b_mat, c_mat, a_mat, d_vec)
    return y


def mux_score(meta, v, cost, *, normalize: bool = True):
    """Fused router head: Pallas on TPU, jnp elsewhere."""
    if use_pallas():
        return _mux_pallas(meta, v, cost, normalize=normalize,
                           interpret=_interpret())
    return ref.mux_score_ref(meta, v, cost, normalize=normalize)
