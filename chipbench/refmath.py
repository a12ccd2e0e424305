"""Plain float32 building blocks of the references in ``reference/``.

Nothing here imports the program.  Every matrix product runs at
``Precision.HIGHEST`` (a TPU otherwise multiplies float32 in bfloat16).
``QUANT`` names the lower precisions a control computes in instead.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST
QUANT = ("int8", "fp8")


def mm(x, w, quant=None):
    """x (..., i) @ w (i, o).  ``quant`` None: float32 at HIGHEST.
    'int8': w rounded to int8 with one symmetric scale per output
    column, x in bfloat16.  'fp8': x and w in float8_e4m3fn, each with
    one scale per row of x and per column of w."""
    if quant is None:
        return jnp.matmul(x.astype(jnp.float32), w.astype(jnp.float32),
                          precision=HIGHEST)
    w = w.astype(jnp.float32)
    if quant == "int8":
        s = jnp.max(jnp.abs(w), axis=0, keepdims=True) / 127.0
        wq = jnp.round(w / jnp.where(s > 0, s, 1.0)).astype(jnp.int8)
        y = jnp.matmul(x.astype(jnp.bfloat16), wq.astype(jnp.bfloat16),
                       preferred_element_type=jnp.float32)
        return y * s
    if quant == "fp8":
        f8 = jnp.float8_e4m3fn
        top = float(jnp.finfo(f8).max)
        sw = jnp.max(jnp.abs(w), axis=0, keepdims=True) / top
        sx = jnp.max(jnp.abs(x.astype(jnp.float32)), axis=-1,
                     keepdims=True) / top
        wq = (w / jnp.where(sw > 0, sw, 1.0)).astype(f8)
        xq = (x.astype(jnp.float32) / jnp.where(sx > 0, sx, 1.0)).astype(f8)
        y = jnp.matmul(xq.astype(jnp.bfloat16), wq.astype(jnp.bfloat16),
                       preferred_element_type=jnp.float32)
        return y * sx * sw
    raise ValueError(f"unknown precision {quant!r}")


def act_dtype(quant):
    """The dtype activations are held in between products."""
    return jnp.float32 if quant is None else jnp.bfloat16


def layer_norm(x, eps):
    """LayerNorm without weight or bias (OLMo's non-parametric LN)."""
    x = x.astype(jnp.float32)
    mu = x.mean(-1, keepdims=True)
    var = ((x - mu) ** 2).mean(-1, keepdims=True)
    return (x - mu) / jnp.sqrt(var + eps)


def rms_norm(x, w, eps):
    x = x.astype(jnp.float32)
    return x / jnp.sqrt((x * x).mean(-1, keepdims=True) + eps) * w.astype(
        jnp.float32)


def rope(x, positions, theta):
    """Rotary embedding, rotate-half layout: x (T, heads, d), the first
    d/2 features paired with the last d/2."""
    d = x.shape[-1]
    inv = 1.0 / theta ** (np.arange(0, d, 2, dtype=np.float64) / d)
    ang = positions[:, None].astype(jnp.float32) * jnp.asarray(inv, jnp.float32)
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x = x.astype(jnp.float32)
    a, b = x[..., :d // 2], x[..., d // 2:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], axis=-1)


def causal_attention(q, k, v, scale, block=512, quant=None):
    """Softmax attention of every query over the keys at or before it.
    q (T, H, d), k (T, H, d), v (T, H, e) -> (T, H, e), queries in
    blocks so that a (H, block, T) score slab is the largest array."""
    t = q.shape[0]
    dt = act_dtype(quant)
    prec = HIGHEST if quant is None else None
    outs = []
    for s0 in range(0, t, block):
        qb = q[s0:s0 + block].astype(dt)
        sc = jnp.einsum("qhd,khd->hqk", qb, k.astype(dt), precision=prec,
                        preferred_element_type=jnp.float32) * scale
        qi = s0 + jnp.arange(qb.shape[0])[:, None]
        sc = jnp.where(jnp.arange(t)[None, :] <= qi, sc, -jnp.inf)
        p = jax.nn.softmax(sc, axis=-1)
        outs.append(jnp.einsum("hqk,khe->qhe", p.astype(dt), v.astype(dt),
                               precision=prec,
                               preferred_element_type=jnp.float32))
    return jnp.concatenate(outs, axis=0)


def silu(x):
    return x * jax.nn.sigmoid(x)


def attn_scale(head_dim: int) -> float:
    return 1.0 / math.sqrt(head_dim)


def config_key(c):
    """A configuration's scalar settings as a hashable static key."""
    return tuple(sorted((k, v) for k, v in c.items()
                        if isinstance(v, (int, float, str, bool, type(None)))))
