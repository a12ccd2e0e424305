"""Plain reference of a dense decoder with multi-head (or grouped)
attention: OLMo-style non-parametric LayerNorm or RMSNorm, rotary
positions, SwiGLU MLP, tied or separate output head.

It follows the configuration file's sizes and names, computes in
float32 at HIGHEST precision, one layer at a time, and imports nothing
of the program.  ``to_program`` is the only place that knows the
program's parameter tree: it hands the benchmark's weights to the
system under test.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from chipbench import refmath as rm


def _vd(c):
    return c.get("v_head_dim") or c["head_dim"]


def weight_specs(c):
    """name -> (shape, kind); kind 'embed' (std 0.02), 'dense' (std
    1/sqrt(fan-in)) or 'norm' (1 + 0.1 * normal)."""
    L, D, F, V = c["num_layers"], c["d_model"], c["d_ff"], c["vocab_size"]
    H, K, hd, vd = c["num_heads"], c["num_kv_heads"], c["head_dim"], _vd(c)
    s = {"embed": ((V, D), "embed"),
         "wq": ((L, D, H * hd), "dense"), "wk": ((L, D, K * hd), "dense"),
         "wv": ((L, D, K * vd), "dense"), "wo": ((L, H * vd, D), "dense"),
         "w_gate": ((L, D, F), "dense"), "w_up": ((L, D, F), "dense"),
         "w_down": ((L, F, D), "dense")}
    if c["norm"] == "rmsnorm":
        s.update(attn_norm=((L, D), "norm"), mlp_norm=((L, D), "norm"),
                 final_norm=((D,), "norm"))
    if not c["tie_embeddings"]:
        s["head"] = ((D, V), "dense")
    return s


def to_program(w, c):
    """The program's parameter tree (``repro.models.transformer``) over
    the same arrays."""
    def norm(name):
        return {} if c["norm"] == "nonparametric_ln" else {"w": w[name]}
    p = {"embed": w["embed"],
         "blocks": {"p0": {
             "norm1": norm("attn_norm"),
             "attn": {k: w[k] for k in ("wq", "wk", "wv", "wo")},
             "norm2": norm("mlp_norm"),
             "mlp": {"up": w["w_up"], "gate": w["w_gate"],
                     "down": w["w_down"]}}},
         "final_norm": norm("final_norm")}
    if not c["tie_embeddings"]:
        p["head"] = w["head"]
    return p


def _norm(c, x, w):
    if c["norm"] == "nonparametric_ln":
        return rm.layer_norm(x, c["norm_eps"])
    return rm.rms_norm(x, w, c["norm_eps"])


@partial(jax.jit, static_argnums=(0, 5))
def _layer(cf, h, ws, index, positions, quant):
    c = dict(cf)
    lw = {k: v[index] for k, v in ws.items()}
    T = h.shape[0]
    H, K, hd, vd = c["num_heads"], c["num_kv_heads"], c["head_dim"], _vd(c)
    dt = rm.act_dtype(quant)
    x = _norm(c, h, lw.get("attn_norm")).astype(dt)
    q = rm.mm(x, lw["wq"], quant).reshape(T, H, hd)
    k = rm.mm(x, lw["wk"], quant).reshape(T, K, hd)
    v = rm.mm(x, lw["wv"], quant).reshape(T, K, vd)
    q = rm.rope(q, positions, c["rope_theta"]).astype(dt)
    k = rm.rope(k, positions, c["rope_theta"]).astype(dt)
    k = jnp.repeat(k, H // K, axis=1)
    v = jnp.repeat(v.astype(dt), H // K, axis=1)
    o = rm.causal_attention(q, k, v, rm.attn_scale(hd), quant=quant)
    out = rm.mm(o.reshape(T, H * vd).astype(dt), lw["wo"], quant)
    rs = c.get("residual_scale") or 1.0
    h = (h + rs * out).astype(dt)
    x = _norm(c, h, lw.get("mlp_norm")).astype(dt)
    y = rm.silu(rm.mm(x, lw["w_gate"], quant)) * rm.mm(x, lw["w_up"], quant)
    y = rm.mm(y.astype(dt), lw["w_down"], quant)
    return (h + rs * y).astype(dt)


@partial(jax.jit, static_argnums=(0, 5, 6))
def _head(cf, h, w_out, final_norm, first, rows, quant):
    c = dict(cf)
    h = jax.lax.dynamic_slice_in_dim(h, first, rows, axis=0)
    x = _norm(c, h, final_norm).astype(rm.act_dtype(quant))
    if c["tie_embeddings"]:
        w_out = w_out.T
    return rm.mm(x, w_out, quant)


LAYER_KEYS = ("wq", "wk", "wv", "wo", "w_gate", "w_up", "w_down",
              "attn_norm", "mlp_norm")


def logits(w, c, tokens, first, rows, quant=None):
    """Logits (rows, V) of ``tokens`` (T,) at positions first ..
    first + rows - 1, each row predicting the token after it.  Tokens
    past the live ones may be padding: attention is causal, so they
    change no earlier row."""
    cf = rm.config_key(c)
    tokens = jnp.asarray(tokens, jnp.int32)
    positions = jnp.arange(tokens.shape[0], dtype=jnp.int32)
    h = w["embed"][tokens].astype(jnp.float32) * (c.get("embed_scale") or 1.0)
    h = h.astype(rm.act_dtype(quant))
    ws = {k: w[k] for k in LAYER_KEYS if k in w}
    for layer in range(c["num_layers"]):
        h = _layer(cf, h, ws, jnp.int32(layer), positions, quant)
    w_out = w["embed"] if c["tie_embeddings"] else w["head"]
    return _head(cf, h, w_out, w.get("final_norm"), jnp.int32(first), rows,
                 quant)

