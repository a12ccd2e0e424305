"""Per-kernel shape/dtype sweeps: Pallas (interpret=True) vs ref.py oracle."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ref
from repro.kernels.flash_attention import flash_attention
from repro.kernels.mux_score import mux_score
from repro.kernels.paged_attention import paged_attention
from repro.kernels.selective_scan import selective_scan

KEY = jax.random.key(0)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize(
    "b,s,t,h,k,hd,vd,window,chunk,cap",
    [
        (2, 128, 128, 4, 2, 64, 64, None, None, None),     # GQA causal
        (1, 256, 256, 4, 4, 64, 64, 64, None, None),       # sliding window
        (2, 96, 96, 4, 1, 32, 32, None, None, 50.0),       # MQA + softcap
        (1, 256, 256, 8, 2, 64, 64, None, 96, None),       # chunked local
        (2, 64, 192, 4, 2, 64, 32, None, None, None),      # kv-longer + vd!=hd
    ])
def test_flash_attention_sweep(b, s, t, h, k, hd, vd, window, chunk, cap,
                               dtype):
    kq, kk, kv = jax.random.split(KEY, 3)
    q = jax.random.normal(kq, (b, s, h, hd)).astype(dtype)
    kmat = jax.random.normal(kk, (b, t, k, hd)).astype(dtype)
    v = jax.random.normal(kv, (b, t, k, vd)).astype(dtype)
    out = flash_attention(q, kmat, v, causal=True, window=window, chunk=chunk,
                          logit_cap=cap, block_q=64, block_k=64,
                          interpret=True)
    want = ref.flash_attention_ref(q, kmat, v, causal=True, window=window,
                                   chunk=chunk, logit_cap=cap)
    tol = 2e-5 if dtype == jnp.float32 else 2e-2
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(want, np.float32), atol=tol)


@pytest.mark.parametrize(
    "b,h,k,hd,vd,pages,ps,m,window,chunk,cap",
    [
        (3, 4, 2, 16, 16, 10, 8, 4, None, None, None),   # GQA
        (2, 4, 1, 32, 32, 8, 4, 5, 7, None, None),       # MQA + window
        (1, 8, 2, 16, 8, 12, 8, 3, None, 6, None),       # chunked, vd != hd
        (2, 2, 2, 16, 16, 6, 16, 2, None, None, 25.0),   # softcap
    ])
def test_paged_attention_sweep(b, h, k, hd, vd, pages, ps, m, window, chunk,
                               cap):
    """Pallas paged decode (interpret) vs the gather oracle: per-row
    lengths, block-table indirection, window/chunk masks."""
    kq, kk, kv, kt = jax.random.split(KEY, 4)
    q = jax.random.normal(kq, (b, h, hd))
    k_pages = jax.random.normal(kk, (pages, k, ps, hd))
    v_pages = jax.random.normal(kv, (pages, k, ps, vd))
    # each row gets m distinct pages drawn from 1..pages-1 (0 = scratch)
    perm = np.stack([np.random.RandomState(i).permutation(pages - 1)[:m] + 1
                     for i in range(b)])
    bt = jnp.asarray(perm, jnp.int32)
    lengths = jnp.asarray(
        np.random.RandomState(7).randint(1, m * ps + 1, size=(b,)), jnp.int32)
    out = paged_attention(q, k_pages, v_pages, bt, lengths, window=window,
                          chunk=chunk, logit_cap=cap, interpret=True)
    want = ref.paged_attention_ref(q, k_pages, v_pages, bt, lengths,
                                   window=window, chunk=chunk, logit_cap=cap)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want), atol=2e-5)


def test_paged_attention_v_dim_is_k_slice():
    """v_dim reads v as the leading features of the k slab — the
    absorbed-MLA latent layout (v = c_kv slice, one DMA per page)."""
    b, h, hd, ps, m, pages, vdim = 2, 4, 24, 4, 3, 8, 16
    kq, kk = jax.random.split(KEY)
    q = jax.random.normal(kq, (b, h, hd))
    k_pages = jax.random.normal(kk, (pages, 1, ps, hd))     # MQA latent
    bt = jnp.asarray([[1, 2, 3], [4, 5, 6]], jnp.int32)
    lengths = jnp.asarray([m * ps, 5], jnp.int32)
    out = paged_attention(q, k_pages, k_pages, bt, lengths, v_dim=vdim,
                          interpret=True)
    want = ref.paged_attention_ref(q, k_pages, k_pages[..., :vdim], bt,
                                   lengths)
    assert out.shape == (b, h, vdim)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want), atol=2e-5)


def test_paged_attention_int8_dequant_in_kernel():
    """int8 pages + bf16 scale slabs: kernel dequantizes after the page
    DMA and stays within quantisation error of an unquantized pool."""
    from repro.models.attention import (init_paged_kv_cache,
                                        paged_cache_prefill)
    b, h, k, hd, ps, m = 2, 4, 2, 16, 4, 3
    pages = 1 + b * m
    kk = jax.random.normal(jax.random.fold_in(KEY, 1), (b, m * ps, k, hd))
    vv = jax.random.normal(jax.random.fold_in(KEY, 2), (b, m * ps, k, hd))
    q = jax.random.normal(jax.random.fold_in(KEY, 3), (b, h, hd))
    bt = jnp.asarray(np.arange(1, pages).reshape(b, m), jnp.int32)
    lengths = jnp.asarray([m * ps, 2 * ps - 1], jnp.int32)
    outs = {}
    for dt in (jnp.float32, jnp.int8):
        cache = init_paged_kv_cache(pages, ps, k, hd, dtype=dt)
        cache = paged_cache_prefill(cache, kk, vv, bt, start=0)
        outs[dt] = paged_attention(
            q, cache["k"], cache["v"], bt, lengths,
            k_scales=cache.get("k_scale"), v_scales=cache.get("v_scale"),
            interpret=True)
    np.testing.assert_allclose(np.asarray(outs[jnp.int8], np.float32),
                               np.asarray(outs[jnp.float32], np.float32),
                               atol=0.06)


def _quantize_pages(pages):
    """Per-(slot, head) symmetric int8 + bf16 scales, like the pool's:
    pages (P, K, ps, hd) -> scales (P, K, 1, ps)."""
    sc = np.abs(np.asarray(pages)).max(axis=-1) / 127.0 + 1e-8
    qp = np.clip(np.round(np.asarray(pages) / sc[..., None]), -127, 127)
    return (jnp.asarray(qp, jnp.int8),
            jnp.asarray(sc[:, :, None, :], jnp.bfloat16))


_GROUP_VARIANTS = {
    "full": {},
    "window": {"window": 9},
    "chunked": {"chunk": 16},
    "mla_vdim": {"v_dim": 8},
}


@pytest.mark.parametrize("qtag", ["bf16", "int8"])
@pytest.mark.parametrize("variant", sorted(_GROUP_VARIANTS))
@pytest.mark.parametrize("g", [1, 2, 4, 8])
def test_paged_grouped_token_identical_to_per_head(g, variant, qtag):
    """The GQA re-grid is a pure traffic optimisation: for every group
    size x mask variant x page dtype, the grouped kernel's output is
    TOKEN-IDENTICAL (bitwise) to the per-head baseline grid on a
    mixed-length batch, and its analytic HBM bytes are exactly 1/g."""
    b, h, hd, ps, m = 3, 8, 16, 8, 4
    kk = h // g
    pages = 1 + b * m
    kw = dict(_GROUP_VARIANTS[variant])
    kq, kp, kv = jax.random.split(jax.random.fold_in(KEY, g), 3)
    q = jax.random.normal(kq, (b, h, hd), jnp.bfloat16)
    k_pages = jax.random.normal(kp, (pages, kk, ps, hd), jnp.bfloat16)
    v_pages = (k_pages if variant == "mla_vdim"
               else jax.random.normal(kv, (pages, kk, ps, hd), jnp.bfloat16))
    ks = vs = None
    if qtag == "int8":
        k_pages, ks = _quantize_pages(k_pages)
        v_pages, vs = (k_pages, ks) if variant == "mla_vdim" \
            else _quantize_pages(v_pages)
    bt = jnp.asarray(np.arange(1, pages).reshape(b, m), jnp.int32)
    lengths = jnp.asarray([5, 17, 32], jnp.int32)     # mixed-length batch
    outs = {}
    for grouped in (True, False):
        outs[grouped] = paged_attention(
            q, k_pages, v_pages, bt, lengths, k_scales=ks, v_scales=vs,
            grouped=grouped, interpret=True, **kw)
    assert np.array_equal(np.asarray(outs[True], np.float32),
                          np.asarray(outs[False], np.float32))
    from repro.kernels.paged_attention import decode_hbm_bytes
    by = {gr: decode_hbm_bytes(k_pages, v_pages, bt, lengths, num_q_heads=h,
                               grouped=gr, window=kw.get("window"),
                               chunk=kw.get("chunk"), v_dim=kw.get("v_dim"))
          for gr in (True, False)}
    assert by[True] * g == by[False]


def test_paged_zero_length_rows_are_exact_zeros():
    """A freshly admitted row can reach the kernel with length 0 (no
    visible tokens): every page is skipped, and _finalize must emit
    exact zeros instead of 0/eps garbage — in kernel AND oracle."""
    b, h, kk, hd, ps, m = 3, 4, 2, 16, 4, 3
    pages = 1 + b * m
    kq, kp = jax.random.split(KEY)
    q = jax.random.normal(kq, (b, h, hd))
    k_pages = jax.random.normal(kp, (pages, kk, ps, hd))
    bt = jnp.asarray(np.arange(1, pages).reshape(b, m), jnp.int32)
    lengths = jnp.asarray([0, 7, 0], jnp.int32)
    for grouped in (True, False):
        out = np.asarray(paged_attention(q, k_pages, k_pages, bt, lengths,
                                         grouped=grouped, interpret=True))
        assert np.all(np.isfinite(out))
        np.testing.assert_array_equal(out[0], 0.0)
        np.testing.assert_array_equal(out[2], 0.0)
        assert np.abs(out[1]).max() > 0
    want = np.asarray(ref.paged_attention_ref(q, k_pages, k_pages, bt,
                                              lengths))
    assert np.all(np.isfinite(want))
    np.testing.assert_array_equal(want[[0, 2]], 0.0)


def test_paged_combined_prefetch_matches_separate_operands():
    """decode_prefetch packs (bt, lengths) into one (B, M+1) operand;
    the kernel must read identical liveness from either encoding."""
    from repro.kernels.paged_attention import decode_prefetch
    b, h, kk, hd, ps, m = 2, 8, 2, 16, 8, 4
    pages = 1 + b * m
    kq, kp, kv = jax.random.split(KEY, 3)
    q = jax.random.normal(kq, (b, h, hd))
    k_pages = jax.random.normal(kp, (pages, kk, ps, hd))
    v_pages = jax.random.normal(kv, (pages, kk, ps, hd))
    bt = jnp.asarray(np.arange(1, pages).reshape(b, m), jnp.int32)
    lengths = jnp.asarray([13, 32], jnp.int32)
    pf = decode_prefetch(bt, lengths)
    assert pf.shape == (b, m + 1) and pf.dtype == jnp.int32
    for kw in ({}, {"window": 9}, {"chunk": 16}):
        sep = paged_attention(q, k_pages, v_pages, bt, lengths,
                              interpret=True, **kw)
        comb = paged_attention(q, k_pages, v_pages, bt, lengths,
                               prefetch=pf, interpret=True, **kw)
        assert np.array_equal(np.asarray(sep), np.asarray(comb))


def test_decode_hbm_bytes_accounting():
    """The analytic byte counter mirrors the grid: full-length rows pay
    all pages, masks drop dead pages, int8 pays quantized width + scale
    slabs, and grouped/per-head differ by exactly g."""
    from repro.kernels.paged_attention import decode_hbm_bytes
    ps, kk, hd, m = 8, 2, 16, 4
    h = 8
    k_pages = jnp.zeros((9, kk, ps, hd), jnp.float32)
    bt = np.arange(1, 9).reshape(2, m)
    full = decode_hbm_bytes(k_pages, k_pages, bt, [32, 32], num_q_heads=h)
    # 2 rows x 4 live pages x 2 kv heads x (ps*hd*4 k + ps*hd*4 v)
    assert full == 2 * 4 * kk * (ps * hd * 4 * 2)
    short = decode_hbm_bytes(k_pages, k_pages, bt, [32, 1], num_q_heads=h)
    assert short == full // 8 * 5            # row 1 touches 1 of 4 pages
    win = decode_hbm_bytes(k_pages, k_pages, bt, [32, 32], num_q_heads=h,
                           window=4)
    assert win < full                        # only the trailing page lives
    per_head = decode_hbm_bytes(k_pages, k_pages, bt, [32, 32],
                                num_q_heads=h, grouped=False)
    assert per_head == full * (h // kk)
    q8 = jnp.zeros((9, kk, ps, hd), jnp.int8)
    quant = decode_hbm_bytes(q8, q8, bt, [32, 32], num_q_heads=h)
    assert quant == 2 * 4 * kk * (ps * hd * 1 * 2 + 2 * ps * 2)
    vd = decode_hbm_bytes(k_pages, k_pages, bt, [32, 32], num_q_heads=h,
                          v_dim=hd // 2)
    assert vd == 2 * 4 * kk * (ps * hd * 4 + ps * (hd // 2) * 4)


@pytest.mark.parametrize("b,s,d,n,chunk,bd", [
    (2, 128, 64, 16, 64, 32),
    (1, 256, 128, 8, 128, 128),
    (2, 64, 32, 4, 32, 32),
])
def test_selective_scan_sweep(b, s, d, n, chunk, bd):
    ks = jax.random.split(KEY, 5)
    x = jax.random.normal(ks[0], (b, s, d))
    dt = jax.nn.softplus(jax.random.normal(ks[1], (b, s, d)))
    bm = jax.random.normal(ks[2], (b, s, n))
    cm = jax.random.normal(ks[3], (b, s, n))
    am = -jnp.exp(jax.random.normal(ks[4], (d, n)) * 0.5)
    dv = jnp.ones((d,))
    y = selective_scan(x, dt, bm, cm, am, dv, chunk=chunk, block_d=bd,
                       interpret=True)
    want, _ = ref.selective_scan_ref(x, dt, bm, cm, am, dv)
    np.testing.assert_allclose(np.asarray(y), np.asarray(want),
                               rtol=2e-5, atol=2e-5)


def test_selective_scan_matches_decode_chain():
    """Chunked kernel == running the per-token recurrence sequentially."""
    b, s, d, n = 1, 32, 16, 4
    ks = jax.random.split(KEY, 5)
    x = jax.random.normal(ks[0], (b, s, d))
    dt = jax.nn.softplus(jax.random.normal(ks[1], (b, s, d)))
    bm = jax.random.normal(ks[2], (b, s, n))
    cm = jax.random.normal(ks[3], (b, s, n))
    am = -jnp.exp(jax.random.normal(ks[4], (d, n)) * 0.5)
    dv = jnp.zeros((d,))
    y = selective_scan(x, dt, bm, cm, am, dv, chunk=8, block_d=16,
                       interpret=True)
    h = jnp.zeros((b, d, n))
    outs = []
    for t in range(s):
        decay = jnp.exp(dt[:, t, :, None] * am[None])
        h = decay * h + (dt[:, t] * x[:, t])[:, :, None] * bm[:, t, None, :]
        outs.append(jnp.einsum("bdn,bn->bd", h, cm[:, t]))
    want = jnp.stack(outs, 1)
    np.testing.assert_allclose(np.asarray(y), np.asarray(want), atol=1e-5)


@pytest.mark.parametrize("b,m,n", [(10, 64, 6), (300, 32, 2), (7, 128, 16)])
def test_mux_score_sweep(b, m, n):
    meta = jax.random.normal(KEY, (b, m))
    v = jax.random.normal(KEY, (n, m))
    c = jnp.arange(1.0, n + 1)
    w = mux_score(meta, v, c, interpret=True, block_b=64)
    want = ref.mux_score_ref(meta, v, c)
    np.testing.assert_allclose(np.asarray(w), np.asarray(want), atol=1e-6)
    np.testing.assert_allclose(np.asarray(w.sum(-1)), 1.0, atol=1e-5)
