"""Per-kernel shape/dtype sweeps: Pallas (interpret=True) vs ref.py oracle."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ref
from repro.kernels.flash_attention import flash_attention
from repro.kernels.mux_score import mux_score
from repro.kernels.paged_attention import (decode_prefetch,
                                           paged_attention)
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


#: every paged kernel test reads layer LAYER of a LAYERS-deep stacked
#: pool, the only pool form the kernel takes; the oracle reads
#: ``stacked[LAYER]``
LAYERS, LAYER = 2, 1


def _paged(q, k_pages, v_pages, bt, lengths, layer=LAYER, **kw):
    """The kernel (interpret mode) on a stacked pool, block table and
    lengths packed into the one prefetch operand."""
    return paged_attention(q, k_pages, v_pages, decode_prefetch(bt, lengths),
                           jnp.asarray(layer, jnp.int32), interpret=True,
                           **kw)


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
    k_pages = jax.random.normal(kk, (LAYERS, pages, k, ps, hd))
    v_pages = jax.random.normal(kv, (LAYERS, pages, k, ps, vd))
    # each row gets m distinct pages drawn from 1..pages-1 (0 = scratch)
    perm = np.stack([np.random.RandomState(i).permutation(pages - 1)[:m] + 1
                     for i in range(b)])
    bt = jnp.asarray(perm, jnp.int32)
    lengths = jnp.asarray(
        np.random.RandomState(7).randint(1, m * ps + 1, size=(b,)), jnp.int32)
    out = _paged(q, k_pages, v_pages, bt, lengths, window=window,
                 chunk=chunk, logit_cap=cap)
    want = ref.paged_attention_ref(q, k_pages[LAYER], v_pages[LAYER], bt,
                                   lengths, window=window, chunk=chunk,
                                   logit_cap=cap)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want), atol=2e-5)


def test_paged_attention_v_dim_is_k_slice():
    """v_dim reads v as the leading features of the k slab — the
    absorbed-MLA latent layout (v = c_kv slice, one DMA per page)."""
    b, h, hd, ps, m, pages, vdim = 2, 4, 24, 4, 3, 8, 16
    kq, kk = jax.random.split(KEY)
    q = jax.random.normal(kq, (b, h, hd))
    k_pages = jax.random.normal(kk, (LAYERS, pages, 1, ps, hd))  # MQA latent
    bt = jnp.asarray([[1, 2, 3], [4, 5, 6]], jnp.int32)
    lengths = jnp.asarray([m * ps, 5], jnp.int32)
    out = _paged(q, k_pages, k_pages, bt, lengths, v_dim=vdim)
    want = ref.paged_attention_ref(q, k_pages[LAYER],
                                   k_pages[LAYER, ..., :vdim], bt, lengths)
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
        cache = jax.tree.map(lambda x: jnp.stack([x] * LAYERS), cache)
        cache = paged_cache_prefill(cache, kk, vv, bt, start=0, layer=LAYER)
        outs[dt] = _paged(q, cache["k"], cache["v"], bt, lengths,
                          k_scales=cache.get("k_scale"),
                          v_scales=cache.get("v_scale"))
    np.testing.assert_allclose(np.asarray(outs[jnp.int8], np.float32),
                               np.asarray(outs[jnp.float32], np.float32),
                               atol=0.06)


def _quantize_pages(pages):
    """Per-(slot, head) symmetric int8 + bf16 scales, like the pool's:
    pages (L, P, K, ps, hd) -> scales (L, P, K, 1, ps)."""
    sc = np.abs(np.asarray(pages, np.float32)).max(axis=-1) / 127.0 + 1e-8
    qp = np.clip(np.round(np.asarray(pages, np.float32) / sc[..., None]),
                 -127, 127)
    return (jnp.asarray(qp, jnp.int8),
            jnp.asarray(sc[..., None, :], jnp.bfloat16))


def _dequant(x, sc):
    """int8 pages times their (..., 1, ps) scale rows, in float32."""
    return x.astype(jnp.float32) * jnp.swapaxes(sc, -1, -2).astype(jnp.float32)


@pytest.mark.parametrize("layer", [0, 2])
@pytest.mark.parametrize("variant", ["bf16", "int8", "mla_vdim"])
def test_paged_attention_stacked_pool_reads_layer(variant, layer):
    """A stacked (L, P, K, ps, hd) pool read at ``layer`` (a traced
    scalar-prefetch operand) attends that layer's pages alone: the
    oracle on pages[layer], for bf16 pages, int8 pages with their scale
    slabs, and MLA's v_dim slice."""
    n_layers, b, h, kk, hd, ps, m = 3, 3, 4, 2, 16, 8, 4
    v_dim = None
    if variant == "mla_vdim":
        kk, hd, v_dim = 1, 24, 16
    pages = 1 + b * m
    kq, kp, kv = jax.random.split(jax.random.fold_in(KEY, layer), 3)
    q = jax.random.normal(kq, (b, h, hd))
    k_pages = jax.random.normal(kp, (n_layers, pages, kk, ps, hd))
    v_pages = (k_pages if variant == "mla_vdim"
               else jax.random.normal(kv, (n_layers, pages, kk, ps, hd)))
    ks = vs = None
    k_want, v_want = k_pages[layer], v_pages[layer]
    if variant == "int8":
        k_pages, ks = _quantize_pages(k_pages)
        v_pages, vs = _quantize_pages(v_pages)
        k_want = _dequant(k_pages[layer], ks[layer])
        v_want = _dequant(v_pages[layer], vs[layer])
    if v_dim is not None:
        v_want = v_want[..., :v_dim]
    perm = np.stack([np.random.RandomState(i).permutation(pages - 1)[:m] + 1
                     for i in range(b)])
    bt = jnp.asarray(perm, jnp.int32)
    lengths = jnp.asarray([5, 17, 32], jnp.int32)
    want = ref.paged_attention_ref(q, k_want, v_want, bt, lengths)
    out = _paged(q, k_pages, v_pages, bt, lengths, layer=layer, k_scales=ks,
                 v_scales=vs, v_dim=v_dim)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want), atol=2e-5)


_GROUP_VARIANTS = {
    "full": {},
    "window": {"window": 9},
    "chunked": {"chunk": 16},
    "mla_vdim": {"v_dim": 8},
}


@pytest.mark.parametrize("qtag", ["bf16", "int8"])
@pytest.mark.parametrize("variant", sorted(_GROUP_VARIANTS))
@pytest.mark.parametrize("g", [1, 2, 4, 8])
def test_paged_attention_group_sizes_match_oracle(g, variant, qtag):
    """For every GQA group size x mask variant x page dtype, the
    kernel's one (row, page) grid, which runs all K kv heads and their
    g query heads in one step, matches the gather oracle on a
    mixed-length batch."""
    b, h, hd, ps, m = 3, 8, 16, 8, 4
    kk = h // g
    pages = 1 + b * m
    kw = dict(_GROUP_VARIANTS[variant])
    kq, kp, kv = jax.random.split(jax.random.fold_in(KEY, g), 3)
    q = jax.random.normal(kq, (b, h, hd))
    shape = (LAYERS, pages, kk, ps, hd)
    k_pages = jax.random.normal(kp, shape, jnp.bfloat16)
    v_pages = (k_pages if variant == "mla_vdim"
               else jax.random.normal(kv, shape, jnp.bfloat16))
    ks = vs = None
    k_want, v_want = k_pages[LAYER], v_pages[LAYER]
    if qtag == "int8":
        k_pages, ks = _quantize_pages(k_pages)
        v_pages, vs = (k_pages, ks) if variant == "mla_vdim" \
            else _quantize_pages(v_pages)
        k_want = _dequant(k_pages[LAYER], ks[LAYER])
        v_want = _dequant(v_pages[LAYER], vs[LAYER])
    if "v_dim" in kw:
        v_want = v_want[..., :kw["v_dim"]]
    bt = jnp.asarray(np.arange(1, pages).reshape(b, m), jnp.int32)
    lengths = jnp.asarray([5, 17, 32], jnp.int32)     # mixed-length batch
    out = _paged(q, k_pages, v_pages, bt, lengths, k_scales=ks, v_scales=vs,
                 **kw)
    want = ref.paged_attention_ref(
        q, k_want.astype(jnp.float32), v_want.astype(jnp.float32), bt,
        lengths, window=kw.get("window"), chunk=kw.get("chunk"))
    np.testing.assert_allclose(np.asarray(out), np.asarray(want), atol=2e-5)


def test_paged_zero_length_rows_are_exact_zeros():
    """A freshly admitted row can reach the kernel with length 0 (no
    visible tokens): every page is skipped, and _finalize must emit
    exact zeros instead of 0/eps garbage — in kernel AND oracle."""
    b, h, kk, hd, ps, m = 3, 4, 2, 16, 4, 3
    pages = 1 + b * m
    kq, kp = jax.random.split(KEY)
    q = jax.random.normal(kq, (b, h, hd))
    k_pages = jax.random.normal(kp, (LAYERS, pages, kk, ps, hd))
    bt = jnp.asarray(np.arange(1, pages).reshape(b, m), jnp.int32)
    lengths = jnp.asarray([0, 7, 0], jnp.int32)
    out = np.asarray(_paged(q, k_pages, k_pages, bt, lengths))
    assert np.all(np.isfinite(out))
    np.testing.assert_array_equal(out[0], 0.0)
    np.testing.assert_array_equal(out[2], 0.0)
    assert np.abs(out[1]).max() > 0
    want = np.asarray(ref.paged_attention_ref(q, k_pages[LAYER],
                                              k_pages[LAYER], bt, lengths))
    assert np.all(np.isfinite(want))
    np.testing.assert_array_equal(want[[0, 2]], 0.0)


@pytest.mark.parametrize("variant", ["full", "window"])
@pytest.mark.parametrize("g", [1, 4])
def test_paged_dead_pages_never_reach_the_output(g, variant):
    """Block-table entries outside a row's live span (past its length,
    before its window, every entry of a length-0 row) name pages filled
    with NaN: the output is finite and bitwise equal to the same call
    with those entries naming a page of zeros."""
    b, kk, hd, ps, m = 4, 2, 16, 8, 5
    h = kk * g
    kw = _GROUP_VARIANTS[variant]
    window = kw.get("window")
    lengths = np.asarray([0, 5, 23, 30], np.int32)
    nan_page = 1 + b * m
    kq, kp, kv = jax.random.split(jax.random.fold_in(KEY, g), 3)
    q = jax.random.normal(kq, (b, h, hd), jnp.bfloat16)
    # page 0 holds zeros, the last page NaN, the rest random
    shape = (LAYERS, nan_page + 1, kk, ps, hd)
    k_pages = jax.random.normal(kp, shape, jnp.bfloat16)
    v_pages = jax.random.normal(kv, shape, jnp.bfloat16)
    k_pages = k_pages.at[:, 0].set(0).at[:, -1].set(jnp.nan)
    v_pages = v_pages.at[:, 0].set(0).at[:, -1].set(jnp.nan)
    bt = np.arange(1, nan_page).reshape(b, m)
    dead = np.zeros((b, m), bool)
    for r, length in enumerate(lengths.tolist()):
        for i in range(m):
            live = i * ps < length
            if window is not None:
                live &= i * ps + ps - 1 > length - 1 - window
            dead[r, i] = not live
    assert dead[0].all() and dead.any(axis=1).all()
    outs = {}
    for fill in (0, nan_page):
        tab = jnp.asarray(np.where(dead, fill, bt), jnp.int32)
        outs[fill] = np.asarray(_paged(q, k_pages, v_pages, tab,
                                       jnp.asarray(lengths), **kw),
                                np.float32)
    assert np.all(np.isfinite(outs[nan_page]))
    assert np.array_equal(outs[nan_page], outs[0])
    np.testing.assert_array_equal(outs[0][0], 0.0)


@pytest.mark.parametrize("variant", sorted(_GROUP_VARIANTS))
def test_paged_live_span_is_exactly_the_live_pages(variant):
    """The K/V index maps clamp each page step into [first, last] of
    ``_live_span``: for every length, that span holds exactly the pages
    the kernel's liveness test keeps, so each live page is fetched once
    and a dead step repeats its neighbour's block index."""
    from repro.kernels.paged_attention import _live_span
    ps, m = 8, 6
    window, chunk = (_GROUP_VARIANTS[variant].get(k) for k in ("window",
                                                              "chunk"))
    for length in range(m * ps + 1):
        first, last = (int(x) for x in _live_span(
            jnp.int32(length), page_size=ps, window=window, chunk=chunk,
            last_step=m - 1))
        q_pos = length - 1
        live = [i for i in range(m)
                if i * ps < length
                and (window is None or i * ps + ps - 1 > q_pos - window)
                and (chunk is None or i * ps + ps - 1 >= (q_pos // chunk)
                     * chunk)]
        if live:
            assert live == list(range(first, last + 1)), length
        else:
            assert (first, last) == (0, 0), length


@pytest.mark.parametrize("kv_dtype", ["bfloat16", "int8"])
def test_paged_decode_attention_kernel_builds_its_prefetch(monkeypatch,
                                                           kv_dtype):
    """``paged_decode_attention`` called with no prefetch operand builds
    it itself when it takes the kernel path, and matches its own jnp
    gather path on the same stacked pool, for bf16 and int8 pages."""
    from repro.kernels import ops as kops
    from repro.models import attention as attn
    b, h, kk, hd, ps, m = 3, 4, 2, 16, 4, 3
    pages = 1 + b * m
    ks = jax.random.split(jax.random.fold_in(KEY, 5), 3)
    cache = attn.init_paged_kv_cache(pages, ps, kk, hd, dtype=kv_dtype)
    cache = jax.tree.map(lambda x: jnp.stack([x] * LAYERS), cache)
    bt = jnp.asarray(np.arange(1, pages).reshape(b, m), jnp.int32)
    cache = attn.paged_cache_prefill(
        cache, jax.random.normal(ks[0], (b, m * ps, kk, hd)),
        jax.random.normal(ks[1], (b, m * ps, kk, hd)), bt, start=0,
        layer=LAYER)
    q = jax.random.normal(ks[2], (b, 1, h, hd))
    pos = jnp.asarray([m * ps - 1, 5, 0], jnp.int32)
    want = attn.paged_decode_attention(q, cache, bt, pos, layer=LAYER)
    monkeypatch.setattr(kops, "_FORCE", True)
    out = attn.paged_decode_attention(q, cache, bt, pos, layer=LAYER)
    assert out.shape == want.shape == (b, 1, h, hd)
    # the gather path dequantizes int8 pages into bf16 before its
    # matmuls; the kernel applies the scales in f32 after them
    tol = 2e-5 if kv_dtype == "bfloat16" else 2e-2
    np.testing.assert_allclose(np.asarray(out), np.asarray(want), atol=tol)


def test_mla_decode_latent_pages_through_kernel(monkeypatch):
    """Absorbed-MLA decode over a paged latent pool: the kernel path
    (v the latent's leading kv_lora features, prefetch built inside
    ``mla_decode``) matches the gather path, output and written pool."""
    from repro.kernels import ops as kops
    from repro.models import attention as attn
    from repro.models import mla
    b, d_model, h, kv_lora, d_nope, d_rope, ps, m = 3, 32, 2, 16, 8, 8, 4, 3
    dims = dict(num_heads=h, kv_lora=kv_lora, d_nope=d_nope, d_rope=d_rope,
                v_head_dim=8, rope_theta=10000.0)
    pages = 1 + b * m
    ks = jax.random.split(jax.random.fold_in(KEY, 6), 3)
    params = mla.init_mla(ks[0], d_model=d_model, q_lora=16,
                          **{k: v for k, v in dims.items()
                             if k != "rope_theta"})
    cache = attn.init_paged_kv_cache(pages, ps, 1, kv_lora + d_rope,
                                     v_head_dim=1, dtype=jnp.float32)
    cache = jax.tree.map(lambda x: jnp.stack([x] * LAYERS), cache)
    bt = jnp.asarray(np.arange(1, pages).reshape(b, m), jnp.int32)
    lat = jax.random.normal(ks[1], (b, m * ps, 1, kv_lora + d_rope))
    cache = attn.paged_cache_prefill(cache, lat, lat[..., :1], bt, start=0,
                                     layer=LAYER)
    x = jax.random.normal(ks[2], (b, 1, d_model))
    pos = jnp.asarray([m * ps - 1, 6, 0], jnp.int32)
    want, want_cache = mla.mla_decode(params, x, cache, pos, block_tables=bt,
                                      layer=LAYER, **dims)
    monkeypatch.setattr(kops, "_FORCE", True)
    out, out_cache = mla.mla_decode(params, x, cache, pos, block_tables=bt,
                                    layer=LAYER, **dims)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want), atol=2e-5)
    np.testing.assert_array_equal(np.asarray(out_cache["k"]),
                                  np.asarray(want_cache["k"]))


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
