"""Paged KV cache: pool accounting, paged<->ring decode parity (per
step, across full/window/chunked/GQA/MLA), page reclaim, int8 pages,
and the token-level continuous-decode scheduler."""
import asyncio

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import LayerSpec, ModelConfig
from repro.models import transformer as tf
from repro.serving.engine import Engine, ServeConfig
from repro.serving.kv_cache import OutOfPages, PagePool
from repro.serving.scheduler import PagedLLMConfig, PagedLLMScheduler


def tiny_config(variant: str, kv_cache_dtype: str = "float32") -> ModelConfig:
    kw = dict(name=f"tiny-{variant}", arch_type="dense", num_layers=2,
              d_model=32, d_ff=64, vocab_size=64, num_heads=4,
              num_kv_heads=2, head_dim=8, compute_dtype="float32",
              param_dtype="float32", kv_cache_dtype=kv_cache_dtype)
    if variant == "full":
        kw["pattern"] = (LayerSpec(attn_kind="full"),)
    elif variant == "swa":
        kw["pattern"] = (LayerSpec(attn_kind="swa"),)
        kw["window"] = 6
    elif variant == "chunked":
        kw["pattern"] = (LayerSpec(attn_kind="chunked"),)
        kw["chunk"] = 5
    elif variant == "gqa_mixed":
        kw["pattern"] = (LayerSpec(attn_kind="full"),
                         LayerSpec(attn_kind="swa"))
        kw["window"] = 6
        kw["num_kv_heads"] = 1          # MQA
    elif variant == "mla":
        kw["pattern"] = (LayerSpec(mixer="mla"),)
        kw.update(num_heads=2, q_lora=16, kv_lora=8, d_nope=8, d_rope=4,
                  v_head_dim=8)
    else:
        raise ValueError(variant)
    return ModelConfig(**kw)


# ---------------------------------------------------------------------------
# PagePool
# ---------------------------------------------------------------------------

def test_page_pool_accounting():
    pool = PagePool(num_pages=6, page_size=4)
    assert pool.num_free == 5            # page 0 is scratch
    a = pool.alloc(2)
    b = pool.alloc(2)
    assert not set(a) & set(b) and 0 not in a + b
    assert pool.pages_in_use == 4 and pool.peak_in_use == 4
    pool.free(a)
    assert pool.num_free == 3
    c = pool.alloc(3)                    # reuses a's pages
    assert set(a) <= set(c)
    assert pool.peak_in_use == 5
    with pytest.raises(OutOfPages):
        pool.alloc(1)
    pool.free(b)
    pool.free(c)
    assert pool.pages_in_use == 0 and pool.num_free == 5
    with pytest.raises(ValueError):
        pool.free(b)                     # double free
    d = pool.alloc(1)
    with pytest.raises(ValueError):
        pool.free(d + [99])              # foreign page: nothing mutates ...
    assert pool.pages_in_use == 1        # ... so d stays held
    with pytest.raises(ValueError):
        pool.free([d[0], d[0]])          # duplicate ids in one call
    assert pool.pages_in_use == 1
    pool.free(d)
    assert pool.pages_for(1) == 1 and pool.pages_for(4) == 1
    assert pool.pages_for(5) == 2
    row = pool.block_table([3, 1], max_pages=4)
    np.testing.assert_array_equal(row, [3, 1, 0, 0])


def test_page_pool_refcounts():
    """Refcount semantics under sharing: incref'd pages survive decref
    by one holder, return to the free list only at zero, and the COW
    headroom tracks writable shared pages (see test_pool_property.py
    for the randomized harness over the same invariants)."""
    pool = PagePool(num_pages=8, page_size=4)
    a = pool.alloc(3)
    assert [pool.refcount(pg) for pg in a] == [1, 1, 1]
    pool.incref(a[:2])                       # a second holder maps 2 pages
    assert pool.refcount(a[0]) == 2
    assert pool.pages_in_use == 3            # unique pages, shared count once
    assert pool.shared_pages == 2
    pool.mark_cow_risk(a[1])
    assert pool.cow_headroom == 1
    pool.decref(a)                           # first holder retires
    assert pool.pages_in_use == 2 and pool.num_free == 5
    assert pool.cow_headroom == 0            # exclusive again: no copy due
    with pytest.raises(ValueError):
        pool.incref([a[2]])                  # free page cannot be increfed
    pool.decref(a[:2])                       # last holder retires
    assert pool.pages_in_use == 0 and pool.num_free == 7
    assert pool.refcount(a[0]) == 0


# ---------------------------------------------------------------------------
# Paged <-> ring numerical parity, per decode step
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("variant",
                         ["full", "swa", "chunked", "gqa_mixed", "mla"])
def test_paged_matches_ring_per_step(variant):
    cfg = tiny_config(variant)
    key = jax.random.key(3)
    params = tf.init_params(cfg, key)
    b, s, p, ps = 1, 18, 7, 4
    tokens = jax.random.randint(key, (b, s), 0, cfg.vocab_size)

    logits_r, ring = tf.prefill(params, cfg, tokens[:, :p], cache_len=s,
                                cache_dtype=jnp.float32)
    m = -(-s // ps)
    paged = tf.init_caches(cfg, 0, 0, jnp.float32, num_pages=m + 1,
                           page_size=ps)
    bt = jnp.arange(1, m + 1, dtype=jnp.int32)[None]
    logits_p, paged = tf.prefill_paged(params, cfg, tokens[:, :p], paged, bt,
                                       last_index=p - 1)
    np.testing.assert_allclose(np.asarray(logits_r), np.asarray(logits_p),
                               atol=2e-5)
    for i in range(p, s):
        logits_r, ring = tf.decode_step(params, cfg, tokens[:, i:i + 1],
                                        ring, i)
        logits_p, paged = tf.decode_step(params, cfg, tokens[:, i:i + 1],
                                         paged, jnp.asarray([i]),
                                         block_tables=bt)
        np.testing.assert_allclose(np.asarray(logits_r), np.asarray(logits_p),
                                   atol=3e-5, err_msg=f"{variant} pos={i}")


@pytest.mark.parametrize("variant", ["full", "mla"])
def test_stacked_pool_chunked_prefill_and_decode_match_ring(variant):
    """Three layers over one stacked pool, carried through the layer
    scan: a prompt prefilled in two chunks (the whole-prompt program,
    then the tail program) and decoded matches the ring path, and each
    decode step changes every layer's pool only at the (page, slot)
    rows of the step's token."""
    from repro.models import attention as attn
    cfg = tiny_config(variant).with_(num_layers=3)
    assert cfg.num_groups == 3
    key = jax.random.key(5)
    params = tf.init_params(cfg, key)
    b, s, c, p, ps = 1, 20, 6, 11, 4
    tokens = jax.random.randint(key, (b, s), 0, cfg.vocab_size)
    logits_r, ring = tf.prefill(params, cfg, tokens[:, :p], cache_len=s,
                                cache_dtype=jnp.float32)
    m = -(-s // ps)
    paged = tf.init_caches(cfg, 0, 0, jnp.float32, num_pages=m + 2,
                           page_size=ps)
    bt = jnp.asarray([[3, 1, 4, 2, 6]], jnp.int32)[:, :m]
    _, paged = tf.prefill_paged(params, cfg, tokens[:, :c], paged, bt,
                                last_index=c - 1)
    c_ = jnp.asarray(c, jnp.int32)
    logits_p, paged = tf.prefill_paged(params, cfg, tokens[:, c:p], paged,
                                       bt, last_index=p - c - 1,
                                       q_offset=c_, insert_from=c_)
    np.testing.assert_allclose(np.asarray(logits_r), np.asarray(logits_p),
                               atol=3e-5)
    for i in range(p, s):
        before = jax.tree.map(np.asarray, paged)
        logits_r, ring = tf.decode_step(params, cfg, tokens[:, i:i + 1],
                                        ring, i)
        logits_p, paged = tf.decode_step(params, cfg, tokens[:, i:i + 1],
                                         paged, jnp.asarray([i]),
                                         block_tables=bt)
        np.testing.assert_allclose(np.asarray(logits_r), np.asarray(logits_p),
                                   atol=3e-5, err_msg=f"{variant} pos={i}")
        page, slot = int(bt[0, i // ps]), i % ps
        for name, leaf in paged["p0"].items():
            now = np.asarray(leaf)                 # (G, P, K, ps, hd)
            changed = np.argwhere(now != before["p0"][name])
            assert len(changed), (name, i)
            assert set(map(tuple, changed[:, [1, 3]])) == {(page, slot)}
            assert set(changed[:, 0]) == {0, 1, 2}

    # one layer's write leaves the other layers bit-for-bit alone
    pool = paged["p0"]
    k_new, v_new = (jnp.ones((2, 1) + pool[n].shape[-3::2], jnp.float32)
                    for n in ("k", "v"))                # (B, 1, K, width)
    wrote = attn.paged_cache_insert(pool, k_new, v_new,
                                    jnp.asarray([[2], [5]]),
                                    jnp.asarray([1, 3]), layer=1)
    for name in pool:
        old, new = np.asarray(pool[name]), np.asarray(wrote[name])
        np.testing.assert_array_equal(old[[0, 2]], new[[0, 2]])
        rows = np.argwhere(old[1] != new[1])
        assert set(map(tuple, rows[:, [0, 2]])) <= {(2, 1), (5, 3)}
        np.testing.assert_array_equal(new[1, 2, :, 1], 1.0)
        np.testing.assert_array_equal(new[1, 5, :, 3], 1.0)


def test_mixed_length_batch_matches_solo():
    """Two requests at different positions decode in ONE paged batch;
    each row matches the same request decoded alone (token-level
    continuous batching is numerically per-row)."""
    cfg = tiny_config("full")
    params = tf.init_params(cfg, jax.random.key(0))
    eng = Engine(cfg, params, ServeConfig(max_len=48))
    pool = eng.init_paged(num_pages=30, page_size=4, decode_batch=4)
    key = jax.random.key(9)
    p1 = np.asarray(jax.random.randint(key, (6,), 0, cfg.vocab_size))
    p2 = np.asarray(jax.random.randint(jax.random.fold_in(key, 1), (13,), 0,
                                       cfg.vocab_size))
    ref1 = eng.generate_paged(p1, max_new_tokens=8)["tokens"]
    ref2 = eng.generate_paged(p2, max_new_tokens=6)["tokens"]

    s1 = eng.prefill_into_pages(p1, max_new_tokens=8)
    eng.decode_step_batch([s1])
    eng.decode_step_batch([s1])          # s1 is 2 tokens ahead ...
    s2 = eng.prefill_into_pages(p2, max_new_tokens=6)  # ... when s2 joins
    while not (s1.done and s2.done):
        eng.decode_step_batch([s for s in (s1, s2) if not s.done])
    eng.pool.free(s1.pages)
    eng.pool.free(s2.pages)
    np.testing.assert_array_equal(np.concatenate([p1, s1.tokens]), ref1)
    np.testing.assert_array_equal(np.concatenate([p2, s2.tokens]), ref2)
    assert pool.pages_in_use == 0


def test_page_reclaim_reuse_identical_output():
    """After a request finishes its pages are immediately reusable, and
    a follow-up request landing on the reclaimed (dirty) pages produces
    the exact same output as on a fresh pool."""
    cfg = tiny_config("full")
    params = tf.init_params(cfg, jax.random.key(0))
    eng = Engine(cfg, params, ServeConfig(max_len=32))
    # pool fits exactly one request: reuse is forced
    pool = eng.init_paged(num_pages=5, page_size=4, decode_batch=2)
    key = jax.random.key(2)
    pa = np.asarray(jax.random.randint(key, (9,), 0, cfg.vocab_size))
    pb = np.asarray(jax.random.randint(jax.random.fold_in(key, 1), (9,), 0,
                                       cfg.vocab_size))
    out_a = eng.generate_paged(pa, max_new_tokens=7)["tokens"]
    assert pool.pages_in_use == 0
    out_b = eng.generate_paged(pb, max_new_tokens=7)["tokens"]    # dirty pages
    out_a2 = eng.generate_paged(pa, max_new_tokens=7)["tokens"]   # dirtier
    np.testing.assert_array_equal(out_a, out_a2)
    assert not np.array_equal(out_a, out_b)   # actually different requests
    assert pool.pages_in_use == 0 and pool.peak_in_use == 4


@pytest.mark.parametrize("variant", ["full", "swa"])
def test_decode_prepare_counts_pages(variant):
    """``repro.decode.prepare`` carries the step's rows, the pages the
    rows map and, of those, the pages holding a token once the step
    inserts; with span reclaim the handed-back pages leave both."""
    from repro.serving.observability import Tracer
    cfg = tiny_config(variant)
    params = tf.init_params(cfg, jax.random.key(0))
    eng = Engine(cfg, params, ServeConfig(max_len=32))
    eng.init_paged(num_pages=20, page_size=4, decode_batch=2)
    eng.tracer = tracer = Tracer()
    seqs = [eng.prefill_into_pages(np.arange(n) % cfg.vocab_size,
                                   max_new_tokens=12) for n in (5, 9)]
    want = []
    for _ in range(10):
        mapped = sum(pg is not None for s in seqs for pg in s.pages)
        held = sum(s.pos // 4 + 1 - s.reclaimed_upto for s in seqs)
        want.append({"rows": 2, "pages_reserved": mapped,
                     "pages_written": held})
        eng.decode_step_batch(seqs)
    got = [ev[6] for ev in tracer.events()
           if ev[2] == "repro.decode.prepare"]
    assert got == want
    # whole spans are reserved up front: 5 + 12 and 9 + 12 tokens
    assert want[0] == {"rows": 2, "pages_reserved": 5 + 6,
                       "pages_written": 2 + 3}
    if variant == "swa":                # window 6: early pages go back
        assert seqs[1].reclaimed_upto > 0
    for s in seqs:
        eng.pool.release(s)


def test_paged_program_names_are_pinned():
    """The device trace names each paged program ``jit_<function>``;
    the benchmark's decode-step, prefill and roofline readers find the
    programs by these names, so a rename fails here instead of
    silencing those metrics."""
    import re
    cfg = tiny_config("full")
    params = tf.init_params(cfg, jax.random.key(0))
    eng = Engine(cfg, params, ServeConfig(max_len=32))
    eng.init_paged(num_pages=8, page_size=4, decode_batch=2)
    caches, i32 = eng._paged_caches, jnp.int32
    row, one = jnp.zeros((2,), i32), jnp.asarray(0, i32)
    bt = jnp.zeros((2, eng._max_pages), i32)
    tok = jnp.zeros((2, 1), i32)
    toks = jnp.zeros((1, 8), i32)
    lowered = {
        "paged_decode_fn": eng._paged_decode.lower(
            params, tok, caches, bt, row),
        "paged_decode_cow_fn": eng._paged_decode_cow.lower(
            params, tok, caches, bt, row, row, row),
        "paged_prefill_fn": eng._paged_prefill.lower(
            params, toks, caches, bt[:1], one),
        "paged_prefill_tail_fn": eng._paged_prefill_tail.lower(
            params, toks, caches, bt[:1], one, one, one),
    }
    for name, low in lowered.items():
        module = re.match(r"HloModule (\S+?),", low.compile().as_text())
        assert module.group(1) == f"jit_{name}"


def test_capacity_and_pool_errors():
    cfg = tiny_config("full")
    params = tf.init_params(cfg, jax.random.key(0))
    eng = Engine(cfg, params, ServeConfig(max_len=16))
    prompts = jnp.zeros((1, 10), jnp.int32)
    with pytest.raises(ValueError, match="max_len"):
        eng.generate(prompts, max_new_tokens=10)
    eng.init_paged(num_pages=4, page_size=4, decode_batch=2)
    with pytest.raises(ValueError, match="max_len"):
        eng.prefill_into_pages(np.zeros((10,), np.int32), max_new_tokens=10)
    with pytest.raises(OutOfPages, match="exhausted"):
        eng.prefill_into_pages(np.zeros((10,), np.int32), max_new_tokens=6)
    assert eng.pool.pages_in_use == 0    # failed admission leaked nothing
    with pytest.raises(ValueError, match="max_new_tokens"):
        eng.prefill_into_pages(np.zeros((4,), np.int32), max_new_tokens=0)


def test_int8_paged_pool():
    """kv_cache_dtype=int8 threads through the paged allocator: pages
    are stored quantized and decode stays within quantisation error of
    the float pool."""
    cfg8 = tiny_config("full", kv_cache_dtype="int8")
    params = tf.init_params(cfg8, jax.random.key(0))
    s, p, ps = 16, 6, 4
    m = -(-s // ps)
    tokens = jax.random.randint(jax.random.key(4), (1, s), 0, cfg8.vocab_size)
    bt = jnp.arange(1, m + 1, dtype=jnp.int32)[None]

    caches8 = tf.init_caches(cfg8, 0, 0, num_pages=m + 1, page_size=ps)
    leaf = caches8["p0"]["k"]
    assert leaf.dtype == jnp.int8
    assert "k_scale" in caches8["p0"]
    cachesf = tf.init_caches(cfg8, 0, 0, jnp.float32, num_pages=m + 1,
                             page_size=ps)
    l8, caches8 = tf.prefill_paged(params, cfg8, tokens[:, :p], caches8, bt,
                                   last_index=p - 1)
    lf, cachesf = tf.prefill_paged(params, cfg8, tokens[:, :p], cachesf, bt,
                                   last_index=p - 1)
    np.testing.assert_allclose(np.asarray(l8), np.asarray(lf), atol=0.15)
    for i in range(p, s):
        l8, caches8 = tf.decode_step(params, cfg8, tokens[:, i:i + 1],
                                     caches8, jnp.asarray([i]),
                                     block_tables=bt)
        lf, cachesf = tf.decode_step(params, cfg8, tokens[:, i:i + 1],
                                     cachesf, jnp.asarray([i]),
                                     block_tables=bt)
        np.testing.assert_allclose(np.asarray(l8), np.asarray(lf), atol=0.15,
                                   err_msg=f"pos={i}")


def test_sampled_generation_batch_independent():
    """temperature > 0: a request's sampled tokens are a function of
    (seed, prompt) alone — repeatable across calls and identical
    whether it decodes solo or continuously batched with others."""
    cfg = tiny_config("full")
    params = tf.init_params(cfg, jax.random.key(0))
    eng = Engine(cfg, params, ServeConfig(max_len=32, temperature=0.7))
    eng.init_paged(num_pages=16, page_size=4, decode_batch=2)
    pa = np.arange(5) % cfg.vocab_size
    pb = (np.arange(7) * 3) % cfg.vocab_size
    out_a = eng.generate_paged(pa, max_new_tokens=6)["tokens"]
    np.testing.assert_array_equal(
        out_a, eng.generate_paged(pa, max_new_tokens=6)["tokens"])
    out_b = eng.generate_paged(pb, max_new_tokens=6)["tokens"]
    s1 = eng.prefill_into_pages(pa, max_new_tokens=6)
    s2 = eng.prefill_into_pages(pb, max_new_tokens=6)
    while not (s1.done and s2.done):
        eng.decode_step_batch([s for s in (s1, s2) if not s.done])
    eng.pool.free(s1.pages)
    eng.pool.free(s2.pages)
    np.testing.assert_array_equal(np.concatenate([pa, s1.tokens]), out_a)
    np.testing.assert_array_equal(np.concatenate([pb, s2.tokens]), out_b)


def test_warmup_page_padded_length_at_max_len():
    """warmup must not trip the capacity check when a prompt length
    page-pads up to max_len (regression: pages_for(30)*8 == max_len)."""
    cfg = tiny_config("full")
    params = tf.init_params(cfg, jax.random.key(0))
    eng = Engine(cfg, params, ServeConfig(max_len=32))
    eng.init_paged(num_pages=20, page_size=8, decode_batch=2)
    sched = PagedLLMScheduler([eng], PagedLLMConfig(max_new_tokens=2))
    sched.warmup([30])
    assert eng.pool.pages_in_use == 0


def test_paged_rejects_mamba():
    cfg = ModelConfig(name="ssm", arch_type="ssm", num_layers=1, d_model=16,
                      d_ff=32, vocab_size=32,
                      pattern=(LayerSpec(mixer="mamba"),),
                      d_inner=32, ssm_state=4, dt_rank=4)
    with pytest.raises(NotImplementedError):
        tf.init_caches(cfg, 0, 0, num_pages=4, page_size=4)


# ---------------------------------------------------------------------------
# Token-level continuous-decode scheduler
# ---------------------------------------------------------------------------

def test_scheduler_continuous_decode_trace():
    """A staggered mixed-length trace through PagedLLMScheduler: every
    output matches the solo-decoded reference, at least one decode
    batch mixes requests admitted at different times, and the pool
    drains back to zero pages in use."""
    cfg = tiny_config("full")
    params = tf.init_params(cfg, jax.random.key(0))
    eng = Engine(cfg, params, ServeConfig(max_len=64))
    eng.init_paged(num_pages=40, page_size=4, decode_batch=4)
    key = jax.random.key(5)
    lens = [5, 11, 17, 8]
    prompts = [np.asarray(jax.random.randint(jax.random.fold_in(key, i),
                                             (l,), 0, cfg.vocab_size))
               for i, l in enumerate(lens)]
    refs = [eng.generate_paged(p, max_new_tokens=10)["tokens"]
            for p in prompts]

    async def main():
        sched = PagedLLMScheduler([eng], PagedLLMConfig(max_new_tokens=10))
        sched.warmup(lens)
        async with sched:
            handles = [sched.submit(prompts[0]),
                       sched.submit(prompts[1])]
            # let the first two get ahead so the later admissions join a
            # *running* decode batch
            while sched.decode_batches < 2:
                await asyncio.sleep(0.005)
            handles += [sched.submit(prompts[2]),
                        sched.submit(prompts[3])]
            outs = await asyncio.gather(*handles)
        return sched, outs

    sched, outs = asyncio.run(main())
    for out, ref in zip(outs, refs):
        np.testing.assert_array_equal(out, ref)
    snap = sched.snapshot()
    assert snap["completed"] == 4 and snap["failed"] == 0
    assert snap["mixed_admission_batches"] >= 1
    assert snap["pools"][0]["pages_in_use"] == 0
    assert 0 < snap["pools"][0]["peak_pages_in_use"] < \
        snap["pools"][0]["num_pages"]
    assert snap["tokens_generated"] >= 4 * 10 - 4   # first tokens from prefill


def test_stop_without_drain_reclaims_pages():
    """Cancelling a scheduler mid-generation must hand the stranded
    sequences' pages back to the pool — the engine outlives the
    scheduler and would otherwise serve with shrunken capacity."""
    cfg = tiny_config("full")
    params = tf.init_params(cfg, jax.random.key(0))
    eng = Engine(cfg, params, ServeConfig(max_len=64))
    eng.init_paged(num_pages=20, page_size=4, decode_batch=2)

    async def main():
        sched = PagedLLMScheduler([eng], PagedLLMConfig(max_new_tokens=40))
        await sched.start()
        handle = sched.submit(np.zeros((8,), np.int32))
        while sched.decode_batches < 1:     # request is mid-generation
            await asyncio.sleep(0.005)
        await sched.stop(drain=False)
        assert handle.done()
        return sched

    asyncio.run(main())
    assert eng.pool.pages_in_use == 0


def test_paged_lifecycle_restart_and_double_start():
    """SchedulerLifecycle regression on the token-level runtime:
    double start raises, a stopped scheduler rejects submissions, and
    the same instance restarts cleanly and serves again."""
    cfg = tiny_config("full")
    params = tf.init_params(cfg, jax.random.key(0))
    eng = Engine(cfg, params, ServeConfig(max_len=32))
    eng.init_paged(num_pages=12, page_size=4, decode_batch=2)
    prompt = np.arange(5, dtype=np.int32) % cfg.vocab_size
    ref = eng.generate_paged(prompt, max_new_tokens=4)["tokens"]

    async def main():
        sched = PagedLLMScheduler([eng], PagedLLMConfig(max_new_tokens=4))
        await sched.start()
        with pytest.raises(RuntimeError, match="already started"):
            await sched.start()
        out1 = await sched.submit(prompt)
        await sched.stop()
        await sched.stop()                   # idempotent
        with pytest.raises(RuntimeError, match="not running"):
            sched.submit_nowait(prompt)
        async with sched:                    # restart the same instance
            out2 = await sched.submit(prompt)
        return out1, out2

    out1, out2 = asyncio.run(main())
    np.testing.assert_array_equal(out1, ref)
    np.testing.assert_array_equal(out2, ref)
    assert eng.pool.pages_in_use == 0


def test_paged_lifecycle_drain_then_cancel_mid_decode():
    """drain() leaves nothing inflight; a later no-drain stop mid
    generation fails the stranded future AND returns its pages —
    cancel-mid-decode must not shrink the engine's pool."""
    cfg = tiny_config("full")
    params = tf.init_params(cfg, jax.random.key(0))
    eng = Engine(cfg, params, ServeConfig(max_len=64))
    eng.init_paged(num_pages=20, page_size=4, decode_batch=2)

    async def main():
        sched = PagedLLMScheduler([eng], PagedLLMConfig(max_new_tokens=30))
        await sched.start()
        fut1 = sched.submit(np.zeros(4, np.int32), max_new_tokens=2).future
        await sched.drain()
        assert fut1.done() and not fut1.cancelled()
        fut2 = sched.submit(np.zeros(8, np.int32)).future
        while sched.decode_batches < 2:      # provably mid-generation
            await asyncio.sleep(0.005)
        await sched.stop(drain=False)
        # the stranded future is resolved one way or the other —
        # cancelled by stop, or failed by the reclamation hook
        assert fut2.done()
        if not fut2.cancelled():
            with pytest.raises(RuntimeError, match="stopped before"):
                fut2.result()

    asyncio.run(main())
    assert eng.pool.pages_in_use == 0


def test_scheduler_backpressure_oversized_request():
    """A request larger than the whole pool fails fast; one that merely
    has to wait for pages completes once earlier requests retire."""
    cfg = tiny_config("full")
    params = tf.init_params(cfg, jax.random.key(0))
    eng = Engine(cfg, params, ServeConfig(max_len=32))
    eng.init_paged(num_pages=6, page_size=4, decode_batch=2)  # 20 tokens
    key = jax.random.key(6)
    small = [np.asarray(jax.random.randint(jax.random.fold_in(key, i),
                                           (6,), 0, cfg.vocab_size))
             for i in range(3)]
    refs = [eng.generate_paged(p, max_new_tokens=6)["tokens"] for p in small]

    async def main():
        sched = PagedLLMScheduler([eng], PagedLLMConfig(max_new_tokens=6))
        async with sched:
            # 3 x 12 tokens = 3 pages each; pool holds 5 -> the third
            # waits for reclaimed pages.  (submit_nowait here doubles
            # as the paged compat-shim pin.)
            handles = [sched.submit(p) for p in small]
            too_big = sched.submit_nowait(
                np.zeros((26,), np.int32), max_new_tokens=6)
            outs = await asyncio.gather(*handles)
            with pytest.raises(OutOfPages):
                await too_big
        return sched, outs

    sched, outs = asyncio.run(main())
    for out, ref in zip(outs, refs):
        np.testing.assert_array_equal(out, ref)
    assert sched.snapshot()["pools"][0]["pages_in_use"] == 0


class _FakeChunkBackend:
    """capacity()-only stand-in so the adaptive-chunk policy can be
    unit-tested without a device worker loop."""

    def capacity(self):
        from repro.serving.backend import BackendCapacity
        return BackendCapacity(decode_batch=4, page_size=4, num_pages=16,
                               free_pages=16)

    def bind_metrics(self, metrics, model_id):
        pass

    def bind_tracer(self, tracer):
        pass


def _policy_sched(tracer=None, **cfg_kw):
    cfg = PagedLLMConfig(prefill_chunk_pages=2, adaptive_chunk=True,
                         min_chunk_pages=1, max_chunk_pages=8,
                         chunk_slack=4.0, **cfg_kw)
    return PagedLLMScheduler(backends=[_FakeChunkBackend()], cfg=cfg,
                             clock=lambda: 0.0, tracer=tracer)


def _join(sched, deadline_t, max_new=10, generated=0):
    from types import SimpleNamespace
    from repro.serving.scheduler.request import Request, SamplingParams
    req = Request(rid=1, x=None, arrival_t=0.0, deadline_t=deadline_t,
                  params=SamplingParams(max_new_tokens=max_new))
    return sched.slots[0].join(req, SimpleNamespace(tokens=[0] * generated),
                               admit_step=0)


def test_adaptive_chunk_policy_slo_slack():
    """SLO-aware chunk sizing: idle backend -> ceiling; no inter-token
    evidence -> base; tight stream slack -> floor; generous -> ceiling;
    in-between -> base.  (base=2, lo=1, hi=8 pages; itl p50 = 10ms;
    thresholds at 4*base*itl = 80ms and 4*hi*itl = 320ms of slack.)"""
    sched = _policy_sched()
    assert sched._adaptive_chunk_pages(0) == 8       # nothing decoding
    ent = _join(sched, deadline_t=0.15)
    assert sched._adaptive_chunk_pages(0) == 2       # no itl evidence yet
    for _ in range(8):
        sched.metrics.itl_by_model[0].add(0.010)
    # slack = 0.15 - 10 remaining tokens * 10ms = 50ms < 80ms -> floor
    assert sched._adaptive_chunk_pages(0) == 1
    sched.slots[0].retire(ent)
    _join(sched, deadline_t=1.0)                     # slack 900ms -> ceiling
    assert sched._adaptive_chunk_pages(0) == 8
    ent3 = _join(sched, deadline_t=0.3)              # tightest rules: 200ms
    assert sched._adaptive_chunk_pages(0) == 2       # between -> base
    ent3.seq.tokens.extend([0] * 5)                  # 5 left: slack 250ms
    assert sched._adaptive_chunk_pages(0) == 2


def test_adaptive_chunk_policy_measured_stalls():
    """Once >=5 chunks have been measured, the policy sizes against the
    per-page stall distribution's p90 instead of the one-page-per-decode
    heuristic: largest of {lo, base, hi} whose chunk_slack-padded stall
    fits the tightest stream's slack.  (5ms/page p90, margin 4x ->
    hi=8 needs 160ms, base=2 needs 40ms, lo=1 needs 20ms of slack.)"""
    sched = _policy_sched()
    ent = _join(sched, deadline_t=0.3)
    for _ in range(8):
        sched.metrics.itl_by_model[0].add(0.010)
    # under 5 samples -> no evidence -> heuristic (slack 200ms: base)
    for _ in range(4):
        sched.metrics.on_chunk_stall(0, 2, 0.010)
    assert sched.metrics.chunk_stall_per_page(0) is None
    assert sched._adaptive_chunk_pages(0) == 2
    sched.metrics.on_chunk_stall(0, 2, 0.010)        # 5th sample
    assert sched.metrics.chunk_stall_per_page(0) == pytest.approx(0.005)
    # measured policy kicks in: slack 200ms >= 160ms -> ceiling
    assert sched._adaptive_chunk_pages(0) == 8
    sched.slots[0].retire(ent)
    _join(sched, deadline_t=0.2)                     # slack 100ms -> base
    assert sched._adaptive_chunk_pages(0) == 2
    _join(sched, deadline_t=0.13)                    # slack 30ms -> floor
    assert sched._adaptive_chunk_pages(0) == 1
    _join(sched, deadline_t=0.105)                   # slack 5ms: nothing
    assert sched._adaptive_chunk_pages(0) == 1       # fits -> still floor
    snap = sched.metrics.snapshot()
    assert snap["chunk_stall_page_p90_ms"][0] == pytest.approx(5.0)


def test_chunk_stall_measurement_guards():
    """Degenerate measurements never poison the policy: zero-page calls
    are dropped, and all-zero durations (fake clocks) leave the policy
    on the heuristic path rather than dividing slack by zero."""
    sched = _policy_sched()
    sched.metrics.on_chunk_stall(0, 0, 0.010)        # dropped
    assert len(sched.metrics.chunk_stall_page[0]) == 0
    for _ in range(6):
        sched.metrics.on_chunk_stall(0, 1, 0.0)
    assert sched.metrics.chunk_stall_per_page(0) == 0.0
    _join(sched, deadline_t=1.0)
    for _ in range(8):
        sched.metrics.itl_by_model[0].add(0.010)
    # per-page 0.0 -> measured branch skipped -> heuristic ceiling
    assert sched._adaptive_chunk_pages(0) == 8


def test_auto_chunk_bounds_follow_stall_distribution():
    """auto_chunk_bounds re-tunes the policy's (lo, hi) from the
    measured per-page stall distribution: a heavy tail (p90 > 2x p50)
    narrows to (1, base), a tight one (p90 within 25% of p50) widens
    to (base, hi), in-between keeps the config bounds — and before
    there is evidence the config bounds stand."""
    sched = _policy_sched(auto_chunk_bounds=True)
    assert sched._chunk_bounds(0) == (1, 8)          # no evidence yet
    for _ in range(8):                               # heavy tail: 20x ratio
        sched.metrics.on_chunk_stall(0, 1, 0.001)
    for _ in range(2):
        sched.metrics.on_chunk_stall(0, 1, 0.020)
    assert sched._chunk_bounds(0) == (1, 2)          # narrow to (1, base)
    # the narrowed ceiling binds the whole policy: idle -> hi -> base
    assert sched._adaptive_chunk_pages(0) == 2

    tight = _policy_sched(auto_chunk_bounds=True)
    for _ in range(10):                              # ratio 1.0
        tight.metrics.on_chunk_stall(0, 1, 0.010)
    assert tight._chunk_bounds(0) == (2, 8)          # widen to (base, hi)
    assert tight._adaptive_chunk_pages(0) == 8       # idle -> ceiling

    mid = _policy_sched(auto_chunk_bounds=True)
    for _ in range(8):                               # ratio 1.5: in-between
        mid.metrics.on_chunk_stall(0, 1, 0.010)
    for _ in range(2):
        mid.metrics.on_chunk_stall(0, 1, 0.015)
    assert mid._chunk_bounds(0) == (1, 8)            # config bounds stand
    # default (auto off) never consults the distribution at all
    fixed = _policy_sched()
    for _ in range(10):
        fixed.metrics.on_chunk_stall(0, 1, 0.001)
    fixed.metrics.on_chunk_stall(0, 1, 0.050)
    assert fixed._chunk_bounds(0) == (1, 8)


def test_auto_chunk_bounds_warmup_compiles_single_page():
    """The warmup ladder always includes the one-page chunk shape when
    auto_chunk_bounds is on — the tuned floor may narrow to a single
    page mid-serve and must already be compiled."""

    class _WarmupRecorder(_FakeChunkBackend):
        def __init__(self):
            self.chunk_tokens = []

        def warmup(self, prompt_lens, chunk_tokens=None):
            self.chunk_tokens.append(chunk_tokens)

    def ladder(auto):
        b = _WarmupRecorder()
        PagedLLMScheduler(
            backends=[b], clock=lambda: 0.0,
            cfg=PagedLLMConfig(prefill_chunk_pages=2, adaptive_chunk=True,
                               min_chunk_pages=2, max_chunk_pages=8,
                               auto_chunk_bounds=auto)).warmup([8])
        return b.chunk_tokens

    assert ladder(auto=False) == [8, 32]             # base + hi (ps=4)
    assert ladder(auto=True) == [8, 4, 32]           # + the 1-page shape


def test_next_chunk_tokens_traces_counter():
    """_next_chunk_tokens converts the policy's pages to tokens and
    exposes the choice as the 'chunk_pages' tracer counter; with
    adaptive_chunk off it returns the static base size untraced."""
    from repro.serving.observability.tracer import COUNTER, Tracer
    tracer = Tracer()
    sched = _policy_sched(tracer=tracer)
    assert sched._next_chunk_tokens(0) == 8 * 4      # idle -> hi pages
    _join(sched, deadline_t=0.01)
    for _ in range(8):
        sched.metrics.itl_by_model[0].add(0.010)
    assert sched._next_chunk_tokens(0) == 1 * 4      # floor, page_size=4
    counts = [e for e in tracer.events()
              if e[1] == COUNTER and e[2] == "chunk_pages"]
    assert [c[6]["m0"] for c in counts] == [8, 1]
    static = PagedLLMScheduler(backends=[_FakeChunkBackend()],
                               cfg=PagedLLMConfig(prefill_chunk_pages=2),
                               clock=lambda: 0.0)
    assert static._next_chunk_tokens(0) == 2 * 4
    off = PagedLLMScheduler(backends=[_FakeChunkBackend()],
                            cfg=PagedLLMConfig())
    assert off._next_chunk_tokens(0) is None
