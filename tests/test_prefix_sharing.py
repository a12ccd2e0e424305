"""Prefix-sharing COW-correctness contract: identical prompts (and
shared-prefix batches) generate token-identical outputs with sharing
on vs off, across full/window/chunked/GQA/MLA paged variants and under
forced-Pallas interpret mode; copy-on-write never lets one request's
decode tokens leak into another's prefix."""
import asyncio

import jax
import numpy as np
import pytest

from repro.configs.base import LayerSpec, ModelConfig
from repro.models import transformer as tf
from repro.serving.engine import Engine, ServeConfig
from repro.serving.kv_cache import PagePool
from repro.serving.scheduler import PagedLLMConfig, PagedLLMScheduler

PS = 4          # page size everywhere here: small so prefixes span pages


def tiny_config(variant: str) -> ModelConfig:
    kw = dict(name=f"share-{variant}", arch_type="dense", num_layers=2,
              d_model=32, d_ff=64, vocab_size=64, num_heads=4,
              num_kv_heads=2, head_dim=8, compute_dtype="float32",
              param_dtype="float32", kv_cache_dtype="float32")
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


def make_engine(cfg, params, sharing: bool, num_pages: int = 40) -> Engine:
    eng = Engine(cfg, params, ServeConfig(max_len=64))
    eng.init_paged(num_pages=num_pages, page_size=PS, decode_batch=4,
                   prefix_sharing=sharing)
    return eng


def prompts_with_shared_prefix(cfg, prefix_len=8, tails=(3, 5), seed=7):
    key = jax.random.key(seed)
    prefix = np.asarray(jax.random.randint(key, (prefix_len,), 0,
                                           cfg.vocab_size))
    out = []
    for i, t in enumerate(tails):
        tail = np.asarray(jax.random.randint(jax.random.fold_in(key, i + 1),
                                             (t,), 0, cfg.vocab_size))
        out.append(np.concatenate([prefix, tail]))
    return out


# ---------------------------------------------------------------------------
# Parity: sharing on vs off, all paged variants
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("variant",
                         ["full", "swa", "chunked", "gqa_mixed", "mla"])
def test_shared_prefix_parity_on_vs_off(variant):
    """A follower request that maps a resident's 2-page prefix and
    prefills only its tail generates exactly the tokens a no-sharing
    engine produces — for every paged attention variant."""
    cfg = tiny_config(variant)
    params = tf.init_params(cfg, jax.random.key(3))
    pa, pb = prompts_with_shared_prefix(cfg)
    off = make_engine(cfg, params, sharing=False)
    ref_a = off.generate_paged(pa, max_new_tokens=6)["tokens"]
    ref_b = off.generate_paged(pb, max_new_tokens=6)["tokens"]

    on = make_engine(cfg, params, sharing=True)
    sa = on.prefill_into_pages(pa, max_new_tokens=6)
    sb = on.prefill_into_pages(pb, max_new_tokens=6)
    assert sa.shared_prefix_len == 0            # first resident: no match
    assert sb.shared_prefix_len == 8            # 2 aligned pages mapped
    assert sb.pages[:2] == sa.pages[:2]         # same physical pages
    assert all(on.pool.refcount(pg) == 2 for pg in sa.pages[:2])
    while not (sa.done and sb.done):
        on.decode_step_batch([s for s in (sa, sb) if not s.done])
    np.testing.assert_array_equal(np.concatenate([pa, sa.tokens]), ref_a)
    np.testing.assert_array_equal(np.concatenate([pb, sb.tokens]), ref_b)
    on.pool.release(sa)
    on.pool.release(sb)
    assert on.pool.pages_in_use == 0 and on.pool.prefix_entries == 0


@pytest.mark.parametrize("variant", ["full", "mla"])
def test_identical_prompt_decode_cow_parity(variant):
    """Two identical unaligned prompts share every prompt page
    including the partially-filled boundary page; the first decode
    insert into it copy-on-writes, and both generations stay
    token-identical to the no-sharing reference."""
    cfg = tiny_config(variant)
    params = tf.init_params(cfg, jax.random.key(4))
    p = np.asarray(jax.random.randint(jax.random.key(9), (10,), 0,
                                      cfg.vocab_size))       # 10 % 4 = 2
    off = make_engine(cfg, params, sharing=False)
    ref = off.generate_paged(p, max_new_tokens=6)["tokens"]

    on = make_engine(cfg, params, sharing=True)
    a = on.prefill_into_pages(p, max_new_tokens=6)
    b = on.prefill_into_pages(p, max_new_tokens=6)
    assert b.shared_prefix_len == 9             # p - 1: only the final
    assert b.pages[:3] == a.pages[:3]           # token is recomputed
    boundary = a.pages[2]
    assert on.pool.refcount(boundary) == 2
    assert on.pool.cow_headroom == 1            # admission held 1 page back
    on.decode_step_batch([a, b])                # both insert at pos 10
    assert on.cow_count == 1                    # exactly one private copy
    assert on.pool.refcount(boundary) == 1
    assert a.pages[2] != b.pages[2]
    while not (a.done and b.done):
        on.decode_step_batch([s for s in (a, b) if not s.done])
    np.testing.assert_array_equal(np.concatenate([p, a.tokens]), ref)
    np.testing.assert_array_equal(np.concatenate([p, b.tokens]), ref)
    on.pool.release(a)
    on.pool.release(b)
    assert on.pool.pages_in_use == 0 and on.pool.cow_headroom == 0


def test_shared_batch_vs_solo():
    """A shared-prefix pair decoding in ONE batch matches each request
    decoded solo on a fresh no-sharing pool (sharing is invisible to
    the numerics, not just to the final argmax winner)."""
    cfg = tiny_config("gqa_mixed")
    params = tf.init_params(cfg, jax.random.key(5))
    pa, pb = prompts_with_shared_prefix(cfg, prefix_len=12, tails=(2, 6),
                                        seed=11)
    off = make_engine(cfg, params, sharing=False)
    refs = [off.generate_paged(x, max_new_tokens=8)["tokens"]
            for x in (pa, pb)]
    on = make_engine(cfg, params, sharing=True)
    sa = on.prefill_into_pages(pa, max_new_tokens=8)
    on.decode_step_batch([sa])
    on.decode_step_batch([sa])                  # sa is mid-generation ...
    sb = on.prefill_into_pages(pb, max_new_tokens=8)  # ... when sb joins
    assert sb.shared_prefix_len == 12
    while not (sa.done and sb.done):
        on.decode_step_batch([s for s in (sa, sb) if not s.done])
    np.testing.assert_array_equal(np.concatenate([pa, sa.tokens]), refs[0])
    np.testing.assert_array_equal(np.concatenate([pb, sb.tokens]), refs[1])
    on.pool.release(sa)
    on.pool.release(sb)
    assert on.pool.pages_in_use == 0


def test_parity_under_forced_pallas_interpret(monkeypatch):
    """The COW contract holds when decode runs through the Pallas
    paged-attention kernel (interpret mode on CPU): shared-prefix and
    identical-prompt generations match the no-sharing engine."""
    from repro.kernels import ops as kops
    cfg = tiny_config("full")
    params = tf.init_params(cfg, jax.random.key(6))
    pa, pb = prompts_with_shared_prefix(cfg, prefix_len=8, tails=(2, 2),
                                        seed=13)
    off = make_engine(cfg, params, sharing=False)
    on = make_engine(cfg, params, sharing=True)
    monkeypatch.setattr(kops, "_FORCE", True)
    ref_a = off.generate_paged(pa, max_new_tokens=4)["tokens"]
    ref_b = off.generate_paged(pb, max_new_tokens=4)["tokens"]
    sa = on.prefill_into_pages(pa, max_new_tokens=4)
    sb = on.prefill_into_pages(pb, max_new_tokens=4)
    assert sb.shared_prefix_len == 8
    while not (sa.done and sb.done):
        on.decode_step_batch([s for s in (sa, sb) if not s.done])
    np.testing.assert_array_equal(np.concatenate([pa, sa.tokens]), ref_a)
    np.testing.assert_array_equal(np.concatenate([pb, sb.tokens]), ref_b)
    on.pool.release(sa)
    on.pool.release(sb)
    assert on.pool.pages_in_use == 0


def test_cow_is_fused_into_decode_step_trace():
    """COW runs INSIDE the decode jit (one compiled program copies the
    boundary page and inserts the token): the trace must show a 'cow'
    instant with fused=True and NO standalone copy_page span — a
    separate copy dispatch would be the old two-program round trip."""
    from repro.serving.observability.tracer import INSTANT, Tracer
    cfg = tiny_config("full")
    params = tf.init_params(cfg, jax.random.key(4))
    p = np.asarray(jax.random.randint(jax.random.key(9), (10,), 0,
                                      cfg.vocab_size))
    on = make_engine(cfg, params, sharing=True)
    on.tracer = tracer = Tracer()
    a = on.prefill_into_pages(p, max_new_tokens=2)
    b = on.prefill_into_pages(p, max_new_tokens=2)
    on.decode_step_batch([a, b])                # COW fires here
    assert on.cow_count == 1
    evs = tracer.events()
    cows = [e for e in evs if e[2] == "cow" and e[1] == INSTANT]
    assert len(cows) == 1
    assert cows[0][6]["fused"] is True
    assert not [e for e in evs if "copy_page" in e[2]]
    on.pool.release(a)
    on.pool.release(b)


# ---------------------------------------------------------------------------
# Semantics around the edges
# ---------------------------------------------------------------------------

def test_sharing_noop_on_unaligned_divergence():
    """Prompts that diverge inside the first page share nothing —
    the index is page-aligned by design (documented no-op)."""
    cfg = tiny_config("full")
    params = tf.init_params(cfg, jax.random.key(0))
    on = make_engine(cfg, params, sharing=True)
    pa = np.asarray([1, 2, 3, 4, 5, 6, 7, 8])
    pb = np.asarray([1, 2, 9, 4, 5, 6, 7, 8])   # differs at token 2
    sa = on.prefill_into_pages(pa, max_new_tokens=2)
    sb = on.prefill_into_pages(pb, max_new_tokens=2)
    assert sb.shared_prefix_len == 0
    assert not set(sa.pages) & set(sb.pages)
    on.pool.release(sa)
    on.pool.release(sb)
    assert on.pool.pages_in_use == 0


def test_release_after_sharer_retires_keeps_pages_alive():
    """Retiring the original resident decrefs but must not free pages
    a follower still maps; the follower keeps generating correctly and
    the pool drains only when the last holder releases."""
    cfg = tiny_config("full")
    params = tf.init_params(cfg, jax.random.key(1))
    off = make_engine(cfg, params, sharing=False)
    pa, pb = prompts_with_shared_prefix(cfg, seed=17)
    ref_b = off.generate_paged(pb, max_new_tokens=6)["tokens"]
    on = make_engine(cfg, params, sharing=True)
    sa = on.prefill_into_pages(pa, max_new_tokens=6)
    sb = on.prefill_into_pages(pb, max_new_tokens=6)
    shared = list(sb.pages[:2])
    on.pool.release(sa)                          # original retires first
    assert all(on.pool.refcount(pg) == 1 for pg in shared)
    while not sb.done:
        on.decode_step_batch([sb])
    np.testing.assert_array_equal(np.concatenate([pb, sb.tokens]), ref_b)
    on.pool.release(sb)
    assert on.pool.pages_in_use == 0 and on.pool.prefix_entries == 0


def test_pool_zero_token_and_empty_free_edges():
    """pages_for(0) is 0 (an empty sequence holds nothing), negative
    sizes raise, decref([]) / free([]) are no-ops, and the prefix index
    never creates entries for empty prompts."""
    pool = PagePool(num_pages=6, page_size=4)
    assert pool.pages_for(0) == 0
    assert pool.pages_for(1) == 1
    with pytest.raises(ValueError, match=">= 0"):
        pool.pages_for(-1)
    pool.free([])                                # documented no-op
    pool.decref([])
    assert pool.pages_in_use == 0 and pool.num_free == 5
    assert pool.register_prefix(np.zeros((0,), np.int32), []) == []
    assert pool.lookup_prefix(np.zeros((0,), np.int32)) == ([], 0)
    assert pool.prefix_entries == 0


def test_scheduler_admission_budgets_unique_pages():
    """A pool too small for two private copies serves a shared-prefix
    pair concurrently: admission charges only unique pages, outputs
    match solo references, and the trace provably overlapped."""
    cfg = tiny_config("full")
    params = tf.init_params(cfg, jax.random.key(2))
    # each request: 12 prompt + 4 new = 16 tokens = 4 pages; two private
    # copies need 8 pages but only 6 are allocatable -> only sharing
    # (4 + 2 unique) lets the pair run together
    pa, pb = prompts_with_shared_prefix(cfg, prefix_len=8, tails=(4, 4),
                                        seed=19)
    off = make_engine(cfg, params, sharing=False, num_pages=7)
    refs = [off.generate_paged(x, max_new_tokens=4)["tokens"]
            for x in (pa, pb)]
    eng = make_engine(cfg, params, sharing=True, num_pages=7)

    async def main():
        sched = PagedLLMScheduler([eng], PagedLLMConfig(max_new_tokens=4))
        async with sched:
            handles = [sched.submit(pa), sched.submit(pb)]
            outs = await asyncio.gather(*handles)
        return sched, outs

    sched, outs = asyncio.run(main())
    np.testing.assert_array_equal(outs[0], refs[0])
    np.testing.assert_array_equal(outs[1], refs[1])
    snap = sched.snapshot()
    assert snap["completed"] == 2 and snap["failed"] == 0
    assert snap["prefill_tokens_shared"] == 8    # pb mapped the prefix
    assert snap["pools"][0]["peak_pages_in_use"] == 6   # 4 + 2 unique
    assert snap["pools"][0]["pages_in_use"] == 0


def test_shared_prompt_trace_prefills_prefix_once():
    """A system-prompt trace through the scheduler — three requests
    opening with the same 3-page prefix plus a duplicate of the first,
    one decode slot each — served with sharing off and on: identical
    outputs; with sharing the
    prefix is prefilled exactly once (every follower maps all of it,
    the duplicate all but its final token), strictly fewer unique pages
    are held, and every copy-on-write is fused into a decode step."""
    from repro.serving.observability.tracer import INSTANT, Tracer
    cfg = tiny_config("gqa_mixed")
    params = tf.init_params(cfg, jax.random.key(8))
    prefix_len, tails = 3 * PS, (3, 6, 2)
    prompts = prompts_with_shared_prefix(cfg, prefix_len=prefix_len,
                                         tails=tails, seed=23)
    prompts.append(prompts[0].copy())

    def serve(sharing):
        eng = make_engine(cfg, params, sharing=sharing)
        tracer = Tracer()
        sched = PagedLLMScheduler([eng], PagedLLMConfig(max_new_tokens=5),
                                  tracer=tracer)

        async def main():
            async with sched:
                return await asyncio.gather(*(sched.submit(p)
                                              for p in prompts))

        outs = asyncio.run(main())
        snap = sched.snapshot()
        assert snap["completed"] == len(prompts) and snap["failed"] == 0
        assert snap["pools"][0]["pages_in_use"] == 0
        return outs, snap, tracer.events()

    base, base_snap, _ = serve(False)
    shared, snap, evs = serve(True)
    for a, b in zip(base, shared):
        np.testing.assert_array_equal(a, b)
    assert base_snap["prefill_tokens_shared"] == 0
    assert snap["prefill_tokens_shared"] == \
        (len(tails) - 1) * prefix_len + len(prompts[0]) - 1
    assert (snap["pools"][0]["peak_pages_in_use"]
            < base_snap["pools"][0]["peak_pages_in_use"])
    cows = [e for e in evs if e[2] == "cow" and e[1] == INSTANT]
    assert snap["cow_copies"] >= 1 and len(cows) == snap["cow_copies"]
    assert all(e[6]["fused"] is True for e in cows)
    assert not [e for e in evs if "copy_page" in e[2]
                or "copy_page" in str((e[6] or {}).get("op", ""))]
