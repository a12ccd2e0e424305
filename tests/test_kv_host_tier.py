"""KV memory hierarchy correctness contract: spilling a prefix to the
host tier and restoring it on a later hit is invisible to outputs —
token-identical generations with tiering on vs off across
full/window/chunked/GQA/MLA paged variants — and invisible to the
pool's ownership accounting: a cancelled restore leaks nothing, a
watermark keeps admission headroom free, a demand spill completes an
allocation that would otherwise reject, and the disaggregated
backend's staging pool retains transferred prefixes so repeat system
prompts skip the prefill compute."""
import asyncio

import jax
import numpy as np
import pytest

from repro.models import transformer as tf
from repro.serving.backend import DisaggregatedBackend
from repro.serving.engine import Engine, ServeConfig
from repro.serving.kv_cache import OutOfPages
from repro.serving.scheduler import (PagedLLMConfig, PagedLLMScheduler,
                                     SamplingParams)
from test_prefix_sharing import (PS, prompts_with_shared_prefix,
                                 tiny_config)

#: variants where every layer attends the full context, so span
#: reclaim never frees prefix pages mid-decode and the retained /
#: spilled / restored page counts are exact.  Window and chunked
#: attention reclaim pages below their span — chunk 0 then never
#: reaches the host tier and a later lookup is a clean miss (the
#: tolerant branch: parity must still hold, counters need not).
FULL_CONTEXT = ("full", "gqa_mixed", "mla")


def make_tiered_engine(cfg, params, *, num_pages=40, host_pages=16,
                       watermark=0.0, lazy=False) -> Engine:
    eng = Engine(cfg, params, ServeConfig(max_len=64))
    eng.init_paged(num_pages=num_pages, page_size=PS, decode_batch=4,
                   prefix_sharing=True, host_tier_pages=host_pages,
                   spill_watermark=watermark, lazy_decode_alloc=lazy)
    return eng


def make_flat_engine(cfg, params, *, num_pages=40) -> Engine:
    eng = Engine(cfg, params, ServeConfig(max_len=64))
    eng.init_paged(num_pages=num_pages, page_size=PS, decode_batch=4,
                   prefix_sharing=True)
    return eng


# ---------------------------------------------------------------------------
# Parity: tiering on vs off, all paged variants
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("variant",
                         ["full", "swa", "chunked", "gqa_mixed", "mla"])
def test_spill_restore_parity_on_vs_off(variant):
    """Generate, spill everything to host, regenerate: the restored
    prefix (including the partially-filled boundary page) produces
    exactly the tokens a tier-less engine produces, for every paged
    attention variant; a shared-prefix follower restores only the full
    prefix chunks it can use."""
    cfg = tiny_config(variant)
    params = tf.init_params(cfg, jax.random.key(3))
    pa, pb = prompts_with_shared_prefix(cfg)    # 8-token prefix, tails 3/5
    exact = variant in FULL_CONTEXT
    flat = make_flat_engine(cfg, params)
    ref_a = flat.generate_paged(pa, max_new_tokens=6)["tokens"]
    ref_b = flat.generate_paged(pb, max_new_tokens=6)["tokens"]

    eng = make_tiered_engine(cfg, params)
    out_a = eng.generate_paged(pa, max_new_tokens=6)["tokens"]
    np.testing.assert_array_equal(out_a, ref_a)
    retained = eng.pool.retained_pages
    if exact:
        assert retained == 3                    # 2 full chunks + boundary

    eng.pool.drop_retained()                    # force everything cold
    assert eng.pool.pages_in_use == 0
    # single-owner pages spill (never drop): host holds all of them
    assert eng.host_tier.stats()["pages_in_use"] == retained

    # repeat prompt: restore from host, prefill only the final token
    out_a2 = eng.generate_paged(pa, max_new_tokens=6)["tokens"]
    np.testing.assert_array_equal(out_a2, ref_a)
    if exact:
        ht = eng.host_tier.stats()
        assert ht["restored_pages"] == 3 and ht["hits"] == 1
        assert ht["pages_in_use"] == 0          # consumed: one tier owns it

    # partial host hit: pb shares only the 2 full prefix chunks
    eng.pool.drop_retained()
    out_b = eng.generate_paged(pb, max_new_tokens=6)["tokens"]
    np.testing.assert_array_equal(out_b, ref_b)
    if exact:
        assert eng.host_tier.stats()["restored_pages"] == 5

    eng.pool.drop_retained()
    assert eng.pool.pages_in_use == 0 and eng.pool.prefix_entries == 0


def test_repeated_host_hits_stay_token_identical():
    """Each repeat of a prompt whose prefix went cold since its last run
    restores it from the host tier: every repeat is one hit restoring
    all three retained pages, and every output is bitwise the tier-less
    engine's."""
    cfg = tiny_config("full")
    params = tf.init_params(cfg, jax.random.key(3))
    pa, _ = prompts_with_shared_prefix(cfg)
    ref = make_flat_engine(cfg, params).generate_paged(
        pa, max_new_tokens=6)["tokens"]
    eng = make_tiered_engine(cfg, params)
    np.testing.assert_array_equal(
        eng.generate_paged(pa, max_new_tokens=6)["tokens"], ref)
    trials = 3
    for _ in range(trials):
        eng.pool.drop_retained()                # the prefix goes to host
        np.testing.assert_array_equal(
            eng.generate_paged(pa, max_new_tokens=6)["tokens"], ref)
    ht = eng.host_tier.stats()
    assert ht["hits"] == trials and ht["restored_pages"] == 3 * trials
    eng.pool.drop_retained()
    assert eng.pool.pages_in_use == 0


def test_spill_and_restore_spans_lie_on_the_tracer_clock():
    """The spill and restore spans take their times from the tracer's
    clock, so they fall inside the run's own interval on that clock."""
    from repro.serving.observability import Tracer
    cfg = tiny_config("full")
    params = tf.init_params(cfg, jax.random.key(3))
    pa, _ = prompts_with_shared_prefix(cfg)
    eng = make_tiered_engine(cfg, params)
    eng.tracer = tracer = Tracer()
    t0 = tracer.clock()
    eng.generate_paged(pa, max_new_tokens=4)
    eng.pool.drop_retained()                    # spills
    eng.generate_paged(pa, max_new_tokens=4)    # restores
    t1 = tracer.clock()
    spans = {ev[2]: ev for ev in tracer.events() if ev[1] == "X"}
    for name in ("repro.pool.spill", "repro.pool.restore"):
        _, _, _, _, ts, dur, args = spans[name]
        assert t0 <= ts and ts + dur <= t1
        assert args["pages"] == 3


def test_restored_prefix_tokens_count_as_shared():
    """A restored prefix is shared compute, not recomputed compute:
    the repeat generation's sealing accounts its restored span in
    prefill_tokens_shared exactly like a resident hit would."""
    cfg = tiny_config("full")
    params = tf.init_params(cfg, jax.random.key(3))
    pa, _ = prompts_with_shared_prefix(cfg)     # len 11: shared cap 10
    eng = make_tiered_engine(cfg, params)
    eng.generate_paged(pa, max_new_tokens=4)
    eng.pool.drop_retained()
    before = eng.prefill_tokens_shared
    eng.generate_paged(pa, max_new_tokens=4)
    assert eng.prefill_tokens_shared - before == len(pa) - 1


# ---------------------------------------------------------------------------
# Pressure behaviour: watermark, spill-not-reject, cancellation
# ---------------------------------------------------------------------------

def test_watermark_spills_proactively_at_release():
    """With a spill watermark, releasing a sequence spills retained
    pages down to the free-page target instead of waiting for an
    allocation to come up short."""
    cfg = tiny_config("full")
    params = tf.init_params(cfg, jax.random.key(3))
    # 12 allocatable pages, target int(0.9 * 12) = 10 free.  The
    # 11-token prompt (+6 budget) retains 3 pages at release, leaving
    # 9 free — one short, so exactly one page spills eagerly.
    eng = make_tiered_engine(cfg, params, num_pages=13, watermark=0.9)
    pa, _ = prompts_with_shared_prefix(cfg)
    eng.generate_paged(pa, max_new_tokens=6)
    st = eng.pool.stats()
    assert st["num_free"] >= 10                 # watermark target held
    assert st["pages_spilled"] == 1 and st["retained_pages"] == 2
    assert eng.host_tier.stats()["pages_in_use"] == 1
    eng.pool.drop_retained()
    assert eng.pool.pages_in_use == 0


def test_demand_spill_completes_would_reject_alloc():
    """The eviction + re-admission trace: a prompt whose allocation
    exceeds free pages (because retention holds the rest) completes by
    spilling the cold prefix — where a flat pool with the same free
    pages raises OutOfPages — and the spilled prefix restores on its
    next admission."""
    cfg = tiny_config("full")
    params = tf.init_params(cfg, jax.random.key(3))
    pa, _ = prompts_with_shared_prefix(cfg)
    pc = np.asarray(jax.random.randint(jax.random.key(99), (16,), 0,
                                       cfg.vocab_size))
    # 8 allocatable pages; A seals holding 5 (11 tokens + 6 budget),
    # retains 3 at release; C needs 4+2 = 6 pages > 5 free
    eng = make_tiered_engine(cfg, params, num_pages=9, host_pages=8)
    eng.generate_paged(pa, max_new_tokens=6)
    assert eng.pool.retained_pages == 3 and eng.pool.num_free == 5
    # a flat pool with 5 free pages rejects this admission outright
    with pytest.raises(OutOfPages):
        make_flat_engine(cfg, params, num_pages=6).prefill_into_pages(
            pc, max_new_tokens=6)
    need, _ = eng.admission_page_cost(pc, 6)
    assert need == 6
    seq = eng.prefill_into_pages(pc, max_new_tokens=6)   # spills, admits
    assert eng.pool.stats()["pages_spilled"] >= need - 5
    eng.pool.release(seq)
    # and the spilled prefix is not lost: A's next admission restores
    eng.pool.drop_retained()
    seq_a = eng.prefill_into_pages(pa, max_new_tokens=6)
    assert seq_a.shared_prefix_len == len(pa) - 1
    assert eng.host_tier.stats()["restored_pages"] >= 3
    eng.pool.release(seq_a)
    eng.pool.drop_retained()
    assert eng.pool.pages_in_use == 0


def test_mid_restore_failure_leaks_nothing(monkeypatch):
    """A restore whose scatter dies mid-flight (device failure /
    cancellation) hands its freshly-allocated pages back and leaves
    the host copies untouched — the admission then rolls back to an
    empty pool, pages exact."""
    cfg = tiny_config("full")
    params = tf.init_params(cfg, jax.random.key(3))
    pa, _ = prompts_with_shared_prefix(cfg)
    eng = make_tiered_engine(cfg, params)
    eng.generate_paged(pa, max_new_tokens=6)
    eng.pool.drop_retained()
    assert eng.host_tier.stats()["pages_in_use"] == 3

    def boom(*_a, **_k):
        raise RuntimeError("scatter died mid-restore")
    monkeypatch.setattr(eng, "_tier_scatter", boom)
    with pytest.raises(RuntimeError, match="mid-restore"):
        eng.prefill_into_pages(pa, max_new_tokens=6)
    assert eng.pool.pages_in_use == 0           # new pages handed back
    assert eng.host_tier.stats()["pages_in_use"] == 3   # host intact
    assert eng.host_tier.stats()["restored_pages"] == 0


def test_host_tier_capacity_lru_eviction():
    """A host tier smaller than the spill demand evicts its coldest
    entries; the device side still frees its pages (eviction never
    blocks reclaim)."""
    cfg = tiny_config("full")
    params = tf.init_params(cfg, jax.random.key(3))
    pa, _ = prompts_with_shared_prefix(cfg)
    eng = make_tiered_engine(cfg, params, host_pages=2)
    eng.generate_paged(pa, max_new_tokens=6)
    eng.pool.drop_retained()                    # 3 spill into 2 slots
    ht = eng.host_tier.stats()
    assert ht["pages_in_use"] == 2 and ht["evicted_pages"] >= 1
    assert eng.pool.pages_in_use == 0


# ---------------------------------------------------------------------------
# Lazy decode allocation (scheduler admission satellite)
# ---------------------------------------------------------------------------

def test_lazy_decode_alloc_reserves_prompt_only():
    """Lazy sealing holds pages_for(p + 1), not the full
    prompt+budget span; decode then grows page-by-page, and admission
    cost reports the smaller up-front reservation."""
    cfg = tiny_config("full")
    params = tf.init_params(cfg, jax.random.key(3))
    pa, _ = prompts_with_shared_prefix(cfg)     # p = 11
    flat = make_flat_engine(cfg, params)
    assert flat.admission_page_cost(pa, 8)[0] == flat.pool.pages_for(19)
    ref = flat.generate_paged(pa, max_new_tokens=8)["tokens"]

    eng = make_tiered_engine(cfg, params, lazy=True)
    assert eng.admission_page_cost(pa, 8)[0] == eng.pool.pages_for(12)
    seq = eng.prefill_into_pages(pa, max_new_tokens=8)
    assert len(seq.pages) == eng.pool.pages_for(12)     # p + 1 only
    while not seq.done:
        eng.decode_step_batch([seq])
    assert len(seq.pages) == eng.pool.pages_for(len(pa) + 8)
    np.testing.assert_array_equal(
        np.concatenate([pa, np.asarray(seq.tokens, np.int32)]), ref)
    eng.pool.release(seq)
    eng.pool.drop_retained()
    assert eng.pool.pages_in_use == 0


def test_lazy_grow_out_of_pages_tags_victim():
    """A decode step that cannot grow a lazily-sealed sequence raises
    OutOfPages tagged with grow_seq and mutates nothing — the
    scheduler fails only that sequence, exactly like the COW path."""
    cfg = tiny_config("full")
    params = tf.init_params(cfg, jax.random.key(3))
    eng = Engine(cfg, params, ServeConfig(max_len=64))
    # 4 allocatable pages: an 11-token prompt seals lazily into 3
    # pages (12-token span); decode crosses into a 4th page at
    # position 12 and needs a 5th at position 16 — which never exists
    eng.init_paged(num_pages=5, page_size=PS, decode_batch=4,
                   prefix_sharing=True, lazy_decode_alloc=True)
    pa = np.asarray(jax.random.randint(jax.random.key(5), (11,), 0,
                                       cfg.vocab_size))
    seq = eng.prefill_into_pages(pa, max_new_tokens=8)
    with pytest.raises(OutOfPages) as ei:
        while not seq.done:
            eng.decode_step_batch([seq])
    assert ei.value.grow_seq is seq
    assert len(seq.pages) == 4                  # grew to the wall first
    eng.pool.release(seq)                       # complete rollback
    assert eng.pool.pages_in_use == 0


# ---------------------------------------------------------------------------
# Disaggregated staging retention
# ---------------------------------------------------------------------------

def test_disagg_staging_retains_transferred_prefix():
    """The gather stage's release RETAINS a transferred prefix in the
    tiered staging pool: a repeated system prompt maps it and skips
    the prefill compute (the transfer still copies), token-identical
    to the flat-engine reference."""
    cfg = tiny_config("full")
    params = tf.init_params(cfg, jax.random.key(3))
    pa, _ = prompts_with_shared_prefix(cfg)
    ref = make_flat_engine(cfg, params).generate_paged(
        pa, max_new_tokens=6)["tokens"]

    backend = DisaggregatedBackend.build(
        cfg, params, ServeConfig(max_len=64), num_pages=40, page_size=PS,
        decode_batch=4, host_tier_pages=16)

    async def run_twice():
        sched = PagedLLMScheduler(backends=[backend], cfg=PagedLLMConfig())
        async with sched:
            out1 = await sched.submit(
                pa, SamplingParams(max_new_tokens=6)).result()
            computed_mid = backend.prefill_engine.prefill_tokens_computed
            out2 = await sched.submit(
                pa, SamplingParams(max_new_tokens=6)).result()
        return out1, out2, computed_mid

    out1, out2, computed_mid = asyncio.run(run_twice())
    np.testing.assert_array_equal(out1, ref)
    np.testing.assert_array_equal(out2, ref)
    pre = backend.prefill_engine
    assert pre.pool.retained_pages >= 3         # staging kept the prefix
    # the repeat ran tail-only: its shared span never recomputed
    assert pre.prefill_tokens_shared >= len(pa) - 1
    assert pre.prefill_tokens_computed - computed_mid <= PS
    assert backend.transfers >= 2               # transfer still copies
    pre.pool.drop_retained()
    assert pre.pool.pages_in_use == 0
