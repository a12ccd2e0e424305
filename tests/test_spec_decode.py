"""Speculative decoding is token-exact by construction: the verifier's
multi-token step recomputes exactly what plain greedy decode would
have, so accepted-or-not, the committed stream is bitwise the plain
stream.  This matrix drives SpeculativeBackend across attention
variants x KV-cache dtypes x backend topologies and asserts output
identity against target-only ``generate_paged``, with both drafter
regimes covered: a same-params drafter (acceptance is structural) and
an independent drafter (most drafts reject, exercising rollback).
Also: the acceptance-EMA fallback to plain decode, and the k=0
degenerate path for mux-probed hard inputs."""
import asyncio

import jax
import numpy as np
import pytest

from test_paged_decode import tiny_config

from repro.models import transformer as tf
from repro.serving.backend import (DisaggregatedBackend, InProcessBackend,
                                   RemoteStubBackend)
from repro.serving.engine import Engine, ServeConfig
from repro.serving.spec_decode import SpeculativeBackend

MAX_LEN = 48
MAX_NEW = 10
DRAFT_K = 3

# Curated so every attention variant, KV dtype, backend topology, and
# drafter regime appears at least twice without running the full
# 5x2x3x2 cross product (compile time, not coverage, is the binding
# constraint — the verify kernel under test is shared by all cells).
MATRIX = [
    ("full",      "bfloat16", "inproc", "same"),
    ("swa",       "int8",     "inproc", "diverse"),
    ("chunked",   "bfloat16", "disagg", "same"),
    ("gqa_mixed", "int8",     "remote", "diverse"),
    ("mla",       "bfloat16", "inproc", "same"),
    ("full",      "int8",     "disagg", "diverse"),
    ("swa",       "bfloat16", "remote", "same"),
]


def build_engine(cfg, params, *, lazy=False, max_len=MAX_LEN, pages=60):
    eng = Engine(cfg, params, ServeConfig(max_len=max_len))
    eng.init_paged(num_pages=pages, page_size=4, decode_batch=4,
                   span_reclaim=not lazy, lazy_decode_alloc=lazy)
    return eng


def make_spec(cfg, params, dparams, backend_kind, **spec_kw):
    """Returns (driveable backend, SpeculativeBackend for stats)."""
    draft = build_engine(cfg, dparams, lazy=True, max_len=MAX_LEN + 16,
                         pages=80)
    spec_kw.setdefault("draft_k", DRAFT_K)
    if backend_kind == "disagg":
        target = DisaggregatedBackend.build(
            cfg, params, ServeConfig(max_len=MAX_LEN), num_pages=60,
            page_size=4, decode_batch=4)
    else:
        target = InProcessBackend(build_engine(cfg, params))
    spec = SpeculativeBackend(target, draft, **spec_kw)
    if backend_kind == "remote":
        return RemoteStubBackend(spec), spec
    return spec, spec


def prompts_for(cfg):
    return [np.asarray(jax.random.randint(jax.random.key(i), (7 + i,), 0,
                                          cfg.vocab_size))
            for i in range(3)]


def plain_refs(cfg, params, prompts):
    eng = build_engine(cfg, params)
    return [list(eng.generate_paged(p, max_new_tokens=MAX_NEW)["tokens"]
                 [len(p):]) for p in prompts]


async def drive(backend, prompts, max_new=MAX_NEW):
    await backend.start()
    outs = []
    try:
        seqs = []
        for p in prompts:
            seq = backend.begin(p, max_new_tokens=max_new)
            while not await backend.prefill_chunk(seq):
                pass
            seqs.append(seq)
        live = list(seqs)
        while live:
            await backend.decode_batch(live)
            live = [s for s in live if not s.done]
        for s in seqs:
            outs.append(list(s.tokens))
            backend.release(s)
    finally:
        await backend.stop()
    return outs


def assert_drained(spec: SpeculativeBackend):
    stats = spec.stats()
    assert stats["draft_pool"]["pages_in_use"] == 0, stats
    assert stats["pool"]["pages_in_use"] == 0, stats
    if "prefill_pool" in stats:
        assert stats["prefill_pool"]["pages_in_use"] == 0, stats


@pytest.mark.parametrize(
    "variant,kv_dtype,backend_kind,drafter", MATRIX,
    ids=[f"{v}-{d}-{b}-{dr}" for v, d, b, dr in MATRIX])
def test_spec_decode_parity(variant, kv_dtype, backend_kind, drafter):
    cfg = tiny_config(variant, kv_cache_dtype=kv_dtype)
    params = tf.init_params(cfg, jax.random.key(0))
    dparams = (params if drafter == "same"
               else tf.init_params(cfg, jax.random.key(7)))
    prompts = prompts_for(cfg)
    refs = plain_refs(cfg, params, prompts)

    backend, spec = make_spec(cfg, params, dparams, backend_kind)
    outs = asyncio.run(drive(backend, prompts))
    assert outs == refs                      # bitwise the plain stream

    stats = spec.stats()
    assert stats["draft_tokens"] > 0
    if drafter == "same":
        # structural acceptance: the drafter IS the verifier
        assert stats["accepted_tokens"] == stats["draft_tokens"]
    assert_drained(spec)


def test_acceptance_ema_fallback():
    """An independent drafter whose tokens keep rejecting must trip the
    acceptance-rate EMA floor and collapse to plain decode — releasing
    the draft cache — while the output stream stays exact."""
    cfg = tiny_config("full")
    params = tf.init_params(cfg, jax.random.key(0))
    dparams = tf.init_params(cfg, jax.random.key(9))
    prompts = prompts_for(cfg)
    refs = plain_refs(cfg, params, prompts)

    backend, spec = make_spec(cfg, params, dparams, "inproc",
                              ema_alpha=0.9, ema_floor=0.9)
    outs = asyncio.run(drive(backend, prompts))
    assert outs == refs

    stats = spec.stats()
    assert stats["spec_fallbacks"] == len(prompts)
    assert stats["accepted_tokens"] < stats["draft_tokens"]
    assert_drained(spec)


def test_k0_degenerate_plain_decode():
    """Mux-probed hard inputs (k=0) never draft: the request runs plain
    target decode from the first sweep, with no draft pages ever held."""
    cfg = tiny_config("full")
    params = tf.init_params(cfg, jax.random.key(0))
    prompts = prompts_for(cfg)
    refs = plain_refs(cfg, params, prompts)

    backend, spec = make_spec(cfg, params, params, "inproc",
                              k_fn=lambda prompt: 0)
    outs = asyncio.run(drive(backend, prompts))
    assert outs == refs

    stats = spec.stats()
    assert stats["draft_tokens"] == 0
    assert stats["verify_rounds"] == 0
    # probe-routed plain decode is a routing decision, not a dynamic
    # collapse — the spec_fallbacks counter only tracks the latter
    assert stats["spec_fallbacks"] == 0
    assert stats["draft_pool"]["peak_pages_in_use"] == 0
    assert_drained(spec)


def _surgery_target(dcfg, dparams, layers):
    """A deeper target that computes bitwise the drafter's logits: the
    drafter's layers, then extra layers whose attention and MLP output
    projections are zero, so they add nothing to the residual stream."""
    import dataclasses

    import jax.numpy as jnp
    tcfg = dataclasses.replace(dcfg, name=dcfg.name + "-target",
                               num_layers=layers)
    tp = tf.init_params(tcfg, jax.random.key(1))
    blocks = {}
    for name, tblk in tp["blocks"].items():
        dblk = dparams["blocks"][name]
        tail = jax.tree.map(lambda t, d: t[d.shape[0]:], tblk, dblk)
        tail["attn"]["wo"] = jnp.zeros_like(tail["attn"]["wo"])
        tail["mlp"]["down"] = jnp.zeros_like(tail["mlp"]["down"])
        blocks[name] = jax.tree.map(
            lambda d, t: jnp.concatenate([d, t], axis=0), dblk, tail)
    return tcfg, {**{k: v for k, v in dparams.items() if k != "blocks"},
                  "blocks": blocks}


def test_mixed_draft_lengths_through_scheduler():
    """The scheduler drives a SpeculativeBackend over a deeper target
    that computes the drafter's logits exactly; short prompts draft
    DRAFT_K tokens ahead, long ones are routed to plain decode (k=0).
    Outputs are bitwise the plain target's through the same scheduler,
    drafts happen, none fall back, and nearly every draft is accepted
    (a float-ULP argmax tie between the one-token and multi-token step
    shapes may reject one; the verifier's pick is then committed)."""
    from repro.serving.scheduler import PagedLLMConfig, PagedLLMScheduler
    dcfg = tiny_config("full")
    dparams = tf.init_params(dcfg, jax.random.key(0))
    tcfg, tparams = _surgery_target(dcfg, dparams, 2 * dcfg.num_layers)
    prompts = [np.asarray(jax.random.randint(jax.random.key(i), (n,), 0,
                                             tcfg.vocab_size))
               for i, n in enumerate((7, 9, 8, 20))]

    def k_fn(prompt):
        return 0 if len(prompt) >= 16 else DRAFT_K

    def serve(backend):
        sched = PagedLLMScheduler(
            backends=[backend], cfg=PagedLLMConfig(max_new_tokens=MAX_NEW))

        async def main():
            async with sched:
                return await asyncio.gather(*(sched.submit(p)
                                              for p in prompts))

        outs = asyncio.run(main())
        snap = sched.snapshot()
        assert snap["completed"] == len(prompts) and snap["failed"] == 0
        return outs, snap

    plain, plain_snap = serve(InProcessBackend(build_engine(tcfg, tparams)))
    draft = build_engine(dcfg, dparams, lazy=True, max_len=MAX_LEN + 16,
                         pages=80)
    spec = SpeculativeBackend(InProcessBackend(build_engine(tcfg, tparams)),
                              draft, draft_k=DRAFT_K, k_fn=k_fn)
    outs, snap = serve(spec)
    for a, b in zip(plain, outs):
        np.testing.assert_array_equal(a, b)
    assert plain_snap["draft_tokens"] == 0
    assert snap["draft_tokens"] > 0 and snap["spec_fallbacks"] == 0
    assert snap["accepted_tokens"] >= 0.95 * snap["draft_tokens"]
    assert_drained(spec)
