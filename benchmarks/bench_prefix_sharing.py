"""Prefix-sharing copy-on-write pages vs the private-pages baseline.

Closed-form demo on a random-init mini decoder (no accelerator, no
trained state): a shared-prompt trace — N requests whose prompts open
with the same multi-page system prefix, the shape of both the paper's
probe-many-models-with-one-input pattern and production system-prompt
traffic — is served twice through PagedLLMScheduler:

  baseline  prefix_sharing=False (the PR 2 allocator): every request
            prefills its whole prompt and holds private pages.
  sharing   prefix_sharing=True: the first request prefills the prefix
            once; every follower maps the same physical pages
            (refcounted), prefills only its divergent tail, and
            admission charges *unique* pages.

Reported per mode: prefill tokens actually computed (and the prefill
FLOPs they imply at ~2 * params FLOPs/token), peak *unique* pages, and
wall time.  The run *asserts* the sharing contract — the shared prefix
is prefilled exactly once (every follower maps all of it), outputs are
token-identical across modes, and peak unique pages land strictly
below the baseline — then emits the CSV rows plus
results/BENCH_prefix_sharing.json.

A second entry point, ``run_host_tier`` (``--only host_tier``), measures
the KV memory hierarchy: cold-start TTFT (full prefix prefill) vs
host-hit TTFT (the prefix restores from the host tier and only the
divergent tail prefills).  It *asserts* the tier contract — host-hit
TTFT strictly below cold-start with token-identical outputs, and an
eviction + re-admission trace whose allocation exceeds the free pages
(it would previously reject with OutOfPages) completing via spill —
then emits results/BENCH_host_tier.json.

  PYTHONPATH=src python -m benchmarks.bench_prefix_sharing
  PYTHONPATH=src python -m benchmarks.bench_prefix_sharing --trace out.json
  PYTHONPATH=src python -m benchmarks.run --only prefix,host_tier
"""
from __future__ import annotations

import asyncio
import time
from typing import Dict, List

import jax
import numpy as np

from benchmarks import common
from repro.configs.base import LayerSpec, ModelConfig
from repro.models import transformer as tf
from repro.serving.engine import Engine, ServeConfig
from repro.serving.kv_cache import pool_bytes_per_page
from repro.serving.observability import Tracer
from repro.serving.scheduler import PagedLLMConfig, PagedLLMScheduler

MAX_LEN = 256
MAX_NEW = 16
PAGE_SIZE = 16
PREFIX_PAGES = 3                       # the shared system prompt: 3 pages
PREFIX_LEN = PREFIX_PAGES * PAGE_SIZE  # = 48 tokens, page-aligned
SUFFIX_LENS = [9, 14, 6, 17, 11, 8]    # 6 requests, divergent user tails
NUM_PAGES = 1 + 48
DECODE_BATCH = 8


def bench_config() -> ModelConfig:
    return ModelConfig(
        name="bench-prefix", arch_type="dense", num_layers=2, d_model=64,
        d_ff=128, vocab_size=256,
        pattern=(LayerSpec(attn_kind="full"), LayerSpec(attn_kind="swa")),
        window=16, num_heads=4, num_kv_heads=2, head_dim=16,
        compute_dtype="float32", param_dtype="float32",
        kv_cache_dtype="float32")


def _prompts(cfg: ModelConfig) -> List[np.ndarray]:
    key = jax.random.key(23)
    prefix = np.asarray(jax.random.randint(key, (PREFIX_LEN,), 0,
                                           cfg.vocab_size))
    out = []
    for i, sl in enumerate(SUFFIX_LENS):
        tail = np.asarray(jax.random.randint(jax.random.fold_in(key, i + 1),
                                             (sl,), 0, cfg.vocab_size))
        out.append(np.concatenate([prefix, tail]))
    # a retried/duplicate request: identical to the first prompt, so it
    # shares the partially-filled boundary page too and its first decode
    # insert exercises the fused copy-on-write path
    out.append(out[0].copy())
    return out


def serve_trace(cfg: ModelConfig, params, prompts, *,
                sharing: bool, tracer: Tracer = None) -> Dict:
    engine = Engine(cfg, params, ServeConfig(max_len=MAX_LEN))
    pool = engine.init_paged(num_pages=NUM_PAGES, page_size=PAGE_SIZE,
                             decode_batch=DECODE_BATCH,
                             prefix_sharing=sharing)
    sched = PagedLLMScheduler([engine],
                              PagedLLMConfig(max_new_tokens=MAX_NEW),
                              tracer=tracer)
    sched.warmup(sorted({len(p) for p in prompts}))
    pool.peak_in_use = 0                   # don't count warmup
    engine.prefill_tokens_computed = 0
    engine.prefill_tokens_shared = 0
    engine.cow_count = 0
    outs: List[np.ndarray] = []

    async def run_and_collect():
        async with sched:
            # the first request is resident (registered in the prefix
            # index) before any follower admits: per-engine admissions
            # are serialized by the worker, so one submission order
            # exercises first-prefills / followers-map deterministically
            handles = [sched.submit(p, max_new_tokens=MAX_NEW)
                       for p in prompts]
            outs.extend(await asyncio.gather(*handles))

    t0 = time.time()
    asyncio.run(run_and_collect())
    wall = time.time() - t0
    snap = sched.snapshot()
    assert snap["completed"] == len(prompts) and snap["failed"] == 0, snap
    stats = snap["pools"][0]
    assert stats["pages_in_use"] == 0, f"pages leaked: {stats}"
    n_params = sum(int(np.prod(np.shape(x)))
                   for x in jax.tree.leaves(params))
    per_page = pool_bytes_per_page(cfg, PAGE_SIZE)
    return {
        "wall_s": wall,
        "outputs": [np.asarray(o) for o in outs],
        "prefill_tokens_computed": engine.prefill_tokens_computed,
        "prefill_tokens_shared": engine.prefill_tokens_shared,
        # ~2 * params FLOPs per prefill token (dense decoder forward)
        "prefill_flops": 2 * n_params * engine.prefill_tokens_computed,
        "peak_unique_pages": stats["peak_pages_in_use"],
        "cache_bytes": stats["peak_pages_in_use"] * per_page,
        "cow_copies": snap["cow_copies"],
        "mixed_admission_batches": snap["mixed_admission_batches"],
        "tokens_generated": snap["tokens_generated"],
    }


def run() -> None:
    cfg = bench_config()
    params = tf.init_params(cfg, jax.random.key(0))
    prompts = _prompts(cfg)
    trace = common.trace_dest("prefix_sharing")
    tr_base = Tracer() if trace else None
    tr_shared = Tracer()        # always live: the fused-COW assert reads it
    base = serve_trace(cfg, params, prompts, sharing=False, tracer=tr_base)
    shared = serve_trace(cfg, params, prompts, sharing=True, tracer=tr_shared)
    common.export_trace(tr_base, common.tag_trace(trace, "baseline"))
    if trace:
        common.export_trace(tr_shared, common.tag_trace(trace, "sharing"))

    # ---- the sharing contract, asserted --------------------------------
    # divergent-tail followers map the aligned prefix pages; the
    # duplicate of prompt 0 maps everything but its final token
    want_shared = (len(SUFFIX_LENS) - 1) * PREFIX_LEN + len(prompts[0]) - 1
    assert shared["prefill_tokens_shared"] == want_shared, (
        "every follower must map the whole shared prefix: the prefix is "
        f"prefilled exactly once, got {shared['prefill_tokens_shared']} "
        f"shared tokens, want {want_shared}")
    assert shared["cow_copies"] >= 1, (
        "the duplicate prompt must trigger at least one boundary-page COW")
    assert base["prefill_tokens_shared"] == 0
    assert shared["peak_unique_pages"] < base["peak_unique_pages"], (
        f"sharing must hold strictly fewer unique pages: "
        f"{shared['peak_unique_pages']} vs {base['peak_unique_pages']}")
    for out_b, out_s in zip(base["outputs"], shared["outputs"]):
        np.testing.assert_array_equal(out_b, out_s)   # parity across modes

    # COW is fused into the decode insert: the trace must carry one
    # "cow" instant (fused=True) per copy and NO standalone copy_page
    # dispatch — a separate copy program would be the old two-call path
    from repro.serving.observability.tracer import INSTANT
    evs = tr_shared.events()
    cow_evs = [e for e in evs if e[2] == "cow" and e[1] == INSTANT]
    assert len(cow_evs) == shared["cow_copies"], (
        f"{len(cow_evs)} cow instants vs {shared['cow_copies']} copies")
    assert all(e[6].get("fused") is True for e in cow_evs)
    # executor calls are all named repro.executor.run, their op in args
    assert not [e for e in evs if "copy_page" in e[2]
                or "copy_page" in str((e[6] or {}).get("op", ""))], (
        "standalone page-copy dispatch found: COW is not fused")

    flops_saved = 1.0 - (shared["prefill_flops"]
                         / max(base["prefill_flops"], 1))
    page_saving = base["peak_unique_pages"] / max(
        shared["peak_unique_pages"], 1)
    common.emit(
        "prefix_sharing_baseline",
        base["wall_s"] * 1e6,
        f"prefill_tokens={base['prefill_tokens_computed']} "
        f"prefill_flops={base['prefill_flops']} "
        f"peak_unique_pages={base['peak_unique_pages']}")
    common.emit(
        "prefix_sharing_shared",
        shared["wall_s"] * 1e6,
        f"prefill_tokens={shared['prefill_tokens_computed']} "
        f"prefill_flops={shared['prefill_flops']} "
        f"prefill_flops_saved_frac={flops_saved:.3f} "
        f"peak_unique_pages={shared['peak_unique_pages']} "
        f"page_saving={page_saving:.2f}x "
        f"cow_copies={shared['cow_copies']} outputs=identical")
    drop = {"outputs"}
    common.emit_json("prefix_sharing", {
        "config": {"max_len": MAX_LEN, "max_new_tokens": MAX_NEW,
                   "page_size": PAGE_SIZE, "prefix_len": PREFIX_LEN,
                   "suffix_lens": SUFFIX_LENS, "num_pages": NUM_PAGES},
        "baseline": {k: v for k, v in base.items() if k not in drop},
        "sharing": {k: v for k, v in shared.items() if k not in drop},
        "prefill_flops_saved_frac": flops_saved,
        "peak_unique_page_saving_factor": page_saving,
        "outputs_identical": True,
        "cow_fused": True,          # asserted against the trace above
    })


# ---------------------------------------------------------------------------
# Host-tier memory hierarchy: cold-start vs host-hit TTFT
# ---------------------------------------------------------------------------

HOST_PREFIX_PAGES = 12                 # a long system prompt: 12 pages
HOST_PREFIX_LEN = HOST_PREFIX_PAGES * PAGE_SIZE   # = 192 tokens
HOST_TAIL_LEN = 9                      # divergent user tail
HOST_TRIALS = 7                        # median over repeats


def _host_prompt(cfg: ModelConfig) -> np.ndarray:
    key = jax.random.key(31)
    prefix = np.asarray(jax.random.randint(key, (HOST_PREFIX_LEN,), 0,
                                           cfg.vocab_size))
    tail = np.asarray(jax.random.randint(jax.random.fold_in(key, 1),
                                         (HOST_TAIL_LEN,), 0,
                                         cfg.vocab_size))
    return np.concatenate([prefix, tail])


def run_host_tier() -> None:
    cfg = bench_config()
    params = tf.init_params(cfg, jax.random.key(0))
    prompt = _host_prompt(cfg)

    # cold reference: a flat pool re-prefills the whole prompt every
    # time (release frees and unregisters everything)
    flat = Engine(cfg, params, ServeConfig(max_len=MAX_LEN))
    flat.init_paged(num_pages=NUM_PAGES, page_size=PAGE_SIZE,
                    decode_batch=DECODE_BATCH, prefix_sharing=True)
    ref = flat.generate_paged(prompt, max_new_tokens=MAX_NEW)["tokens"]
    cold_runs = [flat.generate_paged(prompt, max_new_tokens=MAX_NEW)
                 for _ in range(HOST_TRIALS)]

    # host-hit: every trial starts fully cold on the DEVICE (the
    # retained prefix dropped to host) but warm in the host tier, so
    # TTFT = restore (gather from host + one scatter) + tail prefill
    tiered = Engine(cfg, params, ServeConfig(max_len=MAX_LEN))
    tiered.init_paged(num_pages=NUM_PAGES, page_size=PAGE_SIZE,
                      decode_batch=DECODE_BATCH, prefix_sharing=True,
                      host_tier_pages=2 * HOST_PREFIX_PAGES)
    tiered.generate_paged(prompt, max_new_tokens=MAX_NEW)   # seed + compile
    tiered.pool.drop_retained()
    tiered.generate_paged(prompt, max_new_tokens=MAX_NEW)   # compile tail
    hit_runs = []
    for _ in range(HOST_TRIALS):
        tiered.pool.drop_retained()
        hit_runs.append(tiered.generate_paged(prompt,
                                              max_new_tokens=MAX_NEW))

    # ---- the tier contract, asserted -----------------------------------
    for r in cold_runs + hit_runs:      # bitwise-identical across tiers
        np.testing.assert_array_equal(r["tokens"], ref)
    ht = tiered.host_tier.stats()
    assert ht["hits"] >= HOST_TRIALS and ht["restored_pages"] >= (
        HOST_TRIALS * HOST_PREFIX_PAGES), ht
    ttft_cold = float(np.median([r["prefill_s"] for r in cold_runs]))
    ttft_hit = float(np.median([r["prefill_s"] for r in hit_runs]))
    assert ttft_hit < ttft_cold, (
        f"host-hit TTFT must beat cold-start: {ttft_hit * 1e6:.0f}us vs "
        f"{ttft_cold * 1e6:.0f}us")

    # ---- eviction + re-admission: spill-not-reject ---------------------
    # 17 allocatable pages; the long prompt seals holding 14, its
    # release retains 13 (12 full chunks + boundary), leaving 4 free.
    # The next admission needs 11 — a flat pool would raise OutOfPages
    # — and completes by spilling the cold prefix to host.
    small = Engine(cfg, params, ServeConfig(max_len=MAX_LEN))
    pool = small.init_paged(num_pages=18, page_size=PAGE_SIZE,
                            decode_batch=DECODE_BATCH, prefix_sharing=True,
                            host_tier_pages=2 * HOST_PREFIX_PAGES)
    small.generate_paged(prompt, max_new_tokens=MAX_NEW)
    other = np.asarray(jax.random.randint(jax.random.key(47), (160,), 0,
                                          cfg.vocab_size))
    need, _ = small.admission_page_cost(other, MAX_NEW)
    free_before = pool.num_free
    assert need > free_before, (need, free_before)   # flat pool: reject
    seq = small.prefill_into_pages(other, max_new_tokens=MAX_NEW)
    spilled = pool.stats()["pages_spilled"]
    assert spilled >= need - free_before, pool.stats()
    pool.release(seq)
    pool.drop_retained()
    assert pool.pages_in_use == 0, pool.stats()

    common.emit("host_tier_cold_ttft", ttft_cold * 1e6,
                f"prefix_pages={HOST_PREFIX_PAGES} prompt_len={len(prompt)}")
    common.emit(
        "host_tier_hit_ttft", ttft_hit * 1e6,
        f"speedup={ttft_cold / max(ttft_hit, 1e-9):.2f}x "
        f"restored_pages_per_hit={HOST_PREFIX_PAGES + 1} outputs=identical")
    common.emit_json("host_tier", {
        "config": {"max_len": MAX_LEN, "max_new_tokens": MAX_NEW,
                   "page_size": PAGE_SIZE, "prefix_len": HOST_PREFIX_LEN,
                   "prompt_len": len(prompt), "num_pages": NUM_PAGES,
                   "host_tier_pages": 2 * HOST_PREFIX_PAGES,
                   "trials": HOST_TRIALS},
        "ttft_cold_us": ttft_cold * 1e6,
        "ttft_host_hit_us": ttft_hit * 1e6,
        "ttft_speedup": ttft_cold / max(ttft_hit, 1e-9),
        "outputs_identical": True,
        "host_tier": ht,
        "spill_not_reject": {"pages_needed": need,
                             "free_pages_before": free_before,
                             "pages_spilled": spilled,
                             "completed": True},
    })


if __name__ == "__main__":
    print("name,us_per_call,derived")
    run()
    run_host_tier()
