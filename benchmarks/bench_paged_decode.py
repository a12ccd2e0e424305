"""Ring-buffer vs paged KV cache at mixed request lengths, plus the
GQA-grouped decode-kernel contract (bytes/token and tokens/s vs the
per-head grid).

Closed-form demo on a random-init mini decoder (no accelerator, no
trained state): the same model serves a trace of requests with very
different prompt lengths two ways —

  ring    Engine.generate on one padded batch: every request is padded
          to the longest prompt, every batch slot reserves
          max_len KV slots, and the whole batch decodes in lockstep.
  paged   PagedLLMScheduler: requests arrive staggered, prefill into
          free pages, join the running decode batch at their own
          position, and free their pages the step they finish.

Reported per mode: decode tokens/s and the KV memory ceiling (ring:
batch x max_len reservation; paged: peak pages in use x bytes/page).
The run *asserts* the paged contract — at least one decode batch mixes
requests admitted at different times, and the pool accounting drains
to zero pages held — then emits the CSV row plus
results/BENCH_paged_decode.json.

  PYTHONPATH=src python -m benchmarks.bench_paged_decode
  PYTHONPATH=src python -m benchmarks.bench_paged_decode --trace out.json
  PYTHONPATH=src python -m benchmarks.run --only paged
"""
from __future__ import annotations

import asyncio
import functools
import time
from typing import Dict, List

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks import common
from repro.configs.base import LayerSpec, ModelConfig
from repro.models import transformer as tf
from repro.serving.engine import Engine, ServeConfig
from repro.serving.kv_cache import (pool_bytes_per_page, pool_bytes_per_token,
                                    ring_cache_bytes)
from repro.serving.observability import Tracer
from repro.serving.scheduler import PagedLLMConfig, PagedLLMScheduler

# both engines are provisioned to serve requests up to MAX_LEN tokens;
# the ring engine must reserve that worst case per batch slot, the
# paged engine only holds pages for tokens actually resident
MAX_LEN = 256
MAX_NEW = 24
PAGE_SIZE = 16
PROMPT_LENS = [8, 24, 12, 48, 16, 40, 8, 32]
DECODE_BATCH = 8
ARRIVAL_GAP_S = 0.002


def bench_config() -> ModelConfig:
    return ModelConfig(
        name="bench-paged", arch_type="dense", num_layers=2, d_model=64,
        d_ff=128, vocab_size=256,
        pattern=(LayerSpec(attn_kind="full"), LayerSpec(attn_kind="swa")),
        window=16, num_heads=4, num_kv_heads=2, head_dim=16,
        compute_dtype="float32", param_dtype="float32",
        kv_cache_dtype="float32")


def _prompts(cfg: ModelConfig) -> List[np.ndarray]:
    key = jax.random.key(11)
    return [np.asarray(jax.random.randint(jax.random.fold_in(key, i),
                                          (l,), 0, cfg.vocab_size))
            for i, l in enumerate(PROMPT_LENS)]


def bench_ring(cfg: ModelConfig, params, prompts) -> Dict:
    engine = Engine(cfg, params, ServeConfig(max_len=MAX_LEN))
    pmax = max(PROMPT_LENS)
    batch = np.zeros((len(prompts), pmax), np.int32)
    for i, p in enumerate(prompts):          # right-pad to the longest
        batch[i, :len(p)] = p
    engine.generate(jnp.asarray(batch), max_new_tokens=MAX_NEW)  # compile
    res = engine.generate(jnp.asarray(batch), max_new_tokens=MAX_NEW)
    return {
        "tokens_per_s": res["tokens_per_s"],
        "decode_s": res["decode_s"],
        "cache_bytes": ring_cache_bytes(cfg, len(prompts), MAX_LEN,
                                        jnp.float32),
        "padded_prompt_tokens": int(batch.size),
        "real_prompt_tokens": int(sum(PROMPT_LENS)),
    }


async def _drive_paged(sched: PagedLLMScheduler, prompts) -> None:
    async with sched:
        half = len(prompts) // 2
        handles = [sched.submit(p, max_new_tokens=MAX_NEW)
                   for p in prompts[:half]]
        # late arrivals join only after the first wave is mid-decode, so
        # the trace provably exercises join-a-running-batch admission
        while sched.decode_batches < 1:
            await asyncio.sleep(0.001)
        for p in prompts[half:]:
            handles.append(sched.submit(p, max_new_tokens=MAX_NEW))
            await asyncio.sleep(ARRIVAL_GAP_S)
        await asyncio.gather(*handles)


def bench_paged(cfg: ModelConfig, params, prompts,
                tracer: Tracer = None) -> Dict:
    engine = Engine(cfg, params, ServeConfig(max_len=MAX_LEN))
    # pool sized in pages for the trace's actual tokens, not B x max_len
    pool = engine.init_paged(num_pages=1 + 32, page_size=PAGE_SIZE,
                             decode_batch=DECODE_BATCH)
    sched = PagedLLMScheduler([engine], PagedLLMConfig(max_new_tokens=MAX_NEW),
                              tracer=tracer)
    sched.warmup(sorted(set(PROMPT_LENS)))
    pool.peak_in_use = 0                     # don't count warmup
    t0 = time.time()
    asyncio.run(_drive_paged(sched, prompts))
    wall = time.time() - t0
    snap = sched.snapshot()

    # ---- the paged contract, asserted via pool + batch accounting ----
    assert snap["completed"] == len(prompts) and snap["failed"] == 0, snap
    assert snap["mixed_admission_batches"] >= 1, \
        "no decode batch mixed requests admitted at different times"
    stats = snap["pools"][0]
    assert stats["pages_in_use"] == 0, \
        f"pages leaked after completion: {stats}"
    assert 0 < stats["peak_pages_in_use"] < stats["num_pages"], stats

    per_page = pool_bytes_per_page(cfg, PAGE_SIZE, jnp.float32)
    busy_s = sum(snap["utilization"]) * snap["elapsed_s"]
    return {
        # busy = decode-time only, the key comparable to the ring
        # engine's tokens_per_s; wall additionally includes prefill,
        # staggered arrivals, and event-loop overhead
        "tokens_per_s": snap["tokens_generated"] / max(busy_s, 1e-9),
        "wall_tokens_per_s": snap["tokens_generated"] / max(wall, 1e-9),
        "wall_s": wall,
        "decode_busy_s": busy_s,
        "decode_batches": snap["decode_batches"],
        "mixed_admission_batches": snap["mixed_admission_batches"],
        "tokens_generated": snap["tokens_generated"],
        "peak_pages_in_use": stats["peak_pages_in_use"],
        "num_pages": stats["num_pages"],
        "page_size": stats["page_size"],
        "bytes_per_page": per_page,
        # pool STORAGE per token — the roofline's floor on what one
        # full-stack decode step must re-read per token per layer
        "pool_bytes_per_token": pool_bytes_per_token(cfg, PAGE_SIZE,
                                                     jnp.float32),
        "cache_bytes": stats["peak_pages_in_use"] * per_page,
        "mean_batch_fill": snap["mean_batch_fill"],
    }


def bench_kernel_grouping() -> Dict:
    """Grouped (KV-head grid) vs per-head paged decode kernel on a g=8
    GQA config: token-identical outputs, analytic HBM bytes/token ratio
    of exactly K/H, and steady-state step time (jitted interpret-mode
    Pallas, compile excluded — execution cost tracks the grid, which is
    g-fold smaller grouped).  The asserts ARE the PR's perf contract.
    """
    from repro.kernels import paged_attention as pk
    B, H, K, hd, ps, M = 4, 8, 1, 16, 8, 4           # g = 8 (MQA-like GQA)
    g = H // K
    pages = 1 + B * M
    rng = np.random.RandomState(7)
    q = jnp.asarray(rng.randn(B, H, hd), jnp.float32)
    k_pages = jnp.asarray(rng.randn(pages, K, ps, hd), jnp.float32)
    v_pages = jnp.asarray(rng.randn(pages, K, ps, hd), jnp.float32)
    bt = np.arange(1, 1 + B * M).reshape(B, M).astype(np.int32)
    lengths = np.array([3, 11, 25, 32], np.int32)    # mixed: short rows
    btj, lj = jnp.asarray(bt), jnp.asarray(lengths)  # skip pages

    outs: Dict[bool, np.ndarray] = {}
    step_s: Dict[bool, float] = {}
    for grouped in (False, True):
        f = jax.jit(functools.partial(pk.paged_attention, grouped=grouped,
                                      interpret=True))
        outs[grouped] = np.asarray(f(q, k_pages, v_pages, btj, lj))
        best = float("inf")
        for _ in range(20):
            t0 = time.perf_counter()
            f(q, k_pages, v_pages, btj, lj).block_until_ready()
            best = min(best, time.perf_counter() - t0)
        step_s[grouped] = best

    hbm = {grouped: pk.decode_hbm_bytes(k_pages, v_pages, bt, lengths,
                                        num_q_heads=H, grouped=grouped)
           for grouped in (False, True)}
    res = {
        "config": {"batch": B, "num_heads": H, "num_kv_heads": K,
                   "group": g, "head_dim": hd, "page_size": ps,
                   "pages_per_row": M, "lengths": lengths.tolist()},
        "hbm_bytes_per_token": {
            "grouped": hbm[True] / B,
            "per_head": hbm[False] / B,
            "ratio": hbm[True] / hbm[False],
        },
        "step_us": {"grouped": step_s[True] * 1e6,
                    "per_head": step_s[False] * 1e6},
        "tokens_per_s": {"grouped": B / step_s[True],
                         "per_head": B / step_s[False]},
        "token_identical": bool(np.array_equal(outs[True], outs[False])),
    }
    # ---- the grouped-kernel contract, asserted -----------------------
    assert res["token_identical"], \
        "grouped kernel output diverged from the per-head kernel"
    assert hbm[True] / hbm[False] <= 1 / g + 0.15, \
        f"grouped bytes/token {hbm[True] / hbm[False]:.3f} of per-head " \
        f"exceeds 1/g + 0.15 = {1 / g + 0.15:.3f} at g={g}"
    assert res["tokens_per_s"]["grouped"] > res["tokens_per_s"]["per_head"], \
        f"grouped decode not faster: {res['step_us']}"
    return res


def run() -> None:
    cfg = bench_config()
    params = tf.init_params(cfg, jax.random.key(0))
    prompts = _prompts(cfg)
    ring = bench_ring(cfg, params, prompts)
    trace = common.trace_dest("paged_decode")   # ring mode has no scheduler
    tracer = Tracer() if trace else None
    paged = bench_paged(cfg, params, prompts, tracer=tracer)
    common.export_trace(tracer, trace)
    kernel = bench_kernel_grouping()

    saving = ring["cache_bytes"] / max(paged["cache_bytes"], 1)
    common.emit(
        "paged_decode_ring",
        ring["decode_s"] * 1e6,
        f"tokens_per_s={ring['tokens_per_s']:.1f} "
        f"cache_bytes={ring['cache_bytes']} "
        f"padded_prompt_tokens={ring['padded_prompt_tokens']} "
        f"real_prompt_tokens={ring['real_prompt_tokens']}")
    common.emit(
        "paged_decode_paged",
        paged["wall_s"] * 1e6,
        f"tokens_per_s={paged['tokens_per_s']:.1f} "
        f"wall_tokens_per_s={paged['wall_tokens_per_s']:.1f} "
        f"cache_bytes={paged['cache_bytes']} "
        f"peak_pages={paged['peak_pages_in_use']}/{paged['num_pages']} "
        f"mixed_admission_batches={paged['mixed_admission_batches']} "
        f"batch_fill={paged['mean_batch_fill']:.2f} "
        f"cache_saving={saving:.2f}x pages_freed=all")
    common.emit(
        "paged_decode_kernel",
        kernel["step_us"]["grouped"],
        f"grouped_tokens_per_s={kernel['tokens_per_s']['grouped']:.1f} "
        f"per_head_tokens_per_s={kernel['tokens_per_s']['per_head']:.1f} "
        f"hbm_bytes_per_token={kernel['hbm_bytes_per_token']['grouped']:.0f} "
        f"bytes_ratio={kernel['hbm_bytes_per_token']['ratio']:.3f} "
        f"token_identical={kernel['token_identical']}")
    common.emit_json("paged_decode", {
        "config": {"max_len": MAX_LEN, "max_new_tokens": MAX_NEW,
                   "page_size": PAGE_SIZE, "prompt_lens": PROMPT_LENS,
                   "decode_batch": DECODE_BATCH},
        "ring": ring,
        "paged": paged,
        "kernel": kernel,
        # the bench-trajectory key: measured decode K/V HBM bytes per
        # generated token of the grouped kernel on the g=8 microbench
        "hbm_bytes_per_token": kernel["hbm_bytes_per_token"]["grouped"],
        "cache_bytes_saving_factor": saving,
    })


if __name__ == "__main__":
    print("name,us_per_call,derived")
    run()
