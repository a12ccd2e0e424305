#!/usr/bin/env python3
"""Serve olmo-1b at full width through the paged scheduler on a TPU.

    python chip_smoke.py               # one chip
    python chip_smoke.py --four-chips  # four replicas behind ClusterRouter

One chip: bf16 olmo-1b parameters from a fixed seed, an ``Engine`` with
a pool of 512 pages of 64 tokens, and ``PagedLLMScheduler`` over an
``InProcessBackend`` serving eight requests of mixed prompt length
(128 to 1024 tokens, 32 new tokens each).  Two of them carry the same
prompt, so prefix sharing and the fused copy-on-write decode run too.
The run fails unless JAX's backend is the TPU, the compiled decode step
holds the Pallas kernel (``tpu_custom_call``), every request finished
with ``length`` or ``stop`` and none failed, and the paged path's
logits at the first decode steps agree with the model's own uncached
forward pass over the same tokens within ``LOGIT_TOL``.

Four chips (``--four-chips``, and nothing else): four one-chip olmo-1b
replicas in this process, each with its parameters and page pool on
its own device and behind a loopback ``SocketBackendServer``, serve
one set of requests through ``ClusterRouter``; the outputs must be
token-identical to replica 0 serving them alone, and each replica's
arrays must sit on a device of its own.

No phase's exception is caught: a failure ends the run with a
traceback and a nonzero exit.  The last line of stdout is one JSON
object, ``{"ok": true, "device": {...}}``, and only a passing run
prints it.
"""
from __future__ import annotations

import argparse
import asyncio
import json
import os
import sys
import time

import numpy as np

ROOT = os.path.dirname(os.path.abspath(__file__))

# Sizes of the run; the tests shrink them to smoke width on the CPU.
ARCH = "olmo-1b"
NUM_PAGES = 512
NEW_TOKENS = 32
CHUNK_PAGES = 4                  # chunked prefill: 4-page chunks
# the first prompt is served twice (its twin is submitted once the
# first has its first token); 333 is not a page multiple, so the twin
# shares the partly filled boundary page and decode must copy it
PROMPT_LENS = (333, 128, 200, 512, 640, 777, 1024)
MAX_LEN = 1024 + 64
LOGIT_STEPS = 4                  # decode steps whose logits are checked
# bf16 parameters and activations through 16 layers: the paged step
# and the uncached forward round differently, so they agree to about
# 1% of the logits' range (0.0127 on a TPU v5e); attending one token
# short already moves the logits by over 10%
LOGIT_TOL = 0.05                 # max |paged - forward| / max |forward|
# four chips: prompts no longer than one chunk keep each replica's
# compile to two prefill shapes and the decode step
FOUR_CHIP_PROMPT_LENS = (100, 128, 200, 256, 110, 120, 230, 250)


def require(cond: bool, what: str) -> None:
    if not cond:
        raise RuntimeError(f"chip smoke failed: {what}")


def logits_error(cfg, params, tokens, prompt_len: int) -> float:
    """Teacher-forced comparison of the paged path with the model's
    uncached forward pass.  The paged side prefills ``prompt_len``
    tokens into a small pool, then runs ``LOGIT_STEPS`` decode steps on
    the tokens that followed; the reference runs ``tf.forward`` over
    the same tokens with no cache.  Returns max |paged - reference|
    over max |reference| across those ``LOGIT_STEPS + 1`` logit rows."""
    import jax
    import jax.numpy as jnp

    from repro.launch.serve import PAGE_SIZE
    from repro.models import transformer as tf

    n = prompt_len + LOGIT_STEPS
    pages = -(-(n + 1) // PAGE_SIZE)
    caches = tf.init_caches(cfg, 0, 0, num_pages=1 + pages,
                            page_size=PAGE_SIZE)
    bt = jnp.arange(1, 1 + pages, dtype=jnp.int32)[None]
    pad = -(-prompt_len // PAGE_SIZE) * PAGE_SIZE
    toks = np.zeros((1, pad), np.int32)
    toks[0, :prompt_len] = tokens[:prompt_len]
    prefill = jax.jit(lambda p, t, c, b, last: tf.prefill_paged(
        p, cfg, t, c, b, last))
    decode = jax.jit(lambda p, t, c, b, pos: tf.decode_step(
        p, cfg, t, c, pos, block_tables=b))
    logits, caches = prefill(params, toks, caches, bt, prompt_len - 1)
    rows = [logits[0, 0]]
    for pos in range(prompt_len, n):
        logits, caches = decode(params, jnp.asarray([[tokens[pos]]]), caches,
                                bt, jnp.asarray([pos], jnp.int32))
        rows.append(logits[0, 0])
    got = np.asarray(jnp.stack(rows), np.float32)

    def reference(p, t):
        h, _, _ = tf.forward(p, cfg, t)
        return tf.unembed(p, cfg, h[:, prompt_len - 1:])[0]

    want = np.asarray(jax.jit(reference)(params, jnp.asarray(tokens[None, :n])),
                      np.float32)
    require(bool(np.isfinite(got).all() and np.isfinite(want).all()),
            "non-finite logits")
    return float(np.abs(got - want).max() / np.abs(want).max())


async def _serve_with_twin(sched, prompts):
    """Submit the first prompt, then — once it has its first token, so
    its pages are indexed — its twin, then the rest."""
    from repro.serving.scheduler import EventType, SamplingParams
    async with sched:
        lead = sched.submit(prompts[0], SamplingParams(
            max_new_tokens=NEW_TOKENS, stream=True))
        async for ev in lead:
            if ev.type is EventType.FIRST_TOKEN:
                break
        handles = [lead] + [sched.submit(p, SamplingParams(
            max_new_tokens=NEW_TOKENS)) for p in [prompts[0]] + prompts[1:]]
        outs = [np.asarray(await h) for h in handles]
    return outs, [h.request.finish_reason for h in handles]


def one_chip(cfg) -> dict:
    """Serve ``cfg`` on JAX's default device and measure what the
    checks need.  Returns the report ``main`` checks and prints."""
    import jax

    from repro.launch.serve import (PAGE_SIZE, paged_engine, random_prompts,
                                    serving_params)
    from repro.serving.backend import InProcessBackend
    from repro.serving.scheduler import PagedLLMConfig, PagedLLMScheduler

    t0 = time.perf_counter()
    params = serving_params(cfg)
    jax.block_until_ready(params)
    engine = paged_engine(cfg, params, max_len=MAX_LEN, num_pages=NUM_PAGES)
    t_built = time.perf_counter()
    sched = PagedLLMScheduler(
        backends=[InProcessBackend(engine)],
        cfg=PagedLLMConfig(max_new_tokens=NEW_TOKENS,
                           prefill_chunk_pages=CHUNK_PAGES))
    # longer prompts run as chunks, whose one shape the warmup compiles
    sched.warmup([n for n in PROMPT_LENS if n <= CHUNK_PAGES * PAGE_SIZE])
    t_warm = time.perf_counter()
    # warmup shares and copies pages too: count only what serving adds
    shared0, cow0 = engine.prefill_tokens_shared, engine.cow_count
    prompts = random_prompts(cfg, PROMPT_LENS)
    outs, reasons = asyncio.run(_serve_with_twin(sched, prompts))
    t_served = time.perf_counter()
    decode_text = engine.lower_paged_decode().compile().as_text()
    err = logits_error(cfg, params, outs[0], len(prompts[0]))
    t_checked = time.perf_counter()
    return {
        "outs": outs, "prompts": [prompts[0]] + list(prompts),
        "reasons": reasons, "failed": sched.snapshot()["failed"],
        "prefill_tokens_shared": engine.prefill_tokens_shared - shared0,
        "cow_copies": engine.cow_count - cow0,
        "decode_has_kernel": "tpu_custom_call" in decode_text,
        "logits_rel_err": err,
        "seconds": {"build": t_built - t0, "warmup_compile": t_warm - t_built,
                    "serve": t_served - t_warm,
                    "checks": t_checked - t_served},
    }


def check_one_chip(report: dict) -> None:
    from repro.launch.serve import FINISHED_OK
    reasons = report["reasons"]
    require(all(r in FINISHED_OK for r in reasons),
            f"finish reasons {reasons}")
    require(report["failed"] == 0, f"{report['failed']} requests failed")
    require(report["prefill_tokens_shared"] > 0, "no prefix was shared")
    require(report["cow_copies"] > 0, "no copy-on-write decode ran")
    require(report["logits_rel_err"] <= LOGIT_TOL,
            f"logits error {report['logits_rel_err']} > {LOGIT_TOL}")


async def _serve_router(backends, prompts, pcfg):
    """Each backend behind a loopback socket server; one router over
    their clients serves ``prompts``."""
    from repro.launch.serve import serve
    from repro.serving.cluster import (ClusterRouter, SocketBackendServer,
                                       SocketClientBackend)
    from repro.serving.scheduler import PagedLLMScheduler

    servers = []
    try:
        for i, backend in enumerate(backends):
            srv = SocketBackendServer(backend, host_label=f"chip{i}")
            await srv.start()
            servers.append(srv)
        # replicas were warmed up: a long silence means a dead host
        clients = [SocketClientBackend("127.0.0.1", srv.port,
                                       name=f"sock:chip{i}", streaming=True,
                                       timeout_s=60.0)
                   for i, srv in enumerate(servers)]
        router = ClusterRouter(clients,
                               decode_batch_hint=backends[0].engine.decode_batch)
        sched = PagedLLMScheduler(backends=[router], cfg=pcfg)
        results = await serve(sched, prompts, NEW_TOKENS)
        return results, router.stats()["cluster"]
    finally:
        for srv in servers:
            await srv.close()


def four_chips(cfg, devices) -> dict:
    """One replica per device, served alone (replica 0) and as a
    cluster behind ``ClusterRouter``.  Returns the report ``main``
    checks and prints."""
    from repro.launch.serve import (PAGE_SIZE, paged_engine, random_prompts,
                                    serve, serving_params)
    from repro.serving.backend import InProcessBackend
    from repro.serving.scheduler import PagedLLMConfig, PagedLLMScheduler

    pcfg = PagedLLMConfig(max_new_tokens=NEW_TOKENS,
                          prefill_chunk_pages=CHUNK_PAGES)
    t0 = time.perf_counter()
    engines = []
    for dev in devices:
        engine = paged_engine(cfg, serving_params(cfg, device=dev),
                              max_len=MAX_LEN, num_pages=NUM_PAGES,
                              device=dev)
        InProcessBackend(engine).warmup(FOUR_CHIP_PROMPT_LENS,
                                        chunk_tokens=CHUNK_PAGES * PAGE_SIZE)
        engines.append(engine)
    t_built = time.perf_counter()
    prompts = random_prompts(cfg, FOUR_CHIP_PROMPT_LENS)
    alone = asyncio.run(serve(PagedLLMScheduler(
        backends=[InProcessBackend(engines[0])], cfg=pcfg), prompts,
        NEW_TOKENS))
    t_alone = time.perf_counter()
    routed, cluster = asyncio.run(_serve_router(
        [InProcessBackend(e, name=f"chip{i}") for i, e in enumerate(engines)],
        prompts, pcfg))
    t_routed = time.perf_counter()
    return {
        "alone": alone, "routed": routed, "prompts": prompts,
        "cluster": cluster,
        "replica_devices": [sorted(str(d) for d in e.devices())
                            for e in engines],
        "seconds": {"build_and_warmup": t_built - t0,
                    "serve_alone": t_alone - t_built,
                    "serve_routed": t_routed - t_alone},
    }


def check_four_chips(report: dict, devices) -> None:
    from repro.launch.serve import FINISHED_OK
    reasons = [r for _, r in report["alone"] + report["routed"]]
    require(all(r in FINISHED_OK for r in reasons),
            f"finish reasons {reasons}")
    require(report["cluster"]["requests_lost"] == 0, "requests were lost")
    same = [np.array_equal(a, b) for (a, _), (b, _)
            in zip(report["alone"], report["routed"])]
    require(all(same), f"router outputs differ from one replica: {same}")
    want = [[str(d)] for d in devices]
    require(report["replica_devices"] == want,
            f"replica devices {report['replica_devices']} != {want}")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the four-replica router phase")
    args = ap.parse_args(argv)

    import jax
    if jax.default_backend() != "tpu":
        print(f"chip_smoke: no TPU found (JAX backend is "
              f"{jax.default_backend()!r})", file=sys.stderr)
        return 1
    sys.path.insert(0, os.path.join(ROOT, "src"))
    from repro.configs import get_config
    from repro.launch.compile_cache import enable_compile_cache

    print(f"compile cache: {enable_compile_cache()}")
    devices = jax.devices()
    print(f"devices: {devices}")
    cfg = get_config(ARCH)
    print(f"config: {cfg.name} layers={cfg.num_layers} d_model={cfg.d_model} "
          f"heads={cfg.num_heads}/{cfg.num_kv_heads} head_dim={cfg.head_dim} "
          f"d_ff={cfg.d_ff} vocab={cfg.vocab_size}")
    if args.four_chips:
        require(len(devices) == 4, f"--four-chips needs 4 devices, "
                                   f"JAX sees {len(devices)}")
        report = four_chips(cfg, devices)
        print(f"served {len(report['prompts'])} requests alone on "
              f"{devices[0]} and through ClusterRouter over "
              f"{len(devices)} replicas")
        print(f"cluster: {report['cluster']}")
        print(f"replica devices: {report['replica_devices']}")
        print("seconds: " + ", ".join(f"{k} {v!r}"
                                      for k, v in report["seconds"].items()))
        check_four_chips(report, devices)
        print("router outputs token-identical to one replica: True")
    else:
        report = one_chip(cfg)
        generated = sum(len(o) - len(p)
                        for o, p in zip(report["outs"], report["prompts"]))
        print(f"served {len(report['outs'])} requests, {generated} tokens "
              f"generated, finish reasons {report['reasons']}, "
              f"failed {report['failed']}")
        print(f"prefix tokens shared {report['prefill_tokens_shared']}, "
              f"copy-on-write pages {report['cow_copies']}")
        print(f"decode step holds tpu_custom_call: "
              f"{report['decode_has_kernel']}")
        print(f"logits max rel error vs uncached forward: "
              f"{report['logits_rel_err']!r} (tolerance {LOGIT_TOL})")
        print("seconds: " + ", ".join(f"{k} {v!r}"
                                      for k, v in report["seconds"].items()))
        require(report["decode_has_kernel"],
                "the compiled decode step holds no tpu_custom_call")
        check_one_chip(report)
    d = devices[0]
    print(json.dumps({"ok": True, "device": {
        "platform": d.platform, "kind": d.device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
