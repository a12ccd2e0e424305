"""Serving launcher: one model through the paged scheduler.

  python -m repro.launch.serve --arch olmo-1b --smoke --requests 4 --tokens 16

Parameters are bf16 from a fixed seed; requests are random prompts
served by ``PagedLLMScheduler`` over one ``InProcessBackend`` whose
``Engine`` keeps its KV in a paged pool.  ``chip_smoke.py`` builds the
same engine at full width from the helpers below.
"""
from __future__ import annotations

import argparse
import asyncio
import contextlib
import sys
from typing import List, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import SingleDeviceSharding

from repro.configs import get_config, get_smoke_config
from repro.configs.base import ModelConfig
from repro.launch.compile_cache import enable_compile_cache
from repro.models import transformer as tf
from repro.serving.backend import InProcessBackend
from repro.serving.engine import Engine, ServeConfig
from repro.serving.scheduler import (PagedLLMConfig, PagedLLMScheduler,
                                     SamplingParams)

SEED = 0
PAGE_SIZE = 64                  # tokens per KV page
FINISHED_OK = ("length", "stop")


def serving_params(cfg: ModelConfig, device=None):
    """bf16 parameters of ``cfg`` from ``SEED``, made by one jitted
    program on ``device`` (JAX's default device when None)."""
    def init(key):
        return jax.tree.map(lambda x: x.astype(jnp.bfloat16),
                            tf.init_params(cfg, key))
    out = None if device is None else SingleDeviceSharding(device)
    return jax.jit(init, out_shardings=out)(jax.random.key(SEED))


def paged_engine(cfg: ModelConfig, params, *, max_len: int, num_pages: int,
                 device=None) -> Engine:
    """An ``Engine`` with a pool of ``num_pages`` pages of ``PAGE_SIZE``
    tokens, allocated on ``device`` (JAX's default device when None).
    Its jitted steps run where the parameters are, so pass parameters
    placed on the same device."""
    engine = Engine(cfg, params, ServeConfig(max_len=max_len))
    with (jax.default_device(device) if device is not None
          else contextlib.nullcontext()):
        engine.init_paged(num_pages=num_pages, page_size=PAGE_SIZE)
    return engine


def random_prompts(cfg: ModelConfig,
                   lengths: Sequence[int]) -> List[np.ndarray]:
    rng = np.random.default_rng(SEED)
    return [rng.integers(0, cfg.vocab_size, size=n, dtype=np.int32)
            for n in lengths]


async def serve(sched: PagedLLMScheduler, prompts: Sequence[np.ndarray],
                max_new_tokens: int) -> List[Tuple[np.ndarray, str]]:
    """Serve every prompt; returns (prompt + generated tokens,
    finish_reason) per request.  A failed request raises."""
    async with sched:
        handles = [sched.submit(p, SamplingParams(
            max_new_tokens=max_new_tokens)) for p in prompts]
        outs = [np.asarray(await h) for h in handles]
    return [(o, h.request.finish_reason) for o, h in zip(outs, handles)]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="olmo-1b")
    ap.add_argument("--smoke", action="store_true",
                    help="reduced-width config of --arch")
    ap.add_argument("--requests", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=64)
    ap.add_argument("--tokens", type=int, default=16)
    args = ap.parse_args(argv)

    print(f"compile cache: {enable_compile_cache()}")
    cfg = get_smoke_config(args.arch) if args.smoke else get_config(args.arch)
    max_len = args.prompt_len + args.tokens
    engine = paged_engine(
        cfg, serving_params(cfg), max_len=max_len,
        num_pages=1 + args.requests * -(-max_len // PAGE_SIZE))
    sched = PagedLLMScheduler(backends=[InProcessBackend(engine)],
                              cfg=PagedLLMConfig(max_new_tokens=args.tokens))
    prompts = random_prompts(cfg, [args.prompt_len] * args.requests)
    results = asyncio.run(serve(sched, prompts, args.tokens))
    reasons = [r for _, r in results]
    print(f"{cfg.name}: {len(results)} requests, "
          f"{sum(len(o) - len(p) for (o, _), p in zip(results, prompts))} "
          f"tokens generated, finish reasons {reasons}, "
          f"failed {sched.snapshot()['failed']}")
    return 0 if all(r in FINISHED_OK for r in reasons) else 1


if __name__ == "__main__":
    sys.exit(main())
