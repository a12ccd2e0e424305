"""``python -m repro.serving.cluster.serve`` — one cluster host.

Stands up a deterministic tiny paged engine behind a
:class:`~repro.serving.cluster.transport.SocketBackendServer` and
serves until SIGTERM/SIGINT.  The model is seeded (``--model-seed``),
so every host built with the same flags holds bitwise-identical
parameters — which is what makes the cluster tests' token-identity
assertions meaningful: a router output must match a local engine
built by :func:`build_tiny_backend` with the same arguments.

Prints ``LISTENING <port>`` on stdout once the socket is bound (port
0 asks the kernel), so a parent process can spawn N hosts on ephemeral
ports and scrape where they landed.  The shared auth secret comes from
``--secret`` or ``REPRO_CLUSTER_SECRET``; without either, the dev
default is accepted only on loopback binds — a non-loopback ``--bind``
refuses to start rather than serve with a secret anyone can read out
of the source.  When
``REPRO_TRACE_DIR`` is set, a host-labelled tracer records the whole
run and exports ``trace_cluster_<label>.json`` there on shutdown —
merged multi-host traces render each host as its own Perfetto process
group because every track is prefixed ``<label>:``.
"""
from __future__ import annotations

import argparse
import asyncio
import contextlib
import os
import signal
import sys

from repro.configs.base import ModelConfig
from repro.launch.compile_cache import enable_compile_cache
from repro.models import transformer as tf
from repro.serving.backend import InProcessBackend
from repro.serving.cluster.transport import SocketBackendServer
from repro.serving.engine import Engine, ServeConfig
from repro.serving.observability import Tracer


def tiny_model_config(scale: int = 1) -> ModelConfig:
    """The cluster hosts' deterministic tiny model (same shape family
    as the test zoo: 2 layers, GQA 4/2 heads, float32 end to end so
    CPU runs are bitwise reproducible).  ``scale`` widens d_model /
    head_dim / d_ff together: the ITL benchmark runs scale 2 so the
    decode step costs a few milliseconds and the transport's fixed
    per-token overhead sits at the fraction it would occupy on a real
    model, instead of dominating a sub-2ms toy step."""
    return ModelConfig(name=f"cluster-tiny-x{scale}", arch_type="dense",
                       num_layers=2, d_model=32 * scale, d_ff=64 * scale,
                       vocab_size=64,
                       num_heads=4, num_kv_heads=2, head_dim=8 * scale,
                       compute_dtype="float32", param_dtype="float32",
                       kv_cache_dtype="float32")


def build_tiny_backend(*, num_pages: int = 64, page_size: int = 4,
                       decode_batch: int = 4, max_len: int = 64,
                       model_seed: int = 0, host_tier_pages: int = 0,
                       prefix_sharing: bool = True,
                       model_scale: int = 1) -> InProcessBackend:
    """One host's serving backend.  Deterministic in its arguments:
    same flags ⇒ same params ⇒ token-identical outputs across hosts
    and against a local reference engine."""
    import jax

    cfg = tiny_model_config(model_scale)
    params = tf.init_params(cfg, jax.random.key(model_seed))
    engine = Engine(cfg, params, ServeConfig(max_len=max_len))
    engine.init_paged(num_pages=num_pages, page_size=page_size,
                      decode_batch=decode_batch,
                      prefix_sharing=prefix_sharing,
                      host_tier_pages=host_tier_pages)
    return InProcessBackend(engine, name=f"paged:{cfg.name}")


def _parser() -> argparse.ArgumentParser:
    p = argparse.ArgumentParser(
        prog="python -m repro.serving.cluster.serve",
        description="Serve one cluster host over the socket transport.")
    p.add_argument("--bind", default="127.0.0.1")
    p.add_argument("--port", type=int, default=0,
                   help="0 = kernel-assigned (scrape LISTENING line)")
    p.add_argument("--host-label", default=None,
                   help="trace/process label (default: host-<port>)")
    p.add_argument("--secret", default=None,
                   help="shared auth secret (default: REPRO_CLUSTER_SECRET;"
                        " required, via either, for non-loopback --bind)")
    p.add_argument("--num-pages", type=int, default=64)
    p.add_argument("--page-size", type=int, default=4)
    p.add_argument("--decode-batch", type=int, default=4)
    p.add_argument("--max-len", type=int, default=64)
    p.add_argument("--model-seed", type=int, default=0)
    p.add_argument("--model-scale", type=int, default=1,
                   help="widen d_model/head_dim/d_ff by this factor "
                        "(benchmarks use 2 for a realistic decode step)")
    p.add_argument("--host-tier-pages", type=int, default=0,
                   help=">0 keeps released prefixes restorable (and "
                        "advertised in the placement digest)")
    p.add_argument("--no-prefix-sharing", action="store_true")
    return p


async def _amain(args: argparse.Namespace) -> int:
    backend = build_tiny_backend(
        num_pages=args.num_pages, page_size=args.page_size,
        decode_batch=args.decode_batch, max_len=args.max_len,
        model_seed=args.model_seed, host_tier_pages=args.host_tier_pages,
        prefix_sharing=not args.no_prefix_sharing,
        model_scale=args.model_scale)
    server = SocketBackendServer(backend, host=args.bind, port=args.port,
                                 secret=args.secret,
                                 host_label=args.host_label or "pending")
    await server.start()
    label = args.host_label or f"host-{server.port}"
    server.host_label = label

    tracer = None
    trace_dir = os.environ.get("REPRO_TRACE_DIR")
    if trace_dir:
        tracer = Tracer(host=label)
        backend.bind_tracer(tracer)

    print(f"LISTENING {server.port}", flush=True)

    stop = asyncio.Event()
    loop = asyncio.get_running_loop()
    for sig in (signal.SIGTERM, signal.SIGINT):
        with contextlib.suppress(NotImplementedError):
            loop.add_signal_handler(sig, stop.set)
    await stop.wait()
    await server.close()
    if tracer is not None:
        os.makedirs(trace_dir, exist_ok=True)
        tracer.export(os.path.join(trace_dir,
                                   f"trace_cluster_{label}.json"))
    return 0


def main(argv=None) -> int:
    args = _parser().parse_args(argv)
    enable_compile_cache()
    try:
        return asyncio.run(_amain(args))
    except KeyboardInterrupt:
        return 0


if __name__ == "__main__":
    sys.exit(main())
