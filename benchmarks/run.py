# One function per paper table. Print ``name,us_per_call,derived`` CSV.
"""Benchmark harness entry point.

  PYTHONPATH=src python -m benchmarks.run [--only fig1,table1,...]

Tables/figures (each also runnable standalone as benchmarks.<name>):
  fig1    — cross-model expertise matrix            (paper Fig. 1)
  table1  — mobile/cloud collaborative inference    (paper Table I)
  table2  — cloud-API multiplexing                  (paper Table II)
  fig6    — contrastive embedding separation        (paper Fig. 3/6)
  mux_kernel — fused router-head microbenchmark     (serving hot path)
  scheduler  — continuous-batching goodput vs load  (serving runtime)
  paged      — ring vs paged KV decode, mixed lens  (serving memory/runtime)
  prefix     — prefix-sharing COW pages vs private  (serving memory/prefill)
  host_tier  — cold-start vs host-hit TTFT, spill   (serving memory hierarchy)
  chunked    — chunked vs serial prefill TTFT       (serving streaming/TTFT)
  disagg     — disaggregated vs interleaved prefill (serving backends/ITL)
  obs_overhead — traced vs untraced throughput      (serving observability)
  spec_decode — speculative mux-drafted decoding    (serving latency/decode)
  cluster    — multi-host router over sockets       (serving cluster/ITL)
  roofline   — dry-run roofline table               (EXPERIMENTS §Roofline)

``--trace-dir DIR`` makes every serving benchmark also export a Chrome
trace-event JSON (load in Perfetto / chrome://tracing) to DIR.

State (trained zoo + muxes) is cached under results/bench_state; set
REPRO_BENCH_SCALE=smoke for a fast pass, =full for paper-scale steps.
"""
from __future__ import annotations

import argparse
import os
import sys
import time


def bench_mux_kernel():
    """Microbenchmark of the fused mux head (jnp oracle vs interpret
    kernel path) — wall time per call on this host plus FLOPs."""
    import jax
    import jax.numpy as jnp
    from benchmarks import common
    from repro.kernels import ref

    b, m, n = 1024, 64, 6
    key = jax.random.key(0)
    meta = jax.random.normal(key, (b, m))
    v = jax.random.normal(key, (n, m))
    cost = jnp.arange(1.0, n + 1)
    f = jax.jit(lambda a: ref.mux_score_ref(a, v, cost))
    f(meta).block_until_ready()
    t0 = time.time()
    iters = 50
    for _ in range(iters):
        f(meta).block_until_ready()
    us = (time.time() - t0) * 1e6 / iters
    flops = 2 * b * m * n
    common.emit("mux_kernel", us, f"requests={b} flops_per_call={flops}")


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default="",
                    help="comma list: fig1,table1,table2,fig6,mux_kernel,"
                         "scheduler,paged,prefix,host_tier,chunked,disagg,"
                         "obs_overhead,spec_decode,cluster,roofline")
    ap.add_argument("--trace-dir", default="",
                    help="export a Chrome trace JSON per serving benchmark "
                         "into this directory (Perfetto-loadable)")
    args, _ = ap.parse_known_args()
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    only = set(args.only.split(",")) if args.only else None
    if args.trace_dir:
        # benchmarks pick the destination up via common.trace_dest()
        os.environ["REPRO_TRACE_DIR"] = args.trace_dir

    def want(name):
        return only is None or name in only

    print("name,us_per_call,derived")
    t0 = time.time()
    state = None
    if want("fig1") or want("table1") or want("table2") or want("fig6"):
        from benchmarks import common
        state = common.get_state()
    if want("fig1"):
        from benchmarks import fig1_expertise
        fig1_expertise.run(state)
    if want("table1"):
        from benchmarks import table1_mobile_cloud
        table1_mobile_cloud.run(state)
    if want("table2"):
        from benchmarks import table2_cloud_api
        table2_cloud_api.run(state)
    if want("fig6"):
        from benchmarks import fig6_separation
        fig6_separation.run(state)
    if want("mux_kernel"):
        bench_mux_kernel()
    if want("scheduler"):
        from benchmarks import bench_scheduler
        bench_scheduler.run()
    if want("paged"):
        from benchmarks import bench_paged_decode
        bench_paged_decode.run()
    if want("prefix"):
        from benchmarks import bench_prefix_sharing
        bench_prefix_sharing.run()
    if want("host_tier"):
        from benchmarks import bench_prefix_sharing
        bench_prefix_sharing.run_host_tier()
    if want("chunked"):
        from benchmarks import bench_chunked_prefill
        bench_chunked_prefill.run()
    if want("disagg"):
        from benchmarks import bench_disagg
        bench_disagg.run()
    if want("obs_overhead"):
        from benchmarks import bench_obs_overhead
        bench_obs_overhead.run()
    if want("spec_decode"):
        from benchmarks import bench_spec_decode
        bench_spec_decode.run()
    if want("cluster"):
        from benchmarks import bench_cluster
        bench_cluster.run()
    if want("roofline"):
        from benchmarks import roofline
        roofline.run()
    print(f"# total wall: {time.time() - t0:.1f}s")


if __name__ == '__main__':
    main()
