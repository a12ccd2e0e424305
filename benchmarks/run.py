# One function per paper table. Print ``name,us_per_call,derived`` CSV.
"""Benchmark harness entry point.

  PYTHONPATH=src python -m benchmarks.run [--only fig1,table1,...]

Tables/figures (each also runnable standalone as benchmarks.<name>):
  fig1    — cross-model expertise matrix            (paper Fig. 1)
  table1  — mobile/cloud collaborative inference    (paper Table I)
  table2  — cloud-API multiplexing                  (paper Table II)
  fig6    — contrastive embedding separation        (paper Fig. 3/6)
  roofline — dry-run roofline table                 (EXPERIMENTS §Roofline)

Serving speed is measured on the chip by ``chipbench/``, not here.

State (trained zoo + muxes) is cached under results/bench_state; set
REPRO_BENCH_SCALE=smoke for a fast pass, =full for paper-scale steps.
"""
from __future__ import annotations

import argparse
import time


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default="",
                    help="comma list: fig1,table1,table2,fig6,roofline")
    args, _ = ap.parse_known_args()
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    only = set(args.only.split(",")) if args.only else None

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
    if want("roofline"):
        from benchmarks import roofline
        roofline.run()
    print(f"# total wall: {time.time() - t0:.1f}s")


if __name__ == '__main__':
    main()
