"""Roofline table from compiled-HLO dry-run terms (EXPERIMENTS.md
§Roofline).

Reads results/dryrun/*.json (written by repro.launch.dryrun) and
prints per (arch x shape x mesh): the three roofline terms, the
dominant bottleneck, MODEL_FLOPS/HLO_FLOPS, and per-device memory.
The paged decode kernel's measured roofline share comes from the chip
benchmark (``chipbench/``, metric ``paged_attn_roofline``).

  PYTHONPATH=src python -m benchmarks.roofline
"""
from __future__ import annotations

import glob
import json
import os
import time

from benchmarks import common

DRYRUN_DIR = os.environ.get("REPRO_DRYRUN_DIR", "results/dryrun")


def load_records(mesh: str = None):
    recs = []
    for path in sorted(glob.glob(os.path.join(DRYRUN_DIR, "*.json"))):
        with open(path) as f:
            r = json.load(f)
        if mesh and r.get("mesh") != mesh:
            continue
        recs.append(r)
    return recs


def run():
    t0 = time.time()
    recs = load_records(mesh="16x16")
    if not recs:
        print("# roofline: no dry-run artifacts found "
              f"(run python -m repro.launch.dryrun --all; dir={DRYRUN_DIR})")
        common.emit("roofline", 0.0, "no_dryrun_artifacts")
        return {}

    print("\n# Roofline — single-pod (16x16), per-device terms from compiled HLO")
    print("arch,shape,compute_ms,memory_ms,collective_ms,bottleneck,"
          "model/hlo_flops,mem_per_dev_GiB")
    worst = None
    coll_bound = None
    for r in recs:
        roof = r["roofline"]
        mem = ((r["memory"]["argument_bytes"] or 0)
               + r["memory"].get("temp_bytes_tpu_estimate",
                                 r["memory"].get("temp_bytes") or 0)) / 2 ** 30
        ratio = r["flops_ratio_model_over_hlo"]
        print(f"{r['arch']},{r['shape']},{roof['compute_s'] * 1e3:.2f},"
              f"{roof['memory_s'] * 1e3:.2f},{roof['collective_s'] * 1e3:.2f},"
              f"{roof['bottleneck']},{ratio:.2f},{mem:.2f}")
        dom = max(roof["compute_s"], roof["memory_s"], roof["collective_s"])
        frac = roof["compute_s"] / max(dom, 1e-12)
        if worst is None or frac < worst[0]:
            worst = (frac, r["arch"], r["shape"])
        cshare = roof["collective_s"] / max(dom, 1e-12)
        if roof["bottleneck"] == "collective" and (
                coll_bound is None or roof["collective_s"] > coll_bound[0]):
            coll_bound = (roof["collective_s"], r["arch"], r["shape"])
    us = (time.time() - t0) * 1e6 / max(len(recs), 1)
    derived = f"n={len(recs)}"
    if worst:
        derived += f" worst_compute_fraction={worst[1]}x{worst[2]}@{worst[0]:.3f}"
    if coll_bound:
        derived += f" most_collective_bound={coll_bound[1]}x{coll_bound[2]}"
    common.emit("roofline", us, derived)
    return {"records": recs, "worst": worst, "coll_bound": coll_bound}


if __name__ == "__main__":
    run()
