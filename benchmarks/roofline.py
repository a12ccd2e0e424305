"""Roofline tables: compiled-HLO dry-run terms, and the paged decode
kernels' achieved vs peak HBM bandwidth (EXPERIMENTS.md §Roofline).

Default mode reads results/dryrun/*.json (written by
repro.launch.dryrun) and prints per (arch x shape x mesh): the three
roofline terms, the dominant bottleneck, MODEL_FLOPS/HLO_FLOPS, and
per-device memory.

``--paged`` runs every paged decode kernel variant (full / window /
chunked / int8 / MLA v_dim, each grouped and per-head) and reports its
analytic K/V bytes/token from the kernel's own grid accounting.  On a
TPU it also times the compiled kernel and reports achieved bytes/s
against the chip's published peak (common.device_peaks); off the chip
it reports the byte counts only, since a CPU or interpret-mode time is
not a device bandwidth.  It also folds in the
hbm_bytes_per_token field of results/BENCH_paged_decode.json; under CI
a missing bench artifact is a HARD FAILURE (nonzero exit), not a
silent zero-row pass — run ``benchmarks.run --only paged`` first.

  PYTHONPATH=src python -m benchmarks.roofline
  PYTHONPATH=src python -m benchmarks.roofline --paged
"""
from __future__ import annotations

import argparse
import functools
import glob
import json
import os
import sys
import time

from benchmarks import common

DRYRUN_DIR = os.environ.get("REPRO_DRYRUN_DIR", "results/dryrun")
BENCH_ARTIFACT = os.path.join("results", "BENCH_paged_decode.json")


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


# ---------------------------------------------------------------------------
# --paged: achieved vs peak bytes/s for every paged decode kernel variant
# ---------------------------------------------------------------------------

def _paged_inputs(variant: str, rng):
    """One decode-step problem per kernel variant.  Returns
    (call_kwargs, arrays) with arrays = (q, k_pages, v_pages, bt,
    lengths, k_scales, v_scales)."""
    import jax.numpy as jnp
    import numpy as np

    B, H, hd, ps, M = 4, 8, 16, 8, 4
    kk = 1 if variant == "mla_vdim" else 2
    pages = 1 + B * M
    q = jnp.asarray(rng.randn(B, H, hd), jnp.float32)
    k = rng.randn(pages, kk, ps, hd).astype(np.float32)
    v = rng.randn(pages, kk, ps, hd).astype(np.float32)
    bt = np.arange(1, 1 + B * M).reshape(B, M).astype(np.int32)
    lengths = np.array([3, 11, 25, 32], np.int32)
    kw = {}
    ks = vs = None
    if variant == "gqa_window":
        kw["window"] = 9
    elif variant == "gqa_chunked":
        kw["chunk"] = 16
    elif variant == "gqa_int8":
        # per-(slot, head) symmetric int8 quantization, like the pool's
        ks_np = np.abs(k).max(axis=-1) / 127.0 + 1e-8
        vs_np = np.abs(v).max(axis=-1) / 127.0 + 1e-8
        k = np.clip(np.round(k / ks_np[..., None]), -127, 127)
        v = np.clip(np.round(v / vs_np[..., None]), -127, 127)
        ks = jnp.asarray(ks_np[:, :, None, :], jnp.bfloat16)  # (P, K, 1, ps)
        vs = jnp.asarray(vs_np[:, :, None, :], jnp.bfloat16)
        k = k.astype(np.int8)
        v = v.astype(np.int8)
    elif variant == "mla_vdim":
        kw["v_dim"] = hd // 2
        v = k                           # v = leading features of the k slab
    dtype = jnp.int8 if variant == "gqa_int8" else jnp.float32
    return kw, (q, jnp.asarray(k, dtype), jnp.asarray(v, dtype),
                jnp.asarray(bt), jnp.asarray(lengths), ks, vs), bt, lengths


def run_paged(ci: bool = None):
    """Analytic K/V bytes/token per paged decode kernel variant; on a
    TPU also the compiled kernel's steady-state step time and achieved
    bytes/s against the chip's peak."""
    import jax
    import numpy as np
    from repro.kernels import paged_attention as pk

    if ci is None:
        ci = bool(os.environ.get("CI"))
    t_start = time.time()
    dev = jax.devices()[0]
    on_chip = dev.platform == "tpu"
    peak = (common.device_peaks(dev.device_kind)["hbm_bytes_per_s"]
            if on_chip else None)
    rng = np.random.RandomState(3)
    variants = ("gqa_full", "gqa_window", "gqa_chunked", "gqa_int8",
                "mla_vdim")
    print(f"\n# Roofline — paged decode kernels on {dev.device_kind}")
    if on_chip:
        print(f"# peak = {peak / 1e9:.1f} GB/s (common.DEVICE_PEAKS)")
        print("variant,kernel,hbm_bytes_per_token,tokens_per_s,"
              "achieved_GBps,achieved_pct")
    else:
        print("# off the chip: byte counts only, no times")
        print("variant,kernel,hbm_bytes_per_token")
    rows = []
    for variant in variants:
        kw, arrays, bt, lengths = _paged_inputs(variant, rng)
        q, k_pages, v_pages, btj, lj, ks, vs = arrays
        B = q.shape[0]
        for grouped in (True, False):
            bpt = pk.decode_hbm_bytes(
                k_pages, v_pages, bt, lengths, num_q_heads=q.shape[1],
                grouped=grouped, window=kw.get("window"),
                chunk=kw.get("chunk"), v_dim=kw.get("v_dim")) / B
            row = {"variant": variant,
                   "kernel": "grouped" if grouped else "per_head",
                   "hbm_bytes_per_token": bpt}
            rows.append(row)
            if not on_chip:
                print(f"{variant},{row['kernel']},{bpt:.0f}")
                continue
            f = jax.jit(functools.partial(
                pk.paged_attention, grouped=grouped, k_scales=ks,
                v_scales=vs, **kw))
            f(q, k_pages, v_pages, btj, lj).block_until_ready()  # compile
            best = float("inf")
            for _ in range(10):
                t0 = time.perf_counter()
                f(q, k_pages, v_pages, btj, lj).block_until_ready()
                best = min(best, time.perf_counter() - t0)
            tps = B / best
            row.update(tokens_per_s=tps, achieved_bytes_per_s=bpt * tps,
                       achieved_pct=100.0 * bpt * tps / peak)
            print(f"{variant},{row['kernel']},{bpt:.0f},{tps:.0f},"
                  f"{bpt * tps / 1e9:.3f},{row['achieved_pct']:.4f}")

    # fold in the smoke bench's measured bytes/token — and refuse to
    # pass silently when the artifact is missing under CI
    bench = None
    if os.path.exists(BENCH_ARTIFACT):
        with open(BENCH_ARTIFACT) as f:
            bench = json.load(f)
        print(f"# bench artifact: hbm_bytes_per_token="
              f"{bench.get('hbm_bytes_per_token')} ({BENCH_ARTIFACT})")
    elif ci:
        print(f"# roofline --paged: FATAL: {BENCH_ARTIFACT} missing under "
              "CI — run `python -m benchmarks.run --only paged` first; "
              "refusing to report a roofline with no bench evidence",
              file=sys.stderr)
        sys.exit(1)
    else:
        print(f"# roofline --paged: warning: {BENCH_ARTIFACT} missing "
              "(run benchmarks.run --only paged to populate it)")

    us = (time.time() - t_start) * 1e6 / max(len(rows), 1)
    derived = f"n={len(rows)} device={dev.device_kind}"
    if on_chip:
        best_row = max(rows, key=lambda r: r["achieved_pct"])
        derived += (f" best={best_row['variant']}/{best_row['kernel']}"
                    f"@{best_row['achieved_pct']:.4f}%")
    common.emit("roofline_paged", us, derived)
    payload = {"device_kind": dev.device_kind, "peak_bytes_per_s": peak,
               "rows": rows,
               "bench_hbm_bytes_per_token":
                   bench.get("hbm_bytes_per_token") if bench else None}
    common.emit_json("roofline_paged", payload)
    return payload


if __name__ == "__main__":
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--paged", action="store_true",
                    help="report the paged decode kernels' HBM bytes/token "
                         "(and, on a TPU, achieved vs peak bandwidth) "
                         "instead of reading dry-run artifacts")
    ns = ap.parse_args()
    if ns.paged:
        run_paged()
    else:
        run()
