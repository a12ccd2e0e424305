"""Model layer (``models/transformer.py`` decode step): the least time
the chip could take for the traced window's decode steps over the time
they took.  Per step the least time is the larger of its FLOPs over the
bf16 peak and its minimum bytes (every weight once, each row's live
cache once, the new cache entries) over the HBM peak, from
``chipbench/costs.py``; the step's rows and keys come from the
harness's record of each call.  Mean least time per step over mean
device time per step.  Moves itl_p50_ms."""
from chipbench import trace


def _is_decode(name: str) -> bool:
    return "paged_decode_fn" in name or "paged_decode_cow_fn" in name


def read(rec):
    seconds, count = trace.module_time_s(rec.trace, _is_decode)
    if not count or not rec.decode:
        return None
    pk, c = rec.peaks, rec.config
    least = 0.0
    for _t, rows, keys in rec.decode:
        contexts = [keys / rows] * rows       # costs are linear in keys
        k = rec.costs.decode_step(c, contexts)
        least += max(k["flops"] / pk["bf16_flops_per_s"],
                     k["bytes"] / pk["hbm_bytes_per_s"])
    return least / len(rec.decode) / (seconds / count) * 100.0
