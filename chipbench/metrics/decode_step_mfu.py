"""Model layer (``models/transformer.py`` decode step): the decode
step's model FLOPs (``chipbench/costs.py``) over its device time times
the chip's bf16 peak, mean over the traced window's steps.  It bounds
the kernel rooflines from above: a kernel taken off the decode path
leaves its own roofline silent, not this.  Moves itl_p50_ms."""
from chipbench import trace


def _is_decode(name: str) -> bool:
    return "paged_decode_fn" in name or "paged_decode_cow_fn" in name


def read(rec):
    seconds, count = trace.module_time_s(rec.trace, _is_decode)
    if not count or not rec.decode:
        return None
    flops = sum(rec.costs.decode_step(rec.config, [keys / rows] * rows)
                ["flops"] for _t, rows, keys in rec.decode) / len(rec.decode)
    return flops / (seconds / count) / rec.peaks["bf16_flops_per_s"] * 100.0
