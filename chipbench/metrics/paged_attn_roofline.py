"""Kernel layer (``kernels/paged_attention.py``, the Pallas paged
decode kernel): the least time its work could take over the time the
kernel's device operations took in the traced window.  The work is the
algorithm's, from ``chipbench/costs.py``: each row's live keys and
values read once, its queries in and outputs out, and the attention
FLOPs; whichever of FLOPs over the bf16 peak and bytes over the HBM
peak is larger.  Mean least time per decode step
over mean kernel time per decode step.  Moves itl_p50_ms."""
from chipbench import trace


def _is_decode(name: str) -> bool:
    return "paged_decode_fn" in name or "paged_decode_cow_fn" in name


def is_kernel(event) -> bool:
    """The Pallas kernel inside the decode programs: the only Mosaic
    custom call there (``custom_call_target="tpu_custom_call"``)."""
    module = str(event.stats.get("module", ""))
    return _is_decode(module) and "tpu_custom_call" in event.name


def read(rec):
    seconds, _n = trace.ops_time_s(rec.trace, is_kernel)
    _s, steps = trace.module_time_s(rec.trace, _is_decode)
    if not seconds or not steps or not rec.decode:
        return None
    pk, c = rec.peaks, rec.config
    least = 0.0
    for _t, rows, keys in rec.decode:
        k = rec.costs.decode_step(c, [keys / rows] * rows)
        least += max(k["attn_flops"] / pk["bf16_flops_per_s"],
                     k["attn_bytes"] / pk["hbm_bytes_per_s"])
    return least / len(rec.decode) / (seconds / steps) * 100.0
