"""Engine layer (``serving/engine.py``, the jitted ``paged_prefill_fn``
and ``paged_prefill_tail_fn``): device time of those programs in the
traced window over the prompt tokens they prefilled there (tokens
computed, not padding, not prefix pages mapped from another request).
Moves tokens_per_s: device time spent on prefill is time the decode
batch does not step."""
from chipbench import trace

PROGRAMS = ("paged_prefill_fn", "paged_prefill_tail_fn")


def is_prefill(name: str) -> bool:
    return any(p in name for p in PROGRAMS)


def read(rec):
    tokens = sum(n for _t, n, _start in rec.prefill)
    seconds, count = trace.module_time_s(rec.trace, is_prefill)
    if not tokens or not count:
        return None
    return seconds / tokens * 1e6
