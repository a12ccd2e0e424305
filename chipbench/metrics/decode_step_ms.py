"""Engine layer (``serving/engine.py``, the jitted ``paged_decode_fn``
and ``paged_decode_cow_fn``): mean device time of one decode step, the
programs' device time in the traced window over their executions there.
Moves itl_p50_ms."""
from chipbench import trace

PROGRAMS = ("paged_decode_fn", "paged_decode_cow_fn")


def is_decode(name: str) -> bool:
    return any(p in name for p in PROGRAMS)


def read(rec):
    seconds, count = trace.module_time_s(rec.trace, is_decode)
    if not count:
        return None
    return seconds / count * 1e3
