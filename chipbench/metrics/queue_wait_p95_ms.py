"""Scheduler layer (``serving/scheduler/runtime.py``): 95th percentile
of the wait from a request's send time to the moment the scheduler
takes it off its queue and starts its prefill (``Request.started_t``),
over every request sent in the window.  A request never started waits
until the run stopped offering load.  Moves tokens_per_s: in a closed
loop a request that waits holds its client, and its decode slot stays
empty."""
import numpy as np


def read(rec):
    waits = [r["started"] - r["sent"] for r in rec.requests
             if rec.w0 <= r["sent"] < rec.w1]
    if not waits:
        return None
    return float(np.percentile(waits, 95) * 1e3)
