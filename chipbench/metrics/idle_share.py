"""Device layer (TPU): the share of the traced window in which no
operation ran on the device, 1 - (union of the device's operation
intervals) / window, averaged over the chips used.  Moves
tokens_per_s."""
from chipbench import trace


def read(rec):
    if not rec.trace.devices():
        return None
    return (1.0 - trace.busy_s(rec.trace) / rec.trace.window_s) * 100.0
