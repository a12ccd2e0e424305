"""Model layer: model FLOPs of every prompt token prefilled and every
token decoded in the traced window (``chipbench/costs.py``), over the
window's seconds times the chip's bf16 peak.  Moves tokens_per_s."""


def read(rec):
    c = rec.config
    flops = sum(rec.costs.prefill_flops(c, n, start)
                for _t, n, start in rec.prefill)
    flops += sum(rec.costs.decode_step(c, [keys / rows] * rows)["flops"]
                 for _t, rows, keys in rec.decode)
    if not flops:
        return None
    return flops / rec.trace.window_s / rec.peaks["bf16_flops_per_s"] * 100.0
