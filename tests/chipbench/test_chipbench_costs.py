"""FLOPs and minimum bytes against hand counts at the published widths,
and the peak table."""
import json
from pathlib import Path

import pytest

from chipbench import costs, peaks

CONFIGS = Path(__file__).resolve().parents[2] / "chipbench" / "configs"


def conf(name):
    return json.loads((CONFIGS / f"{name}.json").read_text())


def test_olmo_1b_hand_counts():
    c = conf("olmo-1b")
    # per layer: q, k, v, o of 2048 x 2048, then gate, up, down of 2048 x 8192
    assert costs.layer_matmul_params(c) == 4 * 2048 * 2048 + 3 * 2048 * 8192
    # 16 layers and the tied embedding as the head, bf16, no norm weights
    assert costs.weight_bytes(c) == 2 * (16 * 67_108_864 + 50_304 * 2048)
    assert costs.kv_bytes_per_token(c) == 128 * 1024      # 128 KiB
    k = costs.decode_step(c, [100, 200])
    per_key = 2 * 16 * 16 * (128 + 128)
    assert k["flops"] == 2 * 2 * (16 * 67_108_864 + 2048 * 50_304) + 300 * per_key
    assert k["bytes"] == (2_353_528_832 + 300 * 131_072
                          + 2 * (131_072 + 2 * 2048))
    assert k["attn_flops"] == 300 * per_key
    # the cache read, plus q in and out per row and layer (16 heads x 256)
    assert k["attn_bytes"] == 300 * 131_072 + 2 * 2 * 16 * 16 * 256
    # four tokens at positions 10..13 attend 11 + 12 + 13 + 14 keys
    assert costs.prefill_flops(c, 4, 10) == (
        2 * 16 * 67_108_864 * 4 + 50 * per_key + 2 * 2048 * 50_304)


def test_grouped_query_hand_counts():
    """olmo-1b's shapes with 4 key-value heads of 16: the cache and the
    key and value projections shrink by 4, the queries do not."""
    c = dict(conf("olmo-1b"), num_kv_heads=4)
    assert costs.layer_matmul_params(c) == (2 * 2048 * 2048 + 2 * 2048 * 512
                                            + 3 * 2048 * 8192)
    assert costs.kv_bytes_per_token(c) == 32 * 1024
    k = costs.decode_step(c, [1000])
    assert k["attn_flops"] == 1000 * 2 * 16 * 16 * 256
    assert k["attn_bytes"] == 1000 * 32 * 1024 + 2 * 16 * 16 * 256


def test_peaks_known_and_unknown():
    p = peaks.peaks("TPU v5 lite")
    assert p == {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
    with pytest.raises(ValueError, match="no published peaks"):
        peaks.peaks("TPU v9 imaginary")
