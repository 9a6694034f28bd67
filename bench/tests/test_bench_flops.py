"""Operation and byte counts against hand counts."""

import math

from bench import common, flops


def dims(name):
    c = common.load_json(common.BENCH / "configs" / f"{name}.json")
    return common.reference_module(c).dims(c)


def test_qwen3_1p7b_counts():
    m = dims("qwen3-1.7b")
    per_layer = 2048 * 128 * (16 + 16) + 16 * 128 * 2048 + 3 * 2048 * 6144
    assert flops.linear_params(m) == 28 * per_layer == 1_409_286_144
    assert flops.head_params(m) == 151936 * 2048
    assert 1.71e9 < flops.total_params(m) < 1.73e9  # 1.72 G, tied embedding once
    assert flops.kv_bytes_per_token(m) == 28 * 8 * 128 * 2 * 4.25 / 8 == 30464


def test_starcoder2_3b_counts():
    m = dims("starcoder2-3b")
    per_layer = 3072 * 128 * (24 + 4) + 24 * 128 * 3072 + 2 * 3072 * 12288
    assert flops.linear_params(m) == 30 * per_layer
    assert 3.0e9 < flops.total_params(m) < 3.2e9
    assert flops.kv_bytes_per_token(m) == 30 * 2 * 128 * 2 * 4.25 / 8 == 8160


TINY = {"d": 8, "f": 16, "L": 2, "hq": 2, "hkv": 1, "hd": 4, "V": 10,
        "mlp": "gelu", "norm": "rmsnorm", "qk_norm": False, "bias": False, "tied": True}


def test_prefill_call_by_hand():
    lin = 2 * (8 * 4 * (2 + 2) + 2 * 4 * 8 + 2 * 8 * 16)  # 2 layers
    assert flops.linear_params(TINY) == lin == 896
    # one row: 3 tokens from position 5 attend to 6, 7, 8 positions
    attn = 4 * 2 * 4 * 2 * (6 + 7 + 8)
    assert flops.prefill_call_flops(TINY, [(5, 3)], finished=1) == (
        2 * 896 * 3 + attn + 2 * 80)
    assert flops.prefill_call_flops(TINY, [(0, 2), (4, 1)], finished=0) == (
        2 * 896 * 3 + 4 * 2 * 4 * 2 * (1 + 2 + 5))


def test_decode_call_by_hand():
    assert flops.decode_call_flops(TINY, [3, 10]) == (
        2 * (896 + 80) * 2 + 4 * 2 * 4 * 2 * 13)


def test_paged_attention_cost_by_hand():
    f, b = flops.paged_attention_cost(TINY, [3, 17], page_size=16)
    assert f == 4 * 2 * 4 * 2 * 20
    per_token = 2 * 1 * 2 * 4 * (0.5 + 1 / 32)  # L, hkv, k+v, hd, bytes/lane
    assert b == (1 + 2) * 16 * per_token + 2 * 2 * 2 * 2 * 4 * 2


def test_roofline_picks_the_binding_bound():
    peak = {"bf16_flops_per_s": 100.0, "hbm_bytes_per_s": 10.0}
    assert flops.roofline_seconds(1000, 10, peak) == (10.0, "compute")
    assert flops.roofline_seconds(10, 1000, peak) == (100.0, "memory")
    assert math.isclose(flops.roofline_seconds(0, 5, peak)[0], 0.5)
