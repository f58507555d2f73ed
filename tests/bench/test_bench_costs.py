"""bench/costs.py against operations and bytes worked out by hand."""
import pytest

import costs

MOON = {"model_type": "deepseek_v3", "hidden_size": 2048,
        "num_attention_heads": 16, "num_key_value_heads": 16,
        "head_dim": 128, "vocab_size": 163840, "n_routed_experts": 64,
        "num_experts_per_tok": 6, "moe_intermediate_size": 1408,
        "n_shared_experts": 2, "intermediate_size": 11264,
        "first_k_dense_replace": 1, "num_hidden_layers": 3}
MIX = {"model_type": "mixtral", "hidden_size": 4096,
       "num_attention_heads": 32, "num_key_value_heads": 8, "head_dim": 128,
       "vocab_size": 32000, "num_local_experts": 8, "num_experts_per_tok": 2,
       "intermediate_size": 14336, "num_hidden_layers": 2}


def test_expert_gemms_one_decode_step_of_mixtral():
    # 64 routed rows over all 8 experts: 2*64*4096*3*14336 FLOPs; each
    # expert's three 4096x14336 float32 matrices once; rows in and out
    flops, nbytes = costs.expert_gemms(MIX, useful=64, experts=8)
    assert flops == 2 * 64 * 4096 * 3 * 14336 == 22_548_578_304
    w = 8 * 3 * 4096 * 14336 * 4
    acts = 64 * (4096 + 2 * 14336 + 4096) * 4
    assert nbytes == w + acts == 5_637_144_576 + 9_437_184


def test_moe_dispatch_one_step_of_moonlight():
    # 24 tokens, 144 routed rows: router scores 24x64 read and 24x6
    # weights + indices written; permute reads 24 and writes 144 rows of
    # 2048; unpermute reads 144 rows and their weights, writes 24 rows
    flops, nbytes = costs.moe_dispatch(MOON, T=24, useful=144)
    router = (24 * 64 + 2 * 24 * 6) * 4
    permute = (24 + 144) * 2048 * 4
    unpermute = (144 * 2048 + 144 + 24 * 2048) * 4
    assert nbytes == router + permute + unpermute
    assert flops == 24 * 64 * 4 + 2 * 144 * 2048


def test_token_flops_of_moonlight_decode_token():
    d, H, D = 2048, 16, 128
    attn = 2 * d * (4 * H * D) + 4 * H * D * 100
    dense = 2 * 3 * d * 11264
    moe = 2 * (d * 64 + 3 * d * (6 * 1408 + 2 * 1408))
    body = 3 * attn + dense + 2 * moe
    assert costs.token_flops(MOON, 100, False) == body
    assert costs.token_flops(MOON, 100, True) == body + 2 * d * 163840


def test_least_time_names_its_bound():
    peak = {"flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
    assert costs.least_time(197e12, 1.0, peak) == (1.0, "compute")
    t, bound = costs.least_time(1.0, 819e9 * 2, peak)
    assert (t, bound) == (pytest.approx(2.0), "memory")
