"""The peak table and the FLOP counters, against hand counts."""

import pytest

from harness import arch, device

DS = arch.get({"model_type": "deepseek"})


def test_v5e_peaks():
    p = device.peaks("TPU v5 lite")
    assert p["bf16_flops_per_s"] == 197e12
    assert p["hbm_bytes_per_s"] == 819e9
    assert p["hbm_bytes"] == 16e9
    assert p["ici_bits_per_s"] == 1600e9
    assert "Google Cloud" in p["source"]


def test_unknown_device_is_an_error():
    with pytest.raises(KeyError):
        device.peaks("TPU v9 imaginary")


DENSE = {"d_model": 4, "n_heads": 2, "n_kv_heads": 2, "head_dim": 2,
         "d_ff": 8, "vocab": 10, "n_routed": 0, "n_shared": 0, "top_k": 0,
         "d_ff_expert": 0, "ffn_kinds": ["dense", "dense"]}


def test_dense_model_hand_count():
    # per layer: q,k,v,o 4*4*4 = 64 weights, ffn 3*4*8 = 96 -> 160; 2 layers
    assert DS.layer_matmul_params(DENSE) == 320
    # prefill of 3: 2*320*3 matmul, causal 6 pairs * 2*2*2heads*2dh * 2 layers,
    # one logits row 2*4*10
    assert DS.prefill_flops(DENSE, 3) == 2 * 320 * 3 + 6 * 16 * 2 + 80
    # decode at position 3 attends 4 keys
    assert DS.decode_flops(DENSE, 3) == 2 * 320 + 4 * 16 * 2 + 80


def test_moe_model_counts_routed_top_k_only():
    m = dict(DENSE, n_routed=8, n_shared=1, top_k=2, d_ff_expert=3,
             ffn_kinds=["dense", "moe"])
    moe_layer = 64 + 4 * 8 + 3 * 4 * 3 * (2 + 1)       # attn, router, 3 experts
    assert DS.layer_matmul_params(m) == 160 + moe_layer
    # one decode token at position 0 costs the same weights, one key
    assert DS.decode_flops(m, 0) == 2 * (160 + moe_layer) + 16 * 2 + 80
