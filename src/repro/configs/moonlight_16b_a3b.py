"""Moonlight-16B-A3B: the DeepSeek-V3 block (multi-head latent attention, a
sigmoid router with a selection bias, 2 shared + 64 routed experts top-6),
an untied output head.
[https://huggingface.co/moonshotai/Moonlight-16B-A3B/blob/main/config.json]"""

from .base import ModelConfig

CONFIG = ModelConfig(
    name="moonlight-16b-a3b",
    family="moe",
    source="https://huggingface.co/moonshotai/Moonlight-16B-A3B",
    n_layers=27,
    d_model=2048,
    n_heads=16,
    n_kv_heads=16,
    d_ff=11264,             # dense FFN of the first (dense) layer
    vocab=163840,
    rope_theta=50_000.0,
    tie_embeddings=False,
    kv_lora_rank=512,
    qk_nope_head_dim=128,
    qk_rope_head_dim=64,
    v_head_dim=128,
    n_routed=64,
    n_shared=2,
    top_k=6,
    d_ff_expert=1408,
    first_k_dense=1,
    router_score="sigmoid",
    routed_scaling=2.446,
)
