"""DeepSeek-V3 block (``model_type`` ``deepseek_v3``: DeepSeek-V3, Kimi-K2,
Moonlight): multi-head latent attention (MLA), a sigmoid router whose
per-expert correction bias chooses the experts but never weights them
(``noaux_tc``), routed and shared SwiGLU experts after
``first_k_dense_replace`` dense layers, and an untied output head.

The reference follows the published modeling code, not the program's
absorbed decode: every layer decompresses the normalised 512-wide latent
into per-head keys (non-rotary part from the latent, rotary part the one
``k_pe`` all heads share) and values, and attends with a query/key width of
``qk_nope + qk_rope`` and the value width, scaled by (qk width)^-0.5.
Rotary turns adjacent dims (2i, 2i+1) of the rotary parts only.  RMS norm
applies ``1 + w`` as the program's weights are laid out; the latent's norm
takes the published code's default epsilon, 1e-6.  Weights are upcast one
layer (or one expert) at a time, and attention runs in blocks of queries,
so the reference fits beside the weights on one chip.
"""

from __future__ import annotations

import functools
from typing import Dict, Tuple

from .. import flops
from ..model import VOCAB_PAD
from ..reference import _jnp, _mkey, fp8, mm, rms_norm
from .deepseek import mlp, swiglu

# ---- the configuration file --------------------------------------------------

# published key -> the program's ModelConfig field
_KEYS = {
    "num_hidden_layers": "n_layers", "hidden_size": "d_model",
    "num_attention_heads": "n_heads", "num_key_value_heads": "n_kv_heads",
    "intermediate_size": "d_ff", "vocab_size": "vocab",
    "rope_theta": "rope_theta", "rms_norm_eps": "norm_eps",
    "n_routed_experts": "n_routed", "n_shared_experts": "n_shared",
    "num_experts_per_tok": "top_k", "moe_intermediate_size": "d_ff_expert",
    "first_k_dense_replace": "first_k_dense",
    "kv_lora_rank": "kv_lora_rank", "qk_nope_head_dim": "qk_nope_head_dim",
    "qk_rope_head_dim": "qk_rope_head_dim", "v_head_dim": "v_head_dim",
    "routed_scaling_factor": "routed_scaling",
}
# what the program implements of the published options, checked so that a
# configuration asking for anything else fails before weights are made
_FIXED = {"q_lora_rank": None, "scoring_func": "sigmoid",
          "topk_method": "noaux_tc", "n_group": 1, "topk_group": 1,
          "norm_topk_prob": True, "hidden_act": "silu", "moe_layer_freq": 1}

# the latent's RMS norm in the published code keeps its default epsilon
KV_NORM_EPS = 1e-6


def program_config(conf: Dict):
    """The program's frozen ``ModelConfig`` for a configuration file."""
    from repro.configs.base import ModelConfig
    for key, want in _FIXED.items():
        if conf.get(key, want) != want:
            raise ValueError(f"{conf['name']}: {key}={conf[key]!r}; the program "
                             f"implements {key}={want!r}")
    if conf.get("rope_scaling"):
        raise ValueError(f"{conf['name']}: rope_scaling is not implemented")
    kw = {field: conf[key] for key, field in _KEYS.items()}
    return ModelConfig(name=conf["name"], source=conf["source"], family="moe",
                       param_dtype=conf["torch_dtype"],
                       tie_embeddings=conf["tie_word_embeddings"],
                       router_score="sigmoid", **kw)


def dims(cfg) -> Dict:
    """The sizes the FLOP and byte counters and the reference read."""
    import numpy as np
    return {"d_model": cfg.d_model, "n_heads": cfg.n_heads, "d_ff": cfg.d_ff,
            "vocab": cfg.vocab, "n_routed": cfg.n_routed,
            "n_shared": cfg.n_shared, "top_k": cfg.top_k,
            "d_ff_expert": cfg.d_ff_expert, "first_k_dense": cfg.first_k_dense,
            "kv_lora_rank": cfg.kv_lora_rank,
            "qk_nope_head_dim": cfg.qk_nope_head_dim,
            "qk_rope_head_dim": cfg.qk_rope_head_dim,
            "v_head_dim": cfg.v_head_dim, "routed_scaling": cfg.routed_scaling,
            "rope_theta": cfg.rope_theta, "norm_eps": cfg.norm_eps,
            "param_bytes": np.dtype(cfg.param_dtype).itemsize,
            "ffn_kinds": list(cfg.ffn_kinds())}


# wide enough that logits spread as at full width, small enough for the CPU
TINY = dict(hidden_size=512, num_attention_heads=4, num_key_value_heads=4,
            intermediate_size=1024, vocab_size=4096, kv_lora_rank=64,
            qk_nope_head_dim=32, qk_rope_head_dim=16, v_head_dim=32)


def tiny(conf: Dict, layers: int, routing: str) -> Dict:
    """``conf`` cut to CPU size: same keys and code paths, tiny widths.
    ``published``: 64 experts, top-6, 2 shared, narrow; ``small``: 8
    experts, top-2, 1 shared, in float32."""
    conf = dict(conf, **TINY, num_hidden_layers=layers,
                moe_intermediate_size=64)
    if routing == "small":
        conf.update(torch_dtype="float32", n_routed_experts=8,
                    num_experts_per_tok=2, n_shared_experts=1)
    elif routing == "published":
        # at 2.446 a top-6 choice that bf16 rounding flips moves the hidden
        # state 2.4 times as far as in the softmax models: at this size the
        # sound program's mean logit gap reads 0.0004-0.024 (10 seeds) across
        # the CPU limit of 0.01 that the shared tests set; at 1.0 it reads at
        # most 0.0039 (12 seeds).  So the shared serving tests do not run
        # the scaled bf16 path; the float32 cut keeps the published scale,
        # and test_bench_deepseek_v3 serves this cut at 2.446 against the
        # float8 control and a stale-state fault.
        conf["routed_scaling_factor"] = 1.0
    else:
        raise ValueError(f"routing {routing!r}: 'published' or 'small'")
    return conf


# ---- the weights' layout -----------------------------------------------------

def _attn_shapes(cfg, n):
    d, h, c = cfg.d_model, cfg.n_heads, cfg.kv_lora_rank
    hv = h * cfg.v_head_dim
    lead = (n,) if n else ()
    return {"norm": (lead + (d,), None),
            "wq": (lead + (d, h * cfg.qk_head_dim), d),
            "wkv_a": (lead + (d, cfg.latent_dim), d),
            "kv_norm": (lead + (c,), None),
            "wkv_b": (lead + (c, h * (cfg.qk_nope_head_dim + cfg.v_head_dim)), c),
            "wo": (lead + (hv, d), hv)}


def _mlp_shapes(d, f, n):
    lead = (n,) if n else ()
    return {"norm": (lead + (d,), None), "wg": (lead + (d, f), d),
            "wu": (lead + (d, f), d), "wd": (lead + (f, d), f)}


def _moe_shapes(cfg, n):
    d, e, fe = cfg.d_model, cfg.n_routed, cfg.d_ff_expert
    fs = cfg.n_shared * fe
    return {"norm": ((n, d), None), "router": ((n, d, e), d, "float32"),
            "router_bias": ((n, e), None, "float32"),
            "wg": ((n, e, d, fe), d), "wu": ((n, e, d, fe), d),
            "wd": ((n, e, fe, d), fe),
            "swg": ((n, d, fs), d), "swu": ((n, d, fs), d),
            "swd": ((n, fs, d), fs)}


def param_shapes(cfg) -> Dict:
    """Leaf -> (shape, fan_in[, dtype]) in the program's layout: stacked
    MoE layers after ``first_k_dense`` unrolled dense ones, embedding and
    untied head padded to a multiple of ``VOCAB_PAD`` rows."""
    n = cfg.n_layers - cfg.first_k_dense
    vp = -(-cfg.vocab // VOCAB_PAD) * VOCAB_PAD
    return {"embed": ((vp, cfg.d_model), "embed"),
            "lm_head": ((cfg.d_model, vp), "embed"),
            "final_norm": ((cfg.d_model,), None),
            "layers": {"attn": _attn_shapes(cfg, n), "ffn": _moe_shapes(cfg, n)},
            "dense0": [{"attn": _attn_shapes(cfg, 0),
                        "mlp": _mlp_shapes(cfg.d_model, cfg.d_ff, 0)}
                       for _ in range(cfg.first_k_dense)]}


# ---- the plain reference -----------------------------------------------------

Q_BLOCK = 512   # queries a block: (heads, 512, S) float32 scores at a time


def rope_pairs(x, theta: float):
    """x: (S, heads, dim), positions 0..S-1, dims (2i, 2i+1) rotated."""
    jax, jnp = _jnp()
    s, d = x.shape[0], x.shape[-1]
    freqs = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d))
    ang = jnp.arange(s, dtype=jnp.float32)[:, None] * freqs
    cos, sin = jnp.cos(ang)[:, None], jnp.sin(ang)[:, None]
    x1, x2 = x[..., 0::2], x[..., 1::2]
    return jnp.stack([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1).reshape(x.shape)


def attention(x, w, m: Dict, quant: bool):
    """Causal MLA, the latent decompressed per head."""
    jax, jnp = _jnp()
    s = x.shape[0]
    h, n, r = m["n_heads"], m["qk_nope_head_dim"], m["qk_rope_head_dim"]
    c, v = m["kv_lora_rank"], m["v_head_dim"]
    y = rms_norm(x, w["norm"], m["norm_eps"])
    q = mm(y, w["wq"], quant).reshape(s, h, n + r)
    q = jnp.concatenate([q[..., :n], rope_pairs(q[..., n:], m["rope_theta"])], -1)
    kv_a = mm(y, w["wkv_a"], quant)
    lat = rms_norm(kv_a[:, :c], w["kv_norm"], KV_NORM_EPS)
    k_pe = rope_pairs(kv_a[:, None, c:], m["rope_theta"])
    kv = mm(lat, w["wkv_b"], quant).reshape(s, h, n + v)
    k = jnp.concatenate([kv[..., :n], jnp.broadcast_to(k_pe, (s, h, r))], -1)
    val = kv[..., n:]
    if quant:
        q, k, val = fp8(q, -1), fp8(k, -1), fp8(val, -1)
    nb = -(-s // Q_BLOCK)
    qb = jnp.pad(q, ((0, nb * Q_BLOCK - s), (0, 0), (0, 0))).reshape(
        nb, Q_BLOCK, h, n + r)

    def block(args):
        i, qi = args
        sc = jnp.einsum("qhd,khd->hqk", qi, k,
                        precision=jax.lax.Precision.HIGHEST) * (n + r) ** -0.5
        causal = (i * Q_BLOCK + jnp.arange(Q_BLOCK))[:, None] >= jnp.arange(s)[None, :]
        p = jax.nn.softmax(jnp.where(causal, sc, -jnp.inf), -1)
        if quant:
            p = fp8(p, -1)
        return jnp.einsum("hqk,khd->qhd", p, val,
                          precision=jax.lax.Precision.HIGHEST)
    o = jax.lax.map(block, (jnp.arange(nb), qb)).reshape(nb * Q_BLOCK, h, v)[:s]
    return x + mm(o.reshape(s, h * v), w["wo"], quant)


def route(y, router, bias, m: Dict, quant: bool):
    """Sigmoid router: the biased scores choose the top-k, the unbiased
    ones weight them, renormalised and scaled.  (gates, expert ids)."""
    jax, jnp = _jnp()
    scores = jax.nn.sigmoid(mm(y, router, quant))
    _, ids = jax.lax.top_k(scores + bias, m["top_k"])
    gates = jnp.take_along_axis(scores, ids, -1)
    return gates / gates.sum(-1, keepdims=True) * m["routed_scaling"], ids


def moe(x, w, m: Dict, quant: bool):
    """Every expert over every token, weighted by the routed gates."""
    jax, jnp = _jnp()
    y = rms_norm(x, w["norm"], m["norm_eps"])
    gates, ids = route(y, w["router"], w["router_bias"], m, quant)
    dense = jnp.zeros((y.shape[0], m["n_routed"]), jnp.float32).at[
        jnp.arange(y.shape[0])[:, None], ids].set(gates)

    def one(acc, e):
        we = jax.tree.map(lambda a: a[e], (w["wg"], w["wu"], w["wd"]))
        return acc + dense[:, e, None] * swiglu(y, *we, quant), None
    out, _ = jax.lax.scan(one, jnp.zeros_like(y), jnp.arange(m["n_routed"]))
    return x + out + swiglu(y, w["swg"], w["swu"], w["swd"], quant)


@functools.lru_cache(maxsize=None)
def _fns(quant: bool, mkey: Tuple):
    jax, jnp = _jnp()
    m = dict(mkey)

    def embed(emb, tokens):
        return jnp.take(emb, tokens, axis=0).astype(jnp.float32)

    def logits(x, norm, head):
        return mm(rms_norm(x, norm, m["norm_eps"]), head[:, : m["vocab"]], quant)
    return {"embed": jax.jit(embed), "logits": jax.jit(logits),
            "attn": jax.jit(lambda x, w: attention(x, w, m, quant)),
            "dense": jax.jit(lambda x, w: mlp(x, w, m, quant)),
            "moe": jax.jit(lambda x, w: moe(x, w, m, quant))}


def forward(params, tokens, m: Dict, quant: bool = False):
    """Logits (S, vocab) at every position of ``tokens`` (S,)."""
    jax, jnp = _jnp()
    fn = _fns(quant, _mkey(m))
    x = fn["embed"](params["embed"], jnp.asarray(tokens, jnp.int32))
    for d0 in params["dense0"]:
        x = fn["dense"](fn["attn"](x, d0["attn"]), d0["mlp"])
    layers = params["layers"]
    for i in range(len(m["ffn_kinds"]) - len(params["dense0"])):
        w = jax.tree.map(lambda a: a[i], layers)
        x = fn["moe"](fn["attn"](x, w["attn"]), w["ffn"])
    return fn["logits"](x, params["final_norm"], params["lm_head"])


# ---- required work and bytes (the roofline numerators) -----------------------

def _attn_params(m: Dict) -> int:
    """MLA projections: queries, the latent with the rotary key, its
    per-head expansion (or, absorbed, the same weights folded into the
    query and the output) and the output."""
    d, h, c = m["d_model"], m["n_heads"], m["kv_lora_rank"]
    n, r, v = m["qk_nope_head_dim"], m["qk_rope_head_dim"], m["v_head_dim"]
    return d * h * (n + r) + d * (c + r) + c * h * (n + v) + h * v * d


def _ffn_params(m: Dict, kind: str) -> int:
    """FFN weights a token multiplies: the dense FFN, or the router, the
    top-k routed and the shared experts (never all routed experts)."""
    d = m["d_model"]
    if kind == "dense":
        return 3 * d * m["d_ff"]
    return d * m["n_routed"] + 3 * d * m["d_ff_expert"] * (m["top_k"] + m["n_shared"])


def layer_matmul_params(m: Dict) -> int:
    """Weights that multiply every token, summed over the layers."""
    return sum(_attn_params(m) + _ffn_params(m, k) for k in m["ffn_kinds"])


def prefill_flops(m: Dict, seq: int) -> float:
    """A prompt of ``seq`` tokens: every layer's projections for every
    token (the latent decompressed), causal attention over the query/key
    width and the value width, routed top-k and shared experts, and the
    output head for the last token only."""
    h = m["n_heads"]
    qk = m["qk_nope_head_dim"] + m["qk_rope_head_dim"]
    attn = 2 * h * (qk + m["v_head_dim"]) * flops.causal_pairs(seq)
    return float(2 * layer_matmul_params(m) * seq
                 + attn * len(m["ffn_kinds"])
                 + flops.unembed(m["d_model"], m["vocab"]))


def decode_flops(m: Dict, position: int) -> float:
    """One new token at ``position`` in the absorbed form: the projections,
    scores against ``position + 1`` latent rows (latent and rotary key) and
    the weighted sum of their latents, the experts and the head."""
    h, c, r = m["n_heads"], m["kv_lora_rank"], m["qk_rope_head_dim"]
    attn = 2 * h * (c + r + c) * (position + 1)
    return float(2 * layer_matmul_params(m) + attn * len(m["ffn_kinds"])
                 + flops.unembed(m["d_model"], m["vocab"]))


def decode_bytes(m: Dict, position: int) -> float:
    """Bytes one decode step at ``position`` must read: the attention
    projections and the FFN weights the token uses (dense FFN, top-k routed
    and shared experts), the output head, and ``position + 1`` latent rows
    of ``kv_lora_rank + qk_rope_head_dim`` values in every layer."""
    d, b = m["d_model"], m["param_bytes"]
    fe = 3 * d * m["d_ff_expert"] * (m["top_k"] + m["n_shared"])
    weights = sum(_attn_params(m) + (3 * d * m["d_ff"] if k == "dense" else fe)
                  for k in m["ffn_kinds"]) + d * m["vocab"]
    row = (m["kv_lora_rank"] + m["qk_rope_head_dim"]) * b
    return float(weights * b + row * (position + 1) * len(m["ffn_kinds"]))
