"""DeepSeekMoE (``model_type`` ``deepseek``, arXiv:2401.06066): GQA
attention with a full-head rotary embedding, a softmax router, routed and
shared SwiGLU experts after ``first_k_dense_replace`` dense layers.

The reference follows the program's block as the configuration file
describes it (its ``assumed`` lists where that block departs from the
published model): RMS norm applied as ``1 + w``, rotary embedding over the
whole head split in halves, causal softmax attention, SwiGLU feed-forward,
a softmax router whose top-k gates are renormalised, shared experts added,
and logits from the tied embedding.  Weights are upcast one layer (or one
expert) at a time, so the reference fits beside the weights on one chip.
"""

from __future__ import annotations

import functools
from typing import Dict, Tuple

from .. import flops
from ..model import VOCAB_PAD
from ..reference import _ends, _jnp, _mkey, fp8, mm, rms_norm, rope

# ---- the configuration file --------------------------------------------------

# published key -> the program's ModelConfig field
_KEYS = {
    "num_hidden_layers": "n_layers", "hidden_size": "d_model",
    "num_attention_heads": "n_heads", "num_key_value_heads": "n_kv_heads",
    "intermediate_size": "d_ff", "vocab_size": "vocab",
    "rope_theta": "rope_theta", "n_routed_experts": "n_routed",
    "n_shared_experts": "n_shared", "num_experts_per_tok": "top_k",
    "moe_intermediate_size": "d_ff_expert",
    "first_k_dense_replace": "first_k_dense",
}
_EPS_KEYS = ("rms_norm_eps", "layer_norm_eps", "norm_eps")


def program_config(conf: Dict):
    """The program's frozen ``ModelConfig`` for a configuration file."""
    from repro.configs.base import ModelConfig
    kw = {field: conf[key] for key, field in _KEYS.items() if key in conf}
    kw["norm_eps"] = next(conf[k] for k in _EPS_KEYS if k in conf)
    kw["family"] = "moe" if conf.get("n_routed_experts") else "dense"
    kw.setdefault("n_kv_heads", kw.get("n_heads", 0))
    return ModelConfig(name=conf["name"], source=conf["source"],
                       param_dtype=conf["torch_dtype"], **kw)


def dims(cfg) -> Dict:
    """The sizes the FLOP counters and the reference read."""
    return {"d_model": cfg.d_model, "n_heads": cfg.n_heads,
            "n_kv_heads": cfg.n_kv_heads, "head_dim": cfg.head_dim,
            "d_ff": cfg.d_ff, "vocab": cfg.vocab, "n_routed": cfg.n_routed,
            "n_shared": cfg.n_shared, "top_k": cfg.top_k,
            "d_ff_expert": cfg.d_ff_expert, "first_k_dense": cfg.first_k_dense,
            "rope_theta": cfg.rope_theta, "norm_eps": cfg.norm_eps,
            "ffn_kinds": list(cfg.ffn_kinds())}


# wide enough that logits spread as at full width, small enough for the CPU
TINY = dict(hidden_size=512, num_attention_heads=4, num_key_value_heads=4,
            intermediate_size=1024, vocab_size=4096)


def tiny(conf: Dict, layers: int, routing: str) -> Dict:
    """``conf`` cut to CPU size: same keys and code paths, tiny widths.
    ``published``: 64 experts, top-6, 2 shared, narrow; ``small``: 8
    experts, top-2, 1 shared, in float32."""
    conf = dict(conf, **TINY, num_hidden_layers=layers)
    if routing == "small":
        conf["torch_dtype"] = "float32"
    elif routing != "published":
        raise ValueError(f"routing {routing!r}: 'published' or 'small'")
    if "n_routed_experts" in conf:
        conf["moe_intermediate_size"] = 64
        if routing == "small":
            conf.update(n_routed_experts=8, num_experts_per_tok=2,
                        n_shared_experts=1)
    return conf


# ---- the weights' layout -----------------------------------------------------

def _attn_shapes(cfg, n):
    d, h, kv, dh = cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    lead = (n,) if n else ()
    return {"norm": (lead + (d,), None), "wq": (lead + (d, h * dh), d),
            "wk": (lead + (d, kv * dh), d), "wv": (lead + (d, kv * dh), d),
            "wo": (lead + (h * dh, d), h * dh)}


def _mlp_shapes(d, f, n):
    lead = (n,) if n else ()
    return {"norm": (lead + (d,), None), "wg": (lead + (d, f), d),
            "wu": (lead + (d, f), d), "wd": (lead + (f, d), f)}


def _moe_shapes(cfg, n):
    d, e, fe = cfg.d_model, cfg.n_routed, cfg.d_ff_expert
    lead = (n,) if n else ()
    out = {"norm": (lead + (d,), None), "router": (lead + (d, e), d, "float32"),
           "wg": (lead + (e, d, fe), d), "wu": (lead + (e, d, fe), d),
           "wd": (lead + (e, fe, d), fe)}
    if cfg.n_shared:
        fs = cfg.n_shared * fe
        out.update({"swg": (lead + (d, fs), d), "swu": (lead + (d, fs), d),
                    "swd": (lead + (fs, d), fs)})
    return out


def param_shapes(cfg) -> Dict:
    """Leaf -> (shape, fan_in[, dtype]) in the program's layout: stacked
    layers after ``first_k_dense`` unrolled dense ones, tied embedding
    padded to a multiple of ``VOCAB_PAD`` rows."""
    n = cfg.n_layers - cfg.first_k_dense
    vp = -(-cfg.vocab // VOCAB_PAD) * VOCAB_PAD
    ffn = (_moe_shapes(cfg, n) if cfg.is_moe
           else _mlp_shapes(cfg.d_model, cfg.d_ff, n))
    tree = {"embed": ((vp, cfg.d_model), "embed"),
            "final_norm": ((cfg.d_model,), None),
            "layers": {"attn": _attn_shapes(cfg, n), "ffn": ffn}}
    if cfg.first_k_dense:
        tree["dense0"] = [{"attn": _attn_shapes(cfg, 0),
                           "mlp": _mlp_shapes(cfg.d_model, cfg.d_ff, 0)}
                          for _ in range(cfg.first_k_dense)]
    return tree


# ---- the plain reference -----------------------------------------------------

def attention(x, w, m: Dict, quant: bool):
    jax, jnp = _jnp()
    s = x.shape[0]
    h, kv, dh = m["n_heads"], m["n_kv_heads"], m["head_dim"]
    y = rms_norm(x, w["norm"], m["norm_eps"])
    q = rope(mm(y, w["wq"], quant).reshape(s, h, dh), m["rope_theta"])
    k = rope(mm(y, w["wk"], quant).reshape(s, kv, dh), m["rope_theta"])
    v = mm(y, w["wv"], quant).reshape(s, kv, dh)
    k = jnp.repeat(k, h // kv, axis=1)
    v = jnp.repeat(v, h // kv, axis=1)
    if quant:
        q, k, v = fp8(q, -1), fp8(k, -1), fp8(v, -1)
    sc = jnp.einsum("qhd,khd->hqk", q, k,
                    precision=jax.lax.Precision.HIGHEST) * dh ** -0.5
    causal = jnp.arange(s)[:, None] >= jnp.arange(s)[None, :]
    p = jax.nn.softmax(jnp.where(causal, sc, -jnp.inf), -1)
    if quant:
        p = fp8(p, -1)
    o = jnp.einsum("hqk,khd->qhd", p, v, precision=jax.lax.Precision.HIGHEST)
    return x + mm(o.reshape(s, h * dh), w["wo"], quant)


def swiglu(y, wg, wu, wd, quant: bool):
    jax, jnp = _jnp()
    return mm(jax.nn.silu(mm(y, wg, quant)) * mm(y, wu, quant), wd, quant)


def mlp(x, w, m: Dict, quant: bool):
    y = rms_norm(x, w["norm"], m["norm_eps"])
    return x + swiglu(y, w["wg"], w["wu"], w["wd"], quant)


def route(y, router, top_k: int, quant: bool):
    """Softmax router: (gates renormalised over the top-k, expert ids)."""
    jax, jnp = _jnp()
    probs = jax.nn.softmax(mm(y, router, quant), -1)
    gates, ids = jax.lax.top_k(probs, top_k)
    return gates / gates.sum(-1, keepdims=True), ids


def moe(x, w, m: Dict, quant: bool):
    """Every expert over every token, weighted by the routed gates."""
    jax, jnp = _jnp()
    y = rms_norm(x, w["norm"], m["norm_eps"])
    gates, ids = route(y, w["router"], m["top_k"], quant)
    dense = jnp.zeros((y.shape[0], m["n_routed"]), jnp.float32).at[
        jnp.arange(y.shape[0])[:, None], ids].set(gates)

    def one(acc, e):
        we = jax.tree.map(lambda a: a[e], (w["wg"], w["wu"], w["wd"]))
        return acc + dense[:, e, None] * swiglu(y, *we, quant), None
    out, _ = jax.lax.scan(one, jnp.zeros_like(y), jnp.arange(m["n_routed"]))
    if "swg" in w:
        out = out + swiglu(y, w["swg"], w["swu"], w["swd"], quant)
    return x + out


@functools.lru_cache(maxsize=None)
def _layer_fn(kind: str, quant: bool, mkey: Tuple):
    jax, _ = _jnp()
    m = dict(mkey)
    if kind == "attn":
        return jax.jit(lambda x, w: attention(x, w, m, quant))
    if kind == "dense":
        return jax.jit(lambda x, w: mlp(x, w, m, quant))
    return jax.jit(lambda x, w: moe(x, w, m, quant))


def forward(params, tokens, m: Dict, quant: bool = False):
    """Logits (S, vocab) at every position of ``tokens`` (S,)."""
    jax, jnp = _jnp()
    mk = _mkey(m)
    embed, logits = _ends(quant, mk)
    x = embed(params["embed"], jnp.asarray(tokens, jnp.int32))
    for d0 in params.get("dense0", []):
        x = _layer_fn("attn", quant, mk)(x, d0["attn"])
        x = _layer_fn("dense", quant, mk)(x, d0["mlp"])
    layers = params["layers"]
    kinds = m["ffn_kinds"][len(params.get("dense0", [])):]
    for i, kind in enumerate(kinds):
        w = jax.tree.map(lambda a: a[i], layers)
        x = _layer_fn("attn", quant, mk)(x, w["attn"])
        x = _layer_fn(kind, quant, mk)(x, w["ffn"])
    return logits(x, params["final_norm"], params["embed"])


# ---- required work (the MFU numerators) --------------------------------------

def _attn_proj(m: Dict) -> int:
    d, h, kv, dh = m["d_model"], m["n_heads"], m["n_kv_heads"], m["head_dim"]
    return d * h * dh * 2 + d * kv * dh * 2          # q, o and k, v


def layer_matmul_params(m: Dict) -> int:
    """Weights that multiply every token, summed over the layers: attention
    projections, the dense FFNs, and for expert layers the router, the top-k
    routed experts and the shared experts (not all routed experts)."""
    d = m["d_model"]
    total = 0
    for kind in m["ffn_kinds"]:
        total += _attn_proj(m)
        if kind == "dense":
            total += 3 * d * m["d_ff"]
        else:
            fe = m["d_ff_expert"]
            total += d * m["n_routed"] + 3 * d * fe * (m["top_k"] + m["n_shared"])
    return total


def prefill_flops(m: Dict, seq: int) -> float:
    """A prompt of ``seq`` tokens: every layer for every token, causal
    attention, and the output projection for the last token only."""
    per_layer_attn = 2 * 2 * m["n_heads"] * m["head_dim"] * flops.causal_pairs(seq)
    return float(2 * layer_matmul_params(m) * seq
                 + per_layer_attn * len(m["ffn_kinds"])
                 + flops.unembed(m["d_model"], m["vocab"]))


def decode_flops(m: Dict, position: int) -> float:
    """One new token at ``position`` attending ``position + 1`` keys."""
    attn = 2 * 2 * m["n_heads"] * m["head_dim"] * (position + 1)
    return float(2 * layer_matmul_params(m) + attn * len(m["ffn_kinds"])
                 + flops.unembed(m["d_model"], m["vocab"]))
