"""A configuration file as the program runs it, and its weights.

Configuration files keep the published keys (Hugging Face ``config.json``
names).  This module maps them onto the program's ``ModelConfig`` and makes
the weights: random, from the seed, on the device, in one jitted call, in
the served dtype and in the parameter layout the program takes.  The
reference (``harness/reference.py``) reads the same arrays, so neither side
depends on weights that the other made.
"""

from __future__ import annotations

import json
import pathlib
from typing import Dict

import numpy as np

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
# the program pads the embedding rows to a multiple of this (masked logits)
VOCAB_PAD = 512


def load(path: pathlib.Path) -> Dict:
    return json.loads(pathlib.Path(path).read_text())


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


EMBED_STD = 0.02
NORM_STD = 0.1


def _leaf(key, spec, dtype):
    import jax
    import jax.numpy as jnp
    shape, fan = spec[0], spec[1]
    dt = jnp.dtype(spec[2]) if len(spec) > 2 else dtype
    z = jax.random.normal(key, shape, jnp.float32)
    if fan is None:                              # norm weight, applied as 1 + w
        return (NORM_STD * z).astype(dt)
    std = EMBED_STD if fan == "embed" else fan ** -0.5
    return (std * z).astype(dt)


def _is_spec(x) -> bool:
    return isinstance(x, tuple) and isinstance(x[0], tuple)


def make_params(cfg, seed32: int, shardings=None):
    """All weights of ``cfg`` from ``seed32`` in one jitted call on the
    device (``shardings``: an optional pytree of output shardings)."""
    import jax
    import jax.numpy as jnp
    specs = param_shapes(cfg)
    leaves, tree = jax.tree.flatten(specs, is_leaf=_is_spec)
    dtype = jnp.dtype(cfg.param_dtype)

    def init(key):
        keys = jax.random.split(key, len(leaves))
        return jax.tree.unflatten(tree, [_leaf(k, s, dtype)
                                         for k, s in zip(keys, leaves)])
    fn = jax.jit(init, out_shardings=shardings)
    return fn(jax.random.key(seed32))


def check_layout(cfg, params) -> None:
    """The program's own init must describe the same pytree, shapes and
    dtypes as the weights made here; raises where it does not."""
    import jax
    from repro.models import init_params
    want = jax.eval_shape(lambda k: init_params(cfg, k), jax.random.key(0))
    got = jax.tree.map(lambda a: (a.shape, a.dtype), params)
    want = jax.tree.map(lambda a: (a.shape, a.dtype), want)
    if jax.tree.structure(got) != jax.tree.structure(want) or \
            jax.tree.leaves(got) != jax.tree.leaves(want):
        raise ValueError("the program's parameter layout differs from the "
                         "benchmark's weights")


def seed32(seed: int, stream: str) -> int:
    """A 31-bit seed for one named stream of a run's seed (any size)."""
    r = np.random.default_rng([seed, *stream.encode()])
    return int(r.integers(0, 2 ** 31 - 1))
