"""A configuration file as the program runs it, and its weights.

Configuration files keep the published keys (Hugging Face ``config.json``
names); the architecture module that ``model_type`` names
(``harness/arch/``) maps them onto the program's ``ModelConfig`` and gives
the parameter layout.  This module makes the weights: random, from the
seed, on the device, in one jitted call, in the served dtype and in that
layout.  The architecture's reference reads the same arrays, so neither
side depends on weights that the other made.
"""

from __future__ import annotations

import json
import pathlib
from typing import Dict

import numpy as np

# the program pads the embedding rows to a multiple of this (masked logits)
VOCAB_PAD = 512


def load(path: pathlib.Path) -> Dict:
    return json.loads(pathlib.Path(path).read_text())


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


def make_params(mod, cfg, seed32: int, shardings=None):
    """All weights of ``cfg`` in the layout of architecture module ``mod``,
    from ``seed32``, in one jitted call on the device (``shardings``: an
    optional pytree of output shardings)."""
    import jax
    import jax.numpy as jnp
    specs = mod.param_shapes(cfg)
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
