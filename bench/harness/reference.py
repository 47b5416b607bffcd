"""Plain float32 references, independent of the program: what every
architecture's reference (``forward`` of its module in ``harness/arch/``)
shares, and the comparison of served tokens with it.

Nothing here or there imports the program.  Every matmul runs at
``HIGHEST`` precision in float32.  ``quant=True`` gives the control: the
same reference with every matmul operand rounded to float8 (e4m3, one scale
per row of activations and per output column of weights), the precision
below the configurations' bf16.
"""

from __future__ import annotations

import functools
from typing import Dict, List, Sequence, Tuple

import numpy as np

F8_MAX = 448.0


def _jnp():
    import jax
    import jax.numpy as jnp
    return jax, jnp


def fp8(x, axis: int):
    """Round ``x`` to float8 e4m3 with one scale along ``axis``'s slices."""
    jax, jnp = _jnp()
    amax = jnp.max(jnp.abs(x), axis=axis, keepdims=True)
    scale = jnp.where(amax > 0, amax / F8_MAX, 1.0)
    return (x / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) * scale


def mm(a, b, quant: bool):
    """(…, k) @ (k, n) in float32 at HIGHEST precision."""
    jax, jnp = _jnp()
    a, b = a.astype(jnp.float32), b.astype(jnp.float32)
    if quant:
        a, b = fp8(a, -1), fp8(b, -2)
    return jnp.matmul(a, b, precision=jax.lax.Precision.HIGHEST)


def rms_norm(x, w, eps: float):
    jax, jnp = _jnp()
    x = x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps)
    return x * (1.0 + w.astype(jnp.float32))


def rope(x, theta: float):
    """x: (S, heads, dh), positions 0..S-1, halves rotated."""
    jax, jnp = _jnp()
    s, dh = x.shape[0], x.shape[-1]
    freqs = 1.0 / (theta ** (jnp.arange(0, dh, 2, dtype=jnp.float32) / dh))
    ang = jnp.arange(s, dtype=jnp.float32)[:, None] * freqs
    cos, sin = jnp.cos(ang)[:, None], jnp.sin(ang)[:, None]
    x1, x2 = x[..., : dh // 2], x[..., dh // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


@functools.lru_cache(maxsize=None)
def _ends(quant: bool, mkey: Tuple):
    jax, jnp = _jnp()
    m = dict(mkey)

    def embed(emb, tokens):
        return jnp.take(emb, tokens, axis=0).astype(jnp.float32)

    def logits(x, norm, emb):
        y = rms_norm(x, norm, m["norm_eps"])
        return mm(y, emb[: m["vocab"]].T, quant)
    return jax.jit(embed), jax.jit(logits)


def _mkey(m: Dict) -> Tuple:
    return tuple(sorted((k, tuple(v) if isinstance(v, list) else v)
                        for k, v in m.items()))


def served_gaps(forward, params, m: Dict,
                served: Sequence[Tuple[np.ndarray, List[int]]],
                pad_to: int, control: bool = False) -> List[np.ndarray]:
    """Per request, for each served token, how far its reference logit lies
    below the reference's best at that position (``forward``: the
    architecture's reference; ``control``: the gap of the token the float8
    control ranks first instead).  ``served`` holds
    (prompt, served tokens); every sequence is padded to ``pad_to`` at its
    end, which causal attention leaves unseen, so one program serves all
    lengths."""
    gaps = []
    for prompt, toks in served:
        seq = np.concatenate([prompt, np.asarray(toks[:-1], np.int32)])
        pad = np.zeros(pad_to, np.int32)
        pad[: len(seq)] = seq
        pos = np.arange(len(prompt) - 1, len(seq))
        ref = np.asarray(forward(params, pad, m), np.float64)[pos]
        pick = np.asarray(toks)
        if control:
            ctl = np.asarray(forward(params, pad, m, quant=True))[pos]
            pick = ctl.argmax(-1)
        gaps.append(ref.max(-1) - ref[np.arange(len(pos)), pick])
    return gaps
