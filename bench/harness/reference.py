"""Plain float32 references, independent of the program.

Nothing here imports the program.  The references follow the program's
block as the configuration files describe it (their ``assumed`` lists where
that block departs from the published model): RMS norm applied as
``1 + w``, rotary embedding over the whole head split in halves, causal
softmax attention, SwiGLU feed-forward, a softmax router whose top-k gates
are renormalised, shared experts added, and logits from the tied
embedding.  Every matmul runs at ``HIGHEST`` precision in float32.

``quant=True`` gives the control: the same reference with every matmul
operand rounded to float8 (e4m3, one scale per row of activations and per
output column of weights), the precision below the configurations' bf16.
Weights are upcast one layer (or one block of experts) at a time, so the
reference fits beside the weights on one chip.
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


def served_gaps(params, m: Dict, served: Sequence[Tuple[np.ndarray, List[int]]],
                pad_to: int, control: bool = False) -> List[np.ndarray]:
    """Per request, for each served token, how far its reference logit lies
    below the reference's best at that position (``control``: the gap of
    the token the float8 control ranks first instead).  ``served`` holds
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
