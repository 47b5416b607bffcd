"""Mixture-of-Experts layer: router + shared/routed experts.

Three dispatch modes, mirroring the paper's baseline-vs-technique split:

* ``dense``   — exact: every token gets its top-k experts, nothing dropped.
                ``moe_path`` picks the execution from the static token
                count: with fewer selected (token, expert) pairs than
                experts (decode), only the selected experts' weights are
                read (``moe_routed_only``); otherwise every expert computes
                every token, combined by router weight (``moe_all_experts``,
                also the oracle).
* ``scatter`` — capacity-based scatter/gather dispatch (GShard-style).  The
                "collective-style" baseline: under pjit, GSPMD materialises
                the token movement as all-gathers/dynamic-slices.
* ``a2a``     — the fabric-lib analogue: explicit dispatch/combine through
                ``ragged_all_to_all`` inside shard_map on the expert-parallel
                axis (see ``repro.comm.moe_a2a``), the TPU-native mapping of
                the paper's §6 dispatch/combine WRITEs.
"""

from __future__ import annotations

from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from .common import dense_init, rms_norm, split_keys


def init_moe(key, cfg, dtype) -> Dict[str, jax.Array]:
    D, E, Fe = cfg.d_model, cfg.n_routed, cfg.d_ff_expert
    ks = split_keys(key, 7)
    p = {
        "norm": jnp.zeros((D,), dtype),
        "router": dense_init(ks[0], (D, E), jnp.float32, scale=D ** -0.5),
        "wg": dense_init(ks[1], (E, D, Fe), dtype),
        "wu": dense_init(ks[2], (E, D, Fe), dtype),
        "wd": dense_init(ks[3], (E, Fe, D), dtype, scale=Fe ** -0.5),
    }
    if cfg.n_shared:
        Fs = cfg.n_shared * Fe
        p.update({
            "swg": dense_init(ks[4], (D, Fs), dtype),
            "swu": dense_init(ks[5], (D, Fs), dtype),
            "swd": dense_init(ks[6], (Fs, D), dtype, scale=Fs ** -0.5),
        })
    if cfg.router_score == "sigmoid":
        p["router_bias"] = jnp.zeros((E,), jnp.float32)
    return p


def router_select(logits: jax.Array, top_k: int, score: str = "softmax",
                  bias: Optional[jax.Array] = None, scale: float = 1.0
                  ) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """The routing decision: renormalised top-k gates.

    ``score``: ``softmax`` over the experts, or ``sigmoid`` per expert
    (DeepSeek-V3's ``noaux_tc`` with one group): there ``bias`` (E,) is
    added to the scores to choose the top-k, and the gates are the unbiased
    scores of the chosen experts.  Gates are renormalised, then times
    ``scale``.  logits: (T, E) float32.  Returns (gates (T,k), eids (T,k),
    probs (T,E)): ``probs`` are the scores the aux loss balances.
    """
    if score == "sigmoid":
        probs = jax.nn.sigmoid(logits.astype(jnp.float32))
        _, eids = jax.lax.top_k(probs + bias, top_k)
        gates = jnp.take_along_axis(probs, eids, -1)
        probs = probs / probs.sum(-1, keepdims=True)
    else:
        probs = jax.nn.softmax(logits.astype(jnp.float32), axis=-1)
        gates, eids = jax.lax.top_k(probs, top_k)
    gates = gates / jnp.maximum(gates.sum(-1, keepdims=True), 1e-9)
    if scale != 1.0:
        gates = gates * scale
    return gates, eids, probs


def router_topk(logits: jax.Array, top_k: int, score: str = "softmax",
                bias: Optional[jax.Array] = None, scale: float = 1.0
                ) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """``router_select`` + aux load-balance loss.  Returns (gates (T,k),
    eids (T,k), aux_loss)."""
    gates, eids, probs = router_select(logits, top_k, score, bias, scale)
    # Switch-style aux loss: E * sum_e f_e * P_e
    E = logits.shape[-1]
    me = probs.mean(0)                                          # (E,)
    ce = jnp.zeros((E,), jnp.float32).at[eids.reshape(-1)].add(1.0)
    ce = ce / jnp.maximum(ce.sum(), 1.0)
    aux = E * jnp.sum(me * ce)
    return gates, eids, aux


def route(p, h: jax.Array, cfg) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """``router_topk`` of tokens ``h`` (T, D) by the layer's router."""
    logits = h.astype(jnp.float32) @ p["router"]
    return router_topk(logits, cfg.top_k, cfg.router_score,
                       p.get("router_bias"), cfg.routed_scaling)


def _experts_swiglu(p, xe: jax.Array) -> jax.Array:
    """xe: (E, C, D) -> (E, C, D) through per-expert SwiGLU."""
    g = jnp.einsum("ecd,edf->ecf", xe, p["wg"])
    u = jnp.einsum("ecd,edf->ecf", xe, p["wu"])
    return jnp.einsum("ecf,efd->ecd", jax.nn.silu(g) * u, p["wd"])


def _shared_out(p, h: jax.Array) -> jax.Array:
    if "swg" not in p:
        return jnp.zeros_like(h)
    return (jax.nn.silu(h @ p["swg"]) * (h @ p["swu"])) @ p["swd"]


def moe_path(n_tokens: int, cfg) -> str:
    """``"routed"`` when the ``n_tokens * top_k`` selected expert slices are
    fewer bytes than all ``n_routed`` experts, else ``"all"``."""
    return "routed" if n_tokens * cfg.top_k < cfg.n_routed else "all"


def moe_all_experts(p, h: jax.Array, cfg) -> Tuple[jax.Array, jax.Array]:
    """Exact all-experts path (oracle)."""
    T, D = h.shape
    gates, eids, aux = route(p, h, cfg)
    # (E, T, D) expert outputs
    ye = _experts_swiglu(p, jnp.broadcast_to(h[None], (cfg.n_routed, T, D)))
    w = jnp.zeros((T, cfg.n_routed), h.dtype).at[
        jnp.arange(T)[:, None], eids].set(gates.astype(h.dtype))
    y = jnp.einsum("te,etd->td", w, ye)
    return y + _shared_out(p, h), aux


def moe_routed_only(p, h: jax.Array, cfg) -> Tuple[jax.Array, jax.Array]:
    """Exact path that reads only the ``T * top_k`` selected experts' weights.

    Same router call on the same float32 logits as ``moe_all_experts``, so
    the routing is identical.  Each (token, expert) pair slices its expert's
    weights with a dynamic index, which XLA fuses into the dots; a gather
    of the layer's (E, D, F) stack would first copy the whole layer when the
    stack is itself a slice of a scanned layer axis.  SwiGLU and the gated
    sum accumulate in float32.
    """
    f32 = jnp.float32
    gates, eids, aux = route(p, h, cfg)
    T, k = eids.shape
    rows = []
    for t in range(T):
        x = h[t:t + 1]
        y = jnp.zeros((1, h.shape[1]), f32)
        for j in range(k):
            wg, wu, wd = (jax.lax.dynamic_index_in_dim(p[n], eids[t, j], keepdims=False)
                          for n in ("wg", "wu", "wd"))
            g = jnp.dot(x, wg, preferred_element_type=f32)
            u = jnp.dot(x, wu, preferred_element_type=f32)
            a = (jax.nn.silu(g) * u).astype(h.dtype)
            y = y + gates[t, j] * jnp.dot(a, wd, preferred_element_type=f32)
        rows.append(y)
    y = jnp.concatenate(rows, 0).astype(h.dtype)
    return y + _shared_out(p, h), aux


def moe_dense(p, h: jax.Array, cfg) -> Tuple[jax.Array, jax.Array]:
    """Exact MoE: the routed-only or the all-experts path by ``moe_path``
    (static under ``jit``: the token count is a shape)."""
    if moe_path(h.shape[0], cfg) == "routed":
        return moe_routed_only(p, h, cfg)
    return moe_all_experts(p, h, cfg)


def moe_scatter(p, h: jax.Array, cfg, capacity_factor: float = 1.25) -> Tuple[jax.Array, jax.Array]:
    """Capacity-based scatter/gather dispatch (collective-style baseline).

    Tokens beyond an expert's capacity are dropped (contribute zero), as in
    GShard/Switch.  Capacity C = ceil(T * k / E * cf).
    """
    T, D = h.shape
    E, k = cfg.n_routed, cfg.top_k
    C = max(1, int(T * k / E * capacity_factor))
    gates, eids, aux = route(p, h, cfg)

    fe = eids.reshape(-1)                                   # (T*k,)
    fg = gates.reshape(-1).astype(h.dtype)
    ft = jnp.repeat(jnp.arange(T), k)
    oh = jax.nn.one_hot(fe, E, dtype=jnp.int32)             # (T*k, E)
    pos = jnp.take_along_axis(jnp.cumsum(oh, 0) - oh, fe[:, None], 1)[:, 0]
    keep = pos < C
    slot = jnp.where(keep, pos, C)                          # overflow -> parking slot

    xe = jnp.zeros((E, C + 1, D), h.dtype).at[fe, slot].add(
        jnp.where(keep[:, None], h[ft], 0))
    ye = _experts_swiglu(p, xe[:, :C])
    ye = jnp.concatenate([ye, jnp.zeros((E, 1, D), ye.dtype)], axis=1)
    contrib = ye[fe, slot] * (fg * keep.astype(h.dtype))[:, None]
    y = jnp.zeros((T, D), h.dtype).at[ft].add(contrib)
    return y + _shared_out(p, h), aux


def moe_forward(p, x: jax.Array, cfg, mode: str = "scatter",
                ep_axis: Optional[str] = None) -> Tuple[jax.Array, jax.Array]:
    """x: (B, S, D) -> (y, aux_loss)."""
    B, S, D = x.shape
    h = rms_norm(x, p["norm"], cfg.norm_eps).reshape(B * S, D)
    if mode == "dense":
        y, aux = moe_dense(p, h, cfg)
    elif mode == "scatter":
        y, aux = moe_scatter(p, h, cfg)
    elif mode == "a2a":
        from ..comm.moe_a2a import moe_a2a
        y, aux = moe_a2a(p, h, cfg, ep_axis or "model")
    else:
        raise ValueError(f"unknown moe mode {mode}")
    return y.reshape(B, S, D), aux
