"""The least work each measured program needs, from its shapes.

Every count is what the algorithm requires: causal attention counts only
the lower triangle with its diagonal, a mixture of experts only the routed
top-k and the shared experts.  Padding, capacity slack and recomputation
are never counted, so a share built on these numbers cannot pass 100% for
a program that skips or pads work.  A matmul of (m, k) by (k, n) is
2·m·k·n operations.
"""

from __future__ import annotations

from typing import Dict


# ---- model FLOPs per token, the MFU numerators -----------------------------

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
    per_layer_attn = 2 * 2 * m["n_heads"] * m["head_dim"] * (seq * (seq + 1) // 2)
    return float(2 * layer_matmul_params(m) * seq
                 + per_layer_attn * len(m["ffn_kinds"])
                 + 2 * m["d_model"] * m["vocab"])


def decode_flops(m: Dict, position: int) -> float:
    """One new token at ``position`` attending ``position + 1`` keys."""
    attn = 2 * 2 * m["n_heads"] * m["head_dim"] * (position + 1)
    return float(2 * layer_matmul_params(m) + attn * len(m["ffn_kinds"])
                 + 2 * m["d_model"] * m["vocab"])
