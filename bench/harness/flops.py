"""The least work each measured program needs, from its shapes.

Every count is what the algorithm requires: causal attention counts only
the lower triangle with its diagonal, a mixture of experts only the routed
top-k and the shared experts.  Padding, capacity slack and recomputation
are never counted, so a share built on these numbers cannot pass 100% for
a program that skips or pads work.  A matmul of (m, k) by (k, n) is
2·m·k·n operations.

Each architecture module (``harness/arch/``) counts its own prefill and
decode step with the helpers here.
"""

from __future__ import annotations


def causal_pairs(seq: int) -> int:
    """Query-key pairs of causal attention over ``seq`` tokens."""
    return seq * (seq + 1) // 2


def unembed(d_model: int, vocab: int) -> int:
    """The output projection for one token."""
    return 2 * d_model * vocab
