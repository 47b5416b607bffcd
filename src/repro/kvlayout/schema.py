"""KvSchema: a declarative description of a model's reduced-cache state.

The §4 KvCache protocol moves "the cache" — but what the cache *is* differs
per architecture: a uniform ``(L, S, K, Dh)`` k/v stack (stablelm, granite,
qwen3-moe, musicgen), local+special ring/full stacks for pattern archs
(gemma3 ``lk/lv/sk/sv``, llama-vision self/cross), per-layer SSM state
blobs (mamba2 ``conv``/``ssd``), hybrid SSM + shared-attention rings
(zamba2 ``ak/av``), or a head of dense layers in front of the scanned stack
(deepseek ``k0/v0``).  The seed serving stack hard-coded the first shape
and guarded the rest out via ``disagg_unsupported_reason``.

A :class:`KvSchema` names each cache array as a *component* with:

* ``name``     — the cache-dict key the model stack produces/consumes;
* ``layers``   — the model layer ids whose compute produces each stack
  entry (this is what maps UvmWatcher layer progress to transferable
  state);
* ``dtype``    — numpy dtype name of the wire bytes (``"bfloat16"``,
  ``"float32"``; a name, because bfloat16's ``dtype.str`` is the
  uninterpretable ``"<V2"``);
* ``kind``     — the component's extent semantics:
    - ``token``:  one row per *prompt token* (paged over ``page_tokens``);
    - ``ring``:   a ring buffer of ``min(max_len, window)`` token slots,
                  transferred whole (slot occupancy is positional);
    - ``fixed``:  a fixed number of token rows independent of the prompt
                  (vlm cross-attention K/V over the vision sequence);
    - ``blob``:   one fixed-size byte blob per stack layer (SSM conv/ssd
                  state — per-sequence, not per-token);
* page geometry — ``token_bytes``/``blob_bytes`` plus the schema-wide
  ``page_tokens``, from which every WRITE length is derived.

Schemas are derived from ``ModelConfig`` (mirroring ``models.init_cache``
exactly), are serialisable over the ctrl wire (JOIN advertises them; the
Scheduler refuses to pair peers whose schemas differ), and are the input
to the transfer-plan compiler in :mod:`repro.kvlayout.plan`.

All layout decisions live here, at *schema* time — the transfer hot path
never inspects an architecture again (arXiv 2605.00686's plan-ahead
principle; paper §3.4 WR templating).
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Any, Dict, List, Optional, Tuple

import ml_dtypes  # noqa: F401  (registers the "bfloat16" dtype name)
import numpy as np

# Decode headroom baked into the handoff cache: both ends of a transfer
# derive cache geometry from the SAME max_len so ring slot assignment
# (slot = pos % W) and padding agree bit-for-bit.
DECODE_MARGIN = 64

KINDS = ("token", "ring", "fixed", "blob")


def handoff_max_len(seq_len: int) -> int:
    """Canonical cache length for a disaggregated handoff of ``seq_len``."""
    return seq_len + DECODE_MARGIN


@dataclass(frozen=True)
class KvComponent:
    """One named array of the reduced cache (see module docstring)."""

    name: str
    kind: str
    layers: Tuple[int, ...]        # producing model layer per stack entry
    dtype: str                     # numpy dtype name (e.g. "float32")
    token_bytes: int = 0           # bytes/token/stack-layer (token|ring|fixed)
    window: int = 0                # ring capacity cap (ring; 0 = max_len)
    fixed_tokens: int = 0          # token rows (fixed)
    blob_bytes: int = 0            # bytes/stack-layer (blob)

    def __post_init__(self):
        if self.kind not in KINDS:
            raise ValueError(f"unknown component kind {self.kind!r}")

    @property
    def n_stack(self) -> int:
        return len(self.layers)

    def tokens(self, seq_len: int, max_len: int) -> int:
        """Token rows moved per stack layer (0 for blob components)."""
        if self.kind == "token":
            return seq_len
        if self.kind == "ring":
            return min(max_len, self.window) if self.window else max_len
        if self.kind == "fixed":
            return self.fixed_tokens
        return 0

    def layer_bytes(self, seq_len: int, max_len: int) -> int:
        """Payload bytes per stack layer."""
        if self.kind == "blob":
            return self.blob_bytes
        return self.tokens(seq_len, max_len) * self.token_bytes

    def page_len(self, page_tokens: int) -> int:
        """Bytes of one WRITE (page) of this component."""
        if self.kind == "blob":
            return self.blob_bytes
        return page_tokens * self.token_bytes

    def chunks(self, seq_len: int, max_len: int, page_tokens: int) -> int:
        """Pages per stack layer for a ``seq_len`` handoff."""
        if self.kind == "blob":
            return 1
        t = self.tokens(seq_len, max_len)
        return -(-t // page_tokens)


@dataclass(frozen=True)
class KvSchema:
    """The complete cache-state schema of one architecture."""

    arch: str
    n_layers: int
    page_tokens: int
    components: Tuple[KvComponent, ...]

    def component(self, name: str) -> KvComponent:
        for c in self.components:
            if c.name == name:
                return c
        raise KeyError(name)

    def names(self) -> Tuple[str, ...]:
        return tuple(c.name for c in self.components)

    @property
    def slot_bytes(self) -> int:
        """Uniform pool-slot size: every component's page fits in a slot,
        so one shared page allocator serves all components."""
        return max(c.page_len(self.page_tokens) for c in self.components)

    def total_bytes(self, seq_len: int) -> int:
        ml = handoff_max_len(seq_len)
        return sum(c.n_stack * c.layer_bytes(seq_len, ml)
                   for c in self.components)

    # -- wire form (carried in the ctrl JOIN / VIEW-UPDATE) -----------------
    def to_wire(self) -> Dict[str, Any]:
        return {
            "arch": self.arch, "n_layers": self.n_layers,
            "page_tokens": self.page_tokens,
            "components": [{
                "name": c.name, "kind": c.kind, "layers": list(c.layers),
                "dtype": c.dtype, "token_bytes": c.token_bytes,
                "window": c.window, "fixed_tokens": c.fixed_tokens,
                "blob_bytes": c.blob_bytes,
            } for c in self.components],
        }

    @staticmethod
    def from_wire(d: Dict[str, Any]) -> "KvSchema":
        return KvSchema(
            arch=d["arch"], n_layers=int(d["n_layers"]),
            page_tokens=int(d["page_tokens"]),
            components=tuple(KvComponent(
                name=c["name"], kind=c["kind"],
                layers=tuple(int(x) for x in c["layers"]), dtype=c["dtype"],
                token_bytes=int(c["token_bytes"]), window=int(c["window"]),
                fixed_tokens=int(c["fixed_tokens"]),
                blob_bytes=int(c["blob_bytes"]))
                for c in d["components"]),
        )

    def mismatch(self, other: Optional["KvSchema"]) -> Optional[str]:
        """Why a transfer between ``self`` (src) and ``other`` (dst) cannot
        be compiled (None = compatible).  Checked by the Scheduler at
        routing time, so incompatible pairs fail before any WRITE."""
        if other is None:
            return "peer advertises no KvSchema"
        if self.page_tokens != other.page_tokens:
            return (f"page_tokens differ ({self.page_tokens} vs "
                    f"{other.page_tokens})")
        if self.components != other.components:
            return (f"component sets differ ({self.names()} vs "
                    f"{other.names()})")
        return None


# ---------------------------------------------------------------------------
# derivation from ModelConfig (must mirror models.init_cache / prefill)
# ---------------------------------------------------------------------------

def _pattern_period(cfg) -> int:
    """Pattern period, identical to ``models.model._pattern``."""
    if cfg.family in ("ssm", "hybrid") or cfg.first_k_dense:
        return 0
    if cfg.global_every:
        return cfg.global_every
    if cfg.cross_every:
        return cfg.cross_every
    return 0


def schema_from_config(cfg, page_tokens: int = 16) -> KvSchema:
    """Derive the KvSchema of ``cfg``'s reduced cache.

    Every family in ``repro.models`` maps onto token/ring/fixed/blob
    components; the ``layers`` tuples are the model layer ids whose compute
    completes each stack entry, which is what lets the Prefiller's
    UvmWatcher trigger per-span transfers for ANY cache shape.
    """
    dt = np.dtype(cfg.param_dtype).name
    f4 = np.dtype(np.float32).name
    itemsize = np.dtype(cfg.param_dtype).itemsize
    comps: List[KvComponent] = []

    if cfg.family in ("ssm", "hybrid"):
        from ..models.ssm import conv_dim
        all_layers = tuple(range(cfg.n_layers))
        conv_bytes = (cfg.ssm_dconv - 1) * conv_dim(cfg) * itemsize
        ssd_bytes = (cfg.ssm_nheads * cfg.ssm_headdim * cfg.ssm_state
                     * np.dtype(np.float32).itemsize)
        comps.append(KvComponent("conv", "blob", all_layers, dt,
                                 blob_bytes=conv_bytes))
        comps.append(KvComponent("ssd", "blob", all_layers, f4,
                                 blob_bytes=ssd_bytes))
        if cfg.family == "hybrid":
            # the shared attention block's ring cache: one stack entry per
            # group, produced after the group's last mamba layer
            g = cfg.attn_every
            n_groups = cfg.n_layers // g
            ak_layers = tuple((i + 1) * g - 1 for i in range(n_groups))
            tb = cfg.n_kv_heads * cfg.head_dim * itemsize
            for name in ("ak", "av"):
                comps.append(KvComponent(name, "ring", ak_layers, dt,
                                         token_bytes=tb, window=cfg.window))
        return KvSchema(cfg.name, cfg.n_layers, page_tokens, tuple(comps))

    tb = cfg.n_kv_heads * cfg.head_dim * itemsize
    if _pattern_period(cfg):
        kinds = cfg.layer_kinds()
        loc = tuple(i for i, k in enumerate(kinds) if k in ("local", "attn"))
        spe = tuple(i for i, k in enumerate(kinds) if k in ("global", "cross"))
        if cfg.global_every:
            # gemma3: local layers ring over the window; globals full-length
            for name in ("lk", "lv"):
                comps.append(KvComponent(name, "ring", loc, dt,
                                         token_bytes=tb, window=cfg.window))
            for name in ("sk", "sv"):
                comps.append(KvComponent(name, "token", spe, dt,
                                         token_bytes=tb))
        else:
            # vlm: self layers full-length; cross layers hold vision K/V
            for name in ("lk", "lv"):
                comps.append(KvComponent(name, "token", loc, dt,
                                         token_bytes=tb))
            for name in ("sk", "sv"):
                comps.append(KvComponent(name, "fixed", spe, dt,
                                         token_bytes=tb,
                                         fixed_tokens=cfg.vision_seq))
        return KvSchema(cfg.name, cfg.n_layers, page_tokens, tuple(comps))

    # attention families with a uniform scanned stack (+ optional dense head)
    k0 = cfg.first_k_dense
    if k0:
        head = tuple(range(k0))
        for name in ("k0", "v0"):
            comps.append(KvComponent(name, "token", head, dt, token_bytes=tb))
    body = tuple(range(k0, cfg.n_layers))
    for name in ("k", "v"):
        comps.append(KvComponent(name, "token", body, dt, token_bytes=tb))
    return KvSchema(cfg.name, cfg.n_layers, page_tokens, tuple(comps))
