"""Architecture configuration schema.

Every assigned architecture gets one module in ``repro.configs`` exporting a
``CONFIG`` built from :class:`ModelConfig`.  ``reduced()`` derives the
CPU-smoke-test variant (<=2 layers, d_model<=512, <=4 experts) of the same
family, as required by the assignment.
"""

from __future__ import annotations

import dataclasses
from dataclasses import dataclass, field
from typing import Optional, Tuple


@dataclass(frozen=True)
class ModelConfig:
    # -- identity ---------------------------------------------------------
    name: str
    family: str                     # dense | moe | ssm | hybrid | audio | vlm
    source: str                     # citation from the assignment table

    # -- transformer backbone ----------------------------------------------
    n_layers: int
    d_model: int
    n_heads: int = 0                # 0 => attention-free (pure SSM)
    n_kv_heads: int = 0
    d_ff: int = 0                   # dense FFN width (0 => no FFN, e.g. mamba)
    vocab: int = 0
    d_head: int = 0                 # 0 => d_model // n_heads
    rope_theta: float = 10_000.0
    norm_eps: float = 1e-5
    tie_embeddings: bool = True     # False => a separate lm_head (D, V)

    # -- multi-head latent attention (DeepSeek-V2/V3 MLA; 0 => off) -------------
    kv_lora_rank: int = 0           # width of the cached, normalised latent
    qk_nope_head_dim: int = 0       # per-head query/key width without rotary
    qk_rope_head_dim: int = 0       # rotary width, the key's shared by all heads
    v_head_dim: int = 0             # per-head value width

    # -- MoE ----------------------------------------------------------------
    n_routed: int = 0               # routed experts (0 => dense FFN)
    n_shared: int = 0               # always-on shared experts
    top_k: int = 0
    d_ff_expert: int = 0
    first_k_dense: int = 0          # leading dense layers (DeepSeekMoE)
    router_aux_coef: float = 0.01   # load-balance loss coefficient
    router_score: str = "softmax"   # softmax | sigmoid (noaux_tc: a per-expert bias selects)
    routed_scaling: float = 1.0     # routed gates times this

    # -- SSM (Mamba2 / SSD) ----------------------------------------------------
    ssm_state: int = 0
    ssm_headdim: int = 64
    ssm_expand: int = 2
    ssm_ngroups: int = 1
    ssm_dconv: int = 4
    ssm_chunk: int = 128

    # -- hybrid (Zamba2-style) ---------------------------------------------
    attn_every: int = 0             # shared attention block after every N ssm layers

    # -- sliding-window pattern (Gemma3-style) ---------------------------------
    window: int = 0                 # local window size (0 => full attention)
    global_every: int = 0           # 1 global layer per N (5:1 => 6)

    # -- cross-attention (VLM) -------------------------------------------------
    cross_every: int = 0            # 1 cross-attn layer per N
    vision_seq: int = 0             # stub patch-embedding sequence length
    vision_dim: int = 0             # stub patch-embedding feature size

    # -- numerics ---------------------------------------------------------------
    param_dtype: str = "float32"

    # ---------------------------------------------------------------------------
    @property
    def head_dim(self) -> int:
        if self.d_head:
            return self.d_head
        return self.d_model // self.n_heads if self.n_heads else 0

    @property
    def is_mla(self) -> bool:
        return self.kv_lora_rank > 0

    @property
    def qk_head_dim(self) -> int:
        """MLA query/key width per head: the non-rotary and rotary parts."""
        return self.qk_nope_head_dim + self.qk_rope_head_dim

    @property
    def latent_dim(self) -> int:
        """MLA cache row: the normalised latent and the rotary key."""
        return self.kv_lora_rank + self.qk_rope_head_dim

    @property
    def is_moe(self) -> bool:
        return self.n_routed > 0

    @property
    def is_ssm(self) -> bool:
        return self.family in ("ssm", "hybrid")

    @property
    def d_inner(self) -> int:
        return self.ssm_expand * self.d_model

    @property
    def ssm_nheads(self) -> int:
        return self.d_inner // self.ssm_headdim

    @property
    def attention_free(self) -> bool:
        return self.family == "ssm"

    @property
    def subquadratic(self) -> bool:
        """Can this config serve 500k-token contexts?  (assignment rule)"""
        if self.family in ("ssm", "hybrid"):
            return True
        # Dense archs qualify only with a sliding-window/local variant.
        return self.window > 0

    def layer_kinds(self) -> Tuple[str, ...]:
        """Per-layer mixer kind: attn | local | global | cross | mamba."""
        kinds = []
        for i in range(self.n_layers):
            if self.family in ("ssm",):
                kinds.append("mamba")
            elif self.family == "hybrid":
                kinds.append("mamba")  # shared attn handled separately
            elif self.cross_every and (i % self.cross_every == self.cross_every - 1):
                kinds.append("cross")
            elif self.global_every:
                kinds.append("global" if i % self.global_every == self.global_every - 1
                             else "local")
            else:
                kinds.append("attn")
        return tuple(kinds)

    def ffn_kinds(self) -> Tuple[str, ...]:
        kinds = []
        for i in range(self.n_layers):
            if self.d_ff == 0 and not self.is_moe:
                kinds.append("none")
            elif self.is_moe and i >= self.first_k_dense:
                kinds.append("moe")
            else:
                kinds.append("dense")
        return tuple(kinds)

    def reduced(self) -> "ModelConfig":
        """Smoke-test variant of the same family (assignment: <=2 layers,
        d_model<=512, <=4 experts)."""
        d_model = min(self.d_model, 256)
        n_heads = min(self.n_heads, 4) if self.n_heads else 0
        n_kv = max(1, min(self.n_kv_heads, n_heads)) if n_heads else 0
        changes = dict(
            name=self.name + "-reduced",
            n_layers=2,
            d_model=d_model,
            n_heads=n_heads,
            n_kv_heads=n_kv,
            d_head=(d_model // n_heads if n_heads else 0),
            d_ff=min(self.d_ff, 512) if self.d_ff else 0,
            vocab=min(self.vocab, 512),
            n_routed=min(self.n_routed, 4),
            n_shared=min(self.n_shared, 1),
            top_k=min(self.top_k, 2),
            d_ff_expert=min(self.d_ff_expert, 128) if self.d_ff_expert else 0,
            first_k_dense=min(self.first_k_dense, 1),
            ssm_state=min(self.ssm_state, 16),
            ssm_headdim=32 if self.ssm_state else 64,
            ssm_chunk=32,
            attn_every=2 if self.attn_every else 0,
            window=min(self.window, 16) if self.window else 0,
            global_every=2 if self.global_every else 0,
            cross_every=2 if self.cross_every else 0,
            vision_seq=min(self.vision_seq, 16) if self.vision_seq else 0,
            vision_dim=min(self.vision_dim, 64) if self.vision_dim else 0,
            kv_lora_rank=min(self.kv_lora_rank, 64),
            qk_nope_head_dim=min(self.qk_nope_head_dim, 32),
            qk_rope_head_dim=min(self.qk_rope_head_dim, 16),
            v_head_dim=min(self.v_head_dim, 32),
        )
        return dataclasses.replace(self, **changes)

    def param_count(self) -> int:
        """Parameters of the embedding, the untied head and the layers'
        projections, routers and experts (norm weights left out)."""
        D, F, V = self.d_model, self.d_ff, self.vocab
        total = V * D * (1 if self.tie_embeddings else 2)
        Hd, H = self.head_dim, self.n_heads
        if self.is_mla:
            attn = (D * H * self.qk_head_dim + D * self.latent_dim
                    + self.kv_lora_rank * H * (self.qk_nope_head_dim + self.v_head_dim)
                    + H * self.v_head_dim * D)
        else:
            attn = D * H * Hd + 2 * D * self.n_kv_heads * Hd + H * Hd * D
        for kind, ffn in zip(self.layer_kinds(), self.ffn_kinds()):
            if kind in ("attn", "local", "global", "cross"):
                total += attn
            elif kind == "mamba":
                di, g, N = self.d_inner, self.ssm_ngroups, self.ssm_state
                H = self.ssm_nheads
                total += D * (2 * di + 2 * g * N + H) + di * D + (self.ssm_dconv) * (di + 2 * g * N)
            if ffn == "dense":
                total += 3 * D * F
            elif ffn == "moe":
                total += self.n_routed * 3 * D * self.d_ff_expert
                total += self.n_shared * 3 * D * self.d_ff_expert
                total += D * self.n_routed
                total += self.n_routed if self.router_score == "sigmoid" else 0
        if self.family == "hybrid":
            total += attn + 3 * D * self.d_ff  # one shared attention block
        if self.family == "vlm":
            total += self.vision_dim * D       # patch-embedding projector
        return total

    def active_param_count(self) -> int:
        """Parameters active per token (MoE: top_k + shared instead of all)."""
        if not self.is_moe:
            return self.param_count()
        total = self.param_count()
        n_moe_layers = sum(1 for f in self.ffn_kinds() if f == "moe")
        all_routed = n_moe_layers * self.n_routed * 3 * self.d_model * self.d_ff_expert
        act_routed = n_moe_layers * self.top_k * 3 * self.d_model * self.d_ff_expert
        return total - all_routed + act_routed
