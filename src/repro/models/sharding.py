"""Sharding rules: PartitionSpecs for params, batches and caches.

Divisibility-driven rules (DESIGN.md §5): tensors shard on the ``model``
axis only when the relevant *logical* unit (attention heads, experts, FFN
columns) divides evenly; otherwise they replicate — e.g. gemma3's 4 heads
replicate on a 16-way model axis while its FFN shards, and GQA KV
projections replicate whenever n_kv_heads < model parallelism (the same KV
replication the paper handles in §4).

Batch axes: ('pod','data') when present.  Decode caches shard batch over the
data axes when divisible; for long_500k (batch=1) the KV cache shards its
SEQUENCE axis over 'data' instead (flash-decode style).
"""

from __future__ import annotations

import math
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding
from jax.sharding import PartitionSpec as P

from ..comm.context import data_axes
from . import model as M


def _axis_size(mesh: Mesh, name: str) -> int:
    return mesh.shape.get(name, 1) if name in mesh.axis_names else 1


def _div(n: int, m: int) -> bool:
    return m > 0 and n % m == 0


def param_spec_tree(cfg, mesh: Mesh):
    """PartitionSpec pytree matching ``init_params(cfg, key)``."""
    m = _axis_size(mesh, "model")
    H, K, Dh = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    shard_q = _div(H, m)
    shard_kv = _div(K, m)
    shard_ff = _div(cfg.d_ff, m)
    shard_ffe = _div(cfg.d_ff_expert, m)
    shard_exp = _div(cfg.n_routed, m)
    shard_vocab = _div(M.padded_vocab(cfg), m)
    shard_dmodel = _div(cfg.d_model, m)
    shard_di = _div(cfg.d_inner, m) if cfg.is_ssm else False
    shard_shared_ff = _div(cfg.n_shared * cfg.d_ff_expert, m)

    def attn_spec(stacked: bool):
        lead = (None,) if stacked else ()
        if cfg.is_mla:
            sq = "model" if shard_q else None
            return {
                "norm": P(*lead, None),
                "wq": P(*lead, None, sq),
                "wkv_a": P(*lead, None, None),     # the latent is shared by heads
                "kv_norm": P(*lead, None),
                "wkv_b": P(*lead, None, sq),
                "wo": P(*lead, sq, None),
            }
        return {
            "norm": P(*lead, None),
            "wq": P(*lead, None, "model") if shard_q else P(*lead, None, None),
            "wk": P(*lead, None, "model") if shard_kv else P(*lead, None, None),
            "wv": P(*lead, None, "model") if shard_kv else P(*lead, None, None),
            "wo": P(*lead, "model", None) if shard_q else P(*lead, None, None),
        }

    def mlp_spec(stacked: bool):
        lead = (None,) if stacked else ()
        sp = "model" if shard_ff else None
        return {
            "norm": P(*lead, None),
            "wg": P(*lead, None, sp),
            "wu": P(*lead, None, sp),
            "wd": P(*lead, sp, None),
        }

    def moe_spec(stacked: bool):
        lead = (None,) if stacked else ()
        se = "model" if shard_exp else None
        spec = {
            "norm": P(*lead, None),
            "router": P(*lead, None, None),
            "wg": P(*lead, se, None, None),
            "wu": P(*lead, se, None, None),
            "wd": P(*lead, se, None, None),
        }
        if cfg.n_shared:
            ss = "model" if shard_shared_ff else None
            spec.update({"swg": P(*lead, None, ss), "swu": P(*lead, None, ss),
                         "swd": P(*lead, ss, None)})
        if cfg.router_score == "sigmoid":
            spec["router_bias"] = P(*lead, None)
        return spec

    def mamba_spec(stacked: bool):
        lead = (None,) if stacked else ()
        sd = "model" if shard_dmodel else None
        si = "model" if shard_di else None
        return {
            "norm": P(*lead, None),
            "in_proj": P(*lead, sd, None),     # row-parallel
            "conv_w": P(*lead, None, None),
            "conv_b": P(*lead, None),
            "dt_bias": P(*lead, None),
            "A_log": P(*lead, None),
            "D": P(*lead, None),
            "out_norm": P(*lead, None),
            "out_proj": P(*lead, si, None),    # row-parallel
        }

    specs: Dict[str, Any] = {
        # vocab-sharded when divisible; otherwise REPLICATED — d_model
        # sharding makes every unembed a partial-sum and forces a (B,S,V)
        # logits all-reduce (iteration D: 12.9 GB/step on granite-3-8b)
        "embed": P("model", None) if shard_vocab else P(None, None),  # padded vocab
        "final_norm": P(None),
    }
    if not cfg.tie_embeddings:
        specs["lm_head"] = P(None, "model") if shard_vocab else P(None, None)
    if cfg.family == "vlm":
        specs["vision_proj"] = P(None, "model") if shard_dmodel else P(None, None)

    if cfg.family in ("ssm", "hybrid"):
        specs["layers"] = {"mamba": mamba_spec(stacked=True)}
        if cfg.family == "hybrid":
            specs["shared_attn"] = {"attn": attn_spec(False), "mlp": mlp_spec(False)}
    else:
        specs["layers"] = {
            "attn": attn_spec(True),
            "ffn": moe_spec(True) if cfg.is_moe else mlp_spec(True),
        }
        if cfg.first_k_dense:
            specs["dense0"] = [
                {"attn": attn_spec(False), "mlp": mlp_spec(False)}
                for _ in range(cfg.first_k_dense)]
    return specs


def batch_spec_tree(cfg, mesh: Mesh, shape) -> Dict[str, P]:
    """Specs for the data batch of a given InputShape."""
    daxes = data_axes(mesh)
    nd = math.prod(_axis_size(mesh, a) for a in daxes)
    bspec = daxes if _div(shape.global_batch, nd) else None
    specs: Dict[str, P] = {}
    if shape.kind == "train":
        specs = {"tokens": P(bspec, None), "targets": P(bspec, None)}
    else:
        specs = {"tokens": P(bspec, None)}
    if cfg.family == "vlm":
        specs["vision_emb"] = P(bspec, None, None)
    return specs


def cache_spec_tree(cfg, mesh: Mesh, batch: int, seq_len: int) -> Dict[str, P]:
    """Specs matching ``init_cache(cfg, batch, seq_len)``."""
    m = _axis_size(mesh, "model")
    daxes = data_axes(mesh)
    nd = math.prod(_axis_size(mesh, a) for a in daxes)
    data_only = tuple(a for a in daxes if a == "data") or None

    batch_ok = _div(batch, nd)
    bspec = daxes if batch_ok else None
    # long-context: shard the cache sequence axis instead of batch
    seq_spec = None
    if not batch_ok and data_only and _div(seq_len, _axis_size(mesh, "data")):
        seq_spec = "data"

    kv_head = "model" if _div(cfg.n_kv_heads, m) else None
    specs: Dict[str, P] = {}
    if cfg.family in ("ssm", "hybrid"):
        h_spec = "model" if _div(cfg.ssm_nheads, m) else None
        specs["conv"] = P(None, bspec, None, None)
        specs["ssd"] = P(None, bspec, h_spec, None, None)
        if cfg.family == "hybrid":
            W = min(seq_len, cfg.window) if cfg.window else seq_len
            wseq = "data" if (not batch_ok and data_only
                              and _div(W, _axis_size(mesh, "data"))) else None
            specs["ak"] = P(None, bspec, wseq, kv_head, None)
            specs["av"] = P(None, bspec, wseq, kv_head, None)
    elif cfg.global_every or cfg.cross_every:
        # pattern-split caches (model._pattern): local ring/full + special
        W = min(seq_len, cfg.window) if cfg.global_every else seq_len
        S_spec = seq_len if cfg.global_every else cfg.vision_seq
        wseq = "data" if (not batch_ok and data_only
                          and _div(W, _axis_size(mesh, "data"))) else None
        sseq = "data" if (not batch_ok and data_only
                          and _div(S_spec, _axis_size(mesh, "data"))) else None
        specs["lk"] = P(None, bspec, wseq, kv_head, None)
        specs["lv"] = P(None, bspec, wseq, kv_head, None)
        specs["sk"] = P(None, bspec, sseq, kv_head, None)
        specs["sv"] = P(None, bspec, sseq, kv_head, None)
    elif cfg.is_mla:
        specs["latent"] = P(None, bspec, seq_spec, None)
        if cfg.first_k_dense:
            specs["latent0"] = P(None, bspec, seq_spec, None)
    else:
        specs["k"] = P(None, bspec, seq_spec, kv_head, None)
        specs["v"] = P(None, bspec, seq_spec, kv_head, None)
        if cfg.first_k_dense:
            specs["k0"] = P(None, bspec, seq_spec, kv_head, None)
            specs["v0"] = P(None, bspec, seq_spec, kv_head, None)
    return specs


def opt_spec_tree(cfg, mesh: Mesh):
    """AdamW state specs (mu/nu mirror params)."""
    from ..optim import AdamWState
    pspec = param_spec_tree(cfg, mesh)
    return AdamWState(step=P(), mu=pspec, nu=pspec)


def named(mesh: Mesh, tree):
    return jax.tree.map(
        lambda s: NamedSharding(mesh, s), tree,
        is_leaf=lambda x: isinstance(x, P))
