"""Model assembly: init / train-forward / prefill / decode for all families.

Public API (pure functions over param pytrees):
  init_params(cfg, key)                        -> params
  forward_train(params, tokens, cfg, ...)      -> (logits, aux_loss)
  loss_fn(params, batch, cfg, ...)             -> (loss, metrics)
  init_cache(cfg, batch, max_len, ...)         -> cache pytree
  prefill(params, tokens, cfg, ...)            -> (logits, cache)
  decode_step(params, tokens, positions, cache, cfg, ...) -> (logits, cache)

Caches (per family):
  attn:   {"k","v": (L,B,Smax,K,Dh)}  [+ {"ck","cv": (L,B,Sv,K,Dh)} for vlm]
  mla:    {"latent": (L,B,Smax,kv_lora_rank+qk_rope_head_dim)}
          [+ "latent0" for the first_k_dense layers, in place of "k0","v0"]
  ssm:    {"conv": (L,B,K-1,convdim), "ssd": (L,B,H,P,N)}
  hybrid: ssm caches (L=n_mamba) + ring KV for the shared attention block:
          {"ak","av": (n_groups? no — single shared block per application is
           re-applied; its cache is (n_apps,B,W,K,Dh))}
"""

from __future__ import annotations

import functools
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from .attention import (attn_decode, attn_prefill, init_attn, mla_decode,
                        mla_prefill)
from .blocks import layer_metadata, stacked_init
from .common import dense_init, rms_norm, split_keys
from .mlp import init_mlp, mlp_forward
from .moe import init_moe, moe_forward
from .ssm import conv_dim, init_mamba, mamba_decode, mamba_prefill

Params = Dict[str, Any]


# --------------------------------------------------------------------------
# init
# --------------------------------------------------------------------------

def _dtype(cfg):
    return jnp.dtype(cfg.param_dtype)


# Embedding rows are padded so the vocabulary always divides the model axis
# (Megatron-style): granite's 49155 would otherwise force either a
# d_model-sharded embedding (=> a (B,S,V) partial-sum logits all-reduce,
# 12.9 GB/step) or a replicated unembed (=> 16x duplicated logits compute).
# Pad logits are masked to -inf in _unembed.  §Perf iteration D2.
VOCAB_PAD = 512


def padded_vocab(cfg) -> int:
    return -(-cfg.vocab // VOCAB_PAD) * VOCAB_PAD


def init_params(cfg, key: jax.Array) -> Params:
    dtype = _dtype(cfg)
    ks = split_keys(key, 8)
    D = cfg.d_model
    params: Params = {
        "embed": dense_init(ks[0], (padded_vocab(cfg), D), dtype, scale=0.02),
        "final_norm": jnp.zeros((D,), dtype),
    }
    if not cfg.tie_embeddings:
        params["lm_head"] = dense_init(ks[6], (D, padded_vocab(cfg)), dtype)
    if cfg.family == "vlm":
        params["vision_proj"] = dense_init(ks[1], (cfg.vision_dim, D), dtype)

    if cfg.family == "ssm":
        params["layers"] = {"mamba": stacked_init(
            lambda k: init_mamba(k, cfg, dtype), ks[2], cfg.n_layers)}
    elif cfg.family == "hybrid":
        params["layers"] = {"mamba": stacked_init(
            lambda k: init_mamba(k, cfg, dtype), ks[2], cfg.n_layers)}
        params["shared_attn"] = {
            "attn": init_attn(ks[3], cfg, dtype),
            "mlp": init_mlp(ks[4], D, cfg.d_ff, dtype),
        }
    else:
        n_scan = cfg.n_layers - cfg.first_k_dense
        layers: Params = {"attn": stacked_init(
            lambda k: init_attn(k, cfg, dtype), ks[2], n_scan)}
        if cfg.is_moe:
            layers["ffn"] = stacked_init(
                lambda k: init_moe(k, cfg, dtype), ks[3], n_scan)
        else:
            layers["ffn"] = stacked_init(
                lambda k: init_mlp(k, D, cfg.d_ff, dtype), ks[3], n_scan)
        params["layers"] = layers
        if cfg.first_k_dense:
            d0 = []
            for i, k in enumerate(split_keys(ks[5], cfg.first_k_dense)):
                k1, k2 = jax.random.split(k)
                d0.append({"attn": init_attn(k1, cfg, dtype),
                           "mlp": init_mlp(k2, D, cfg.d_ff, dtype)})
            params["dense0"] = d0
    return params


# --------------------------------------------------------------------------
# shared pieces
# --------------------------------------------------------------------------

def _embed(params, tokens, cfg):
    return jnp.take(params["embed"], tokens, axis=0)


def _unembed(params, x, cfg):
    h = rms_norm(x, params["final_norm"], cfg.norm_eps)
    head = params["embed"].T if cfg.tie_embeddings else params["lm_head"]
    logits = (h @ head).astype(jnp.float32)
    Vp = logits.shape[-1]
    if Vp != cfg.vocab:   # mask vocab-padding rows
        pad_mask = jnp.arange(Vp) < cfg.vocab
        logits = jnp.where(pad_mask, logits, -1e30)
    return logits


def _vision(params, vision_emb, cfg):
    if cfg.family != "vlm":
        return None
    return (vision_emb.astype(_dtype(cfg)) @ params["vision_proj"])


def _hybrid_groups(cfg) -> Tuple[int, int, int]:
    """(n_groups, group_size, remainder) for zamba2-style layouts."""
    g = cfg.attn_every
    n_groups = cfg.n_layers // g
    return n_groups, g, cfg.n_layers - n_groups * g


# --------------------------------------------------------------------------
# pattern-split serving path (§Perf iteration B)
#
# Archs with a periodic special layer (gemma3: 1 global per 6; llama-vision:
# 1 cross per 5) serve with SPLIT layer stacks: the frequent "local" layers
# carry only a window-sized ring cache (gemma3) or no extra cache (vlm self
# layers stay full-length), while the rare special layers carry their own
# full-length / vision-length cache.  This removes the uniform-stack waste
# (a 500k cache allocated for 1024-window layers; a 32k self-cache allocated
# for cross layers that never self-attend).
# --------------------------------------------------------------------------

def _pattern(cfg) -> int:
    """Pattern period (0 = no pattern split)."""
    if cfg.family in ("ssm", "hybrid") or cfg.first_k_dense:
        return 0
    if cfg.global_every:
        return cfg.global_every
    if cfg.cross_every:
        return cfg.cross_every
    return 0


def _pattern_split(cfg, layers):
    """Split the uniform layer stack into (local_stack, special_stack)."""
    import numpy as np
    kinds = cfg.layer_kinds()
    loc = np.asarray([i for i, k in enumerate(kinds)
                      if k in ("local", "attn")], np.int32)
    spe = np.asarray([i for i, k in enumerate(kinds)
                      if k in ("global", "cross")], np.int32)
    ltree = jax.tree.map(lambda a: a[loc], layers)
    stree = jax.tree.map(lambda a: a[spe], layers)
    return ltree, stree, len(loc), len(spe)


def _group_stack(tree, n_groups: int, group: int):
    return jax.tree.map(
        lambda a: a[: n_groups * group].reshape(n_groups, group, *a.shape[1:]), tree)


def _tail_stack(tree, n_head: int):
    return jax.tree.map(lambda a: a[n_head:], tree)


# --------------------------------------------------------------------------
# train forward
# --------------------------------------------------------------------------

def forward_train(params: Params, tokens: jax.Array, cfg, *,
                  vision_emb: Optional[jax.Array] = None,
                  moe_mode: str = "scatter", use_kernel: bool = False,
                  remat: bool = True) -> Tuple[jax.Array, jax.Array]:
    """tokens: (B, S) -> (logits (B,S,V) fp32, aux_loss scalar)."""
    B, S = tokens.shape
    x = _embed(params, tokens, cfg)
    vis = _vision(params, vision_emb, cfg)
    aux0 = jnp.zeros((), jnp.float32)

    if cfg.family == "ssm":
        def body(carry, p_l):
            x = carry
            out, _ = mamba_prefill(p_l, x, cfg, use_kernel=use_kernel)
            return x + out, None
        if remat:
            body = jax.checkpoint(body)
        x, _ = jax.lax.scan(body, x, params["layers"]["mamba"])
        return _unembed(params, x, cfg), aux0

    if cfg.family == "hybrid":
        n_groups, gsize, rem = _hybrid_groups(cfg)
        shared = params["shared_attn"]

        def mamba_body(carry, p_l):
            x = carry
            out, _ = mamba_prefill(p_l, x, cfg, use_kernel=use_kernel)
            return x + out, None
        if remat:
            mamba_body = jax.checkpoint(mamba_body)

        def shared_block(x):
            win = jnp.asarray(cfg.window if cfg.window else -1, jnp.int32)
            x = x + attn_prefill(shared["attn"], x, cfg, window=win)
            x = x + mlp_forward(shared["mlp"], x, cfg.norm_eps)
            return x

        def group_body(carry, p_group):
            x = carry
            x, _ = jax.lax.scan(mamba_body, x, p_group)
            return shared_block(x), None

        grouped = _group_stack(params["layers"]["mamba"], n_groups, gsize)
        x, _ = jax.lax.scan(group_body, x, grouped)
        if rem:
            tail = _tail_stack(params["layers"]["mamba"], n_groups * gsize)
            x, _ = jax.lax.scan(mamba_body, x, tail)
        return _unembed(params, x, cfg), aux0

    if _pattern(cfg) and cfg.global_every:
        # windowed pattern archs train with BANDED local attention
        # (iteration C): local layers only visit kv blocks inside the window
        ltree, stree, n_loc, n_spe = _pattern_split(cfg, params["layers"])
        p = _pattern(cfg)
        per_group = p - 1
        rem = n_loc - n_spe * per_group
        positions = jnp.arange(S, dtype=jnp.int32)

        def local_body(x, p_l):
            x = x + attn_prefill(p_l["attn"], x, cfg, positions=positions,
                                 static_window=cfg.window)
            x = x + mlp_forward(p_l["ffn"], x, cfg.norm_eps)
            return x, None

        def group_body(x, xs):
            p_group, p_s = xs
            x, _ = jax.lax.scan(local_body, x, p_group)
            x = x + attn_prefill(p_s["attn"], x, cfg, positions=positions)
            x = x + mlp_forward(p_s["ffn"], x, cfg.norm_eps)
            return x, None

        if remat:
            local_body = jax.checkpoint(local_body)
            group_body = jax.checkpoint(group_body)
        grouped = jax.tree.map(
            lambda a: a[: n_spe * per_group].reshape(n_spe, per_group, *a.shape[1:]),
            ltree)
        x, _ = jax.lax.scan(group_body, x, (grouped, stree))
        if rem:
            tail = jax.tree.map(lambda a: a[n_spe * per_group:], ltree)
            x, _ = jax.lax.scan(local_body, x, tail)
        return _unembed(params, x, cfg), aux0

    # ---- attention families ----------------------------------------------
    meta = layer_metadata(cfg)
    positions = jnp.arange(S, dtype=jnp.int32)

    for d0 in params.get("dense0", []):
        if cfg.is_mla:
            x = x + mla_prefill(d0["attn"], x, cfg, positions)[0]
        else:
            x = x + attn_prefill(d0["attn"], x, cfg, positions=positions)
        x = x + mlp_forward(d0["mlp"], x, cfg.norm_eps)

    k0 = cfg.first_k_dense

    def body(carry, xs):
        x, aux = carry
        p_l, window_l, is_cross_l = xs

        def self_branch(x):
            if cfg.is_mla:
                return mla_prefill(p_l["attn"], x, cfg, positions)[0]
            return attn_prefill(p_l["attn"], x, cfg, window=window_l,
                                positions=positions)

        if cfg.cross_every:
            def cross_branch(x):
                return attn_prefill(p_l["attn"], x, cfg, kv_src=vis,
                                    positions=positions)
            attn_out = jax.lax.cond(is_cross_l, cross_branch, self_branch, x)
        else:
            attn_out = self_branch(x)
        x = x + attn_out

        if cfg.is_moe:
            y, a = moe_forward(p_l["ffn"], x, cfg, mode=moe_mode)
            x = x + y
            aux = aux + a
        else:
            x = x + mlp_forward(p_l["ffn"], x, cfg.norm_eps)
        return (x, aux), None

    if remat:
        body = jax.checkpoint(body)
    (x, aux), _ = jax.lax.scan(
        body, (x, aux0),
        (params["layers"], meta["window"][k0:], meta["is_cross"][k0:]))
    return _unembed(params, x, cfg), aux


def loss_fn(params: Params, batch: Dict[str, jax.Array], cfg, *,
            moe_mode: str = "scatter", use_kernel: bool = False,
            remat: bool = True) -> Tuple[jax.Array, Dict[str, jax.Array]]:
    logits, aux = forward_train(
        params, batch["tokens"], cfg,
        vision_emb=batch.get("vision_emb"),
        moe_mode=moe_mode, use_kernel=use_kernel, remat=remat)
    targets = batch["targets"]
    logp = jax.nn.log_softmax(logits, axis=-1)
    nll = -jnp.take_along_axis(logp, targets[..., None], axis=-1)[..., 0]
    ce = nll.mean()
    loss = ce + cfg.router_aux_coef * aux
    return loss, {"ce": ce, "aux": aux}


# --------------------------------------------------------------------------
# cache
# --------------------------------------------------------------------------

def init_cache(cfg, batch: int, max_len: int, dtype=None) -> Dict[str, jax.Array]:
    dtype = dtype or _dtype(cfg)
    K, Dh = cfg.n_kv_heads, cfg.head_dim
    cache: Dict[str, jax.Array] = {}
    if cfg.family == "ssm":
        cache["conv"] = jnp.zeros((cfg.n_layers, batch, cfg.ssm_dconv - 1, conv_dim(cfg)), dtype)
        cache["ssd"] = jnp.zeros((cfg.n_layers, batch, cfg.ssm_nheads,
                                  cfg.ssm_headdim, cfg.ssm_state), jnp.float32)
    elif cfg.family == "hybrid":
        n_groups, _, _ = _hybrid_groups(cfg)
        W = min(max_len, cfg.window) if cfg.window else max_len
        cache["conv"] = jnp.zeros((cfg.n_layers, batch, cfg.ssm_dconv - 1, conv_dim(cfg)), dtype)
        cache["ssd"] = jnp.zeros((cfg.n_layers, batch, cfg.ssm_nheads,
                                  cfg.ssm_headdim, cfg.ssm_state), jnp.float32)
        cache["ak"] = jnp.zeros((n_groups, batch, W, K, Dh), dtype)
        cache["av"] = jnp.zeros((n_groups, batch, W, K, Dh), dtype)
    elif _pattern(cfg):
        kinds = cfg.layer_kinds()
        n_loc = sum(1 for k in kinds if k in ("local", "attn"))
        n_spe = sum(1 for k in kinds if k in ("global", "cross"))
        W = min(max_len, cfg.window) if cfg.global_every else max_len
        S_spec = max_len if cfg.global_every else cfg.vision_seq
        cache["lk"] = jnp.zeros((n_loc, batch, W, K, Dh), dtype)
        cache["lv"] = jnp.zeros((n_loc, batch, W, K, Dh), dtype)
        cache["sk"] = jnp.zeros((n_spe, batch, S_spec, K, Dh), dtype)
        cache["sv"] = jnp.zeros((n_spe, batch, S_spec, K, Dh), dtype)
    elif cfg.is_mla:
        L = cfg.n_layers - cfg.first_k_dense
        cache["latent"] = jnp.zeros((L, batch, max_len, cfg.latent_dim), dtype)
        if cfg.first_k_dense:
            cache["latent0"] = jnp.zeros(
                (cfg.first_k_dense, batch, max_len, cfg.latent_dim), dtype)
    else:
        L = cfg.n_layers - cfg.first_k_dense
        cache["k"] = jnp.zeros((L, batch, max_len, K, Dh), dtype)
        cache["v"] = jnp.zeros((L, batch, max_len, K, Dh), dtype)
        if cfg.first_k_dense:
            cache["k0"] = jnp.zeros((cfg.first_k_dense, batch, max_len, K, Dh), dtype)
            cache["v0"] = jnp.zeros((cfg.first_k_dense, batch, max_len, K, Dh), dtype)
        if cfg.family == "vlm":
            cache["ck"] = jnp.zeros((L, batch, cfg.vision_seq, K, Dh), dtype)
            cache["cv"] = jnp.zeros((L, batch, cfg.vision_seq, K, Dh), dtype)
    return cache


# --------------------------------------------------------------------------
# pattern-split prefill / decode (iteration B)
# --------------------------------------------------------------------------

def _ring_pack(k: jax.Array, W: int) -> jax.Array:
    """Pack the last W positions of (B,S,...) into ring slots pos % W."""
    B, S = k.shape[:2]
    take = k[:, -W:]
    pos = jnp.arange(max(0, S - W), S, dtype=jnp.int32)
    slots = pos % W
    out = jnp.zeros((B, W) + k.shape[2:], k.dtype)
    return out.at[:, slots].set(take)


def _prefill_pattern(params, tokens, cfg, max_len, vis, moe_mode):
    B, S = tokens.shape
    x = _embed(params, tokens, cfg)
    cache = init_cache(cfg, B, max_len)
    ltree, stree, n_loc, n_spe = _pattern_split(cfg, params["layers"])
    p = _pattern(cfg)
    per_group = p - 1
    rem = n_loc - n_spe * per_group
    W = cache["lk"].shape[2]
    positions = jnp.arange(S, dtype=jnp.int32)
    win = jnp.asarray(cfg.window if cfg.global_every else -1, jnp.int32)

    def pad_kv(k):
        return jnp.pad(k, ((0, 0), (0, max_len - S), (0, 0), (0, 0)))

    def local_body(x, p_l):
        out, (k, v) = attn_prefill(
            p_l["attn"], x, cfg, window=win, positions=positions,
            return_kv=True,
            static_window=cfg.window if cfg.global_every else None)
        x = x + out
        x = x + mlp_forward(p_l["ffn"], x, cfg.norm_eps)
        if cfg.global_every:
            return x, (_ring_pack(k, W), _ring_pack(v, W))
        return x, (pad_kv(k), pad_kv(v))

    def special_body(x, p_s):
        if cfg.global_every:
            out, (k, v) = attn_prefill(p_s["attn"], x, cfg,
                                       positions=positions, return_kv=True)
            k, v = pad_kv(k), pad_kv(v)
        else:
            out, (k, v) = attn_prefill(p_s["attn"], x, cfg, kv_src=vis,
                                       positions=positions, return_kv=True)
        x = x + out
        x = x + mlp_forward(p_s["ffn"], x, cfg.norm_eps)
        return x, (k, v)

    def group_body(x, xs):
        p_group, p_s = xs
        x, lkv = jax.lax.scan(local_body, x, p_group)
        x, skv = special_body(x, p_s)
        return x, (lkv, skv)

    grouped = jax.tree.map(
        lambda a: a[: n_spe * per_group].reshape(n_spe, per_group, *a.shape[1:]),
        ltree)
    x, ((lk, lv), (sk, sv)) = jax.lax.scan(group_body, x, (grouped, stree))
    lk = lk.reshape(n_spe * per_group, *lk.shape[2:])
    lv = lv.reshape(n_spe * per_group, *lv.shape[2:])
    if rem:
        tail = jax.tree.map(lambda a: a[n_spe * per_group:], ltree)
        x, (lk_t, lv_t) = jax.lax.scan(local_body, x, tail)
        lk = jnp.concatenate([lk, lk_t], 0)
        lv = jnp.concatenate([lv, lv_t], 0)
    cache["lk"], cache["lv"] = lk, lv
    cache["sk"], cache["sv"] = sk, sv
    return _unembed(params, x[:, -1:], cfg)[:, 0], cache


def _decode_pattern(params, tokens, positions, cache, cfg, moe_mode):
    B = tokens.shape[0]
    x = _embed(params, tokens, cfg)
    new_cache = dict(cache)
    ltree, stree, n_loc, n_spe = _pattern_split(cfg, params["layers"])
    p = _pattern(cfg)
    per_group = p - 1
    rem = n_loc - n_spe * per_group
    W = cache["lk"].shape[2]
    win = jnp.asarray(cfg.window if cfg.global_every else -1, jnp.int32)

    if cfg.global_every:
        slots = jnp.arange(W, dtype=jnp.int32)
        p_abs = positions[:, None] - ((positions[:, None] - slots) % W)
        cache_pos = jnp.where(p_abs < 0, 2 ** 30, p_abs)
        ring = W
    else:
        cache_pos, ring = None, None

    def local_body(x, xs):
        p_l, k_l, v_l = xs
        out, k, v = attn_decode(p_l["attn"], x, cfg, k_cache=k_l, v_cache=v_l,
                                positions=positions, window=win,
                                cache_positions=cache_pos, ring=ring)
        x = x + out
        x = x + mlp_forward(p_l["ffn"], x, cfg.norm_eps)
        return x, (k, v)

    def special_body(x, p_s, k_s, v_s):
        if cfg.global_every:
            out, k, v = attn_decode(p_s["attn"], x, cfg, k_cache=k_s,
                                    v_cache=v_s, positions=positions)
        else:
            out, _, _ = attn_decode(p_s["attn"], x, cfg, k_cache=k_s,
                                    v_cache=v_s, positions=positions,
                                    cross=True)
            k, v = k_s, v_s
        x = x + out
        x = x + mlp_forward(p_s["ffn"], x, cfg.norm_eps)
        return x, (k, v)

    def group_body(x, xs):
        p_group, p_s, lk_g, lv_g, sk_g, sv_g = xs
        x, lkv = jax.lax.scan(local_body, x, (p_group, lk_g, lv_g))
        x, (sk, sv) = special_body(x, p_s, sk_g, sv_g)
        return x, (lkv, (sk, sv))

    grouped = jax.tree.map(
        lambda a: a[: n_spe * per_group].reshape(n_spe, per_group, *a.shape[1:]),
        ltree)
    lk_g = cache["lk"][: n_spe * per_group].reshape(n_spe, per_group, *cache["lk"].shape[1:])
    lv_g = cache["lv"][: n_spe * per_group].reshape(n_spe, per_group, *cache["lv"].shape[1:])
    x, ((lk, lv), (sk, sv)) = jax.lax.scan(
        group_body, x, (grouped, stree, lk_g, lv_g, cache["sk"], cache["sv"]))
    lk = lk.reshape(n_spe * per_group, *lk.shape[2:])
    lv = lv.reshape(n_spe * per_group, *lv.shape[2:])
    if rem:
        tail = jax.tree.map(lambda a: a[n_spe * per_group:], ltree)
        x, (lk_t, lv_t) = jax.lax.scan(
            local_body, x,
            (tail, cache["lk"][n_spe * per_group:], cache["lv"][n_spe * per_group:]))
        lk = jnp.concatenate([lk, lk_t], 0)
        lv = jnp.concatenate([lv, lv_t], 0)
    new_cache["lk"], new_cache["lv"] = lk, lv
    new_cache["sk"], new_cache["sv"] = sk, sv
    return _unembed(params, x, cfg)[:, 0], new_cache


# --------------------------------------------------------------------------
# prefill
# --------------------------------------------------------------------------

def prefill(params: Params, tokens: jax.Array, cfg, *,
            max_len: Optional[int] = None,
            vision_emb: Optional[jax.Array] = None,
            moe_mode: str = "scatter", use_kernel: bool = False
            ) -> Tuple[jax.Array, Dict[str, jax.Array]]:
    """Full-sequence prefill.  Returns (last-token logits (B,V), cache)."""
    B, S = tokens.shape
    max_len = max_len or S
    if max_len < S:
        raise ValueError("cache must hold at least the prompt")
    vis = _vision(params, vision_emb, cfg)
    if _pattern(cfg):
        return _prefill_pattern(params, tokens, cfg, max_len, vis, moe_mode)
    x = _embed(params, tokens, cfg)
    cache = init_cache(cfg, B, max_len)

    def pad_kv(k):  # (B,S,K,Dh) -> (B,max_len,K,Dh)
        return jnp.pad(k, ((0, 0), (0, max_len - S), (0, 0), (0, 0)))

    if cfg.family in ("ssm", "hybrid"):
        def mamba_body(carry, p_l):
            x = carry
            out, (cs, ss) = mamba_prefill(p_l, x, cfg, use_kernel=use_kernel)
            return x + out, (cs, ss)

        if cfg.family == "ssm":
            x, (cs, ss) = jax.lax.scan(mamba_body, x, params["layers"]["mamba"])
            cache["conv"], cache["ssd"] = cs, ss
        else:
            n_groups, gsize, rem = _hybrid_groups(cfg)
            shared = params["shared_attn"]
            W = cache["ak"].shape[2]
            win = jnp.asarray(cfg.window if cfg.window else -1, jnp.int32)

            def shared_block(x):
                out, (k, v) = attn_prefill(shared["attn"], x, cfg, window=win,
                                           return_kv=True)
                x = x + out
                x = x + mlp_forward(shared["mlp"], x, cfg.norm_eps)
                # ring-buffer the last W positions: slot = pos % W
                kv_slice = (k[:, -W:], v[:, -W:])
                pos = jnp.arange(max(0, S - W), S, dtype=jnp.int32)
                slots = pos % W
                ak = jnp.zeros((B, W) + k.shape[2:], k.dtype).at[:, slots].set(kv_slice[0])
                av = jnp.zeros((B, W) + v.shape[2:], v.dtype).at[:, slots].set(kv_slice[1])
                return x, (ak, av)

            def group_body(carry, p_group):
                x = carry
                x, (cs, ss) = jax.lax.scan(mamba_body, x, p_group)
                x, (ak, av) = shared_block(x)
                return x, ((cs, ss), (ak, av))

            grouped = _group_stack(params["layers"]["mamba"], n_groups, gsize)
            x, ((cs, ss), (ak, av)) = jax.lax.scan(group_body, x, grouped)
            cs = jax.tree.map(lambda a: a.reshape(n_groups * gsize, *a.shape[2:]), cs)
            ss = jax.tree.map(lambda a: a.reshape(n_groups * gsize, *a.shape[2:]), ss)
            if rem:
                tail = _tail_stack(params["layers"]["mamba"], n_groups * gsize)
                x, (cs_t, ss_t) = jax.lax.scan(mamba_body, x, tail)
                cs = jnp.concatenate([cs, cs_t], 0)
                ss = jnp.concatenate([ss, ss_t], 0)
            cache["conv"], cache["ssd"] = cs, ss
            cache["ak"], cache["av"] = ak, av
        return _unembed(params, x[:, -1:], cfg)[:, 0], cache

    # ---- attention families -------------------------------------------------
    if cfg.is_mla:
        return _prefill_mla(params, x, cfg, cache, max_len, moe_mode)
    meta = layer_metadata(cfg)
    positions = jnp.arange(S, dtype=jnp.int32)
    k0 = cfg.first_k_dense
    for i, d0 in enumerate(params.get("dense0", [])):
        out, (k, v) = attn_prefill(d0["attn"], x, cfg, positions=positions,
                                   return_kv=True)
        x = x + out
        x = x + mlp_forward(d0["mlp"], x, cfg.norm_eps)
        cache["k0"] = cache["k0"].at[i].set(pad_kv(k))
        cache["v0"] = cache["v0"].at[i].set(pad_kv(v))

    K, Dh = cfg.n_kv_heads, cfg.head_dim
    Sv = cfg.vision_seq

    def body(x, xs):
        p_l, window_l, is_cross_l = xs

        def self_branch(x):
            # without a global/local pattern every self-attention layer of
            # this stack attends fully: a static window (None) lets
            # attn_prefill take the flash kernel
            out, (k, v) = attn_prefill(
                p_l["attn"], x, cfg, positions=positions, return_kv=True,
                window=window_l if cfg.global_every else None)
            ck = jnp.zeros((B, Sv, K, Dh), x.dtype) if cfg.family == "vlm" else None
            return out, pad_kv(k), pad_kv(v), ck, ck

        if cfg.cross_every:
            def cross_branch(x):
                out, (ck, cv) = attn_prefill(p_l["attn"], x, cfg, kv_src=vis,
                                             positions=positions, return_kv=True)
                z = jnp.zeros((B, max_len, K, Dh), x.dtype)
                return out, z, z, ck, cv
            out, k, v, ck, cv = jax.lax.cond(is_cross_l, cross_branch, self_branch, x)
        else:
            out, k, v, ck, cv = self_branch(x)
        x = x + out
        if cfg.is_moe:
            y, _ = moe_forward(p_l["ffn"], x, cfg, mode=moe_mode)
            x = x + y
        else:
            x = x + mlp_forward(p_l["ffn"], x, cfg.norm_eps)
        ys = (k, v) + ((ck, cv) if cfg.family == "vlm" else ())
        return x, ys

    x, ys = jax.lax.scan(
        body, x, (params["layers"], meta["window"][k0:], meta["is_cross"][k0:]))
    cache["k"], cache["v"] = ys[0], ys[1]
    if cfg.family == "vlm":
        cache["ck"], cache["cv"] = ys[2], ys[3]
    return _unembed(params, x[:, -1:], cfg)[:, 0], cache


# --------------------------------------------------------------------------
# decode
# --------------------------------------------------------------------------

def decode_step(params: Params, tokens: jax.Array, positions: jax.Array,
                cache: Dict[str, jax.Array], cfg, *,
                moe_mode: str = "scatter"
                ) -> Tuple[jax.Array, Dict[str, jax.Array]]:
    """One decode step.  tokens: (B,1); positions: (B,) index of the new
    token.  Returns (logits (B,V) fp32, updated cache)."""
    if _pattern(cfg):
        return _decode_pattern(params, tokens, positions, cache, cfg, moe_mode)
    B = tokens.shape[0]
    x = _embed(params, tokens, cfg)
    new_cache = dict(cache)

    if cfg.family in ("ssm", "hybrid"):
        def mamba_body(carry, xs):
            x = carry
            p_l, cs_l, ss_l = xs
            out, (cs, ss) = mamba_decode(p_l, x, cfg, conv_state=cs_l, ssd_state=ss_l)
            return x + out, (cs, ss)

        if cfg.family == "ssm":
            x, (cs, ss) = jax.lax.scan(
                mamba_body, x,
                (params["layers"]["mamba"], cache["conv"], cache["ssd"]))
            new_cache["conv"], new_cache["ssd"] = cs, ss
        else:
            n_groups, gsize, rem = _hybrid_groups(cfg)
            shared = params["shared_attn"]
            W = cache["ak"].shape[2]
            win = jnp.asarray(cfg.window if cfg.window else -1, jnp.int32)
            # absolute position held by each ring slot (see DESIGN notes)
            slots = jnp.arange(W, dtype=jnp.int32)
            p_abs = positions[:, None] - ((positions[:, None] - slots) % W)
            cache_pos = jnp.where(p_abs < 0, 2 ** 30, p_abs)      # (B,W)

            def shared_block(x, ak, av):
                # write new kv into ring slot positions % W
                out, ak, av = attn_decode(
                    shared["attn"], x, cfg, k_cache=ak, v_cache=av,
                    positions=positions, window=win, cache_positions=cache_pos,
                    ring=W)
                x = x + out
                x = x + mlp_forward(shared["mlp"], x, cfg.norm_eps)
                return x, ak, av

            def group_body(carry, xs):
                x = carry
                p_group, cs_g, ss_g, ak_g, av_g = xs
                x, (cs, ss) = jax.lax.scan(mamba_body, x, (p_group, cs_g, ss_g))
                x, ak, av = shared_block(x, ak_g, av_g)
                return x, (cs, ss, ak, av)

            grouped = _group_stack(params["layers"]["mamba"], n_groups, gsize)
            cs_g = jax.tree.map(lambda a: a[:n_groups * gsize].reshape(
                n_groups, gsize, *a.shape[1:]), cache["conv"])
            ss_g = jax.tree.map(lambda a: a[:n_groups * gsize].reshape(
                n_groups, gsize, *a.shape[1:]), cache["ssd"])
            x, (cs, ss, ak, av) = jax.lax.scan(
                group_body, x, (grouped, cs_g, ss_g, cache["ak"], cache["av"]))
            cs = cs.reshape(n_groups * gsize, *cs.shape[2:])
            ss = ss.reshape(n_groups * gsize, *ss.shape[2:])
            if rem:
                tail = _tail_stack(params["layers"]["mamba"], n_groups * gsize)
                x, (cs_t, ss_t) = jax.lax.scan(
                    mamba_body, x,
                    (tail, cache["conv"][n_groups * gsize:], cache["ssd"][n_groups * gsize:]))
                cs = jnp.concatenate([cs, cs_t], 0)
                ss = jnp.concatenate([ss, ss_t], 0)
            new_cache["conv"], new_cache["ssd"] = cs, ss
            new_cache["ak"], new_cache["av"] = ak, av
        return _unembed(params, x, cfg)[:, 0], new_cache

    # ---- attention families --------------------------------------------------
    if cfg.is_mla:
        return _decode_mla(params, x, positions, new_cache, cfg, moe_mode)
    meta = layer_metadata(cfg)
    k0 = cfg.first_k_dense
    for i, d0 in enumerate(params.get("dense0", [])):
        out, k, v = attn_decode(d0["attn"], x, cfg, k_cache=cache["k0"][i],
                                v_cache=cache["v0"][i], positions=positions)
        x = x + out
        x = x + mlp_forward(d0["mlp"], x, cfg.norm_eps)
        new_cache["k0"] = new_cache["k0"].at[i].set(k)
        new_cache["v0"] = new_cache["v0"].at[i].set(v)

    def body(x, xs):
        if cfg.family == "vlm":
            p_l, window_l, is_cross_l, k_l, v_l, ck_l, cv_l = xs
        else:
            p_l, window_l, is_cross_l, k_l, v_l = xs

        def self_branch(x):
            out, k, v = attn_decode(p_l["attn"], x, cfg, k_cache=k_l,
                                    v_cache=v_l, positions=positions,
                                    window=window_l)
            return out, k, v

        if cfg.cross_every:
            def cross_branch(x):
                out, _, _ = attn_decode(p_l["attn"], x, cfg, k_cache=ck_l,
                                        v_cache=cv_l, positions=positions,
                                        cross=True)
                return out, k_l, v_l
            out, k, v = jax.lax.cond(is_cross_l, cross_branch, self_branch, x)
        else:
            out, k, v = self_branch(x)
        x = x + out
        if cfg.is_moe:
            y, _ = moe_forward(p_l["ffn"], x, cfg, mode=moe_mode)
            x = x + y
        else:
            x = x + mlp_forward(p_l["ffn"], x, cfg.norm_eps)
        ys = (k, v)
        return x, ys

    xs = (params["layers"], meta["window"][k0:], meta["is_cross"][k0:],
          cache["k"], cache["v"])
    if cfg.family == "vlm":
        xs = xs + (cache["ck"], cache["cv"])
    x, (k, v) = jax.lax.scan(body, x, xs)
    new_cache["k"], new_cache["v"] = k, v
    return _unembed(params, x, cfg)[:, 0], new_cache


# --------------------------------------------------------------------------
# latent attention (MLA): one latent cache row per token and layer
# --------------------------------------------------------------------------

def _ffn(p, x, cfg, moe_mode):
    if cfg.is_moe:
        return moe_forward(p, x, cfg, mode=moe_mode)[0]
    return mlp_forward(p, x, cfg.norm_eps)


def _prefill_mla(params, x, cfg, cache, max_len, moe_mode):
    S = x.shape[1]
    positions = jnp.arange(S, dtype=jnp.int32)

    def pad(lat):  # (B,S,latent) -> (B,max_len,latent)
        return jnp.pad(lat, ((0, 0), (0, max_len - S), (0, 0)))

    for i, d0 in enumerate(params.get("dense0", [])):
        out, lat = mla_prefill(d0["attn"], x, cfg, positions)
        x = x + out
        x = x + mlp_forward(d0["mlp"], x, cfg.norm_eps)
        cache["latent0"] = cache["latent0"].at[i].set(pad(lat))

    def body(x, p_l):
        out, lat = mla_prefill(p_l["attn"], x, cfg, positions)
        x = x + out
        return x + _ffn(p_l["ffn"], x, cfg, moe_mode), pad(lat)

    x, cache["latent"] = jax.lax.scan(body, x, params["layers"])
    return _unembed(params, x[:, -1:], cfg)[:, 0], cache


def _decode_mla(params, x, positions, cache, cfg, moe_mode):
    for i, d0 in enumerate(params.get("dense0", [])):
        out, lat = mla_decode(d0["attn"], x, cfg, cache=cache["latent0"][i],
                              positions=positions)
        x = x + out
        x = x + mlp_forward(d0["mlp"], x, cfg.norm_eps)
        cache["latent0"] = cache["latent0"].at[i].set(lat)

    def body(x, xs):
        p_l, lat_l = xs
        out, lat = mla_decode(p_l["attn"], x, cfg, cache=lat_l,
                              positions=positions)
        x = x + out
        return x + _ffn(p_l["ffn"], x, cfg, moe_mode), lat

    x, cache["latent"] = jax.lax.scan(body, x, (params["layers"], cache["latent"]))
    return _unembed(params, x, cfg)[:, 0], cache


# Serving entry points: one compiled program per (config, shapes), with the
# frozen ModelConfig static.  Called eagerly, every lax.scan above traces a
# fresh closure and recompiles on every call.
prefill_jit = jax.jit(prefill, static_argnames=("cfg", "max_len", "moe_mode",
                                                "use_kernel"))
decode_step_jit = jax.jit(decode_step, static_argnames=("cfg", "moe_mode"))
