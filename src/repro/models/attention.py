"""Attention: GQA with full / sliding-window / cross variants, and
multi-head latent attention (MLA, DeepSeek-V2/V3).

Prefill uses a chunked (flash-style) implementation — a double scan over
query and key/value blocks with a running (max, sum, acc) carry — so no
S x S score matrix is ever materialised (required for the 32k/500k shapes).
Masks are computed from index arithmetic inside each block.

Decode attends one query position against the full cache; for long_500k the
cache is sequence-sharded across the ``data`` mesh axis and the softmax
reduction spans shards (GSPMD inserts the collectives; see EXPERIMENTS.md
§Perf for the shard_map flash-decode iteration).
"""

from __future__ import annotations

from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from .common import apply_rope, apply_rope_pairs, dense_init, rms_norm, split_keys

NEG_INF = -1e30

# Prefill attention implementation: the Pallas flash kernel keeps the score
# tiles and running statistics in VMEM (the dominant residual memory-term
# contributor per EXPERIMENTS §Perf).  It is selected for programs compiled
# for a TPU; the chunked-jnp path serves every other platform.  FORCE_FLASH
# is a test hook that runs the kernel everywhere (interpret mode off-TPU).
FORCE_FLASH: bool = False


def init_attn(key, cfg, dtype) -> Dict[str, jax.Array]:
    if cfg.is_mla:
        return init_mla(key, cfg, dtype)
    D, H, K, Dh = cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    ks = split_keys(key, 5)
    return {
        "norm": jnp.zeros((D,), dtype),
        "wq": dense_init(ks[0], (D, H * Dh), dtype),
        "wk": dense_init(ks[1], (D, K * Dh), dtype),
        "wv": dense_init(ks[2], (D, K * Dh), dtype),
        "wo": dense_init(ks[3], (H * Dh, D), dtype, scale=(H * Dh) ** -0.5),
    }


def _split_heads(x, n, dh):
    return x.reshape(*x.shape[:-1], n, dh)


def chunked_attention(q: jax.Array, k: jax.Array, v: jax.Array,
                      q_positions: jax.Array, k_positions: jax.Array,
                      *, causal: bool, window: Optional[jax.Array] = None,
                      q_block: int = 512, k_block: int = 1024,
                      static_window: Optional[int] = None) -> jax.Array:
    """Memory-efficient attention.

    q: (B, Sq, H, Dh);  k: (B, Sk, K, Dh), v: (B, Sk, K, Dv) with H = K * G.
    ``window``: traced scalar; <=0 means full attention, otherwise sliding
    window of that many positions (query attends keys in (qpos-window, qpos]).
    ``static_window``: compile-time window — the kv scan is BANDED, visiting
    only the ceil((window+qb)/kb)+1 kv blocks that can intersect each query
    block (§Perf iteration C: local layers stop paying O(S^2)).
    Returns (B, Sq, H, Dv).
    """
    B, Sq, H, Dh = q.shape
    _, Sk, K, _ = k.shape
    Dv = v.shape[-1]
    G = H // K
    scale = Dh ** -0.5

    qb = min(q_block, Sq)
    kb = min(k_block, Sk)
    # Pad sequence dims to multiples of the block sizes.
    pq = (-Sq) % qb
    pk = (-Sk) % kb
    if pq:
        q = jnp.pad(q, ((0, 0), (0, pq), (0, 0), (0, 0)))
        q_positions = jnp.pad(q_positions, ((0, pq),), constant_values=-1)
    if pk:
        k = jnp.pad(k, ((0, 0), (0, pk), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pk), (0, 0), (0, 0)))
        k_positions = jnp.pad(k_positions, ((0, pk),), constant_values=2**30)
    nq, nk = q.shape[1] // qb, k.shape[1] // kb

    # (B, nq, qb, K, G, Dh) / (B, nk, kb, K, Dh) — kept in storage dtype;
    # block dots accumulate fp32 on the MXU (iteration D: fp32 operand
    # copies double both HBM traffic and the TP-collective bytes of the
    # k/v cotangents in backward)
    qr = (q * scale).reshape(B, nq, qb, K, G, Dh)
    kr = k.reshape(B, nk, kb, K, Dh)
    vr = v.reshape(B, nk, kb, K, Dv)
    qpos = q_positions.reshape(nq, qb)
    kpos = k_positions.reshape(nk, kb)

    if static_window is not None:
        win = jnp.asarray(static_window, jnp.int32)
        n_rel = min(nk, (static_window + qb + kb - 1) // kb + 1)
    else:
        win = window if window is not None else jnp.asarray(0, jnp.int32)
        n_rel = None

    def q_step(qi):
        qblk = qr[:, qi]          # (B, qb, K, G, Dh)
        qp = qpos[qi]             # (qb,)

        def kv_step(carry, ki):
            oob = None
            if n_rel is not None:
                # banded: ki is a relative offset below this q block's last
                # reachable kv block; out-of-range blocks are masked out
                base = (qi * qb) // kb + (qb - 1) // kb
                oob = (base - ki) < 0
                ki = jnp.clip(base - ki, 0, nk - 1)
            m, l, acc = carry
            kblk, vblk, kp = kr[:, ki], vr[:, ki], kpos[ki]
            s = jnp.einsum("bqkgd,btkd->bkgqt", qblk, kblk,
                           preferred_element_type=jnp.float32)     # (B,K,G,qb,kb)
            dpos = qp[:, None] - kp[None, :]                        # (qb, kb)
            mask = jnp.ones_like(dpos, dtype=bool)
            if causal:
                mask &= dpos >= 0
            mask &= jnp.where(win > 0, dpos < win, True)
            if oob is not None:
                mask &= ~oob
            s = jnp.where(mask[None, None, None], s, NEG_INF)
            m_new = jnp.maximum(m, s.max(-1))
            p = jnp.exp(s - m_new[..., None])
            corr = jnp.exp(m - m_new)
            l_new = l * corr + p.sum(-1)
            acc_new = acc * corr[..., None] + jnp.einsum(
                "bkgqt,btkd->bkgqd", p.astype(vblk.dtype), vblk,
                preferred_element_type=jnp.float32)
            return (m_new, l_new, acc_new), None

        m0 = jnp.full((B, K, G, qb), NEG_INF, jnp.float32)
        l0 = jnp.zeros((B, K, G, qb), jnp.float32)
        a0 = jnp.zeros((B, K, G, qb, Dv), jnp.float32)
        ks = jnp.arange(n_rel if n_rel is not None else nk)
        (m, l, acc), _ = jax.lax.scan(kv_step, (m0, l0, a0), ks)
        out = acc / jnp.maximum(l, 1e-20)[..., None]                # (B,K,G,qb,Dv)
        return out.transpose(0, 3, 1, 2, 4)                          # (B,qb,K,G,Dv)

    out = jax.lax.map(q_step, jnp.arange(nq))                        # (nq,B,qb,K,G,Dv)
    out = out.transpose(1, 0, 2, 3, 4, 5).reshape(B, nq * qb, H, Dv)
    return out[:, :Sq].astype(q.dtype)


def attn_prefill(p: Dict[str, jax.Array], x: jax.Array, cfg, *,
                 kv_src: Optional[jax.Array] = None,
                 window: Optional[jax.Array] = None,
                 positions: Optional[jax.Array] = None,
                 return_kv: bool = False,
                 static_window: Optional[int] = None):
    """Self- or cross-attention over a full sequence.

    ``kv_src``: None => self-attention (causal); otherwise cross-attention
    over the given source (no causal mask, no RoPE on source positions).
    """
    B, S, D = x.shape
    H, K, Dh = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    h = rms_norm(x, p["norm"], cfg.norm_eps)
    q = _split_heads(h @ p["wq"], H, Dh)
    src = h if kv_src is None else kv_src
    k = _split_heads(src @ p["wk"], K, Dh)
    v = _split_heads(src @ p["wv"], K, Dh)
    if positions is None:
        positions = jnp.arange(S, dtype=jnp.int32)
    if kv_src is None:
        q = apply_rope(q, positions, cfg.rope_theta)
        k = apply_rope(k, positions, cfg.rope_theta)
        kpos = positions
        causal = True
    else:
        kpos = jnp.arange(src.shape[1], dtype=jnp.int32)
        causal = False
    # Pallas flash path: self-attention with a window known at trace time
    # (a scanned pattern arch passes a traced per-layer window: chunked).
    win_static = static_window
    if win_static is None:
        if window is None:
            win_static = 0             # full causal attention
        elif not isinstance(window, jax.core.Tracer):
            win_static = max(int(window), 0)
    if kv_src is None and win_static is not None and S % 16 == 0:
        def flash(q, k, v, interpret):
            from ..kernels import flash_attention as fa
            G = H // K
            kb = jnp.repeat(k.transpose(0, 2, 1, 3), G, axis=1)   # (B,H,S,Dh)
            vb = jnp.repeat(v.transpose(0, 2, 1, 3), G, axis=1)
            o = fa.flash_attention(q.transpose(0, 2, 1, 3), kb, vb,
                                   causal=True, window=win_static,
                                   interpret=interpret)
            return o.transpose(0, 2, 1, 3)

        def chunked(q, k, v, qpos, kpos):
            return chunked_attention(q, k, v, qpos, kpos, causal=True,
                                     window=window,
                                     static_window=static_window)

        # positions are arguments, not closed over: a custom_vjp may not
        # capture the tracers of an enclosing scan or checkpoint
        @jax.custom_vjp
        def attend(q, k, v, qpos, kpos):
            if FORCE_FLASH:
                from ..kernels.ops import _interp
                return flash(q, k, v, _interp())
            # the kernel where the program is compiled for a TPU
            return jax.lax.platform_dependent(
                q, k, v, qpos, kpos,
                tpu=lambda q, k, v, *_: flash(q, k, v, False),
                default=chunked)

        def attend_fwd(q, k, v, qpos, kpos):
            return attend(q, k, v, qpos, kpos), (q, k, v, qpos, kpos)

        def attend_bwd(res, do):
            # the flash kernel is forward-only: differentiate chunked
            q, k, v, qpos, kpos = res
            dq, dk, dv = jax.vjp(lambda q, k, v: chunked(q, k, v, qpos, kpos),
                                 q, k, v)[1](do)
            return dq, dk, dv, None, None

        attend.defvjp(attend_fwd, attend_bwd)
        o = attend(q, k, v, positions, kpos)
    else:
        o = chunked_attention(q, k, v, positions, kpos, causal=causal,
                              window=window, static_window=static_window)
    out = o.reshape(B, S, H * Dh) @ p["wo"]
    if return_kv:
        return out, (k, v)
    return out


def attn_decode(p: Dict[str, jax.Array], x: jax.Array, cfg, *,
                k_cache: jax.Array, v_cache: jax.Array,
                positions: jax.Array,
                window: Optional[jax.Array] = None,
                cross: bool = False,
                cache_positions: Optional[jax.Array] = None,
                ring: Optional[int] = None):
    """One-token decode against a cache.

    x: (B, 1, D); k_cache/v_cache: (B, Smax, K, Dh); positions: (B,) — the
    index of the NEW token.  For self-attention the new K/V is written into
    the cache at ``positions`` (scatter) and attention spans cache slots
    <= positions (within ``window`` if sliding).  For cross-attention the
    cache is the fixed source KV and nothing is written.

    Returns (out (B,1,D), k_cache, v_cache).
    """
    B, _, D = x.shape
    H, K, Dh = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    Smax = k_cache.shape[1]
    h = rms_norm(x, p["norm"], cfg.norm_eps)
    q = _split_heads(h @ p["wq"], H, Dh)                     # (B,1,H,Dh)
    if not cross:
        k_new = _split_heads(h @ p["wk"], K, Dh)             # (B,1,K,Dh)
        v_new = _split_heads(h @ p["wv"], K, Dh)
        q = apply_rope(q, positions[:, None], cfg.rope_theta)
        k_new = apply_rope(k_new, positions[:, None], cfg.rope_theta)
        bidx = jnp.arange(B)
        slots_w = positions % ring if ring else positions
        k_cache = k_cache.at[bidx, slots_w].set(k_new[:, 0].astype(k_cache.dtype))
        v_cache = v_cache.at[bidx, slots_w].set(v_new[:, 0].astype(v_cache.dtype))

    G = H // K
    # keep cache-sized operands in their storage dtype; accumulate fp32 on
    # the MXU (a materialised fp32 copy of a 500k-token cache costs more
    # HBM traffic than the attention itself — §Perf iteration A)
    qr = (q.reshape(B, K, G, Dh) * (Dh ** -0.5)).astype(k_cache.dtype)
    s = jnp.einsum("bkgd,btkd->bkgt", qr, k_cache,
                   preferred_element_type=jnp.float32)       # (B,K,G,Smax)
    slot = (jnp.arange(Smax, dtype=jnp.int32)[None, :]
            if cache_positions is None else cache_positions)  # (1|B, Smax)
    if not cross:
        dpos = positions[:, None] - slot                      # (B, Smax)
        mask = dpos >= 0
        if window is not None:
            win = window
            mask &= jnp.where(win > 0, dpos < win, True)
        s = jnp.where(mask[:, None, None], s, NEG_INF)
    m = s.max(-1, keepdims=True)
    pattn = jnp.exp(s - m)
    o = jnp.einsum("bkgt,btkd->bkgd", pattn.astype(v_cache.dtype), v_cache,
                   preferred_element_type=jnp.float32)
    o = o / pattn.sum(-1)[..., None]
    out = o.reshape(B, 1, H * Dh).astype(x.dtype) @ p["wo"]
    return out, k_cache, v_cache


# --------------------------------------------------------------------------
# multi-head latent attention (MLA)
#
# Per the published DeepSeek-V3 modeling code (no query compression):
# q = x·W_q, split per head into a non-rotary and a rotary part;
# [c, k_pe] = x·W_kv_a; c goes through its own RMS norm; [k_nope, v] =
# c·W_kv_b per head.  Rotary turns q's rotary part and the one k_pe that all
# heads share, pairing dims (2i, 2i+1).  The cache holds [c, roped k_pe],
# ``latent_dim`` values a token a layer.  Softmax scale: qk_head_dim^-0.5.
# --------------------------------------------------------------------------

# the published code builds the latent's norm with its default epsilon
MLA_KV_NORM_EPS = 1e-6


def init_mla(key, cfg, dtype) -> Dict[str, jax.Array]:
    D, H, C = cfg.d_model, cfg.n_heads, cfg.kv_lora_rank
    Hv = H * cfg.v_head_dim
    ks = split_keys(key, 4)
    return {
        "norm": jnp.zeros((D,), dtype),
        "wq": dense_init(ks[0], (D, H * cfg.qk_head_dim), dtype),
        "wkv_a": dense_init(ks[1], (D, cfg.latent_dim), dtype),
        "kv_norm": jnp.zeros((C,), dtype),
        "wkv_b": dense_init(ks[2], (C, H * (cfg.qk_nope_head_dim + cfg.v_head_dim)), dtype),
        "wo": dense_init(ks[3], (Hv, D), dtype, scale=Hv ** -0.5),
    }


def _mla_qc(p, x, cfg, positions):
    """Queries (..., H, qk_head_dim) and cache rows (..., latent_dim) of
    ``x`` (B, S, D) at ``positions`` (S,) or (B, 1)."""
    H, N, C = cfg.n_heads, cfg.qk_nope_head_dim, cfg.kv_lora_rank
    h = rms_norm(x, p["norm"], cfg.norm_eps)
    q = _split_heads(h @ p["wq"], H, cfg.qk_head_dim)
    q = jnp.concatenate(
        [q[..., :N], apply_rope_pairs(q[..., N:], positions, cfg.rope_theta)], -1)
    kv_a = h @ p["wkv_a"]
    c = rms_norm(kv_a[..., :C], p["kv_norm"], MLA_KV_NORM_EPS)
    k_pe = apply_rope_pairs(kv_a[..., None, C:], positions, cfg.rope_theta)
    return q, jnp.concatenate([c, k_pe[..., 0, :]], -1)


def mla_prefill(p: Dict[str, jax.Array], x: jax.Array, cfg,
                positions: jax.Array) -> Tuple[jax.Array, jax.Array]:
    """Causal MLA over a full sequence, the latent decompressed into per-head
    keys and values, attended by ``chunked_attention`` on every platform
    (the flash kernel takes one width for queries, keys and values).
    x: (B, S, D).  Returns (out (B,S,D), cache rows (B, S, latent_dim))."""
    with jax.named_scope("mla_prefill"):
        B, S, _ = x.shape
        H, N, R = cfg.n_heads, cfg.qk_nope_head_dim, cfg.qk_rope_head_dim
        C, V = cfg.kv_lora_rank, cfg.v_head_dim
        q, lat = _mla_qc(p, x, cfg, positions)
        kv = _split_heads(lat[..., :C] @ p["wkv_b"], H, N + V)
        k = jnp.concatenate(
            [kv[..., :N], jnp.broadcast_to(lat[:, :, None, C:], (B, S, H, R))], -1)
        o = chunked_attention(q, k, kv[..., N:], positions, positions, causal=True)
        return o.reshape(B, S, H * V) @ p["wo"], lat


def mla_decode(p: Dict[str, jax.Array], x: jax.Array, cfg, *,
               cache: jax.Array, positions: jax.Array
               ) -> Tuple[jax.Array, jax.Array]:
    """One-token MLA against the latent cache, absorbed: ``W_uk`` folds into
    the query and ``W_uv`` into the output, so no per-head key or value is
    rebuilt.  x: (B, 1, D); cache: (B, Smax, latent_dim); positions: (B,),
    the new token's index, whose row is written first.  Returns (out
    (B,1,D), cache)."""
    with jax.named_scope("mla_decode"):
        B = x.shape[0]
        H, N, C, V = cfg.n_heads, cfg.qk_nope_head_dim, cfg.kv_lora_rank, cfg.v_head_dim
        f32 = jnp.float32
        q, row = _mla_qc(p, x, cfg, positions[:, None])
        cache = cache.at[jnp.arange(B), positions].set(row[:, 0].astype(cache.dtype))
        wkv_b = p["wkv_b"].reshape(C, H, N + V)
        q_lat = jnp.einsum("bhn,chn->bhc", q[:, 0, :, :N], wkv_b[..., :N],
                           preferred_element_type=f32)
        qf = jnp.concatenate([q_lat, q[:, 0, :, N:].astype(f32)], -1)
        qf = (qf * cfg.qk_head_dim ** -0.5).astype(cache.dtype)
        s = jnp.einsum("bhc,btc->bht", qf, cache, preferred_element_type=f32)
        live = jnp.arange(cache.shape[1])[None, :] <= positions[:, None]
        s = jnp.where(live[:, None], s, NEG_INF)
        pr = jnp.exp(s - s.max(-1, keepdims=True))
        o_lat = jnp.einsum("bht,btc->bhc", pr.astype(cache.dtype), cache[..., :C],
                           preferred_element_type=f32) / pr.sum(-1)[..., None]
        o = jnp.einsum("bhc,chv->bhv", o_lat.astype(x.dtype), wkv_b[..., N:],
                       preferred_element_type=f32)
        out = o.reshape(B, 1, H * V).astype(x.dtype) @ p["wo"]
        return out, cache
