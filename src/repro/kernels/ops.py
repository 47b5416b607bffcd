"""Jit'd public wrappers around the Pallas kernels.

Called directly, the kernels execute in ``interpret=True`` mode when the
default backend is the CPU — the kernel body runs in Python for correctness
validation; on TPU the same ``pl.pallas_call`` lowers to Mosaic.
``INTERPRET`` can be forced for tests.
"""

from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from . import moe_combine as _combine_k
from . import moe_pack as _pack_k
from . import paged_copy as _paged
from . import ref
from . import ssd_scan as _ssd

INTERPRET: Optional[bool] = None  # None => auto (CPU -> True)


def _interp() -> bool:
    if INTERPRET is not None:
        return INTERPRET
    return jax.default_backend() == "cpu"


@functools.partial(jax.custom_vjp, nondiff_argnums=(2,))
def _pack(x: jax.Array, perm: jax.Array, interpret: bool) -> jax.Array:
    return _pack_k.moe_pack(x, perm, interpret=interpret)


def _pack_fwd(x, perm, interpret):
    return _pack(x, perm, interpret), (perm, x.shape[0])


def _pack_bwd(interpret, res, dy):
    perm, T = res
    keep = perm >= 0
    dx = jnp.zeros((T, dy.shape[1]), dy.dtype).at[
        jnp.where(keep, perm, T)].add(
            jnp.where(keep[:, None], dy, 0), mode="drop")
    return dx, None


_pack.defvjp(_pack_fwd, _pack_bwd)


@jax.jit
def moe_pack(x: jax.Array, perm: jax.Array) -> jax.Array:
    """Differentiable row gather (Pallas); -1 rows emit zeros.

    Linear in x: the VJP scatter-adds cotangent rows back (pure jnp — the
    backward is bandwidth-trivial compared to the expert GEMMs).
    """
    return _pack(x, perm, _interp())


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def _combine(ye: jax.Array, inv: jax.Array, gates: jax.Array,
             interpret: bool, out_dtype) -> jax.Array:
    return _combine_k.moe_combine(ye, inv, gates, out_dtype=out_dtype,
                                  interpret=interpret)


def _combine_fwd(ye, inv, gates, interpret, out_dtype):
    return _combine(ye, inv, gates, interpret, out_dtype), (ye, inv, gates)


def _combine_bwd(interpret, out_dtype, res, dy):
    ye, inv, gates = res
    T, K = inv.shape
    M = ye.shape[0]
    keep = inv >= 0
    safe = jnp.where(keep, inv, M)
    w = jnp.where(keep, gates, 0.0).astype(dy.dtype)
    # d_ye[inv[t,k]] += gates[t,k] * dy[t]
    contrib = jnp.einsum("td,tk->tkd", dy, w)
    d_ye = jnp.zeros((M, ye.shape[1]), ye.dtype).at[safe.reshape(-1)].add(
        contrib.reshape(T * K, -1).astype(ye.dtype), mode="drop")
    # d_gates[t,k] = <ye[inv[t,k]], dy[t]>
    rows = jnp.take(ye, jnp.minimum(safe, M - 1), axis=0)
    d_g = jnp.einsum("tkd,td->tk", rows.astype(dy.dtype), dy)
    d_g = jnp.where(keep, d_g, 0.0).astype(gates.dtype)
    return d_ye, None, d_g


_combine.defvjp(_combine_fwd, _combine_bwd)


@functools.partial(jax.jit, static_argnames=("out_dtype",))
def moe_combine(ye: jax.Array, inv: jax.Array, gates: jax.Array, *,
                out_dtype=None) -> jax.Array:
    """Differentiable weighted combine (Pallas), fp32 accumulation; the
    result is ``out_dtype`` (default ``ye.dtype``)."""
    return _combine(ye, inv, gates, _interp(), out_dtype)


# In-graph callers (``comm.moe_a2a``) select by the platform the program is
# compiled for: the Pallas kernels on TPU, the pure-jnp oracle (an XLA
# gather) elsewhere.  Interpret-mode Pallas inside a compiled hot path lowers
# to millions of row-sized loop ops — fine for validating the kernel,
# catastrophic inside a 48-layer program.
def moe_pack_auto(x: jax.Array, perm: jax.Array) -> jax.Array:
    return jax.lax.platform_dependent(
        x, perm, tpu=lambda x, perm: _pack(x, perm, False),
        default=ref.moe_pack)


def moe_combine_auto(ye: jax.Array, inv: jax.Array, gates: jax.Array) -> jax.Array:
    return jax.lax.platform_dependent(
        ye, inv, gates,
        tpu=lambda ye, inv, gates: _combine(ye, inv, gates, False, None),
        default=ref.moe_combine)


@functools.partial(jax.jit, static_argnames=("block_e",))
def paged_copy(src: jax.Array, src_idx: jax.Array, dst: jax.Array,
               dst_idx: jax.Array, *, block_e: int = 2048) -> jax.Array:
    return _paged.paged_copy(src, src_idx, dst, dst_idx, block_e=block_e,
                             interpret=_interp())


def ssd_intra(xw: jax.Array, cum: jax.Array, Br: jax.Array, Cr: jax.Array
              ) -> Tuple[jax.Array, jax.Array]:
    """SSD intra-chunk block in model layout.

    xw: (b,nc,cl,h,p); cum: (b,nc,cl,h); Br, Cr: (b,nc,cl,h,n).
    Returns (y (b,nc,cl,h,p), states (b,nc,h,p,n)) fp32, matching ref.
    """
    b, nc, cl, h, p = xw.shape
    n = Br.shape[-1]
    flat = lambda t: t.transpose(0, 1, 3, 2, 4).reshape(b * nc, h, cl, t.shape[-1])
    xw_f = flat(xw)
    cum_f = cum.transpose(0, 1, 3, 2).reshape(b * nc, h, cl, 1)
    y, st = _ssd.ssd_intra_flat(flat(jnp.asarray(xw)), cum_f,
                                flat(Br), flat(Cr), interpret=_interp())
    y = y.reshape(b, nc, h, cl, p).transpose(0, 1, 3, 2, 4)
    st = st.reshape(b, nc, h, p, n)
    return y, st


@functools.partial(jax.jit, static_argnames=("causal", "window", "block_q", "block_k"))
def flash_attention(q: jax.Array, k: jax.Array, v: jax.Array, *,
                    causal: bool = True, window: int = 0,
                    block_q: int = 128, block_k: int = 128) -> jax.Array:
    from . import flash_attention as _fa
    return _fa.flash_attention(q, k, v, causal=causal, window=window,
                               block_q=block_q, block_k=block_k,
                               interpret=_interp())
