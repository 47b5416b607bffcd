"""Pallas TPU kernel: MoE combine (weighted gather-reduce to token order).

The TPU-native analogue of the paper's §6 combine *receiver*: every token
gathers its top-k expert outputs from the packed receive buffer and reduces
them with the router gates.  Formulating combine as an inverse-permutation
gather (rather than a scatter-add) keeps it deterministic and atomics-free —
the same trick the paper uses by centralising routing info at dispatch so
combine needs a single contiguous scatter.

Accumulation is fp32 regardless of the payload dtype (the paper calls out
DeepEP's bf16 accumulation as an accuracy trade-off; we keep fp32).

Layout: ``ye`` stays in HBM as 32-bit words (``moe_pack.as_words``).  Each
grid step DMAs the ``top_k`` picked rows of ``block_t`` tokens (one lane
tile of words each) into a VMEM scratch of fixed size, so the kernel's VMEM
footprint does not grow with T or M.  A bf16 word is split into its two
elements with integer shifts, giving two fp32 planes (even / odd columns)
that are interleaved back outside the kernel.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .moe_pack import as_words

SUBLANE = 8


def _planes(w: jax.Array, n_planes: int):
    """uint32 words -> fp32 values: one plane for f32, even/odd for bf16."""
    if n_planes == 1:
        return [jax.lax.bitcast_convert_type(w, jnp.float32)]
    return [jax.lax.bitcast_convert_type(w << 16, jnp.float32),
            jax.lax.bitcast_convert_type(w & jnp.uint32(0xFFFF0000),
                                         jnp.float32)]


def _combine_kernel(inv_s, inv_ref, gates_ref, ye_hbm, o_ref, buf, sem, *,
                    block_t: int, top_k: int, n_planes: int):
    """Grid: (T // block_t, W // block_w).

    inv_s: (T*K,) int32 scalar prefetch (row of ye for token t's k-th pick,
    -1 => dropped); inv_ref / gates_ref: (block_t, K) int32 / fp32 blocks;
    ye_hbm: (M, 1, W) uint32 words in HBM; o_ref: (n_planes, block_t,
    block_w) fp32; buf: (K * block_t, 1, block_w) uint32 VMEM scratch.
    """
    t0 = pl.program_id(0) * block_t
    j = pl.program_id(1)
    bw = buf.shape[-1]

    def copy(n):
        k, i = n // block_t, n % block_t
        row = jnp.maximum(inv_s[(t0 + i) * top_k + k], 0)
        return pltpu.make_async_copy(
            ye_hbm.at[row, :, pl.ds(j * bw, bw)], buf.at[n], sem)

    def start(n, carry):
        copy(n).start()
        return carry

    def wait(n, carry):
        copy(n).wait()
        return carry

    jax.lax.fori_loop(0, top_k * block_t, start, 0)
    jax.lax.fori_loop(0, top_k * block_t, wait, 0)

    inv = inv_ref[...]
    gates = gates_ref[...]
    acc = [jnp.zeros((block_t, bw), jnp.float32) for _ in range(n_planes)]
    for k in range(top_k):          # ascending k: the reference's sum order
        w = buf[pl.ds(k * block_t, block_t)].reshape(block_t, bw)
        keep = inv[:, k:k + 1] >= 0
        g = gates[:, k:k + 1]
        acc = [a + jnp.where(keep, v * g, 0.0)
               for a, v in zip(acc, _planes(w, n_planes))]
    for p in range(n_planes):
        o_ref[p] = acc[p]


def moe_combine(ye: jax.Array, inv: jax.Array, gates: jax.Array, *,
                block_t: int = SUBLANE, block_w: int = 2048,
                out_dtype: Optional[jnp.dtype] = None,
                interpret: bool = False) -> jax.Array:
    """ye: (M, D); inv, gates: (T, K) -> (T, D) fp32-accumulated combine.

    The result is cast to ``out_dtype`` (default: ``ye.dtype``)."""
    M, D = ye.shape
    T, K = inv.shape
    dtype = jnp.dtype(ye.dtype)
    out_dtype = dtype if out_dtype is None else jnp.dtype(out_dtype)
    if dtype not in (jnp.dtype(jnp.float32), jnp.dtype(jnp.bfloat16)):
        raise ValueError(f"moe_combine: unsupported payload dtype {dtype}")
    if T == 0:
        return jnp.zeros((0, D), out_dtype)
    n_planes = 4 // dtype.itemsize
    words = as_words(ye)
    W = words.shape[-1]
    bw = min(block_w, W)
    while W % bw:
        bw //= 2
    inv = inv.astype(jnp.int32)
    gates = gates.astype(jnp.float32)
    pt = (-T) % block_t
    if pt:
        inv = jnp.pad(inv, ((0, pt), (0, 0)), constant_values=-1)
        gates = jnp.pad(gates, ((0, pt), (0, 0)))
    Tp = inv.shape[0]

    out = pl.pallas_call(
        functools.partial(_combine_kernel, block_t=block_t, top_k=K,
                          n_planes=n_planes),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(Tp // block_t, W // bw),
            in_specs=[pl.BlockSpec((block_t, K), lambda i, j, s: (i, 0)),
                      pl.BlockSpec((block_t, K), lambda i, j, s: (i, 0)),
                      pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=pl.BlockSpec((n_planes, block_t, bw),
                                   lambda i, j, s: (0, i, j)),
            scratch_shapes=[pltpu.VMEM((K * block_t, 1, bw), jnp.uint32),
                            pltpu.SemaphoreType.DMA(())],
        ),
        out_shape=jax.ShapeDtypeStruct((n_planes, Tp, W), jnp.float32),
        interpret=interpret,
        name="moe_combine",
    )(inv.reshape(-1), inv, gates, words)
    # planes (even, odd columns) -> interleaved columns
    out = jnp.moveaxis(out, 0, -1).reshape(Tp, -1)[:T, :D]
    return out.astype(out_dtype)
