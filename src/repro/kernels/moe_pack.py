"""Pallas TPU kernel: MoE dispatch pack (token gather by permutation).

The TPU-native analogue of the paper's §6 dispatch *send* kernel: tokens are
copied from their natural order into a contiguous per-expert send buffer so
each peer receives one dense slab (paper Fig. 7: "dispatch into private and
contiguous buffers").  On TPU the "peers" are expert-parallel shards and the
slab is handed to ``all_to_all``; this kernel produces it.

Layout: the kernel is a pure DMA gather, HBM to HBM, one row per DMA, with
the permutation in scalar prefetch (SMEM).  Nothing is staged in VMEM, so
the kernel's on-chip footprint does not grow with T or M.  Mosaic DMAs whole
(sublane x lane) tiles only, so rows travel as 32-bit words in a
``(rows, 1, words)`` layout whose tile is one row (:func:`as_words`): bf16,
int8 and byte rows are gathered exactly like float32 ones.  ``perm`` rows of
-1 emit zeros (capacity padding).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

LANE = 128


def as_words(x: jax.Array) -> jax.Array:
    """(R, D) array of 1-, 2- or 4-byte elements -> (R, 1, W) uint32 words.

    Each row's bytes are zero-padded to a multiple of 128 words (one lane
    tile) and reinterpreted little-endian, so word ``j`` of a bf16 row holds
    elements ``2j`` (low half) and ``2j + 1`` (high half)."""
    R, D = x.shape
    itemsize = jnp.dtype(x.dtype).itemsize
    if itemsize not in (1, 2, 4):
        raise ValueError(f"unsupported row dtype {x.dtype}")
    per = 4 // itemsize
    pad = (-D) % (per * LANE)
    if pad:
        x = jnp.pad(x, ((0, 0), (0, pad)))
    if per > 1:
        x = x.reshape(R, -1, per)
    return jax.lax.bitcast_convert_type(x, jnp.uint32).reshape(R, 1, -1)


def from_words(w: jax.Array, dtype, D: int) -> jax.Array:
    """Inverse of :func:`as_words`: (R, 1, W) uint32 -> (R, D) ``dtype``."""
    R = w.shape[0]
    out = jax.lax.bitcast_convert_type(w.reshape(R, -1), jnp.dtype(dtype))
    return out.reshape(R, -1)[:, :D]


def _pack_kernel(perm_ref, x_hbm, zero_hbm, o_hbm, sem, *, block_m: int):
    """Grid: (M // block_m,).  Row ``m0 + i`` of the output is a DMA of row
    ``perm[m0 + i]`` of x (or of the zero row), all in flight at once."""
    m0 = pl.program_id(0) * block_m

    def start(i, carry):
        row = perm_ref[m0 + i]

        @pl.when(row >= 0)
        def _():
            pltpu.make_async_copy(x_hbm.at[row], o_hbm.at[m0 + i], sem).start()

        @pl.when(row < 0)
        def _():
            pltpu.make_async_copy(zero_hbm.at[0], o_hbm.at[m0 + i], sem).start()

        return carry

    def wait(i, carry):
        # every DMA moves one row of the same size: each wait retires one
        pltpu.make_async_copy(zero_hbm.at[0], o_hbm.at[m0 + i], sem).wait()
        return carry

    jax.lax.fori_loop(0, block_m, start, 0)
    jax.lax.fori_loop(0, block_m, wait, 0)


def moe_pack(x: jax.Array, perm: jax.Array, *, block_m: int = 128,
             interpret: bool = False) -> jax.Array:
    """x: (T, D), perm: (M,) -> (M, D) packed rows (−1 ⇒ zeros)."""
    T, D = x.shape
    M = perm.shape[0]
    if M == 0:
        return jnp.zeros((0, D), x.dtype)
    bm = min(block_m, M)
    pm = (-M) % bm
    perm = perm.astype(jnp.int32)
    if pm:
        perm = jnp.pad(perm, ((0, pm),), constant_values=-1)
    Mp = perm.shape[0]
    words = as_words(x)
    W = words.shape[-1]
    out = pl.pallas_call(
        functools.partial(_pack_kernel, block_m=bm),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(Mp // bm,),
            in_specs=[pl.BlockSpec(memory_space=pl.ANY),
                      pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=pl.BlockSpec(memory_space=pl.ANY),
            scratch_shapes=[pltpu.SemaphoreType.DMA(())],
        ),
        out_shape=jax.ShapeDtypeStruct((Mp, 1, W), jnp.uint32),
        interpret=interpret,
        name="moe_pack",
    )(perm, words, jnp.zeros((1, 1, W), jnp.uint32))
    return from_words(out[:M], x.dtype, D)
