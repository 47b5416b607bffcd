"""Host-proxy kernel entry points (numpy-first, Pallas when it pays).

The moekit host proxy runs on plain numpy byte buffers and must stay
importable — and fast to import — without dragging in jax: these wrappers
execute the numpy reference implementation unless jax is ALREADY loaded
with an accelerator backend, in which case they delegate to the Pallas
kernels in :mod:`repro.kernels.ops` (same math, fp32 accumulation).
"""

from __future__ import annotations

import sys

import numpy as np


def _accel_backend() -> bool:
    jax = sys.modules.get("jax")
    return jax is not None and jax.default_backend() != "cpu"


def moe_pack_host(rows: np.ndarray, perm: np.ndarray) -> np.ndarray:
    """Row gather for the moekit receiver shuffle / combine re-pack.

    ``rows``: (M, B) byte rows; ``perm``: (P,) int row indices (-1 => zero
    row).  One fancy-index gather on CPU; the Pallas pack kernel on an
    accelerator backend.
    """
    perm = np.asarray(perm)
    if _accel_backend():
        from . import ops
        return np.asarray(ops.moe_pack(rows, perm.astype(np.int32)))
    rows = np.asarray(rows)
    out = rows[np.maximum(perm, 0)]
    neg = perm < 0
    if neg.any():
        out[neg] = 0
    return out


def moe_combine_host(ye: np.ndarray, inv: np.ndarray,
                     gates: np.ndarray) -> np.ndarray:
    """Weighted combine (fp32 accumulation and result) for the moekit
    source half.

    ``ye``: (M, D) packed expert-output rows; ``inv``: (T, K) packed-row
    index per (token, slot), -1 => dropped; ``gates``: (T, K) weights.
    Slots accumulate in ascending ``k`` order — callers that pre-sort the
    slots by expert id get bit-identical fp32 sums to a dense
    ascending-expert oracle.
    """
    inv = np.asarray(inv)
    if _accel_backend():
        from . import ops
        return np.asarray(ops.moe_combine(
            ye, inv.astype(np.int32), np.asarray(gates, np.float32),
            out_dtype=np.float32))
    ye = np.asarray(ye)
    gates = np.asarray(gates, np.float32)
    T, K = inv.shape
    y = np.zeros((T, ye.shape[1]), np.float32)
    for k in range(K):
        idx = inv[:, k]
        rows = ye[np.maximum(idx, 0)].astype(np.float32)
        contrib = rows * gates[:, k:k + 1]
        y += np.where((idx >= 0)[:, None], contrib, 0.0)
    return y
