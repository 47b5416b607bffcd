"""Expert-parallel MoE dispatch/combine — the fabric-lib pattern on TPU.

This is the TPU-native mapping of the paper's §6 host-proxy protocol:

  paper (RDMA)                          | here (XLA/ICI under shard_map)
  --------------------------------------+--------------------------------
  exchange per-expert token counts      | counts travel WITH the payload
  ("routes" scatter to all peers)       | (expert-id + gate appended as
                                        | feature channels — route and
                                        | token transfer fused, the same
                                        | "parallel token and route
                                        | transfer" trick §1)
  WRITE tokens into a contiguous,       | jax.lax.all_to_all into a
  bounded receive buffer per peer       | bounded (n_ranks, cap, D+2)
  (paper: N*T*max(R, E/N) bound)        | buffer; overflow tokens dropped
                                        | (capacity semantics, GShard)
  receiver shuffles tokens into a       | moe_pack Pallas kernel +
  Grouped-GEMM layout                   | capacity scatter to (E_loc, Ce)
  combine: single scatter re-using      | reverse all_to_all into the
  dispatch routing info                 | SAME slots (routing reused)
  fp32 accumulation (vs DeepEP bf16)    | moe_combine accumulates fp32

Tokens enter sharded over the data axes and are *locally* re-sharded over
the expert-parallel ('model') axis first — the zero-cost sequence-parallel
split — so the all-to-all runs only on the EP axis; GSPMD re-gathers the
output activations afterwards.
"""

from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from ..models.common import rms_norm
from .context import current_mesh, data_axes

# capacity head-room over perfectly-balanced routing
DISPATCH_FACTOR = 2.0


def _capacity_scatter(rows: jax.Array, eids: jax.Array, valid: jax.Array,
                      n_experts: int, cap: int):
    """Scatter rows into (n_experts, cap, D) by expert id.

    Returns (buf, slot) where slot[i] is the row's landing slot (-1 dropped).
    """
    Tl, D = rows.shape
    oh = jax.nn.one_hot(eids, n_experts, dtype=jnp.int32) * valid[:, None]
    pos = jnp.take_along_axis(jnp.cumsum(oh, 0) - oh, eids[:, None], 1)[:, 0]
    keep = (pos < cap) & valid.astype(bool)
    slot = jnp.where(keep, pos, cap)
    buf = jnp.zeros((n_experts, cap + 1, D), rows.dtype).at[eids, slot].add(
        jnp.where(keep[:, None], rows, 0))
    return buf[:, :cap], jnp.where(keep, slot, -1)


def moe_a2a(p, h: jax.Array, cfg, ep_axis: str = "model",
            mesh: Optional[jax.sharding.Mesh] = None
            ) -> Tuple[jax.Array, jax.Array]:
    """Paper-style expert-parallel MoE layer.  h: (T, D) normalised tokens.

    Needs a mesh with ``ep_axis``: passed in, or active via ``use_mesh``.
    """
    mesh = mesh or current_mesh()
    if mesh is None or ep_axis not in mesh.axis_names:
        raise ValueError(
            f"moe_a2a needs a mesh with an {ep_axis!r} axis (pass mesh= or "
            f"enter comm.use_mesh); got {mesh}")

    import math

    T, D = h.shape
    E, k = cfg.n_routed, cfg.top_k
    m = mesh.shape[ep_axis]
    E_loc = E // m
    daxes = data_axes(mesh)
    nd = math.prod(mesh.shape[a] for a in daxes)
    if T % (m * nd) != 0:
        # Token count does not split over the EP axis (small decode batches):
        # fall back to replicated-token EP — each EP rank computes only its
        # local experts' contributions and the combine is a psum, the
        # "collective combine" the paper contrasts against.  For tiny T this
        # moves comparable bytes to a ragged dispatch.
        return moe_ep_psum(p, h, cfg, ep_axis, mesh)
    T_lm = T // (m * nd)
    cap = max(1, int(T_lm * k / m * DISPATCH_FACTOR))
    Ce = max(1, (m * cap) // max(E_loc, 1))

    def local(h_l, router, wg, wu, wd, *shared):
        # h_l: (T_lm, D) — sharded over data axes AND the EP axis.
        Tl = h_l.shape[0]
        logits = h_l.astype(jnp.float32) @ router
        probs = jax.nn.softmax(logits, axis=-1)
        gates, eids = jax.lax.top_k(probs, k)
        gates = gates / jnp.maximum(gates.sum(-1, keepdims=True), 1e-9)
        me = jax.lax.pmean(probs.mean(0), daxes + (ep_axis,))
        ce = jnp.zeros((E,), jnp.float32).at[eids.reshape(-1)].add(1.0)
        ce = jax.lax.pmean(ce / jnp.maximum(ce.sum(), 1.0), daxes + (ep_axis,))
        aux = E * jnp.sum(me * ce)

        # ---- dispatch: pack per-destination-rank send buffer ----------------
        fe = eids.reshape(-1)                                # (Tl*k,) global expert
        fg = gates.reshape(-1)
        ft = jnp.repeat(jnp.arange(Tl), k)
        dest = fe // E_loc                                   # destination EP rank
        # slot within each destination block (same cumsum trick as capacity)
        oh = jax.nn.one_hot(dest, m, dtype=jnp.int32)
        pos = jnp.take_along_axis(jnp.cumsum(oh, 0) - oh, dest[:, None], 1)[:, 0]
        keep = pos < cap
        slot = jnp.where(keep, pos, -1)
        flat_slot = jnp.where(keep, dest * cap + pos, -1)    # (Tl*k,)

        # route info rides with the payload: [token | local-expert-id | gate]
        aug = jnp.concatenate([
            h_l, jnp.zeros((Tl, 2), h_l.dtype)], axis=1)     # (Tl, D+2)
        perm = jnp.full((m * cap,), -1, jnp.int32).at[
            jnp.where(keep, flat_slot, m * cap)].set(ft, mode="drop")
        from ..kernels import ops as kops
        send = kops.moe_pack_auto(aug, perm)                 # (m*cap, D+2)
        meta_e = jnp.full((m * cap,), -1.0, jnp.float32).at[
            jnp.where(keep, flat_slot, m * cap)].set(
                (fe % E_loc).astype(jnp.float32), mode="drop")
        meta_g = jnp.zeros((m * cap,), jnp.float32).at[
            jnp.where(keep, flat_slot, m * cap)].set(fg, mode="drop")
        send = send.at[:, D].set(meta_e.astype(send.dtype))
        send = send.at[:, D + 1].set(meta_g.astype(send.dtype))

        recv = jax.lax.all_to_all(send.reshape(m, cap, D + 2), ep_axis,
                                  split_axis=0, concat_axis=0, tiled=False)
        recv = recv.reshape(m * cap, D + 2)

        # ---- expert compute (grouped, capacity Ce) -------------------------
        r_eid = recv[:, D].astype(jnp.int32)
        r_gate = recv[:, D + 1].astype(jnp.float32)
        r_valid = (r_eid >= 0).astype(jnp.int32)
        r_tok = recv[:, :D]
        buf, r_slot = _capacity_scatter(r_tok, jnp.maximum(r_eid, 0),
                                        r_valid, E_loc, Ce)
        g = jnp.einsum("ecd,edf->ecf", buf, wg)
        u = jnp.einsum("ecd,edf->ecf", buf, wu)
        ye = jnp.einsum("ecf,efd->ecd", jax.nn.silu(g) * u, wd)   # (E_loc,Ce,D)
        # gather back into receive-buffer row order
        ye_pad = jnp.concatenate([ye, jnp.zeros((E_loc, 1, D), ye.dtype)], 1)
        rows = ye_pad[jnp.maximum(r_eid, 0), jnp.where(r_slot >= 0, r_slot, Ce)]
        rows = jnp.where((r_slot >= 0)[:, None], rows, 0)
        # apply gate on the expert side (combine then only sums) — keeps the
        # return payload D-wide
        rows = rows * r_gate[:, None].astype(rows.dtype)

        # ---- combine: reverse all_to_all into the SAME slots ----------------
        back = jax.lax.all_to_all(rows.reshape(m, cap, D), ep_axis,
                                  split_axis=0, concat_axis=0, tiled=False)
        back = back.reshape(m * cap, D)
        inv = jnp.where(keep, flat_slot, -1).reshape(Tl, k)
        ones = jnp.ones((Tl, k), jnp.float32)                # gates pre-applied
        y = kops.moe_combine_auto(back, inv, ones)

        if shared:
            swg, swu, swd = shared
            y = y + (jax.nn.silu(h_l @ swg) * (h_l @ swu)) @ swd
        return y, aux

    in_specs = (P((*daxes, ep_axis), None),                  # h: fully sharded T
                P(None, None),                               # router replicated
                P(ep_axis, None, None),                      # experts EP-sharded
                P(ep_axis, None, None),
                P(ep_axis, None, None))
    args = [h, p["router"], p["wg"], p["wu"], p["wd"]]
    if "swg" in p:
        in_specs = in_specs + (P(None, None),) * 3
        args += [p["swg"], p["swu"], p["swd"]]
    out_specs = (P((*daxes, ep_axis), None), P())

    y, aux = jax.shard_map(local, mesh=mesh, in_specs=in_specs,
                       out_specs=out_specs, check_vma=False)(*args)
    return y, aux


def moe_ep_psum(p, h: jax.Array, cfg, ep_axis: str,
                mesh: jax.sharding.Mesh) -> Tuple[jax.Array, jax.Array]:
    """Replicated-token expert parallelism (collective-style combine).

    Tokens stay sharded over the data axes and replicated over the EP axis;
    each EP rank runs ONLY its local experts over all its tokens and the
    partial outputs are psum'ed.  No token movement — the communication is
    one all-reduce of the activations, the pattern the paper's P2P dispatch
    replaces.  Used as (a) the decode fallback and (b) the §Perf baseline.
    """
    T, D = h.shape
    E, k = cfg.n_routed, cfg.top_k
    m = mesh.shape[ep_axis]
    E_loc = E // m
    daxes = data_axes(mesh)
    cap = max(1, int(T // math_prod(mesh, daxes) * k / max(E_loc, 1) * DISPATCH_FACTOR))

    def local(h_l, router, wg, wu, wd, *shared):
        Tl = h_l.shape[0]
        rank = jax.lax.axis_index(ep_axis)
        logits = h_l.astype(jnp.float32) @ router
        probs = jax.nn.softmax(logits, axis=-1)
        gates, eids = jax.lax.top_k(probs, k)
        gates = gates / jnp.maximum(gates.sum(-1, keepdims=True), 1e-9)
        me = jax.lax.pmean(probs.mean(0), daxes)
        ce = jnp.zeros((E,), jnp.float32).at[eids.reshape(-1)].add(1.0)
        ce = jax.lax.pmean(ce / jnp.maximum(ce.sum(), 1.0), daxes)
        aux = E * jnp.sum(me * ce)

        fe = eids.reshape(-1)
        fg = gates.reshape(-1)
        ft = jnp.repeat(jnp.arange(Tl), k)
        mine = (fe // E_loc) == rank
        le = jnp.where(mine, fe % E_loc, 0)
        buf, slot = _capacity_scatter(h_l[ft], le, mine.astype(jnp.int32),
                                      E_loc, cap)
        g = jnp.einsum("ecd,edf->ecf", buf, wg)
        u = jnp.einsum("ecd,edf->ecf", buf, wu)
        ye = jnp.einsum("ecf,efd->ecd", jax.nn.silu(g) * u, wd)
        ye = jnp.concatenate([ye, jnp.zeros((E_loc, 1, D), ye.dtype)], 1)
        rows = ye[le, jnp.where(slot >= 0, slot, cap)]
        rows = jnp.where((slot >= 0)[:, None], rows, 0) * fg[:, None].astype(ye.dtype)
        y = jnp.zeros((Tl, D), h_l.dtype).at[ft].add(rows.astype(h_l.dtype))
        y = jax.lax.psum(y, ep_axis)
        if shared:
            swg, swu, swd = shared
            y = y + (jax.nn.silu(h_l @ swg) * (h_l @ swu)) @ swd
        return y, aux

    in_specs = (P(daxes if daxes else None, None),
                P(None, None),
                P(ep_axis, None, None), P(ep_axis, None, None), P(ep_axis, None, None))
    args = [h, p["router"], p["wg"], p["wu"], p["wd"]]
    if "swg" in p:
        in_specs = in_specs + (P(None, None),) * 3
        args += [p["swg"], p["swu"], p["swd"]]
    out_specs = (P(daxes if daxes else None, None), P())
    y, aux = jax.shard_map(local, mesh=mesh, in_specs=in_specs,
                       out_specs=out_specs, check_vma=False)(*args)
    return y, aux


def math_prod(mesh, axes) -> int:
    import math
    return max(1, math.prod(mesh.shape[a] for a in axes))
