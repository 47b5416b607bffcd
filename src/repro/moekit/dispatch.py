"""Host-proxy MoE dispatch/combine over the TransferEngine (paper §6).

Low-latency decode fast path.  Protocol per rank and MoE layer invocation:

  dispatch (two phases, ONE WrBatch enqueue each):
    1. count tokens per expert (GPU kernel; modeled as KERNEL_LAUNCH_US)
    2. phase 1 — scatter ROUTES (the (E,) per-expert counts) to every peer
       and speculatively scatter the first ``t_priv`` tokens per destination
       into private per-source buffers (hides route latency — Fig. 11)
    3. once all peers' routes arrive (ImmCounter), phase 2 — coalesce ALL
       remaining tokens for receiver ``r`` into ONE contiguous WRITE landing
       in r's per-source shared region (source-major layout)
    4. receiver completion = ImmCounter over token writes; the grouped-GEMM
       layout is recovered from the exchanged routes ALONE (no peeking at
       peer state) as a route-derived permutation executed by a single
       fancy-index gather (``repro.kernels.host.moe_pack_host``)
    => at most TWO data WRITEs per inter-node peer per round (private +
       shared), plus the route write — the paper's §6 bound, honestly.

  combine:
    expert outputs are returned with a SINGLE zero-copy scatter per source:
    a route-derived permutation packs them (source-major) and the per-source
    row slices ride as ``PayloadDst`` gather-into-snapshot payloads (no
    staging copy).  Each source un-permutes and reduces with its gates in
    fp32 via ``repro.kernels.host.moe_combine_host``.

Offsets are derived on BOTH sides purely from ``routes_buf``: endpoints
exchange only :class:`PeerPorts` (rank + MrDescs), so no endpoint can read
another endpoint's context or buffers except through posted WRITEs.

Payload bytes move for real; tests validate the packed layout and the
combined output against a dense oracle.
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Callable, Dict, List, Optional

import numpy as np

from ..core import (Fabric, MrDesc, PayloadDst, ScatterDst, TransferEngine,
                    TransferError)
from ..obs import traced_phase

KERNEL_LAUNCH_US = 15.0      # launch -> first transfer (paper §6.2)
ROUTE_PROC_US = 20.0         # host-side route processing before the second
                             # round of transfers ("tens of microseconds",
                             # §6.2) — the latency the private buffers hide
ROUTE_IMM = 0x520
TOK_IMM = 0x521
COMB_IMM = 0x522
BARRIER_IMM = 0x523


class DispatchError(TransferError):
    """A MoE dispatch/combine WRITE exhausted its retry budget (dead or
    unreachable peer).  Raised out of ``fabric.run()`` — instead of the
    round silently hanging on an ImmCounter that can never fire — after
    the endpoint's round state has been cleaned up via
    :meth:`MoEEndpoint.abort_round`."""

    def __init__(self, rank: int, round_id: int, reason: str):
        super().__init__(
            f"moe rank{rank} round {round_id} dispatch failed: {reason}")
        self.rank = rank
        self.round_id = round_id
        self.reason = reason


def multi_arange(starts: np.ndarray, counts: np.ndarray) -> np.ndarray:
    """Concatenated ``[starts[i], starts[i]+counts[i])`` ranges, vectorised
    (the route-derived permutations below are built from these)."""
    counts = np.asarray(counts, np.int64).reshape(-1)
    starts = np.asarray(starts, np.int64).reshape(-1)
    total = int(counts.sum())
    if total == 0:
        return np.empty(0, np.int64)
    ends = np.cumsum(counts)
    idx = np.arange(total, dtype=np.int64) - np.repeat(ends - counts, counts)
    return idx + np.repeat(starts, counts)


@dataclass
class MoEConfig:
    n_ranks: int
    n_experts: int             # global
    top_k: int
    max_tokens: int            # T per rank
    token_bytes: int           # payload bytes per token (e.g. 7168 fp8)
    t_priv: int = 32           # private-buffer tokens per (src, dst) pair

    @property
    def e_local(self) -> int:
        return self.n_experts // self.n_ranks

    @property
    def src_region_tokens(self) -> int:
        # paper bound (§6.1): one source contributes at most
        # T * min(top_k, E/N) token copies to one receiver
        return self.max_tokens * min(self.top_k, self.e_local)

    @property
    def recv_cap(self) -> int:
        # total tokens that can land on a rank across all sources
        return self.n_ranks * self.src_region_tokens


@dataclass(frozen=True)
class PeerPorts:
    """Everything an endpoint may know about a peer: its rank and the
    MrDescs of its receive windows.  Serializable — in a real deployment
    this is the JOIN payload.  All placement offsets are derived from the
    exchanged routes, never from peer-side state."""

    rank: int
    d_routes: MrDesc
    d_priv: MrDesc
    d_shared: MrDesc
    d_comb: MrDesc


class MoEEndpoint:
    """One expert-parallel rank: buffers + proxy logic."""

    def __init__(self, fabric: Fabric, cfg: MoEConfig, rank: int,
                 engine: TransferEngine):
        self.fabric = fabric
        self.cfg = cfg
        self.rank = rank
        self.engine = engine
        tb, N, T = cfg.token_bytes, cfg.n_ranks, cfg.max_tokens
        # One backing allocation for both receive windows so the receiver
        # shuffle is a SINGLE fancy-index gather over its row view:
        #   rows [0, N*t_priv)                 — private per-source regions
        #   rows [N*t_priv, +N*src_region)     — shared  per-source regions
        self._n_priv_rows = N * cfg.t_priv
        self._n_shared_rows = N * cfg.src_region_tokens
        self.recv_buf = np.zeros((self._n_priv_rows + self._n_shared_rows) * tb,
                                 np.uint8)
        self.priv_buf = self.recv_buf[:self._n_priv_rows * tb]
        self.shared_buf = self.recv_buf[self._n_priv_rows * tb:]
        self.routes_buf = np.zeros(N * cfg.n_experts * 4, np.uint8)
        self.comb_buf = np.zeros(T * cfg.top_k * tb, np.uint8)
        self.h_routes, self.d_routes = engine.reg_mr(self.routes_buf)
        self.h_priv, self.d_priv = engine.reg_mr(self.priv_buf)
        self.h_shared, self.d_shared = engine.reg_mr(self.shared_buf)
        self.h_comb, self.d_comb = engine.reg_mr(self.comb_buf)
        # tiny staging region for the route counts (token payloads ride
        # PayloadDst gather-into-snapshot — no send staging at all)
        self.route_send = np.zeros(cfg.n_experts * 4, np.uint8)
        self.h_route_send, _ = engine.reg_mr(self.route_send)
        self.ports: List[PeerPorts] = []
        self.stats: Dict[str, float] = {}
        self.round = 0          # per-layer round: scopes imm values

    # -- wiring ------------------------------------------------------------
    def port(self) -> PeerPorts:
        return PeerPorts(rank=self.rank, d_routes=self.d_routes,
                         d_priv=self.d_priv, d_shared=self.d_shared,
                         d_comb=self.d_comb)

    def connect(self, ports: List[PeerPorts]) -> None:
        if [p.rank for p in ports] != list(range(self.cfg.n_ranks)):
            raise ValueError("ports must cover ranks 0..N-1 in order")
        self.ports = ports

    # -- fault cleanup ------------------------------------------------------
    def abort_round(self) -> None:
        """Drop the current round's immediate expectations (route, token
        and combine counters) so a failed round leaves no unfulfilled
        watchers behind — ``Fabric.audit()`` stays clean and the next
        round's (round-scoped) immediates start fresh."""
        ctr = self.engine.counters[0]
        for base in (ROUTE_IMM, TOK_IMM, COMB_IMM):
            ctr.reset(base + (self.round << 8))

    def _fail(self, ctx: Dict, phase: str, reason: str,
              on_error: Optional[Callable[["DispatchError"], None]]) -> None:
        if ctx.get("failed"):
            return               # sibling WRITE of the same round already did
        ctx["failed"] = True
        self.stats["failures"] = self.stats.get("failures", 0) + 1
        self.abort_round()
        err = DispatchError(self.rank, self.round, f"{phase}: {reason}")
        if on_error is not None:
            on_error(err)
            return
        raise err

    # -- dispatch ------------------------------------------------------------
    def dispatch(self, tokens: np.ndarray, eids: np.ndarray,
                 on_complete: Callable[[], None],
                 on_error: Optional[Callable[["DispatchError"], None]] = None
                 ) -> Dict:
        """tokens: (T, token_bytes) uint8; eids: (T, top_k) int32 global ids.

        Returns a context dict used later by combine; ``on_complete`` fires
        when this rank has received ALL tokens routed to its local experts
        (and can run its grouped GEMM).  Under fault injection a WRITE that
        exhausts its retry budget (e.g. a dead peer) aborts the round:
        expectations are reset (:meth:`abort_round`) and a
        :class:`DispatchError` is raised out of ``fabric.run()`` — or
        handed to ``on_error`` when provided."""
        cfg = self.cfg
        N, E, R = cfg.n_ranks, cfg.n_experts, cfg.top_k
        T = tokens.shape[0]
        t0 = self.fabric.now
        self.round += 1
        route_imm = ROUTE_IMM + (self.round << 8)
        tok_imm = TOK_IMM + (self.round << 8)

        # Top-k slots must be distinct experts: the per-source shared
        # regions are sized T * min(top_k, E/N), which duplicate slots
        # overflow (silently corrupting a neighbour region mid-round).
        if T:
            es = np.sort(eids, axis=1)
            if (es[:, 1:] == es[:, :-1]).any():
                raise ValueError("eids rows must hold distinct experts")

        # 1. per-expert counts
        counts = np.bincount(eids.reshape(-1), minlength=E).astype(np.int32)

        # flat assignment list in (expert, token) order
        fe = eids.reshape(-1)
        ft = np.repeat(np.arange(T), R)
        order = np.lexsort((ft, fe))            # stable by expert then token
        fe_s, ft_s = fe[order], ft[order]
        dest = fe_s // cfg.e_local

        ctx = {"counts": counts, "order": order, "eids": eids,
               "fe_s": fe_s, "ft_s": ft_s, "t0": t0, "T": T, "sent_at": None}

        def proxy_phase1() -> None:
            # 2. routes to all peers (small payload, all NICs)
            self.route_send.view(np.int32)[:] = counts
            route_dsts = [ScatterDst(len=E * 4, src=0,
                                     dst=(p.d_routes, self.rank * E * 4))
                          for p in self.ports]

            # 3. speculative private-buffer tokens (first t_priv per dest).
            # Gather-into-snapshot fast path: ONE vectorised fancy-index
            # gather per destination produces the contiguous payload that
            # IS the submission snapshot — no staging copy, no second
            # snapshot copy.
            tb = cfg.token_bytes
            priv_dsts = []
            for r in range(N):
                take = np.nonzero(dest == r)[0][:cfg.t_priv]
                if take.size == 0:
                    continue
                priv_dsts.append(PayloadDst(
                    payload=tokens[ft_s[take]].reshape(-1),
                    dst=(self.ports[r].d_priv, self.rank * cfg.t_priv * tb)))
            # routes + private tokens ride ONE WrBatch (one proxy handoff);
            # each keeps its own imm so completion accounting is unchanged
            xerr = (lambda reason: self._fail(ctx, "dispatch.p1", reason,
                                              on_error))
            with traced_phase(self.fabric, "moe.dispatch.p1"):
                self.engine.submit_scatters([
                    (self.h_route_send, route_dsts, route_imm, None, xerr),
                    (None, priv_dsts, tok_imm, None, xerr),
                ])

        tr = self.fabric.tracer
        if tr is not None:
            tr.compute_span(f"rank{self.rank} gpu", "kernel_launch",
                            t0, t0 + KERNEL_LAUNCH_US, phase="moe.dispatch")
        self.fabric.loop.schedule(KERNEL_LAUNCH_US, proxy_phase1)

        # 4. wait for ALL routes, then ship every receiver its residual
        # tokens as ONE contiguous WRITE into its per-source shared region
        def on_routes() -> None:
            tr = self.fabric.tracer
            if tr is not None:
                now = self.fabric.now
                tr.compute_span(f"rank{self.rank} proxy", "route_proc",
                                now, now + ROUTE_PROC_US,
                                phase="moe.dispatch")
            self.fabric.loop.schedule(ROUTE_PROC_US, lambda: process_routes())

        def process_routes() -> None:
            all_counts = self.routes_buf.view(np.int32).reshape(N, E)
            ctx["all_counts"] = all_counts.copy()
            tb = cfg.token_bytes
            shared_dsts = []
            for r in range(N):
                rest = np.nonzero(dest == r)[0][cfg.t_priv:]
                if rest.size == 0:
                    continue
                # `rest` is expert-sorted; the receiver reconstructs the
                # (expert, source-order) sub-layout from the routes alone.
                shared_dsts.append(PayloadDst(
                    payload=tokens[ft_s[rest]].reshape(-1),
                    dst=(self.ports[r].d_shared,
                         self.rank * cfg.src_region_tokens * tb)))
            if shared_dsts:
                with traced_phase(self.fabric, "moe.dispatch.p2"):
                    self.engine.submit_scatters(
                        [(None, shared_dsts, tok_imm,
                          lambda: ctx.__setitem__("sent_at", self.fabric.now),
                          lambda reason: self._fail(ctx, "dispatch.p2",
                                                    reason, on_error))])
            else:
                ctx["sent_at"] = self.fabric.now

            # receiver completion: expected #token WRITEs to me — at most
            # TWO per source (one private, one shared), derived from the
            # exchanged routes alone.
            e0 = self.rank * cfg.e_local
            my_counts = all_counts[:, e0:e0 + cfg.e_local]
            per_src = my_counts.sum(1)
            n_writes = int((np.minimum(per_src, cfg.t_priv) > 0).sum()) + \
                int((per_src > cfg.t_priv).sum())
            ctx["my_counts"] = my_counts.copy()

            def tokens_done() -> None:
                self.stats["dispatch_us"] = self.fabric.now - t0
                on_complete()

            self.engine.expect_imm_count(tok_imm, n_writes, tokens_done)

        self.engine.expect_imm_count(route_imm, N, on_routes)
        return ctx

    # -- receiver shuffle --------------------------------------------------------
    def _recv_layout(self, my_counts: np.ndarray):
        """Route-derived receive layout: per (source, local expert), how many
        rows sit in the private region vs the shared region, and where."""
        cfg = self.cfg
        my = my_counts.astype(np.int64)                    # (N, e_local)
        cum = np.cumsum(my, axis=1)
        before = cum - my                                  # prefix per (s, e)
        n_priv = np.clip(cfg.t_priv - before, 0, my)       # private rows
        n_resid = my - n_priv                              # shared rows
        resid_before = np.cumsum(n_resid, axis=1) - n_resid
        return before, n_priv, n_resid, resid_before

    def gather_expert_tokens(self, ctx: Dict) -> List[np.ndarray]:
        """Shuffle received bytes into per-local-expert dense slabs (the
        paper's receiver half feeding the Grouped GEMM): a route-derived
        permutation over the receive rows, executed as ONE fancy-index
        gather (``kernels.host.moe_pack_host`` — Pallas on TPU, numpy ref
        fallback on CPU)."""
        from ..kernels.host import moe_pack_host
        cfg = self.cfg
        tb = cfg.token_bytes
        N = cfg.n_ranks
        my = ctx["my_counts"].astype(np.int64)             # (N, e_local)
        before, n_priv, n_resid, resid_before = self._recv_layout(my)
        srt = cfg.src_region_tokens
        src_ids = np.arange(N, dtype=np.int64)
        perms, sizes = [], []
        for e_loc in range(cfg.e_local):
            # rows for (s, e): private prefix then shared residuals, sources
            # ascending — exactly the order the senders packed them in
            starts = np.stack([
                src_ids * cfg.t_priv + before[:, e_loc],
                self._n_priv_rows + src_ids * srt + resid_before[:, e_loc],
            ], axis=1)                                     # (N, 2)
            cnts = np.stack([n_priv[:, e_loc], n_resid[:, e_loc]], axis=1)
            perms.append(multi_arange(starts, cnts))
            sizes.append(int(my[:, e_loc].sum()))
        perm = np.concatenate(perms) if perms else np.empty(0, np.int64)
        rows = self.recv_buf.reshape(-1, tb)
        packed = moe_pack_host(rows, perm)
        splits = np.cumsum(sizes)[:-1]
        return [np.ascontiguousarray(s) for s in np.split(packed, splits)]

    # -- combine ----------------------------------------------------------------
    def combine(self, ctx: Dict, expert_out: List[np.ndarray],
                on_complete: Callable[[], None],
                on_error: Optional[Callable[["DispatchError"], None]] = None
                ) -> None:
        """Send processed tokens back to their sources: ONE zero-copy
        scatter (a single WrBatch enqueue, one WRITE per source).  Fault
        handling mirrors :meth:`dispatch` — retry-budget exhaustion aborts
        the round and raises / reports a :class:`DispatchError`."""
        from ..kernels.host import moe_pack_host
        cfg = self.cfg
        tb = cfg.token_bytes
        N = cfg.n_ranks
        all_counts = ctx["all_counts"]
        t0 = self.fabric.now
        comb_imm = COMB_IMM + (self.round << 8)
        e0 = self.rank * cfg.e_local
        my = all_counts[:, e0:e0 + cfg.e_local].astype(np.int64)   # (N, e_local)

        # Re-permute expert outputs to source-major order with ONE gather:
        # row (s, e) blocks live at slab_off[e] + rows of source s in slab e.
        stacked = (np.concatenate(expert_out) if len(expert_out) > 1
                   else expert_out[0])
        slab_off = np.concatenate([[0], np.cumsum(my.sum(0))])[:-1]  # per e
        col_before = np.cumsum(my, axis=0) - my            # source prefix in slab
        starts = slab_off[None, :] + col_before            # (N, e_local)
        perm = multi_arange(starts, my)                    # source-major
        packed = moe_pack_host(stacked.reshape(-1, tb) if stacked.size
                               else stacked.reshape(0, tb), perm)

        # per-source destination offset: my segment of s's comb_buf starts
        # after all lower-ranked experts' counts from s (routes-derived)
        per_src = my.sum(1)
        lo = np.concatenate([[0], np.cumsum(per_src)])[:-1]
        before_tok = all_counts[:, :e0].sum(1).astype(np.int64)
        dsts = [PayloadDst(payload=packed[lo[s]:lo[s] + per_src[s]].reshape(-1),
                           dst=(self.ports[s].d_comb, int(before_tok[s]) * tb))
                for s in range(N) if per_src[s] > 0]

        def proxy_send() -> None:
            with traced_phase(self.fabric, "moe.combine"):
                self.engine.submit_scatters(
                    [(None, dsts, comb_imm, None,
                      lambda reason: self._fail(ctx, "combine", reason,
                                                on_error))])

        tr = self.fabric.tracer
        if tr is not None:
            tr.compute_span(f"rank{self.rank} gpu", "combine_launch",
                            t0, t0 + KERNEL_LAUNCH_US * 0.5,
                            phase="moe.combine")
        self.fabric.loop.schedule(KERNEL_LAUNCH_US * 0.5, proxy_send)

        # source side: expect one write from each rank hosting my tokens
        my_dest = ctx["fe_s"] // cfg.e_local
        expect = int(np.unique(my_dest).size)

        def done() -> None:
            self.stats["combine_us"] = self.fabric.now - t0
            on_complete()

        self.engine.expect_imm_count(comb_imm, expect, done)

    def combine_result(self, ctx: Dict, gates: np.ndarray,
                       dtype=np.float32) -> np.ndarray:
        """Un-permute the combine buffer and reduce with gates (fp32):
        a route-derived segment reduction via ``kernels.host
        .moe_combine_host`` — O(top_k) vector ops, no per-token Python."""
        from ..kernels.host import moe_combine_host
        cfg = self.cfg
        tb = cfg.token_bytes
        T, R = ctx["T"], cfg.top_k
        # comb_buf rows are in (expert, own token order) — i.e. sorted
        # assignment order.  inv[t, k] = packed row of assignment (t, k).
        inv = np.empty(T * R, np.int64)
        inv[ctx["order"]] = np.arange(T * R)
        inv = inv.reshape(T, R)
        # accumulate experts in ascending order so fp32 summation order
        # matches the dense oracle bit-for-bit
        sort_k = np.argsort(ctx["eids"], axis=1, kind="stable")
        inv_sorted = np.take_along_axis(inv, sort_k, axis=1)
        eids_sorted = np.take_along_axis(ctx["eids"], sort_k, axis=1)
        gk = gates[np.arange(T)[:, None], eids_sorted].astype(np.float32)
        elems = tb // np.dtype(dtype).itemsize
        rows = self.comb_buf.view(dtype).reshape(-1, elems)[:T * R]
        return moe_combine_host(rows, inv_sorted, gk)
