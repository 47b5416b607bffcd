"""Disaggregated inference: KvCache transfer over the TransferEngine (§4).

Faithful implementation of the paper's Appendix A pseudocode, generalised
over :mod:`repro.kvlayout` so EVERY cache architecture serves — uniform k/v
stacks, gemma3-style local/global pattern splits, vlm cross layers,
SSM/hybrid state, and first-k-dense head layers:

  decoder:  compile the request's ``TransferPlan`` -> allocate canonical
            pool pages + a tail slot -> arm one ImmCounter expectation per
            schema component (plus the tail) -> SEND DispatchReq -> wait on
            the counters -> reassemble the cache from the plan -> decode.
  prefiller: recv loop -> on DispatchReq: run prefill, stage the whole
            cache pytree into pool slots (plan canonical order), increment
            a UvmWatcher after each model layer -> the watcher callback
            submits the completed layer span as ONE WrBatch
            (``TransferPlan.submit_span`` — one ``submit_scatters`` call
            covering every component's pages for that span, distinct imm
            per component) -> after the last layer, submit_single_write of
            the tail context (last-token logits) -> poll before freeing.

All layout decisions happen at plan-compile time (arXiv 2605.00686's
plan-ahead principle): the per-request hot path is one enqueue per layer
span regardless of schema complexity, asserted via
``TransferEngine.batch_stats`` in the tests.

Model compute is REAL (a reduced-config jax model); compute time is mapped
onto the virtual clock so the layer-by-layer transfer/compute overlap is
measurable.  A prefiller serves one request at a time (an occupied GPU):
requests queue behind ``_busy_until``, which is what makes queue depth and
TTFT meaningful autoscaling signals.

Elastic membership (§4 "dynamic scaling") runs through ``repro.ctrl``:
pass ``ctrl=`` and the peer JOINs the control plane at startup, publishing
its wire address, KV-pool ``MrDesc``, NIC kind, pool geometry AND its
``KvSchema`` — the Scheduler refuses to pair peers whose schemas differ at
routing time, never mid-transfer.
"""

from __future__ import annotations

from collections import deque
from dataclasses import dataclass
from typing import Any, Deque, Dict, List, Optional

import jax.numpy as jnp
import numpy as np

from ..core import Fabric, MrDesc, NetAddr
from ..ctrl import ControlClient, ControlPlane, CtrlRetryPolicy
from ..ctrl import messages as m
from ..kvlayout import (DECODE_MARGIN, KvSchema, TransferPlan, fill_cache,
                        schema_from_config, stage_cache)
from ..models import decode_step_jit, init_cache, prefill_jit
from ..models.moe import moe_path
from ..obs import host_count, host_span, traced_phase
from .kvpool import KvPool


@m.wire("DREQ")
@dataclass
class DispatchReq:
    input_ids: np.ndarray                 # (S,)
    decoder_addr: NetAddr
    imm: int                              # base immediate of the imm block
    kv_desc: MrDesc
    pages: List[int]                      # decoder pages, plan canonical order
    tail_desc: MrDesc
    tail_idx: int
    request_id: int
    vision_emb: Optional[np.ndarray] = None   # (Sv, Dv) for vlm archs
    # the decoder's KvSchema wire form: the prefiller validates it against
    # its own schema BEFORE any WRITE — the last line of defence for
    # hand-wired peers that bypass the Scheduler's routing-time gate
    schema: Optional[Dict[str, Any]] = None


def _geom_wire(cfg, schema: KvSchema) -> Dict[str, Any]:
    """JSON-safe pool geometry advertised in the ctrl JOIN."""
    return dict(n_layers=cfg.n_layers, page_tokens=schema.page_tokens,
                slot_bytes=schema.slot_bytes)


def _cached_plan(plans: Dict[int, TransferPlan], schema: KvSchema,
                 seq_len: int) -> TransferPlan:
    plan = plans.get(seq_len)
    if plan is None:
        plan = plans[seq_len] = TransferPlan(schema, seq_len)
    return plan


def _pool_pages(schema: KvSchema, max_seq_len: int, max_inflight: int) -> int:
    """KV pool pages that hold ``max_inflight`` concurrent handoffs of
    prompts up to ``max_seq_len`` tokens (the plan's slots per handoff)."""
    return TransferPlan(schema, max_seq_len).n_slots * max_inflight


def disagg_unsupported_reason(cfg) -> Optional[str]:
    """Why the §4 KvCache protocol cannot serve ``cfg`` (None = it can).

    Since ``repro.kvlayout`` every family the model stack produces has a
    transfer schema — uniform k/v, pattern-split (gemma3 local/global, vlm
    cross), SSM/hybrid state, and first-k-dense head layers all serve
    disaggregated.  The guard is retained as the single serving-stack
    capability probe (constructors raise on it, launchers print it) in
    case future families outrun the schema compiler.
    """
    try:
        schema_from_config(cfg)
    except Exception as e:  # pragma: no cover - no current family hits this
        return f"no KvSchema derivation for family '{cfg.family}': {e}"
    return None


def _check_supported(cfg) -> None:
    reason = disagg_unsupported_reason(cfg)
    if reason is not None:  # pragma: no cover - see above
        raise ValueError(
            f"disaggregated serving cannot handle '{cfg.name}': {reason}")


def _vision_batch(cfg, vision_emb) -> Optional[jnp.ndarray]:
    """Wire (Sv, Dv) embeddings -> (1, Sv, Dv); zeros when absent."""
    if cfg.family != "vlm":
        return None
    if vision_emb is None:
        return jnp.zeros((1, cfg.vision_seq, cfg.vision_dim), jnp.float32)
    return jnp.asarray(vision_emb, jnp.float32)[None]


class Prefiller:
    """Prefill node: owns model params and a KV pool as WRITE source.

    The pool holds ``max_inflight`` handoffs of prompts up to
    ``max_seq_len`` tokens, in pages of the model's ``KvSchema``."""

    def __init__(self, fabric: Fabric, node: str, cfg, params, *,
                 nic: str = "efa", page_tokens: int = 16,
                 max_seq_len: int = 64, max_inflight: int = 32,
                 layer_compute_us: float = 50.0,
                 ctrl: Optional[ControlPlane] = None,
                 peer_id: Optional[str] = None, renew_us: float = 500.0,
                 max_renewals: int = 256, host: Optional[str] = None,
                 ctrl_retry: Optional[CtrlRetryPolicy] = None):
        _check_supported(cfg)
        self.cfg = cfg
        self.params = params
        # host: physical machine identity — a prefiller and decoder placed
        # on the same host move KV pages over NVLink (per-pair resolution)
        self.engine = fabric.add_engine(node, nic=nic, host=host)
        self.fabric = fabric
        self.nic = nic
        self.schema = schema_from_config(cfg, page_tokens)
        n_pages = _pool_pages(self.schema, max_seq_len, max_inflight)
        self.pool = KvPool(self.engine, self.schema, n_pages)
        self._plans: Dict[int, TransferPlan] = {}   # seq_len -> compiled plan
        self.layer_compute_us = layer_compute_us
        self.stats: Dict[str, float] = {}
        # (rid, lo, hi, n_writes) per submitted span batch; bounded — only
        # tests read it, a long-lived peer must not accumulate per-request
        # tuples forever
        self.span_log: Deque[tuple] = deque(maxlen=256)
        self._cancelled: set = set()
        self.alive = True
        self.draining = False
        self.inflight = 0
        self.inflight_slots = 0   # KV pool slots staged for in-flight reqs
        self.served = 0
        self._busy_until = 0.0
        self.engine.submit_recvs(1 << 16, 8, self._on_msg)
        self.client: Optional[ControlClient] = None
        if ctrl is not None:
            self.client = ControlClient(
                self.engine, fabric, ctrl.address(),
                peer_id or node, "prefill", renew_us=renew_us,
                max_renewals=max_renewals,
                alive_fn=lambda: self.alive,
                # piggybacked load is POOL-SLOT pressure, not request count:
                # the scheduler's least-loaded policy compares it with its
                # own slot-weighted outstanding ledger (same units)
                inflight_fn=lambda: self.inflight_slots,
                free_pages_fn=lambda: len(self.pool._free),
                on_drain=self._on_drain, retry=ctrl_retry)
            self.client.join(nic=nic, kv_desc=self.pool.desc,
                             geom=_geom_wire(cfg, self.schema),
                             n_pages=n_pages, schema=self.schema.to_wire())

    def _plan(self, seq_len: int) -> TransferPlan:
        return _cached_plan(self._plans, self.schema, seq_len)

    def _fence_epoch(self) -> Optional[int]:
        """View epoch stamped onto outbound KV WRITEs (zombie guard).

        Read fresh at every span submission so a WRITE always carries the
        epoch its sender currently believes in — a zombie that kept the
        stale epoch of its lapsed lease is exactly what the receiving
        engine's fence rejects.  None (no ctrl attachment, or JOIN-ACK not
        yet received) posts unstamped, never-fenced WRITEs — pre-PR
        behaviour."""
        return self.client.epoch if self.client is not None else None

    def address(self) -> NetAddr:
        return self.engine.address(0)

    def cancel(self, request_id: int) -> None:
        self._cancelled.add(request_id)

    def crash(self) -> None:
        """Simulated process death: stop serving AND stop renewing the
        lease — the control plane notices via lease expiry, never via a
        goodbye message."""
        self.alive = False

    # -- control-plane hooks ------------------------------------------------
    def _on_drain(self, msg: m.Drain) -> None:
        self.draining = True
        self._maybe_finish_drain()

    def _maybe_finish_drain(self) -> None:
        if (self.draining and self.inflight == 0 and self.alive
                and self.client is not None and not self.client.left):
            # every in-flight request finished and freed its staging pages
            self.client.leave()

    # -- data plane ---------------------------------------------------------
    def _on_msg(self, payload: bytes) -> None:
        if not self.alive:
            return
        msg = m.decode(payload)
        if self.client is not None and self.client.handle(msg):
            return
        if isinstance(msg, DispatchReq):
            self._on_request(msg)

    def _on_request(self, req: DispatchReq) -> None:
        if req.request_id in self._cancelled:
            return
        if self.draining:
            # the scheduler never routes to a draining peer; anything that
            # races the drain is dropped (the sender re-routes on the next
            # view) rather than silently extending the drain
            self.stats["rejected"] = self.stats.get("rejected", 0) + 1
            return
        cfg = self.cfg
        if req.schema is not None:
            reason = self.schema.mismatch(KvSchema.from_wire(req.schema))
            if reason is not None:
                raise ValueError(
                    f"DispatchReq {req.request_id}: decoder KvSchema "
                    f"incompatible with this prefiller: {reason}")
        S = len(req.input_ids)
        plan = self._plan(S)
        t_start = self.fabric.now
        self.inflight += 1
        self.inflight_slots += plan.n_slots
        self.served += 1

        # One request occupies the GPU at a time: queue behind _busy_until.
        start = max(t_start, self._busy_until)
        self._busy_until = start + cfg.n_layers * self.layer_compute_us
        delay0 = start - t_start
        tr = self.fabric.tracer
        if tr is not None:
            tr.compute_span(f"{self.engine.node} gpu",
                            f"prefill:req{req.request_id}",
                            start, self._busy_until, phase="serving.prefill")
        with host_span(self.fabric, "prefiller.request", rid=req.request_id,
                       seq=S, queued_us=delay0):
            self._serve(req, plan, delay0)

    def _serve(self, req: DispatchReq, plan: TransferPlan,
               delay0: float) -> None:
        """Prefill an admitted request, stage its cache and arm the layer
        spans' WRITEs, ``delay0`` virtual µs after now."""
        cfg = self.cfg
        # REAL prefill compute (all layers at once — jax scan); both ends
        # derive cache geometry from plan.max_len so ring slot assignment
        # and padding agree bit-for-bit.
        with host_span(self.fabric, "prefiller.prefill",
                       rid=req.request_id, seq=len(req.input_ids)):
            tokens = jnp.asarray(req.input_ids, jnp.int32)[None]
            logits, cache = prefill_jit(
                self.params, tokens, cfg, max_len=plan.max_len,
                moe_mode="dense",
                vision_emb=_vision_batch(cfg, req.vision_emb))
        logits = logits[..., :cfg.vocab]   # drop vocab padding

        # stage EVERY schema component into pool slots, canonical order
        local_pages = self.pool.alloc(plan.n_slots)
        with host_span(self.fabric, "prefiller.stage", rid=req.request_id):
            stage_cache(plan, self.pool, local_pages, cache)
            host_count(self.fabric, "kv.staged_bytes", plan.write_bytes)

        # tail context: last-token logits
        tail = np.asarray(logits, np.float32).reshape(-1).view(np.uint8)
        tail_buf = np.zeros(tail.size, np.uint8)
        tail_buf[:] = tail
        tail_handle, _ = self.engine.reg_mr(tail_buf)

        cnt = {"done": 0}
        failed = {"sent": False}
        total_writes = plan.total_writes + 1

        def on_xfer_error(reason: str) -> None:
            # a KV WRITE exhausted its retry budget: abandon THIS attempt
            # (no further spans, pages freed by the poll loop) and surface
            # a structured failure to the decoder, which forwards it to
            # the scheduler for a re-route.  First failure wins — sibling
            # component groups failing later are folded into it; a
            # cancelled attempt stays silent (its decoder-side state is
            # gone, so a late XferFail could only mis-target a re-route).
            # The prefiller doesn't know its attempt number (DispatchReq
            # stays attempt-free so fault-free wire bytes match pre-fault
            # builds bit-exactly) — it sends -1 and the decoder stamps the
            # authoritative attempt from its pending state.
            if (failed["sent"] or not self.alive
                    or req.request_id in self._cancelled):
                return
            failed["sent"] = True
            self.stats["xfer_failures"] = \
                self.stats.get("xfer_failures", 0) + 1
            tr = self.fabric.tracer
            if tr is not None:
                tr.instant("serving", f"xfer_fail:req{req.request_id}",
                           {"reason": reason})
            peer = self.client.peer_id if self.client else self.engine.node
            self.engine.submit_send(req.decoder_addr, m.encode(m.XferFail(
                request_id=req.request_id, attempt=-1,
                peer_id=peer, reason=reason)))

        def send_layers(lo: int, hi: int) -> None:
            # Model layers [lo, hi) completed since the last poll land as
            # ONE batched submission: every component page the span unlocks
            # rides a single WrBatch, distinct imm per component.  The UVM
            # poller coalesces increments, so coalesced layers share it too.
            if (not self.alive or req.request_id in self._cancelled
                    or failed["sent"] or hi <= lo):
                return
            with traced_phase(self.fabric, "serving.kv_span"):
                n = plan.submit_span(
                    self.engine, self.pool.handle, local_pages,
                    req.kv_desc, req.pages, req.imm, lo, hi,
                    on_sent=lambda n: cnt.__setitem__("done", cnt["done"] + n),
                    on_error=on_xfer_error,
                    fence_epoch=self._fence_epoch())
            if n:
                self.span_log.append((req.request_id, lo, hi, n))

        # UvmWatcher: the "GPU" increments after each layer's output is
        # ready; the watcher callback sends the completed span (App. A).
        watcher = self.engine.alloc_uvm_watcher(send_layers)
        for l in range(cfg.n_layers):
            self.fabric.loop.schedule(delay0 + (l + 1) * self.layer_compute_us,
                                      lambda l=l: watcher.store(l + 1))

        def send_tail() -> None:
            if (not self.alive or req.request_id in self._cancelled
                    or failed["sent"]):
                return
            with traced_phase(self.fabric, "serving.tail"):
                self.engine.submit_single_write(
                    tail.size, req.imm + plan.n_imms, (tail_handle, 0),
                    (req.tail_desc, req.tail_idx * tail.size),
                    on_done=lambda: cnt.__setitem__("done", cnt["done"] + 1),
                    on_error=on_xfer_error,
                    fence_epoch=self._fence_epoch())

        self.fabric.loop.schedule(
            delay0 + cfg.n_layers * self.layer_compute_us + 1.0, send_tail)

        def poll_free() -> None:
            if not self.alive:
                return        # crashed: the node (and its pool) is gone
            if req.request_id in self._cancelled or failed["sent"]:
                self.pool.free(local_pages)
                self.inflight -= 1
                self.inflight_slots -= plan.n_slots
                self._maybe_finish_drain()
                return
            if cnt["done"] >= total_writes:
                self.pool.free(local_pages)
                self.inflight -= 1
                self.inflight_slots -= plan.n_slots
                self._maybe_finish_drain()
            else:
                self.fabric.loop.schedule(5.0, poll_free)

        self.fabric.loop.schedule(
            delay0 + cfg.n_layers * self.layer_compute_us, poll_free)


class Decoder:
    """Decode node: pre-allocates pages, dispatches, decodes on completion.

    With ``ctrl=`` the decoder also serves the elastic wire path: the
    scheduler SENDs ``SubmitReq``s here, completion is reported back via
    ``ReqDone``, and ``CancelReq`` (failover) frees the attempt's pages and
    tail slot so nothing leaks when a prefiller dies mid-transfer.  Pool
    and tail slots are sized as the Prefiller's: ``max_inflight`` handoffs
    of prompts up to ``max_seq_len`` tokens.
    """

    def __init__(self, fabric: Fabric, node: str, cfg, params, *,
                 nic: str = "efa", page_tokens: int = 16,
                 max_seq_len: int = 64, max_inflight: int = 32,
                 ctrl: Optional[ControlPlane] = None,
                 peer_id: Optional[str] = None, renew_us: float = 500.0,
                 max_renewals: int = 256, host: Optional[str] = None,
                 ctrl_retry: Optional[CtrlRetryPolicy] = None):
        _check_supported(cfg)
        self.cfg = cfg
        self.params = params
        self.fabric = fabric
        # host: physical machine identity (NVLink domain) — see Prefiller
        self.engine = fabric.add_engine(node, nic=nic, host=host)
        self.schema = schema_from_config(cfg, page_tokens)
        n_pages = _pool_pages(self.schema, max_seq_len, max_inflight)
        self.pool = KvPool(self.engine, self.schema, n_pages)
        self._plans: Dict[int, TransferPlan] = {}
        tail_bytes = cfg.vocab * 4
        # one tail (last-token logits) slot per in-flight handoff
        self.tail_buf = np.zeros(max_inflight * tail_bytes, np.uint8)
        self.tail_handle, self.tail_desc = self.engine.reg_mr(self.tail_buf)
        self._tail_free = list(range(max_inflight))
        self._imm_next = 1
        self.alive = True
        self.draining = False
        self.results: Dict[int, Dict] = {}
        self._pending: Dict[int, Dict] = {}   # rid -> in-flight attempt state
        self._attempt: Dict[int, int] = {}    # rid -> newest attempt seen
        # (rid, attempt, reason) per XferFail accepted — fault forensics
        self.xfer_failed: List[tuple] = []
        # rid -> (attempt, reply_to, peer_id, reason): the last XferFail
        # forwarded to the scheduler, kept for replay when a retransmitted
        # SUBMIT shows the scheduler never saw it
        self._xfail_sent: Dict[int, tuple] = {}
        self.replayed_dones = 0               # ReqDone replays (lost-ack path)
        self.engine.submit_recvs(1 << 16, 32, self._on_msg)
        self.client: Optional[ControlClient] = None
        if ctrl is not None:
            self.client = ControlClient(
                self.engine, fabric, ctrl.address(),
                peer_id or node, "decode", renew_us=renew_us,
                max_renewals=max_renewals,
                alive_fn=lambda: self.alive,
                inflight_fn=lambda: sum(st["plan"].n_slots
                                        for st in self._pending.values()),
                free_pages_fn=lambda: len(self.pool._free),
                on_drain=self._on_drain, retry=ctrl_retry)
            self.client.join(nic=nic, kv_desc=self.pool.desc,
                             geom=_geom_wire(cfg, self.schema),
                             n_pages=n_pages, schema=self.schema.to_wire())

    def _plan(self, seq_len: int) -> TransferPlan:
        return _cached_plan(self._plans, self.schema, seq_len)

    def address(self) -> NetAddr:
        return self.engine.address(0)

    def crash(self) -> None:
        """Simulated process death (mirror of :meth:`Prefiller.crash`):
        stop decoding and stop renewing the lease — peers learn via lease
        expiry, never via a goodbye message.  KV WRITEs already in flight
        still land in this pool's memory (the NIC outlives the process in
        the model), but no completion callback runs."""
        self.alive = False

    # -- control-plane hooks ------------------------------------------------
    def _on_drain(self, msg: m.Drain) -> None:
        self.draining = True
        self._maybe_finish_drain()

    def _maybe_finish_drain(self) -> None:
        if (self.draining and not self._pending and self.alive
                and self.client is not None and not self.client.left):
            self.client.leave()

    # -- wire path ----------------------------------------------------------
    def _on_msg(self, payload: bytes) -> None:
        if not self.alive:
            return
        msg = m.decode(payload)
        if self.client is not None and self.client.handle(msg):
            return
        if isinstance(msg, m.SubmitReq):
            if self.draining:
                # racing a drain: drop — once this decoder LEAVEs, the
                # scheduler re-routes every request still pointed at it
                return
            cur = self._attempt.get(msg.request_id, -1)
            if msg.attempt < cur:
                return      # stale duplicate of an attempt we've superseded
            if msg.attempt == cur:
                # retransmission of the attempt we're already serving: the
                # scheduler didn't see our reply — replay it (lost-ack
                # recovery), or stay silent while the attempt is in flight
                self._replay_reply(msg)
                return
            if msg.request_id in self._pending:
                self.cancel(msg.request_id)   # superseded by a re-route
            self._attempt[msg.request_id] = msg.attempt
            self.submit(msg.request_id, msg.input_ids, msg.prefiller,
                        n_decode=msg.n_decode, reply_to=msg.reply_to,
                        attempt=msg.attempt, vision_emb=msg.vision_emb)
        elif isinstance(msg, m.CancelReq):
            # fence first, unconditionally: even a CANCEL stale by attempt
            # number carries a valid zombie-writer fence (fences only
            # tighten, so installing twice or out of order is harmless)
            if msg.fence_node is not None and msg.fence_epoch is not None:
                self.engine.set_fence(msg.fence_node, msg.fence_epoch)
            # only the newest attempt may be cancelled; an unordered SEND
            # can deliver a stale CANCEL after its re-route's SUBMIT
            if msg.attempt == self._attempt.get(msg.request_id):
                self.cancel(msg.request_id)
        elif isinstance(msg, m.XferFail):
            # prefiller reports a mid-transfer retry exhaustion: free this
            # attempt's pages + imm expectations and escalate to the
            # scheduler for a re-route.  ``_pending`` presence is the
            # staleness guard — each attempt's prefiller sends at most one
            # XferFail (and none once cancelled), and the re-route that
            # would supersede this attempt is only triggered *by* this
            # message passing through here, so a pending entry always
            # belongs to the reporting prefiller's attempt.  The decoder
            # stamps the authoritative attempt number before forwarding
            # (the prefiller sent -1; DispatchReq carries no attempt so
            # fault-free wire bytes stay bit-identical).
            st = self._pending.get(msg.request_id)
            if st is None:
                return      # attempt already cancelled / completed
            attempt = st["attempt"]
            self.xfer_failed.append(
                (msg.request_id, attempt, msg.reason))
            self.cancel(msg.request_id)
            if st["reply_to"] is not None:
                self._xfail_sent[msg.request_id] = (
                    attempt, st["reply_to"], msg.peer_id, msg.reason)
                self.engine.submit_send(st["reply_to"], m.encode(m.XferFail(
                    request_id=msg.request_id, attempt=attempt,
                    peer_id=msg.peer_id, reason=msg.reason)))

    def _replay_reply(self, msg: m.SubmitReq) -> None:
        """Lost-ack recovery: the scheduler retransmitted a SUBMIT for the
        attempt we already know about, meaning our terminal reply (REQ-DONE
        or forwarded XFER-FAIL) may have been lost — re-send it.  While the
        attempt is still in flight the retransmission is a pure duplicate
        and is dropped (the reply will go out once, when it completes)."""
        r = self.results.get(msg.request_id)
        if r is not None and "tokens" in r and r.get("_attempt") == msg.attempt \
                and r.get("_reply_to") is not None:
            self.replayed_dones += 1
            peer = self.client.peer_id if self.client else ""
            self.engine.submit_send(r["_reply_to"], m.encode(m.ReqDone(
                request_id=msg.request_id, attempt=r["_attempt"],
                peer_id=peer, ttft_us=r["ttft_us"],
                tokens=list(r["tokens"]))))
            return
        xf = self._xfail_sent.get(msg.request_id)
        if xf is not None and xf[0] == msg.attempt:
            attempt, reply_to, peer_id, reason = xf
            self.engine.submit_send(reply_to, m.encode(m.XferFail(
                request_id=msg.request_id, attempt=attempt,
                peer_id=peer_id, reason=reason)))

    def cancel(self, request_id: int) -> bool:
        """Abandon an in-flight attempt: free pages + tail slot, drop every
        component's ImmCounter expectation.  Nothing leaks — failover
        re-allocates."""
        st = self._pending.pop(request_id, None)
        if st is None:
            return False
        for off in range(st["n_imms"] + 1):   # components + tail
            self.engine.counters[0].reset(st["imm"] + off)
        self.pool.free(st["pages"])
        self._tail_free.append(st["tail_idx"])
        self.results.pop(request_id, None)
        self._maybe_finish_drain()
        return True

    # ------------------------------------------------------------------
    def submit(self, request_id: int, input_ids: np.ndarray,
               prefiller: NetAddr, n_decode: int = 4, *,
               reply_to: Optional[NetAddr] = None, attempt: int = 0,
               vision_emb: Optional[np.ndarray] = None) -> None:
        if n_decode > DECODE_MARGIN:
            # the handoff cache holds seq_len + DECODE_MARGIN positions;
            # decoding past it would silently drop cache updates (jax
            # clips out-of-bounds .at[] writes) and diverge from monolithic
            raise ValueError(
                f"n_decode={n_decode} exceeds the handoff cache headroom "
                f"(DECODE_MARGIN={DECODE_MARGIN})")
        S = len(input_ids)
        plan = self._plan(S)
        pages = self.pool.alloc(plan.n_slots)
        tail_idx = self._tail_free.pop(0)
        # one immediate per schema component plus the tail write
        imm = self._imm_next
        self._imm_next += plan.n_imms + 1
        t0 = self.fabric.now
        self._pending[request_id] = {
            "pages": pages, "tail_idx": tail_idx, "imm": imm,
            "n_imms": plan.n_imms, "plan": plan,
            "attempt": attempt, "reply_to": reply_to, "seq_len": S,
        }

        tr = self.fabric.tracer
        if tr is not None:
            tr.instant("serving", f"submit:req{request_id}",
                       {"seq_len": S, "attempt": attempt})
        req = DispatchReq(input_ids=np.asarray(input_ids),
                          decoder_addr=self.address(),
                          imm=imm, kv_desc=self.pool.desc, pages=pages,
                          tail_desc=self.tail_desc, tail_idx=tail_idx,
                          request_id=request_id, vision_emb=vision_emb,
                          schema=self.schema.to_wire())

        expectations = plan.expected_counts() + [(plan.n_imms, 1)]  # + tail
        remaining = {"n": len(expectations)}

        def part_done() -> None:
            if not self.alive:
                return      # crashed mid-handoff: never decode as a zombie
            st = self._pending.get(request_id)
            if st is None or st["imm"] != imm:
                return      # attempt was cancelled / superseded
            remaining["n"] -= 1
            if remaining["n"]:
                return
            self.results[request_id] = {
                "ttft_us": self.fabric.now - t0,
                "pages": pages, "tail_idx": tail_idx, "seq_len": S,
                "plan": plan,
            }
            if tr is not None:
                tr.instant("serving", f"kv_ready:req{request_id}",
                           {"ttft_us": self.fabric.now - t0})
            self._decode(request_id, n_decode)

        for off, count in expectations:
            self.engine.expect_imm_count(imm + off, count, part_done)
        self.engine.submit_send(prefiller, m.encode(req))

    def _assemble_cache(self, request_id: int):
        r = self.results[request_id]
        plan: TransferPlan = r["plan"]
        with host_span(self.fabric, "decoder.fill", rid=request_id):
            cache = init_cache(self.cfg, 1, plan.max_len)
            for name, arr in fill_cache(plan, self.pool, r["pages"],
                                        cache).items():
                cache[name] = jnp.asarray(arr, cache[name].dtype)
            host_count(self.fabric, "kv.filled_bytes", plan.write_bytes)
        return cache

    def _decode(self, request_id: int, n_decode: int) -> None:
        cfg = self.cfg
        fab = self.fabric
        r = self.results[request_id]
        with host_span(fab, "decoder.request", rid=request_id,
                       steps=n_decode - 1, ttft_us=r["ttft_us"]):
            tail_bytes = cfg.vocab * 4
            logits = (self.tail_buf[r["tail_idx"] * tail_bytes:
                                    (r["tail_idx"] + 1) * tail_bytes]
                      .view(np.float32).reshape(1, cfg.vocab))
            cache = self._assemble_cache(request_id)
            toks = [int(np.argmax(logits[0]))]
            pos = r["seq_len"]
            for _ in range(n_decode - 1):
                with host_span(fab, "decoder.step", rid=request_id, pos=pos):
                    lg, cache = decode_step_jit(
                        self.params, jnp.asarray([[toks[-1]]]),
                        jnp.asarray([pos], jnp.int32), cache, cfg,
                        moe_mode="dense")
                with host_span(fab, "decoder.sample", rid=request_id):
                    toks.append(int(jnp.argmax(lg[0])))
                pos += 1
            r["tokens"] = toks
            host_count(fab, "decoder.routed_steps",
                       n_decode - 1 if moe_path(1, cfg) == "routed" else 0)
            self.pool.free(r["pages"])
            self._tail_free.append(r["tail_idx"])
            st = self._pending.pop(request_id, None)
            if st is not None and st["reply_to"] is not None:
                # stash the reply identity so a retransmitted SUBMIT for
                # this attempt can replay the REQ-DONE (lost-ack recovery)
                r["_reply_to"] = st["reply_to"]
                r["_attempt"] = st["attempt"]
                peer = self.client.peer_id if self.client else ""
                self.engine.submit_send(st["reply_to"], m.encode(m.ReqDone(
                    request_id=request_id, attempt=st["attempt"],
                    peer_id=peer, ttft_us=r["ttft_us"], tokens=list(toks))))
            self._maybe_finish_drain()
