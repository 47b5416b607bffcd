"""TransferEngine: the Fig. 2 API over the simulated fabric.

One ``TransferEngine`` per node manages a ``DomainGroup`` per GPU (worker
threads in the paper; event-loop continuations here).  A ``Fabric`` owns the
event loop and routes descriptors between engines.

Faithfulness notes:
* There are NO ordering guarantees across any operations — all completion
  notification goes through the ImmCounter or sender-side callbacks.
* ``submit_send`` copies the payload at submission (caller may reuse the
  buffer immediately); one-sided WRITEs are zero-copy in the paper — the
  simulator takes ONE snapshot at submission (modeling the "don't touch src
  until completion" contract); all NIC striping and MTU chunking slice that
  snapshot as zero-copy memoryviews.
* WRITE submissions are batched: every ``submit_*`` templates its work
  requests into a ``WrBatch`` posted in a single event-loop entry (one
  ``ENQUEUE_US`` per submission, per-WR ``post_us`` on the worker — §3.4).
* SEND/RECV uses only the first NIC of a group (paper §3.3).
* Large single WRITEs are striped across all NICs; paged writes, scatter and
  barrier rotate across NICs (paper §3.4 "Sharding inside a DOMAINGROUP").
"""

from __future__ import annotations

import itertools
from dataclasses import dataclass
from typing import Callable, Dict, List, Optional, Sequence, Tuple, Union

import numpy as np

from .domain import (DomainGroup, MemoryRegion, MrDesc, MrHandle, NetAddr,
                     Pages, PayloadDst, ScatterDst, WrBatch)
from .faults import BackpressureError, TransferError
from .imm_counter import ImmCounter
from .netsim import (ENQUEUE_US, EventLoop, NicSpec, CX7, EFA_100, EFA_200,
                     degrade, stable_hash)
from .topology import ChannelPlan, TopoEntry, Topology, cross_spec
from .transport import WireOp
from .uvm import UvmWatcher

# Extra per-WR posting overhead on the scatter/barrier path (WR templating
# still leaves per-peer descriptor setup; calibrated to Table 9).
SCATTER_EXTRA_US = {"cx7": 0.045, "efa": 0.0, "efa4": 0.0, "nvlink": 0.02}

NIC_PRESETS: Dict[str, Tuple[NicSpec, int]] = {
    # name -> (per-NIC spec, NICs per GPU)
    "cx7": (CX7, 1),          # H100 + 1 x 400 Gbps ConnectX-7
    "efa": (EFA_200, 2),      # H200 + 2 x 200 Gbps EFA (p5en)
    "efa4": (EFA_100, 4),     # H100 + 4 x 100 Gbps EFA (p5)
}


class Flag:
    """Atomic-flag completion target (paper: ``OnDone::Flag``)."""

    def __init__(self) -> None:
        self._set = False

    def set(self) -> None:
        """Mark the flag (fired by the transport on completion)."""
        self._set = True

    def is_set(self) -> bool:
        """True once the associated operation completed."""
        return self._set


OnDone = Union[Callable[[], None], Flag, None]


def _fire(done: OnDone) -> None:
    if done is None:
        return
    if isinstance(done, Flag):
        done.set()
    else:
        done()


class BatchStats:
    """Per-engine submission-batching counters (ROADMAP: WRs/enqueue for the
    ablation bench).  One ``record`` per event-loop enqueue; derived ratios
    say how well WR templating amortises the app->worker handoff.

    ``wrs_by_dst`` tracks posted WRs per destination DomainGroup address —
    the accounting behind per-peer WR-budget assertions (the moekit decode
    fast path's "at most 2 data WRITEs per peer per round" invariant is
    tested as deltas of this map)."""

    __slots__ = ("batches", "wrs", "nbytes", "wrs_by_dst")

    def __init__(self) -> None:
        self.batches = 0
        self.wrs = 0
        self.nbytes = 0
        self.wrs_by_dst: Dict = {}

    def record(self, batch: WrBatch) -> None:
        """Account one enqueued WrBatch (called per event-loop handoff)."""
        self.batches += 1
        self.wrs += len(batch)
        self.nbytes += batch.nbytes
        per = self.wrs_by_dst
        for _op, dst_group, _nic, _extra in batch.wrs:
            addr = dst_group.addr
            per[addr] = per.get(addr, 0) + 1

    def snapshot_by_dst(self) -> Dict:
        """Copy of the per-destination WR counts (diff two snapshots to get
        per-peer WRs over a protocol phase)."""
        return dict(self.wrs_by_dst)

    @property
    def wrs_per_enqueue(self) -> float:
        """Mean WRs amortised per app->worker handoff (templating win)."""
        return self.wrs / self.batches if self.batches else 0.0

    @property
    def bytes_per_batch(self) -> float:
        """Mean payload bytes per enqueued batch."""
        return self.nbytes / self.batches if self.batches else 0.0

    def as_dict(self) -> Dict[str, float]:
        """All counters + derived ratios as a flat dict (bench rows)."""
        return {"batches": self.batches, "wrs": self.wrs,
                "nbytes": self.nbytes,
                "wrs_per_enqueue": self.wrs_per_enqueue,
                "bytes_per_batch": self.bytes_per_batch}


class BatchState:
    """Sender-side completion state shared by every logical write of one
    batched submission (replaces the per-op ``{"sent": n}`` dict closures):
    fires ``on_done`` exactly once, when all logical writes report sent.

    ``on_error`` is the terminal failure path (retry exhaustion or peer
    death under a :class:`~repro.core.faults.FaultPlan`): the FIRST failed
    logical write fires it once with a reason string, ``on_done`` is
    permanently suppressed, and with no handler installed a
    :class:`TransferError` propagates out of ``Fabric.run()`` — loud,
    never a silent hang."""

    __slots__ = ("remaining", "on_done", "on_error", "failed")

    def __init__(self, n_logical: int, on_done: OnDone,
                 on_error: Optional[Callable[[str], None]] = None):
        self.remaining = n_logical
        self.on_done = on_done
        self.on_error = on_error
        self.failed = False

    def note_sent(self) -> None:
        """One logical write finished sending; fires ``on_done`` at zero."""
        self.remaining -= 1
        if self.remaining == 0 and not self.failed:
            _fire(self.on_done)

    def note_error(self, reason: str) -> None:
        """One logical write failed terminally; first failure wins."""
        if self.failed:
            return
        self.failed = True
        if self.on_error is not None:
            self.on_error(reason)
        else:
            raise TransferError(reason)


class WriteState:
    """Completion state for ONE logical WRITE (possibly striped over NICs).

    The receiver-side immediate fires exactly once, when the last stripe's
    payload is fully visible; the sender side notifies the owning
    ``BatchState`` once all stripes have local completions.  A stripe that
    exhausts its retry budget marks the whole logical write ``failed`` —
    late deliveries of sibling stripes are then ignored (the immediate
    never fires for a failed write) and the batch takes its error path."""

    __slots__ = ("n_parts", "delivered", "sent", "imm", "counter", "batch",
                 "fabric", "failed")

    def on_fenced(self, op, now: float) -> None:
        """Epoch-fence rejection (zombie-writer guard): the receiving
        engine's fence table holds a higher epoch than this WRITE's stamp —
        the bytes were not written and the immediate must never fire.
        Surfaces through the standard terminal ``on_error`` path (first
        failure wins) after feeding the observability loop: a ``fenced``
        fault count, a tracer/recorder instant, and a rate-limited flight
        dump carrying the fenced WR and its stale epoch."""
        if self.failed:
            return
        fence = op.fences.get(op.src_node)
        reason = (f"fenced: WRITE from {op.src_node} carries view epoch "
                  f"{op.fence_epoch} below fence {fence}")
        fab = self.fabric
        if fab is not None:
            mon = fab.health
            if mon is not None:
                mon.on_fault("fenced")
            args = {"src": op.src_node, "imm": op.imm, "nbytes": op.nbytes,
                    "epoch": op.fence_epoch, "fence": fence}
            tr = fab.tracer
            if tr is not None:
                tr.instant("fault", f"fenced:{op.src_node}", args)
            rec = getattr(fab, "recorder", None)
            if rec is not None:
                if tr is None:
                    rec.note("fault", f"fenced:{op.src_node}", args)
                rec.dump("fence-rejected")
        self.on_error(op, reason)

    def __init__(self, n_parts: int, imm: Optional[int],
                 counter: Optional[ImmCounter], batch: BatchState,
                 fabric: Optional["Fabric"] = None):
        self.n_parts = n_parts
        self.delivered = 0
        self.sent = 0
        self.imm = imm
        self.counter = counter
        self.batch = batch
        self.fabric = fabric
        self.failed = False

    def on_delivered(self, op, now: float) -> None:
        """Receiver-side stripe landing; fires the immediate on the last."""
        if self.failed:
            return
        fab = self.fabric
        if fab is not None and fab.health is not None and op.span is not None:
            fab.health.on_deliver(op.span)
        self.delivered += 1
        if self.delivered == self.n_parts:
            if fab is not None:
                fab.inflight_writes -= 1
            if self.imm is not None:
                self.counter.increment(self.imm, now)

    def on_sent(self, now: float) -> None:
        """Sender-side stripe completion; notifies the batch on the last."""
        if self.failed:
            return
        self.sent += 1
        if self.sent == self.n_parts:
            self.batch.note_sent()

    def on_error(self, op, reason: str) -> None:
        """Terminal stripe failure (from the FaultPlan): fail the logical
        write once — release the in-flight accounting, never fire the
        immediate, and surface the error through the batch."""
        if self.failed:
            return
        self.failed = True
        if self.fabric is not None:
            self.fabric.inflight_writes -= 1
        self.batch.note_error(reason)


class TransferEngine:
    """The paper's Fig. 2 uniform transfer API for one node's GPUs.

    One engine per (simulated) process: it owns a :class:`DomainGroup` per
    device, the per-device :class:`ImmCounter`s, and the two-sided SEND/
    RECV pools.  ``host`` names the physical machine the engine runs on —
    engines sharing a host reach each other over NVLink (when ``nvlink``)
    regardless of NIC kind; it defaults to ``node``, so a single-engine-
    per-name fabric behaves exactly as before the heterogeneous-fabric
    refactor."""

    def __init__(self, fabric: "Fabric", node: str, nic: str, num_devices: int,
                 host: Optional[str] = None, nvlink: bool = True):
        self.fabric = fabric
        self.loop = fabric.loop
        self.node = node
        self.host = host if host is not None else node
        self.nvlink = nvlink
        spec, default_n = NIC_PRESETS[nic]
        self.nic_name = nic
        self.nic_spec = spec
        self.groups: Dict[int, DomainGroup] = {}
        self.counters: Dict[int, ImmCounter] = {}
        self._recv_pools: Dict[int, List] = {}
        self._pending_sends: Dict[int, List] = {}
        # RNR backpressure bound: a NIC RNR-retries only so long before the
        # QP errors out — cap the parked-send queue per device and surface a
        # structured BackpressureError (via on_backpressure when set, else
        # raised) instead of growing without bound
        self.max_pending_sends = 256
        self.on_backpressure: Optional[Callable[[BackpressureError], None]] = None
        self.dropped_sends = 0
        # device -> (WrBatch, created_at): SENDs submitted in the same loop
        # entry coalesce into one enqueue (flushed ENQUEUE_US later)
        self._send_batches: Dict[int, Tuple[WrBatch, float]] = {}
        # epoch fences (repro.ctrl zombie-writer guard): src node -> minimum
        # acceptable view epoch.  Inbound WRITEs stamped with a lower epoch
        # are rejected at landing; empty table = no checks anywhere.
        self.fences: Dict[str, int] = {}
        self.batch_stats = BatchStats()
        for dev in range(num_devices):
            addr = NetAddr(node, dev)
            seed = fabric.seed ^ (stable_hash(addr) & 0xFFFF)
            self.groups[dev] = DomainGroup(self.loop, addr, [spec] * default_n,
                                           seed, topology=fabric.topology)
            self.counters[dev] = ImmCounter()
            fabric._register_group(addr, self.groups[dev], self)

    # -- identity ---------------------------------------------------------
    def main_address(self) -> NetAddr:
        """The engine's device-0 address (control-plane endpoint)."""
        return NetAddr(self.node, 0)

    def address(self, device: int = 0) -> NetAddr:
        """The :class:`NetAddr` of one of this engine's devices."""
        return NetAddr(self.node, device)

    # -- epoch fencing ------------------------------------------------------
    def set_fence(self, src_node: str, min_epoch: int) -> None:
        """Reject future WRITE landings from ``src_node`` stamped with a
        view epoch below ``min_epoch`` (the zombie-writer guard — installed
        when the ctrl plane evicts a peer whose pages are being
        reallocated).  Fences only tighten: a lower ``min_epoch`` than the
        current fence is ignored, so a delayed duplicate CANCEL can never
        loosen the guard."""
        cur = self.fences.get(src_node)
        if cur is None or min_epoch > cur:
            self.fences[src_node] = int(min_epoch)

    # -- memory region management ------------------------------------------
    def reg_mr(self, buf: np.ndarray, device: int = 0) -> Tuple[MrHandle, MrDesc]:
        """Register a flat uint8 buffer; returns (local handle, peer desc)."""
        return self.groups[device].register(buf, device)

    def region_of(self, handle: MrHandle) -> MemoryRegion:
        """The backing :class:`MemoryRegion` for a local handle."""
        return self.fabric.group(handle.owner).region(handle.region_id)

    # -- two-sided SEND/RECV ------------------------------------------------
    def submit_recvs(self, length: int, count: int,
                     cb: Callable[[bytes], None], device: int = 0) -> None:
        """Post ``count`` RECV buffers of ``length`` bytes; ``cb`` gets each
        arriving payload and the buffer is auto re-posted (paper §3.3)."""
        pool = self._recv_pools.setdefault(device, [])
        for _ in range(count):
            pool.append((length, cb))
        # Drain sends that arrived before receives were posted (the fabric
        # queues them, as a NIC would RNR-retry).
        addr = self.address(device)
        pending = self._pending_sends.pop(device, [])
        for payload in pending:
            self._deliver_send(device, payload)

    def _deliver_send(self, device: int, payload: bytes) -> None:
        pool = self._recv_pools.get(device, [])
        if not pool:
            # RNR path: park the payload until a RECV is posted — bounded.
            # At the cap the SEND is dropped (accounting already settled by
            # the caller) and the backpressure error is surfaced.
            pending = self._pending_sends.setdefault(device, [])
            if len(pending) >= self.max_pending_sends:
                self.dropped_sends += 1
                err = BackpressureError(self.node, device, len(pending))
                if self.on_backpressure is not None:
                    self.on_backpressure(err)
                    return
                raise err
            pending.append(payload)
            return
        length, cb = pool.pop(0)
        if len(payload) > length:
            raise ValueError(f"SEND of {len(payload)} bytes exceeds posted RECV of {length}")
        cb(payload)
        # Buffer is automatically re-posted after the callback (paper §3.3).
        pool.append((length, cb))

    def submit_send(self, addr: NetAddr, msg: bytes,
                    cb: OnDone = None, device: int = 0) -> None:
        """RPC-style two-sided send; copies ``msg`` at submission.

        SENDs ride a :class:`WrBatch` (§3.4): every send submitted in the
        same event-loop entry joins the pending batch and the whole train is
        posted by ONE flush ``ENQUEUE_US`` later — control-plane bursts
        (view broadcasts, lease sweeps) pay one app->worker handoff instead
        of one per message.  Submission order is preserved; per-WR posting
        cost on the worker is unchanged.
        """
        payload = bytes(msg)
        src = self.groups[device]
        fab = self.fabric
        dst_group, dst_engine = fab._lookup(addr)
        fab.inflight_sends += 1

        def on_delivered(op: WireOp, now: float) -> None:
            fab.inflight_sends -= 1
            if fab.health is not None and op.span is not None:
                fab.health.on_deliver(op.span)
            dst_engine._deliver_send(addr.dev, payload)

        op = WireOp(kind="send", payload=None, dst_region=None, dst_offset=0,
                    imm=None, on_delivered=on_delivered,
                    on_sent=(lambda now: _fire(cb)) if cb is not None else None,
                    nbytes=len(payload))
        tr = fab.tracer
        mon = fab.health
        if tr is not None:
            op.span = tr.begin_wr("send", addr, len(payload), None,
                                  src=str(src.addr))
        elif mon is not None:
            op.span = mon.begin_wr("send", addr, len(payload), None,
                                   src=str(src.addr))
        pending = self._send_batches.get(device)
        if pending is not None and pending[1] == self.loop.now:
            # SEND/RECV uses only the first NIC in the group.
            pending[0].add(op, dst_group, nic_index=0)
            return
        batch = WrBatch(src)
        batch.add(op, dst_group, nic_index=0)
        self._send_batches[device] = (batch, self.loop.now)

        def flush() -> None:
            cur = self._send_batches.get(device)
            if cur is not None and cur[0] is batch:
                del self._send_batches[device]
            # batch_stats stays a one-sided-WRITE submission metric
            # (bench_ablation/kvlayout hot-path assertions count on it)
            batch.post()

        self.loop.schedule(ENQUEUE_US, flush)

    # -- completion notification --------------------------------------------
    def expect_imm_count(self, imm: int, count: int,
                         cb: Callable[[], None], device: int = 0) -> None:
        """Fire ``cb`` when ``count`` WRITEIMMs carrying ``imm`` have landed."""
        self.counters[device].expect(imm, count, cb)

    def imm_value(self, imm: int, device: int = 0) -> int:
        """Current landed-WRITEIMM count for ``imm`` on ``device``."""
        return self.counters[device].value(imm)

    # -- one-sided WRITE ------------------------------------------------------
    def _add_logical_write(self, batch: WrBatch, batch_state: BatchState,
                           payload, dst: MrDesc, dst_offset: int,
                           imm: Optional[int], stripe: bool,
                           nic_rr: Optional[int] = None,
                           extra_post_us: float = 0.0,
                           synthetic_bytes: Optional[int] = None,
                           fence_epoch: Optional[int] = None) -> None:
        """Template one logical WRITE into ``batch``, striping across NICs
        when ``stripe``.  ``payload`` is a zero-copy buffer view (already
        snapshotted by the caller); stripes slice it without copying.

        ``synthetic_bytes``: timing-only write of that size (no payload copy)
        — used by cluster-scale benchmarks where materialising terabytes of
        real bytes is pointless; all protocol behaviour is identical.

        ``fence_epoch``: stamp the WRITE with the sender's current view
        epoch; the receiving engine rejects it at landing if its fence
        table demands a higher epoch from this node (zombie-writer guard).
        None (default) posts an unstamped, never-fenced WRITE."""
        src_group = batch.group
        fab = self.fabric
        dst_group, dst_engine = fab._lookup(dst.owner)
        dst_region = dst_group.region(dst.region_id) if synthetic_bytes is None else None
        nbytes = (len(payload) if payload is not None else 0) \
            if synthetic_bytes is None else synthetic_bytes
        parts = src_group.split_across_nics(nbytes) if stripe else [(None, 0, nbytes)]
        fab.inflight_writes += 1
        state = WriteState(len(parts), imm,
                           dst_engine.counters[dst.owner.dev], batch_state,
                           fab)
        tr = fab.tracer
        mon = fab.health
        obs_src = (str(src_group.addr)
                   if tr is not None or mon is not None else "")
        for nic_index, off, ln in parts:
            chunk = payload[off:off + ln] if payload is not None else None
            op = WireOp(kind="write", payload=chunk, dst_region=dst_region,
                        dst_offset=dst_offset + off, imm=imm,
                        on_delivered=state.on_delivered, on_sent=state.on_sent,
                        nbytes=ln, on_error=state.on_error)
            if fence_epoch is not None:
                op.fence_epoch = int(fence_epoch)
                op.src_node = src_group.addr.node
                op.fences = dst_engine.fences
                op.on_fenced = state.on_fenced
            if tr is not None:
                op.span = tr.begin_wr("write", dst.owner, ln, imm, src=obs_src)
            elif mon is not None:
                op.span = mon.begin_wr("write", dst.owner, ln, imm,
                                       src=obs_src)
            idx = nic_index if stripe else (nic_rr if nic_rr is not None else None)
            batch.add(op, dst_group, nic_index=idx, extra_post_us=extra_post_us)

    def _enqueue_batch(self, batch: WrBatch) -> None:
        """One application->worker handoff for the whole batch (§3.4)."""
        self.batch_stats.record(batch)
        tr = self.fabric.tracer
        if tr is not None:
            tr.n_batches += 1
            tr.n_wrs += len(batch)
            tr.n_bytes += batch.nbytes
        mon = self.fabric.health
        if mon is not None:
            mon.on_enqueue(str(batch.group.addr), len(batch), batch.nbytes)
        self.loop.schedule(ENQUEUE_US, batch.post)

    def submit_single_write(self, length: int, imm: Optional[int],
                            src: Tuple[MrHandle, int], dst: Tuple[MrDesc, int],
                            on_done: OnDone = None,
                            on_error: Optional[Callable[[str], None]] = None,
                            fence_epoch: Optional[int] = None
                            ) -> None:
        """One-sided WRITE of ``length`` bytes, striped across all NICs;
        ``imm`` (if set) increments the receiver's counter once, when the
        last stripe lands.  ``on_error`` is the terminal failure path under
        fault injection (see :class:`BatchState`); ``fence_epoch`` stamps
        the WRITE for the receiver's epoch fence (zombie-writer guard)."""
        handle, src_off = src
        desc, dst_off = dst
        src_group = self.fabric.group(handle.owner)
        payload = src_group.region(handle.region_id).snapshot(src_off, length)
        batch = WrBatch(src_group)
        self._add_logical_write(batch, BatchState(1, on_done, on_error),
                                payload, desc, dst_off, imm, stripe=True,
                                fence_epoch=fence_epoch)
        self._enqueue_batch(batch)

    def submit_write_batch(self, writes: Sequence[Tuple[int, Optional[int],
                                                        Tuple[MrHandle, int],
                                                        Tuple[MrDesc, int]]],
                           on_done: OnDone = None, device: int = 0,
                           on_error: Optional[Callable[[str], None]] = None
                           ) -> None:
        """Batched single-write submission: N ``(length, imm, (handle,
        src_off), (desc, dst_off))`` WRITEs templated and posted in one
        event-loop entry.  Each entry keeps ``submit_single_write``
        semantics (NIC striping, per-write immediate); ``on_done`` fires
        after ALL entries have sender-side completions; ``on_error`` fires
        once on the first entry that fails terminally."""
        src_group = self.groups[device]
        n = len(writes)
        if n == 0:
            _fire(on_done)
            return
        batch = WrBatch(src_group)
        batch_state = BatchState(n, on_done, on_error)
        for length, imm, (handle, src_off), (desc, dst_off) in writes:
            if handle.owner != src_group.addr:
                raise ValueError("submit_write_batch: mixed source groups")
            payload = src_group.region(handle.region_id).snapshot(src_off, length)
            self._add_logical_write(batch, batch_state, payload, desc,
                                    dst_off, imm, stripe=True)
        self._enqueue_batch(batch)

    def submit_paged_writes(self, page_len: int, imm: Optional[int],
                            src: Tuple[MrHandle, Pages], dst: Tuple[MrDesc, Pages],
                            on_done: OnDone = None,
                            on_error: Optional[Callable[[str], None]] = None,
                            fence_epoch: Optional[int] = None
                            ) -> None:
        """One WRITE per page; pages rotate across NICs.  All pages are
        templated into a single ``WrBatch`` (one enqueue, per-WR posting
        cost amortised on the worker).

        Each page's WRITEIMM increments the receiver's counter by one (the
        KvCache protocol counts ``n_pages * n_layers + 1`` total events).
        """
        handle, src_pages = src
        desc, dst_pages = dst
        if len(src_pages.indices) != len(dst_pages.indices):
            raise ValueError("src/dst page counts differ")
        src_group = self.fabric.group(handle.owner)
        region = src_group.region(handle.region_id)
        src_offs = src_pages.resolve(page_len)
        dst_offs = dst_pages.resolve(page_len)
        n = len(src_offs)
        if n == 0:
            _fire(on_done)
            return
        batch = WrBatch(src_group)
        batch_state = BatchState(n, on_done, on_error)
        n_nics = len(src_group.domains)
        for k, (so, do) in enumerate(zip(src_offs, dst_offs)):
            self._add_logical_write(batch, batch_state,
                                    region.snapshot(so, page_len), desc, do,
                                    imm, stripe=False, nic_rr=k % n_nics,
                                    fence_epoch=fence_epoch)
        self._enqueue_batch(batch)

    # -- peer groups: scatter / barrier ---------------------------------------
    def add_peer_group(self, addrs: Sequence[NetAddr]) -> int:
        """Register a peer group for scatter/barrier; returns its id."""
        return self.fabric._add_peer_group(list(addrs))

    def submit_scatter(self, handle: MrHandle, dsts: Sequence[ScatterDst],
                       imm: Optional[int] = None, on_done: OnDone = None,
                       device: int = 0,
                       on_error: Optional[Callable[[str], None]] = None
                       ) -> None:
        """WRITE a distinct slice of ``handle`` to each peer (paper §3.3).

        WR-templating in the paper amortises descriptor setup; posting cost
        is modeled by the DomainGroup's per-WR posting delay (Table 9).
        """
        self.submit_scatters([(handle, dsts, imm, on_done, on_error)],
                             device=device)

    def submit_scatters(self, groups: Sequence[Tuple],
                        device: int = 0) -> None:
        """Batched scatter submission: several ``(handle, dsts, imm,
        on_done)`` scatters templated into ONE WrBatch / event-loop entry.
        A group may carry an optional 5th element ``on_error`` — the
        per-scatter terminal failure callback under fault injection — and
        an optional 6th element ``fence_epoch`` stamping the scatter's
        WRITEs for the receiver's epoch fence (zombie-writer guard).

        Completion state stays per-scatter (each ``on_done`` fires when its
        own destinations have sender-side completions; each imm counts its
        own WRITEs) — only the submission is coalesced.

        Destinations may be :class:`ScatterDst` (payload sliced from the
        group's ``handle`` region at submission, the snapshot copy) or
        :class:`PayloadDst` (caller-gathered bytes used AS the snapshot —
        zero staging copies; ``handle`` may then be None)."""
        src_group = self.groups[device]
        extra = SCATTER_EXTRA_US.get(self.nic_name, 0.0)
        n_nics = len(src_group.domains)
        batch = WrBatch(src_group)
        for handle, dsts, imm, on_done, *rest in groups:
            on_error = rest[0] if rest else None
            fence_epoch = rest[1] if len(rest) > 1 else None
            n = len(dsts)
            if n == 0:
                _fire(on_done)
                continue
            region = (src_group.region(handle.region_id)
                      if handle is not None else None)
            batch_state = BatchState(n, on_done, on_error)
            for k, sd in enumerate(dsts):
                desc, off = sd.dst
                if isinstance(sd, PayloadDst):
                    payload = sd.payload
                else:
                    payload = region.snapshot(sd.src, sd.len)
                self._add_logical_write(batch, batch_state, payload,
                                        desc, off, imm, stripe=False,
                                        nic_rr=k % n_nics,
                                        extra_post_us=extra,
                                        fence_epoch=fence_epoch)
        if len(batch):
            self._enqueue_batch(batch)

    def submit_synthetic_write(self, nbytes: int, imm: Optional[int],
                               dst: MrDesc, on_done: OnDone = None,
                               device: int = 0,
                               on_error: Optional[Callable[[str], None]] = None
                               ) -> None:
        """Timing-only single write (no payload) — cluster-scale benches."""
        src_group = self.groups[device]
        batch = WrBatch(src_group)
        self._add_logical_write(batch, BatchState(1, on_done, on_error),
                                None, dst, 0,
                                imm, stripe=True, synthetic_bytes=nbytes)
        self._enqueue_batch(batch)

    def submit_synthetic_batch(self, writes: Sequence[Tuple],
                               device: int = 0) -> None:
        """Batched timing-only writes: N ``(nbytes, imm, desc, on_done)``
        entries templated into ONE WrBatch / event-loop entry.  An entry may
        carry an optional 5th element ``on_error`` (terminal failure
        callback under fault injection).  Each entry keeps
        ``submit_synthetic_write`` semantics (NIC striping, its own
        immediate and sender-side ``on_done``) — only the submission is
        coalesced, mirroring ``submit_scatters`` for the payload-free path
        used by cluster-scale benches."""
        src_group = self.groups[device]
        if not writes:
            return
        batch = WrBatch(src_group)
        for nbytes, imm, desc, on_done, *rest in writes:
            on_error = rest[0] if rest else None
            self._add_logical_write(batch, BatchState(1, on_done, on_error),
                                    None, desc, 0, imm, stripe=True,
                                    synthetic_bytes=nbytes)
        self._enqueue_batch(batch)

    def submit_barrier(self, dsts: Sequence[MrDesc], imm: int,
                       on_done: OnDone = None, device: int = 0,
                       on_error: Optional[Callable[[str], None]] = None
                       ) -> None:
        """Immediate-only zero-length WRITE to each peer.

        EFA diverges from the RDMA spec and requires a valid descriptor even
        for zero-sized writes — callers must therefore pass real MrDescs.
        """
        src_group = self.groups[device]
        n = len(dsts)
        if n == 0:
            _fire(on_done)
            return
        batch = WrBatch(src_group)
        batch_state = BatchState(n, on_done, on_error)
        n_nics = len(src_group.domains)
        for k, desc in enumerate(dsts):
            self._add_logical_write(batch, batch_state, b"", desc, 0, imm,
                                    stripe=False, nic_rr=k % n_nics)
        self._enqueue_batch(batch)

    # -- UVM watcher -----------------------------------------------------------
    def alloc_uvm_watcher(self, cb: Callable[[int, int], None]) -> UvmWatcher:
        """A :class:`UvmWatcher` for GPU-progress-driven transfers (§3.3)."""
        return UvmWatcher(self.loop, cb)

    # -- stats -------------------------------------------------------------------
    def bytes_sent(self, device: int = 0) -> int:
        """Total payload bytes this device's NICs have transmitted."""
        return sum(d.nic.bytes_sent for d in self.groups[device].domains)

    # -- leak audit --------------------------------------------------------------
    def audit(self) -> Dict[str, object]:
        """Leaked per-engine state at loop-idle: SENDs parked waiting for a
        RECV that was never posted, SEND batches submitted but not yet
        flushed, and unfulfilled ImmCounter expectations (imm, have, need).
        Empty dict = clean.  Aggregated by :meth:`Fabric.audit`."""
        report: Dict[str, object] = {}
        for dev, pend in self._pending_sends.items():
            if pend:
                report[f"pending_sends[{self.node}/{dev}]"] = len(pend)
        for dev, (batch, _t) in self._send_batches.items():
            if len(batch):
                report[f"unflushed_send_batch[{self.node}/{dev}]"] = len(batch)
        for dev, counter in self.counters.items():
            out = counter.outstanding()
            if out:
                report[f"unfulfilled_imms[{self.node}/{dev}]"] = out
        return report


class Fabric:
    """A simulated cluster: nodes x GPUs x NICs sharing one event loop.

    Engines of different NIC kinds may coexist in one fabric (the
    heterogeneous-fabric refactor): the per-fabric :class:`Topology`
    resolves each (src, dst) address pair to its transport — NVLink for
    same-host pairs, the sender's NIC for same-kind pairs, a derived
    cross-fabric preset for mixed-NIC pairs (see ``docs/TOPOLOGY.md``).
    """

    def __init__(self, seed: int = 0):
        self.loop = EventLoop()
        self.seed = seed
        self.topology = Topology()
        self._groups: Dict[NetAddr, Tuple[DomainGroup, TransferEngine]] = {}
        self._peer_groups: List[List[NetAddr]] = []
        self.nic_kinds: set = set()
        # observability (repro.obs): None => every hook is a single guarded
        # attribute check; attach via Tracer(fabric) / attach_tracer,
        # HealthMonitor(fabric) / attach_health, FlightRecorder(fabric) /
        # attach_recorder, and host-clock spans via attach_spans
        self.tracer = None
        self.health = None
        self.recorder = None
        self.spans = None
        # fault injection (repro.core.faults): None => post_write's hot path
        # pays one attribute check and nothing else; attach via
        # FaultPlan(fabric, ...) which calls attach_faults
        self.faults = None
        # always-on leak accounting (plain int bumps, no timing impact)
        self.inflight_writes = 0
        self.inflight_sends = 0
        self._auditables: List[Tuple[str, object]] = []

    def add_engine(self, node: str, nic: str = "cx7", num_devices: int = 1,
                   host: Optional[str] = None,
                   nvlink: bool = True) -> TransferEngine:
        """Add one engine (node name, NIC preset, GPU count) to the fabric.

        ``host`` is the physical machine identity used for NVLink pair
        resolution; it defaults to ``node``, so distinct engines stay on
        distinct hosts unless told otherwise.  ``nvlink=False`` pins even
        same-host pairs to the NIC.  The pre-PR one-NIC-kind-per-fabric
        restriction is gone — mixed-kind pairs ride a derived cross-fabric
        cost model (:func:`~repro.core.topology.cross_spec`)."""
        self.nic_kinds.add(nic)
        return TransferEngine(self, node, nic, num_devices,
                              host=host, nvlink=nvlink)

    @staticmethod
    def _addr(a) -> NetAddr:
        """Coerce a NetAddr, a bare node name, or a ``str(NetAddr)``
        rendering (``node/gpuN`` — what observability spans carry)."""
        if not isinstance(a, str):
            return a
        node, sep, dev = a.rpartition("/gpu")
        if sep and dev.isdigit():
            return NetAddr(node, int(dev))
        return NetAddr(a, 0)

    def pair_spec(self, src, dst) -> NicSpec:
        """The per-pair transport spec the ``(src, dst)`` pair rides —
        the NVLink preset, a NIC preset, or a derived cross-fabric spec.

        Accepts ``NetAddr``s, bare node-name strings (device 0), or
        ``node/gpuN`` strings (the span address rendering)."""
        src = self._addr(src)
        dst = self._addr(dst)
        src_group = self.group(src)
        return src_group.domains[0].plan_for(dst).spec

    def degrade_pair(self, src, dst, *, bw_scale: float = 1.0,
                     extra_jitter_us: float = 0.0) -> int:
        """Fault injection: degrade every channel carrying (src, dst)
        traffic (see :func:`repro.core.netsim.degrade`).  Channels are
        created on demand — their CRC-derived seeds are order-independent,
        so pre-creating them here never perturbs a clean run's RNG streams.
        Returns the number of channels degraded."""
        src_addr = self._addr(src)
        dst_addr = self._addr(dst)
        src_group = self.group(src_addr)
        n = 0
        for d in src_group.domains:
            # post_write always selects channel_to(dst, d.index)
            degrade(d.channel_to(dst_addr, d.index),
                    bw_scale=bw_scale, extra_jitter_us=extra_jitter_us)
            n += 1
        return n

    def _register_group(self, addr: NetAddr, group: DomainGroup, engine: TransferEngine) -> None:
        if addr in self._groups:
            raise ValueError(f"duplicate address {addr}")
        self._groups[addr] = (group, engine)
        self.topology.register(addr, TopoEntry(
            host=engine.host, nic=engine.nic_name,
            spec=engine.nic_spec, nvlink=engine.nvlink))
        if self.tracer is not None:
            self._wire_tracer(addr, group, engine)
        if self.health is not None:
            group.health = self.health
        if self.faults is not None:
            group.faults = self.faults

    # -- observability (repro.obs) ----------------------------------------------
    def _wire_tracer(self, addr: NetAddr, group: DomainGroup,
                     engine: TransferEngine) -> None:
        group.tracer = self.tracer
        counter = engine.counters.get(addr.dev)
        if counter is not None:
            counter.tracer = self.tracer
            counter.label = str(addr)

    def attach_tracer(self, tracer) -> None:
        """Attach a :class:`repro.obs.Tracer` (or None to detach): wires
        every existing and future DomainGroup and ImmCounter.  Tracing
        never perturbs simulated time — hooks are pure bookkeeping."""
        self.tracer = tracer
        for addr, (group, engine) in self._groups.items():
            group.tracer = tracer
            counter = engine.counters.get(addr.dev)
            if counter is not None:
                counter.tracer = tracer
                counter.label = str(addr)

    def attach_health(self, monitor) -> None:
        """Attach a :class:`repro.obs.HealthMonitor` (or None to detach):
        wires every existing and future DomainGroup's posting hook.  Like
        the tracer, the monitor never perturbs simulated time — an
        always-on-monitored run is bit-identical to an unmonitored one."""
        self.health = monitor
        for group, _engine in self._groups.values():
            group.health = monitor

    def attach_recorder(self, recorder) -> None:
        """Attach a :class:`repro.obs.FlightRecorder` (or None to detach).
        The recorder is fed by the health monitor's delivery stream and by
        ctrl-plane instants; it dumps its ring on failure paths only."""
        self.recorder = recorder

    def attach_spans(self, rec) -> None:
        """Attach host-clock spans (a :class:`repro.obs.HostSpans`, or any
        object with its ``span``/``counters`` interface; None detaches).
        The serving peers open spans per request and the event loop counts
        the events it runs; virtual time is unchanged."""
        self.spans = rec
        self.loop.spans = rec

    def attach_faults(self, plan) -> None:
        """Attach a :class:`repro.core.faults.FaultPlan` (or None to
        detach): wires every existing and future DomainGroup's posting
        path through the plan's WR interception.  An attached plan with no
        injected pairs is bit-identical to no plan at all — it draws no
        RNG and its guard timers cancel without advancing virtual time."""
        self.faults = plan
        for group, _engine in self._groups.values():
            group.faults = plan

    def register_auditable(self, name: str, obj) -> None:
        """Register an object exposing ``audit_leaks() -> dict`` (empty =
        clean) for inclusion in :meth:`audit` — e.g. rlweights pipelines
        reporting unreleased staging reservations."""
        self._auditables.append((name, obj))

    def audit(self) -> Dict[str, object]:
        """Fabric-wide leak report, meaningful at loop-idle: logical
        WRITEs/SENDs without a final delivery, per-engine leftovers
        (parked SENDs, unfulfilled ImmCounter expectations) and registered
        auditables.  ``report["clean"]`` is the single pass/fail bit; see
        :func:`repro.obs.assert_clean` for the test-teardown wrapper."""
        engines: Dict[str, object] = {}
        seen: set = set()
        for addr, (group, engine) in self._groups.items():
            if id(engine) in seen:
                continue
            seen.add(id(engine))
            rep = engine.audit()
            if rep:
                engines[engine.node] = rep
        auditables: Dict[str, object] = {}
        for name, obj in self._auditables:
            rep = obj.audit_leaks()
            if rep:
                auditables[name] = rep
        report: Dict[str, object] = {
            "inflight_writes": self.inflight_writes,
            "inflight_sends": self.inflight_sends,
            "engines": engines,
            "auditables": auditables,
            "pending_events": self.loop.pending,
        }
        report["clean"] = not (self.inflight_writes or self.inflight_sends
                               or engines or auditables)
        return report

    def _lookup(self, addr: NetAddr) -> Tuple[DomainGroup, TransferEngine]:
        return self._groups[addr]

    def group(self, addr: NetAddr) -> DomainGroup:
        """The :class:`DomainGroup` registered at ``addr``."""
        return self._groups[addr][0]

    def _add_peer_group(self, addrs: List[NetAddr]) -> int:
        self._peer_groups.append(addrs)
        return len(self._peer_groups) - 1

    # -- execution helpers -------------------------------------------------------
    def run(self) -> float:
        """Drain the event loop; returns the final virtual time (us)."""
        return self.loop.run_until_idle()

    def run_until(self, pred: Callable[[], bool]) -> float:
        """Run events until ``pred()`` holds; returns the virtual time."""
        return self.loop.run_until(pred)

    @property
    def now(self) -> float:
        """Current virtual time (us)."""
        return self.loop.now
