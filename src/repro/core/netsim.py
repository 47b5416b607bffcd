"""Discrete-event network simulator underpinning the fabric.

Everything in the fabric (``repro.core``) runs in *virtual time* measured in
microseconds.  The simulator is deterministic: given a seed, every run
produces the same event order, which is what the property tests rely on.

The NIC service model is calibrated against Table 2 of the paper:

    service_time(bytes) = fixed_us + bytes * 8e-3 / (bw_gbps * eff)   [us]

with a per-DomainGroup posting-rate cap (``post_us`` per work request) and a
round-trip completion overhead ``rtt_us`` for serially-issued single writes.
With the constants below the simulated Table 2 matches the measured numbers
within ~15% across all message sizes for both EFA and ConnectX-7.
"""

from __future__ import annotations

import heapq
import itertools
import zlib
from dataclasses import dataclass, field
from typing import Callable, List, Optional


def stable_hash(*parts) -> int:
    """Process-stable hash for deriving RNG seeds.

    Python's builtin ``hash()`` randomises str hashing per process
    (PYTHONHASHSEED), which silently broke the simulator's determinism
    guarantee across processes — two identical runs drew different SRD
    jitter.  CRC32 over the repr is stable everywhere.
    """
    return zlib.crc32(repr(parts).encode()) & 0x7FFFFFFF


class EventLoop:
    """Deterministic discrete-event loop (virtual microseconds)."""

    def __init__(self) -> None:
        self._queue: List = []
        self._counter = itertools.count()
        self._cancelled: set = set()
        self.now: float = 0.0
        self._running = False
        # host-clock spans (repro.obs.HostSpans) or None: while attached,
        # each run adds the events it ran to the counter ``fabric.events``
        self.spans = None

    def schedule(self, delay_us: float, fn: Callable[[], None]) -> None:
        """Run ``fn`` ``delay_us`` virtual microseconds from now (FIFO at ties)."""
        if delay_us < 0:
            raise ValueError(f"negative delay {delay_us}")
        heapq.heappush(self._queue, (self.now + delay_us, next(self._counter), fn))

    def schedule_at(self, t_us: float, fn: Callable[[], None]) -> None:
        """Run ``fn`` at absolute virtual time ``t_us`` (clamped to now)."""
        self.schedule(max(0.0, t_us - self.now), fn)

    def schedule_cancelable(self, delay_us: float, fn: Callable[[], None]) -> int:
        """Like :meth:`schedule` but returns a handle for :meth:`cancel`.

        Used for guard timers (per-WR delivery timeouts): a cancelled entry
        is skipped when popped WITHOUT advancing ``now``, so an armed-then-
        cancelled timer never inflates the run's final virtual time — a
        fault-plan run whose timers all cancel ends at the same ``now`` as
        one that never armed them.
        """
        if delay_us < 0:
            raise ValueError(f"negative delay {delay_us}")
        seq = next(self._counter)
        heapq.heappush(self._queue, (self.now + delay_us, seq, fn))
        return seq

    def cancel(self, handle: int) -> None:
        """Cancel a handle from :meth:`schedule_cancelable` (lazy removal)."""
        self._cancelled.add(handle)

    def run_until_idle(self, max_events: int = 10_000_000) -> float:
        """Run until no events remain.  Returns the final virtual time."""
        n = 0
        while self._queue:
            t, seq, fn = heapq.heappop(self._queue)
            if self._cancelled and seq in self._cancelled:
                self._cancelled.discard(seq)
                continue
            self.now = max(self.now, t)
            fn()
            n += 1
            if n > max_events:
                raise RuntimeError("event loop runaway (possible livelock)")
        if self.spans is not None:
            self.spans.counters["fabric.events"] += n
        return self.now

    def run_until(self, pred: Callable[[], bool], max_events: int = 10_000_000) -> float:
        """Run until ``pred()`` is true (checked after each event)."""
        n = 0
        while self._queue and not pred():
            t, seq, fn = heapq.heappop(self._queue)
            if self._cancelled and seq in self._cancelled:
                self._cancelled.discard(seq)
                continue
            self.now = max(self.now, t)
            fn()
            n += 1
            if n > max_events:
                raise RuntimeError("event loop runaway (possible livelock)")
        if self.spans is not None:
            self.spans.counters["fabric.events"] += n
        if not pred():
            raise RuntimeError("event queue drained before predicate held")
        return self.now

    @property
    def pending(self) -> int:
        """Number of not-yet-run events in the queue (cancelled excluded)."""
        return len(self._queue) - len(self._cancelled)


@dataclass(frozen=True)
class NicSpec:
    """Static description of one NIC's performance envelope."""

    name: str
    bw_gbps: float            # line rate of this NIC
    base_latency_us: float    # one-way wire latency
    rtt_us: float             # submit->sender-completion overhead (single write)
    fixed_us: float           # per-op fixed service time on the NIC
    eff: float                # achievable fraction of line rate
    mtu_bytes: int            # max transfer unit for chunking
    ordered: bool             # True => RC-style in-order delivery
    srd_jitter_us: float = 0.0  # delivery jitter for unordered transports

    def service_us(self, nbytes: int) -> float:
        """NIC service time for one op: fixed cost + wire time (Table 2)."""
        return self.fixed_us + nbytes * 8e-3 / (self.bw_gbps * self.eff)


# Calibrated against Table 2 (see module docstring).
CX7 = NicSpec(
    name="cx7", bw_gbps=400.0, base_latency_us=2.5, rtt_us=10.5,
    fixed_us=0.04, eff=0.95, mtu_bytes=4096, ordered=True,
)
# One EFA adapter on a p5en instance (2 x 200 Gbps per GPU).
EFA_200 = NicSpec(
    name="efa200", bw_gbps=200.0, base_latency_us=15.0, rtt_us=31.0,
    fixed_us=0.476, eff=1.0, mtu_bytes=8928, ordered=False, srd_jitter_us=2.0,
)
# One EFA adapter on a p5 instance (4 x 100 Gbps per GPU).
EFA_100 = NicSpec(
    name="efa100", bw_gbps=100.0, base_latency_us=15.0, rtt_us=31.0,
    fixed_us=0.476, eff=1.0, mtu_bytes=8928, ordered=False, srd_jitter_us=2.0,
)

# Intra-node fast path (paper §6 uses NVLink for same-node peers).
NVLINK = NicSpec(
    name="nvlink", bw_gbps=3600.0, base_latency_us=0.3, rtt_us=1.0,
    fixed_us=0.5, eff=0.9, mtu_bytes=1 << 20, ordered=True,
)

# Per-DomainGroup work-request posting overhead (Table 8/9): the host proxy
# posts WRITEs one by one; this is the per-WR CPU cost.
POST_US = {"cx7": 0.09, "efa200": 0.476, "efa100": 0.476, "nvlink": 0.09}

# PCIe/GDRCopy polling latency for the UVM watcher (Table 4: 2.5-6.3 us).
PCIE_POLL_US = 3.0
# App -> worker-thread enqueue latency (Table 8: ~0.98 us p50 combined).
ENQUEUE_US = 0.98


class NicQueue:
    """A single NIC's serialised send pipeline.

    Work requests are served FIFO; the queue tracks ``busy_until`` so that
    back-to-back posts pipeline (throughput = 1/service_time) while an idle
    NIC adds only its own service time.
    """

    def __init__(self, loop: EventLoop, spec: NicSpec):
        self.loop = loop
        self.spec = spec
        self.busy_until = 0.0
        self.bytes_sent = 0
        self.ops_sent = 0
        # fault injection: <1.0 slows every op on this NIC (whole-NIC
        # degradation); per-channel degradation rides the submit() svc_scale
        self.bw_scale = 1.0

    def backlog_us(self, now: float) -> float:
        """Queued-but-unserialised service time at ``now`` (µs) — the
        queue-occupancy gauge sampled by ``repro.obs``: 0 when idle."""
        return max(0.0, self.busy_until - now)

    def submit(self, nbytes: int, on_wire: Callable[[float], None],
               charge_fixed: bool = True, svc_scale: float = 1.0) -> float:
        """Queue ``nbytes`` for transmission.

        ``on_wire(t_delivered)`` is invoked (scheduled) for the time the last
        byte arrives at the remote NIC.  Returns the local send-completion
        time (used for sender-side CQEs).  ``charge_fixed=False`` skips the
        per-op fixed cost (continuation chunks of one WRITE: the NIC charges
        per work request, not per wire packet).  ``svc_scale`` multiplies the
        per-byte serialisation cost (fault injection: a degraded channel
        passes >1.0); the per-op fixed cost is never scaled.
        """
        start = max(self.loop.now, self.busy_until)
        svc = nbytes * 8e-3 / (self.spec.bw_gbps * self.spec.eff)
        scale = svc_scale / self.bw_scale
        if scale != 1.0:
            # guarded so the clean path computes the bit-identical float
            svc *= scale
        if charge_fixed:
            svc += self.spec.fixed_us
        done_tx = start + svc
        self.busy_until = done_tx
        self.bytes_sent += nbytes
        self.ops_sent += 1
        arrive = done_tx + self.spec.base_latency_us
        on_wire(arrive)
        return done_tx


def degrade(channel, bw_scale: float = 1.0, extra_jitter_us: float = 0.0) -> None:
    """Fault injection: degrade one transport channel in place.

    ``bw_scale`` < 1.0 scales the channel's effective bandwidth down (its
    per-byte serialisation cost is multiplied by ``1/bw_scale``; the per-op
    fixed cost and other channels sharing the same NIC queue are untouched,
    so injected faults stay attributable to one (src, dst) pair).
    ``extra_jitter_us`` adds deterministic pseudo-random delivery jitter on
    top of the transport's own (RC channels, normally jitter-free, start
    drawing from their seeded RNG only once this is non-zero — a clean
    fabric's RNG stream is bit-identical to one that never imported this).

    Duck-typed on :class:`repro.core.transport.Channel` to avoid an import
    cycle; ``Fabric.degrade_pair`` applies it to every channel of a pair.
    """
    if bw_scale <= 0.0:
        raise ValueError(f"bw_scale must be > 0, got {bw_scale}")
    channel.svc_scale = 1.0 / bw_scale
    channel.extra_jitter_us = float(extra_jitter_us)
