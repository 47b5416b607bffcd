"""Control-plane tests: wire codec, epoch monotonicity, leases, drain
semantics, crash failover, and the autoscaler policy."""

import jax
import numpy as np
import pytest

from repro.configs import get_config
from repro.core import Fabric, MrDesc, NetAddr
from repro.ctrl import (Autoscaler, ControlClient, ControlPlane,
                        MembershipView, PeerRegistry, PeerView, ScalingPolicy)
from repro.ctrl import messages as m
from repro.models import init_params
from repro.serving import (Decoder, DispatchReq, Prefiller, Scheduler,
                           disagg_unsupported_reason)
from repro.serving.kvpool import PagedKvPool, PoolGeometry


@pytest.fixture(scope="module")
def model():
    cfg = get_config("stablelm-3b").reduced()
    return cfg, init_params(cfg, jax.random.PRNGKey(0))


# ---------------------------------------------------------------------------
# wire codec
# ---------------------------------------------------------------------------

def test_wire_roundtrip_messages():
    desc = MrDesc(3, NetAddr("p0", 0), 4096, ((0, 123), (1, 456)))
    join = m.Join(peer_id="p0", role="prefill", addr=NetAddr("p0", 0),
                  nic="efa", kv_desc=desc,
                  geom={"n_layers": 2, "page_bytes": 2048}, n_pages=64,
                  lease_us=2000.0)
    back = m.decode(m.encode(join))
    assert back == join and isinstance(back.kv_desc, MrDesc)

    sub = m.SubmitReq(request_id=7, input_ids=np.arange(5, dtype=np.int64),
                      prefiller=NetAddr("p0", 0), n_decode=4,
                      reply_to=NetAddr("sched", 0), attempt=2)
    got = m.decode(m.encode(sub))
    np.testing.assert_array_equal(got.input_ids, sub.input_ids)
    assert (got.request_id, got.attempt, got.prefiller) == (7, 2, sub.prefiller)

    dreq = DispatchReq(input_ids=np.arange(9), decoder_addr=NetAddr("d0", 0),
                       imm=5, kv_desc=desc, pages=[4, 5, 6],
                       tail_desc=desc, tail_idx=1, request_id=3)
    got = m.decode(m.encode(dreq))
    assert got.pages == [4, 5, 6] and got.kv_desc == desc
    np.testing.assert_array_equal(got.input_ids, dreq.input_ids)

    for msg in (m.LeaseRenew("p0", 3, 12), m.Drain("p0"), m.Leave("p0"),
                m.JoinAck("p0", 4, 1500.0), m.CancelReq(9, 1),
                m.ReqDone(9, 1, "d0", 123.4, [1, 2, 3])):
        assert m.decode(m.encode(msg)) == msg

    with pytest.raises(ValueError):
        m.decode(b"XXXX\0{}")


# ---------------------------------------------------------------------------
# registry: epoch monotonicity
# ---------------------------------------------------------------------------

def test_registry_epochs_strictly_monotonic():
    reg = PeerRegistry()
    kw = dict(role="prefill", addr=NetAddr("x", 0), nic="efa", kv_desc=None,
              geom={}, n_pages=4, lease_us=100.0, now=0.0)
    assert reg.join(peer_id="a", **kw) == 1
    assert reg.join(peer_id="b", **kw) == 2
    assert reg.join(peer_id="c", **kw) == 3
    # renew refreshes liveness but is NOT a membership change
    assert reg.renew("a", now=50.0, lease_us=100.0, inflight=2, free_pages=1)
    assert reg.epoch == 3
    assert reg.start_drain("b") == 4
    assert reg.start_drain("b") is None        # already draining: no bump
    assert reg.leave("b") == 5
    assert reg.leave("b") is None
    # c's lease (expires at 100) lapses; a was renewed to 150
    died = reg.expire(now=120.0)
    assert [r.peer_id for r in died] == ["c"] and reg.epoch == 6
    epochs = [e for e, _ in reg.epoch_log]
    assert epochs == list(range(1, 7))
    view = reg.view()
    assert view.epoch == 6 and view.ids() == ("a",)
    assert view.peer("a").inflight == 2


def test_view_routable_excludes_draining():
    reg = PeerRegistry()
    kw = dict(role="prefill", addr=NetAddr("x", 0), nic="efa", kv_desc=None,
              geom={}, n_pages=4, lease_us=100.0, now=0.0)
    reg.join(peer_id="a", **kw)
    reg.join(peer_id="b", **kw)
    reg.start_drain("a")
    view = reg.view()
    assert {p.peer_id for p in view.by_role("prefill")} == {"a", "b"}
    assert [p.peer_id for p in view.routable("prefill")] == ["b"]
    # wire round-trip preserves the epoch and statuses
    back = MembershipView.from_wire(view.epoch, view.to_wire())
    assert back.epoch == view.epoch
    assert [p.peer_id for p in back.routable("prefill")] == ["b"]
    assert back.peer("a").status == "draining"


# ---------------------------------------------------------------------------
# control plane over the wire (no model: raw engines + pools)
# ---------------------------------------------------------------------------

class WirePeer:
    """Minimal control-plane citizen: engine + KV pool + ControlClient."""

    def __init__(self, fab, ctrl, name, role, n_pages=8, **kw):
        self.engine = fab.add_engine(name, nic=ctrl.nic)
        self.geom = PoolGeometry(n_layers=2, page_tokens=4, n_kv=1, head_dim=8)
        self.pool = PagedKvPool(self.engine, self.geom, n_pages)
        self.alive = True
        self.views, self.drains = [], []
        self.client = ControlClient(
            self.engine, fab, ctrl.address(), name, role,
            alive_fn=lambda: self.alive, on_drain=self.drains.append,
            on_view=self.views.append, **kw)
        self.engine.submit_recvs(1 << 14, 8, self._on_msg)
        self.client.join(nic=ctrl.nic, kv_desc=self.pool.desc,
                         geom={"page_bytes": self.geom.page_bytes},
                         n_pages=n_pages)

    def _on_msg(self, payload):
        self.client.handle(m.decode(payload))


class ViewCollector:
    """A bare subscriber engine that records every VIEW-UPDATE."""

    def __init__(self, fab, ctrl, name="watch"):
        self.engine = fab.add_engine(name, nic=ctrl.nic)
        self.views = []
        self.engine.submit_recvs(1 << 14, 16, self._on_msg)
        ctrl.subscribe(self.engine.address(0))

    def _on_msg(self, payload):
        msg = m.decode(payload)
        if isinstance(msg, m.ViewUpdate):
            self.views.append(MembershipView.from_wire(msg.epoch, msg.peers))


def test_join_publishes_descriptors_over_wire():
    fab = Fabric(seed=11)
    ctrl = ControlPlane(fab, nic="efa", max_sweeps=16)
    watch = ViewCollector(fab, ctrl)
    a = WirePeer(fab, ctrl, "pf0", "prefill", max_renewals=8)
    b = WirePeer(fab, ctrl, "dc0", "decode", max_renewals=8)
    fab.run()
    assert a.client.joined and b.client.joined
    # near-simultaneous broadcasts may be delivered out of order (SRD);
    # the epoch stamp is what lets subscribers order them
    final = max(watch.views, key=lambda v: v.epoch)
    assert final.epoch == ctrl.registry.epoch
    assert {p.peer_id for p in final.peers} == {"pf0", "dc0"}
    # the MrDesc crossed the wire and equals the locally registered one
    pf = final.peer("pf0")
    assert pf.kv_desc == a.pool.desc and pf.nic == "efa"
    assert pf.geom["page_bytes"] == a.geom.page_bytes
    # one view per membership change, each with a distinct epoch
    epochs = [v.epoch for v in watch.views]
    assert len(set(epochs)) == len(epochs)


def test_lease_expiry_marks_crashed_peer_dead():
    fab = Fabric(seed=12)
    ctrl = ControlPlane(fab, nic="efa", lease_us=500.0, sweep_us=100.0,
                        max_sweeps=40)
    watch = ViewCollector(fab, ctrl)
    a = WirePeer(fab, ctrl, "pf0", "prefill", renew_us=100.0, max_renewals=40)
    WirePeer(fab, ctrl, "pf1", "prefill", renew_us=100.0, max_renewals=40)
    fab.loop.schedule(300.0, lambda: setattr(a, "alive", False))
    fab.run()
    assert ctrl.registry.record("pf0") is None
    assert any(e == "dead:pf0" for _, e in ctrl.registry.epoch_log)
    final = max(watch.views, key=lambda v: v.epoch)
    assert final.ids() == ("pf1",)
    # pf1 kept renewing and is still live
    assert ctrl.registry.record("pf1").status == "live"


def test_scheduler_never_routes_to_draining_peer():
    fab = Fabric(seed=13)
    ctrl = ControlPlane(fab, nic="efa", max_sweeps=24)
    p0 = WirePeer(fab, ctrl, "p0", "prefill", max_renewals=12)
    WirePeer(fab, ctrl, "p1", "prefill", max_renewals=12)
    WirePeer(fab, ctrl, "d0", "decode", max_renewals=12)
    sched = Scheduler(fab, ctrl)
    fab.loop.schedule(100.0, lambda: ctrl.drain("p0"))
    for i in range(8):
        fab.loop.schedule_at(200.0 + 10.0 * i,
                             lambda: sched.submit(np.arange(4), n_decode=1))
    fab.run()
    assert p0.drains and p0.drains[0].peer_id == "p0"
    # p0 stayed in the view (status draining) but took zero new routes
    assert sched.view.peer("p0").status == "draining"
    assert len(sched.routing_log) == 8
    assert all(pf == "p1" for _, _, pf, _ in sched.routing_log)


# ---------------------------------------------------------------------------
# e2e elasticity with the real model
# ---------------------------------------------------------------------------

def test_join_route_drain_leaves_no_leaked_pages(model):
    cfg, params = model
    fab = Fabric(seed=4)
    ctrl = ControlPlane(fab, nic="efa", max_sweeps=60)
    p0 = Prefiller(fab, "p0", cfg, params, nic="efa", ctrl=ctrl,
                   max_renewals=60)
    d0 = Decoder(fab, "d0", cfg, params, nic="efa", ctrl=ctrl,
                 max_renewals=60)
    sched = Scheduler(fab, ctrl)
    rng = np.random.default_rng(1)
    rids = [sched.submit(rng.integers(0, cfg.vocab, size=24), n_decode=2)
            for _ in range(2)]
    # p1 JOINs mid-run, serves traffic, then is drained out
    joined = []
    fab.loop.schedule(120.0, lambda: joined.append(Prefiller(
        fab, "p1", cfg, params, nic="efa", ctrl=ctrl, max_renewals=60)))
    for i in range(3):
        fab.loop.schedule_at(300.0 + 60.0 * i, lambda: rids.append(
            sched.submit(rng.integers(0, cfg.vocab, size=24), n_decode=2)))
    fab.loop.schedule_at(600.0, lambda: ctrl.drain("p1"))
    fab.loop.schedule_at(900.0, lambda: rids.append(
        sched.submit(rng.integers(0, cfg.vocab, size=24), n_decode=2)))
    fab.run()
    assert len(sched.completed) == len(rids) == 6
    p1 = joined[0]
    # the joiner served real traffic...
    assert any(r["prefiller"] == "p1" for r in sched.completed.values())
    # ...and drained out with nothing leaked
    assert p1.client.left and p1.inflight == 0
    assert len(p1.pool._free) == p1.pool.n_pages
    assert len(p0.pool._free) == p0.pool.n_pages
    assert len(d0.pool._free) == d0.pool.n_pages and not d0._pending
    # post-drain request went to p0
    assert sched.completed[rids[-1]]["prefiller"] == "p0"
    # epochs strictly monotonic end to end
    assert sched.view_epochs == sorted(set(sched.view_epochs))


def test_decoder_drain_finishes_and_leaves(model):
    cfg, params = model
    fab = Fabric(seed=15)
    ctrl = ControlPlane(fab, nic="efa", max_sweeps=60)
    Prefiller(fab, "p0", cfg, params, nic="efa", ctrl=ctrl, max_renewals=60)
    d0 = Decoder(fab, "d0", cfg, params, nic="efa", ctrl=ctrl,
                 max_renewals=60)
    d1 = Decoder(fab, "d1", cfg, params, nic="efa", ctrl=ctrl,
                 max_renewals=60)
    sched = Scheduler(fab, ctrl)
    rng = np.random.default_rng(3)
    rids = [sched.submit(rng.integers(0, cfg.vocab, size=24), n_decode=2)
            for _ in range(2)]
    fab.loop.schedule(200.0, lambda: ctrl.drain("d1"))
    for i in range(2):
        fab.loop.schedule_at(400.0 + 60.0 * i, lambda: rids.append(
            sched.submit(rng.integers(0, cfg.vocab, size=24), n_decode=2)))
    fab.run()
    sched.check_drained()
    assert len(sched.completed) == 4
    # d1 finished its in-flight work, freed everything, and LEFT
    assert d1.client.left and not d1._pending
    assert len(d1.pool._free) == d1.pool.n_pages
    assert ctrl.registry.record("d1") is None
    # post-drain requests all decoded on d0
    assert all(sched.completed[r]["decoder"] == "d0" for r in rids[2:])
    assert len(d0.pool._free) == d0.pool.n_pages


def test_lease_expiry_cancels_and_reroutes_inflight(model):
    cfg, params = model
    fab = Fabric(seed=9)
    ctrl = ControlPlane(fab, nic="efa", lease_us=800.0, sweep_us=200.0,
                        max_sweeps=60)
    q0 = Prefiller(fab, "q0", cfg, params, nic="efa", ctrl=ctrl,
                   renew_us=200.0, max_renewals=60)
    d0 = Decoder(fab, "d0", cfg, params, nic="efa", ctrl=ctrl,
                 renew_us=200.0, max_renewals=60)
    sched = Scheduler(fab, ctrl)
    rng = np.random.default_rng(2)
    rids = [sched.submit(rng.integers(0, cfg.vocab, size=24), n_decode=2)
            for _ in range(3)]
    # crash q0 after it has accepted work but before transfers complete;
    # the replacement joins later, after the lease has already lapsed
    fab.loop.schedule(130.0, q0.crash)
    spare = []
    fab.loop.schedule_at(500.0, lambda: spare.append(Prefiller(
        fab, "q1", cfg, params, nic="efa", ctrl=ctrl, renew_us=200.0,
        max_renewals=60)))
    fab.run()
    # the crash was detected via lease expiry, in-flight requests were
    # cancelled at the decoder and re-routed, and all of them completed
    assert ctrl.registry.record("q0") is None
    assert set(sched.rerouted) == set(rids)
    assert len(sched.completed) == 3
    for rid in rids:
        r = sched.completed[rid]
        assert r["prefiller"] == "q1" and r["attempt"] >= 1
        assert len(r["tokens"]) == 2
    # cancelled attempts freed their pages and tail slots
    assert len(d0.pool._free) == d0.pool.n_pages
    n_tails = d0.tail_buf.size // (cfg.vocab * 4)
    assert len(d0._tail_free) == n_tails and not d0._pending


# ---------------------------------------------------------------------------
# autoscaler policy (no fabric: synthetic signals)
# ---------------------------------------------------------------------------

class _FakeCtrl:
    def __init__(self, view):
        self._view = view
        self.drained = []

    def view(self):
        return self._view

    def drain(self, peer_id):
        self.drained.append(peer_id)


class _FakeSched:
    def __init__(self):
        self.depth = 0
        self.ttft_ema = None

    def queue_depth(self):
        return self.depth


def _pf(pid, status="live", inflight=0):
    return PeerView(peer_id=pid, role="prefill", addr=NetAddr(pid, 0),
                    nic="efa", status=status, kv_desc=None, geom={},
                    n_pages=8, inflight=inflight)


def test_autoscaler_policy_decisions():
    view = MembershipView(3, (_pf("a", inflight=2), _pf("b", inflight=0)))
    ctrl, sched = _FakeCtrl(view), _FakeSched()
    spawned = []
    pol = ScalingPolicy(queue_high=3, idle_ticks_down=2, min_prefillers=1,
                        max_prefillers=3, cooldown_us=500.0)
    sc = Autoscaler(ctrl, sched, spawned.append, policy=pol, auto=False,
                    next_index=2)
    # overload -> scale up; cooldown blocks an immediate second action
    sched.depth = 5
    assert sc.step(0.0) == "up" and spawned == [2]
    assert sc.step(100.0) is None
    # still overloaded after cooldown -> another up, capped at max (3 peers)
    assert sc.step(600.0) == "up" and spawned == [2, 3]
    sc.ctrl._view = MembershipView(5, (_pf("a", inflight=2), _pf("b"),
                                       _pf("c"), _pf("d")))
    assert sc.step(1300.0) is None          # at max_prefillers
    # idle for idle_ticks_down consecutive ticks -> drain the least loaded
    sched.depth = 0
    assert sc.step(1400.0) is None          # idle tick 1
    assert sc.step(1550.0) == "down"
    assert ctrl.drained == ["b"]            # least inflight, stable tiebreak
    # while one peer is draining, no further scale-down
    sc.ctrl._view = MembershipView(6, (_pf("a"), _pf("b", status="draining"),
                                       _pf("c"), _pf("d")))
    assert sc.step(2300.0) is None
    assert sc.step(2450.0) is None
    # TTFT SLO violation is an alternative scale-up trigger
    sc.ctrl._view = MembershipView(7, (_pf("a"),))
    sc.policy = ScalingPolicy(queue_high=99, ttft_high_us=200.0,
                              cooldown_us=0.0, max_prefillers=3)
    sched.ttft_ema = 450.0
    assert sc.step(3000.0) == "up"


def test_autoscaler_respects_min_prefillers():
    ctrl, sched = _FakeCtrl(MembershipView(1, (_pf("a"),))), _FakeSched()
    pol = ScalingPolicy(idle_ticks_down=1, min_prefillers=1, cooldown_us=0.0)
    sc = Autoscaler(ctrl, sched, lambda i: None, policy=pol, auto=False)
    for t in (0.0, 100.0, 200.0):
        assert sc.step(t) is None
    assert ctrl.drained == []


# ---------------------------------------------------------------------------
# the state-handoff guard is RETIRED: every cache shape has a KvSchema
# ---------------------------------------------------------------------------

def test_disagg_guard_retired_for_all_archs():
    """`disagg_unsupported_reason` is None for pattern-split (gemma3, vlm),
    SSM/hybrid, and first-k-dense archs — the ROADMAP guard is gone."""
    from repro.configs import ARCH_IDS
    for arch in ARCH_IDS:
        assert disagg_unsupported_reason(get_config(arch).reduced()) is None
    # constructors admit the formerly rejected families (params untouched
    # at construction time, so None suffices here)
    fab = Fabric(seed=0)
    for i, arch in enumerate(("gemma3-1b", "mamba2-780m",
                              "deepseek-moe-16b")):
        cfg = get_config(arch).reduced()
        Prefiller(fab, f"p{i}", cfg, None, nic="efa")
        Decoder(fab, f"d{i}", cfg, None, nic="efa")


def test_scheduler_refuses_mismatched_schemas():
    """A gemma3 prefiller and a stablelm decoder must never be paired: the
    route is refused at the scheduler, not discovered mid-transfer."""
    from repro.kvlayout import schema_from_config

    fab = Fabric(seed=21)
    ctrl = ControlPlane(fab, nic="efa", max_sweeps=16)
    sched = Scheduler(fab, ctrl)
    pf = WirePeer(fab, ctrl, "p0", "prefill", max_renewals=8)
    dc = WirePeer(fab, ctrl, "d0", "decode", max_renewals=8)
    # overwrite the advertised schemas with incompatible ones
    pf_schema = schema_from_config(get_config("gemma3-1b").reduced())
    dc_schema = schema_from_config(get_config("stablelm-3b").reduced())
    fab.loop.schedule(50.0, lambda: pf.client.join(
        nic="efa", kv_desc=pf.pool.desc, geom={}, n_pages=8,
        schema=pf_schema.to_wire()))
    fab.loop.schedule(50.0, lambda: dc.client.join(
        nic="efa", kv_desc=dc.pool.desc, geom={}, n_pages=8,
        schema=dc_schema.to_wire()))
    fab.loop.schedule(200.0, lambda: sched.submit(np.arange(4), n_decode=1))
    fab.run()
    assert len(sched.routing_log) == 0
    assert sched.schema_mismatches > 0
    assert len(sched.backlog) == 1        # parked, never mis-routed
    with pytest.raises(RuntimeError, match="schema mismatches"):
        sched.check_drained()


def test_least_loaded_policy_orders_by_load():
    """policy="least-loaded" prefers the peer with the smallest effective
    load (LEASE-RENEW-piggybacked inflight, or the scheduler's own
    outstanding count when fresher); round-robin stays the default."""
    fab = Fabric(seed=22)
    ctrl = ControlPlane(fab, nic="efa", max_sweeps=4)
    sched = Scheduler(fab, ctrl, policy="least-loaded")
    assert Scheduler(fab, ctrl, node="sched2").policy == "round-robin"
    with pytest.raises(ValueError, match="unknown policy"):
        Scheduler(fab, ctrl, node="sched3", policy="busiest-first")
    sched.view = MembershipView(3, (
        _pf("a", inflight=2), _pf("b", inflight=0), _pf("c", inflight=1)))
    order = [p.peer_id for p in sched._candidates("prefill")]
    assert order == ["b", "c", "a"]
    # the scheduler's own outstanding count dominates when fresher
    sched._outstanding["b"] = 5
    order = [p.peer_id for p in sched._candidates("prefill")]
    assert order == ["c", "a", "b"]
    # round-robin rotates instead
    rr = Scheduler(fab, ctrl, node="sched4")
    rr.view = sched.view
    rr._rr["prefill"] = 1
    assert [p.peer_id for p in rr._candidates("prefill")] == ["b", "c", "a"]


def test_least_loaded_weights_by_plan_slots(model):
    """The local outstanding ledger charges each request its
    ``TransferPlan.n_slots`` on the decoder's advertised KvSchema — pool
    pressure — so one long prompt outweighs several short ones."""
    from repro.kvlayout import KvSchema, TransferPlan, schema_from_config

    cfg, _ = model
    schema = schema_from_config(cfg)
    fab = Fabric(seed=31)
    ctrl = ControlPlane(fab, nic="efa", max_sweeps=4)
    sched = Scheduler(fab, ctrl, policy="least-loaded")

    def _dc(pid):
        return PeerView(peer_id=pid, role="decode", addr=NetAddr(pid, 0),
                        nic="efa", status="live", kv_desc=None, geom={},
                        n_pages=8, inflight=0, schema=schema.to_wire())

    d1, d2 = _dc("d1"), _dc("d2")
    long_slots = sched._req_slots(d1, 400)
    short_slots = sched._req_slots(d1, 10)
    assert long_slots == TransferPlan(schema, 400).n_slots
    assert short_slots == TransferPlan(schema, 10).n_slots
    assert long_slots > 2 * short_slots
    # schema-less peers weigh 1 per request (raw count fallback)
    assert sched._req_slots(_pf("x"), 400) == 1

    # d1 holds ONE long prompt, d2 holds TWO short ones: raw request count
    # says d1 is less loaded; pool pressure says d2 is
    sched.view = MembershipView(3, (d1, d2))
    sched._outstanding = {"d1": long_slots, "d2": 2 * short_slots}
    order = [p.peer_id for p in sched._candidates("decode")]
    assert order == ["d2", "d1"]

    # the ledger releases exactly what routing charged
    st = dict(prefiller="d1", decoder="d2", slots=2 * short_slots)
    sched._release(st)
    assert sched._outstanding == {"d1": long_slots - 2 * short_slots}
    sched._release(dict(prefiller="d1", decoder="x", slots=10 ** 6))
    assert "d1" not in sched._outstanding and "x" not in sched._outstanding
