"""Elastic prefill scaling over the control plane (§4 "dynamic scaling").

ONE simulated timeline, four acts, all routing through PeerRegistry epoch
views (the scheduler holds no static peer list):

  A  overload   — a single prefiller takes an arrival train faster than its
                  service rate; queue depth and TTFT climb.
  B  scale-up   — the Autoscaler sees the depth and spawns a second
                  prefiller, which JOINs the control plane (epoch bump) and
                  absorbs traffic; TTFT recovers.
  C  scale-down — once idle, the Autoscaler drains the least-loaded
                  prefiller: in-flight work finishes, every KV page is
                  freed, the peer LEAVEs.  Zero leaked pages is asserted.
  D  failover   — the surviving prefiller crashes mid-burst (stops renewing
                  its lease); lease expiry marks it dead, in-flight requests
                  are cancelled at their decoders and re-queued, the
                  Autoscaler spawns a replacement, and every post-failure
                  request completes.

``BENCH_SCALING_SMOKE=1`` shrinks the arrival trains for the CI smoke job.
Model compute is real (reduced stablelm); all times are virtual us.
"""

from __future__ import annotations

import json
import os

import numpy as np

from .obs_hooks import assert_no_flags, attach_health, finish_trace, maybe_tracer

SMOKE = os.environ.get("BENCH_SCALING_SMOKE", "") not in ("", "0")

OUT_DIR = os.environ.get(
    "BENCH_OUT", os.path.join(os.path.dirname(__file__), "out"))

GAP_US = 60.0            # arrival spacing (service time is ~100 us/req)
LAYER_US = 50.0
# TTFT SLO for the tracker: between the scaled p95 (~164 us) and the
# overloaded p95 (~332 us), so the overload/failover phases breach and the
# scaled phase recovers — the closed loop the SloTracker rows demonstrate
TTFT_SLO_US = 250.0


def run_timeline(n_a: int, n_b: int, n_d: int, *, prompt_len: int = 24,
                 n_decode: int = 2, nic: str = "efa", seed: int = 7) -> dict:
    import jax

    from repro.configs import get_config
    from repro.core import Fabric
    from repro.ctrl import Autoscaler, ControlPlane, ScalingPolicy
    from repro.models import init_params
    from repro.serving import Decoder, Prefiller, Scheduler, SloTracker

    cfg = get_config("stablelm-3b").reduced()
    params = init_params(cfg, jax.random.PRNGKey(0))
    fab = Fabric(seed=seed)
    # traces the whole elastic timeline (ctrl instants + autoscale decisions)
    tracer = maybe_tracer(fab)
    monitor = attach_health(fab)
    ctrl = ControlPlane(fab, nic=nic, lease_us=600.0, sweep_us=200.0,
                        max_sweeps=150)
    prefillers = []

    def spawn(i: int) -> None:
        prefillers.append(Prefiller(
            fab, f"p{i}", cfg, params, nic=nic, ctrl=ctrl,
            layer_compute_us=LAYER_US, renew_us=200.0, max_renewals=150))

    spawn(0)
    decoders = [Decoder(fab, f"d{i}", cfg, params, nic=nic, ctrl=ctrl,
                        renew_us=200.0, max_renewals=150) for i in range(2)]
    slo = SloTracker(fab, ttft_slo_us=TTFT_SLO_US)
    sched = Scheduler(fab, ctrl, slo=slo)
    scaler = Autoscaler(
        ctrl, sched, spawn,
        policy=ScalingPolicy(queue_high=3, idle_ticks_down=3,
                             min_prefillers=1, max_prefillers=4,
                             cooldown_us=600.0),
        tick_us=150.0, max_ticks=150, next_index=1)

    rng = np.random.default_rng(seed)
    phases: dict = {}

    def arrivals(t0: float, n: int, phase: str) -> None:
        rids: list = []
        phases[phase] = rids
        for i in range(n):
            ids = rng.integers(0, cfg.vocab, size=prompt_len)
            fab.loop.schedule_at(t0 + i * GAP_US, lambda ids=ids: rids.append(
                sched.submit(ids, n_decode=n_decode)))

    t_b = n_a * GAP_US + 360.0
    t_d = t_b + n_b * GAP_US + 1800.0   # leaves an idle window for scale-down
    arrivals(0.0, n_a, "A")
    arrivals(t_b, n_b, "B")
    arrivals(t_d, n_d, "D")
    # crash every live prefiller shortly into phase D: leases lapse, the
    # control plane declares them dead, and the autoscaler must replace them
    fab.loop.schedule_at(t_d + 100.0, lambda: [
        p.crash() for p in prefillers
        if p.alive and p.client is not None and not p.client.left])
    fab.run()

    # -- acceptance checks (the §4 dynamic-scaling contract) ----------------
    n_total = n_a + n_b + n_d
    assert len(sched.completed) == n_total, \
        f"{len(sched.completed)}/{n_total} requests completed"
    ups = [d for d in scaler.decisions if d[1] == "up"]
    downs = [d for d in scaler.decisions if d[1] == "down"]
    assert ups, "autoscaler never scaled up"
    assert downs, "autoscaler never scaled down"
    # a joined-mid-run peer served traffic
    joined = {f"p{i}" for i in range(1, len(prefillers))}
    served_by = {r["prefiller"] for r in sched.completed.values()}
    assert served_by & joined, f"no joined peer served traffic ({served_by})"
    # drained peers left cleanly with zero leaked KV pages
    drained = [p for p in prefillers if p.client.left and p.alive]
    assert drained, "no peer completed a drain"
    for p in drained:
        assert p.inflight == 0 and len(p.pool._free) == p.pool.n_pages, \
            f"{p.client.peer_id} leaked pages through its drain"
    # crash failover: post-failure requests were re-routed and completed
    assert sched.rerouted, "crash did not force any re-route"
    crashed = {p.client.peer_id for p in prefillers if not p.alive}
    for rid in phases["D"]:
        assert sched.completed[rid]["prefiller"] not in crashed
    # decoders end clean: all pages + tail slots back
    for d in decoders:
        assert len(d.pool._free) == d.pool.n_pages
        n_tails = d.tail_buf.size // (cfg.vocab * 4)
        assert len(d._tail_free) == n_tails and not d._pending
    # every route went through an epoch view, and epochs only moved forward
    assert len(sched.routing_log) >= n_total
    assert sched.view_epochs == sorted(sched.view_epochs)
    assert len(set(sched.view_epochs)) == len(sched.view_epochs)

    def ttft(rids):
        return np.asarray([sched.completed[r]["ttft_us"] for r in rids])

    def tput(rids, t0):
        done = max(sched.completed[r]["done_us"] for r in rids)
        return len(rids) / max(done - t0, 1e-9) * 1e3   # req per virtual ms

    # ctrl-plane traffic on a clean fabric must never trip the deviation
    # detector (the always-on monitor rides along the whole elastic timeline)
    assert_no_flags(monitor, "bench_scaling")

    return {
        "phases": phases, "sched": sched, "scaler": scaler, "ctrl": ctrl,
        "slo": slo, "ttft": ttft, "tput": tput, "t_b": t_b, "t_d": t_d,
        "n_prefillers": len(prefillers),
        "metrics": finish_trace(tracer, OUT_DIR, "trace_scaling.json"),
    }


def run(report) -> None:
    n_a, n_b, n_d = (6, 6, 4) if SMOKE else (10, 10, 6)
    r = run_timeline(n_a, n_b, n_d)
    sched, scaler, ttft, tput = r["sched"], r["scaler"], r["ttft"], r["tput"]
    ph = r["phases"]
    rows = {}

    def emit(name, value, derived="", **extra):
        rows[name] = {"value": float(value), **extra}
        report(name, value, derived)

    a, b, d = ttft(ph["A"]), ttft(ph["B"]), ttft(ph["D"])
    up_ts = [t for t, kind, _ in scaler.decisions if kind == "up"]
    down_ts = [t for t, kind, _ in scaler.decisions if kind == "down"]
    emit("scale_ttft_p50_overload", float(np.percentile(a, 50)),
         f"us (1 prefiller, {len(a)} reqs; p95 {np.percentile(a, 95):.0f})",
         p95=float(np.percentile(a, 95)))
    emit("scale_ttft_p50_scaled", float(np.percentile(b, 50)),
         f"us (after scale-up at t={up_ts[0]:.0f}; "
         f"p95 {np.percentile(b, 95):.0f})",
         p95=float(np.percentile(b, 95)))
    emit("scale_ttft_p50_failover", float(np.percentile(d, 50)),
         f"us (crash at t={r['t_d'] + 100:.0f}, {len(sched.rerouted)} "
         f"re-routed, all completed)",
         p95=float(np.percentile(d, 95)))
    emit("scale_tput_overload", tput(ph["A"], 0.0), "req/ms virtual")
    emit("scale_tput_scaled", tput(ph["B"], r["t_b"]), "req/ms virtual")
    emit("scale_epochs", float(sched.view_epochs[-1]),
         f"membership epochs seen by scheduler "
         f"(ups {len(up_ts)}, downs {len(down_ts)}, "
         f"{r['n_prefillers']} prefillers total)",
         ups=len(up_ts), downs=len(down_ts),
         n_prefillers=r["n_prefillers"])
    emit("scale_drain_leaked_pages", 0.0,
         "KV pages leaked through drained scale-down (asserted)")
    # SLO tracker rows: sliding-window percentiles as the autoscaler saw
    # them, plus how often the configured p95 SLO was crossed (overload
    # and failover phases breach; the scaled phase recovers)
    slo = r["slo"]
    s = slo.summary()
    emit("scale_slo_ttft_p95", s["ttft_p95_us"],
         f"us sliding-window p95 over the last {slo.window} TTFTs "
         f"(p50 {s['ttft_p50_us']:.0f}, p99 {s['ttft_p99_us']:.0f}, "
         f"{s['breaches']} breach(es) of the {TTFT_SLO_US:.0f}us SLO)",
         p50=s["ttft_p50_us"], p99=s["ttft_p99_us"],
         breaches=s["breaches"], slo_us=TTFT_SLO_US)
    emit("scale_slo_queue_p95", s["queue_p95"],
         f"queue-depth sliding-window p95 (p99 {s['queue_p99']:.0f}) — "
         f"the percentile signal the autoscaler scales on",
         p99=s["queue_p99"])
    assert s["breaches"] >= 1, \
        "overload/failover phases never breached the TTFT SLO"
    # scale-up must beat the overloaded tail; failover must still complete
    assert np.percentile(b, 95) < np.percentile(a, 95), \
        "scale-up did not improve tail TTFT"

    os.makedirs(OUT_DIR, exist_ok=True)
    doc = {
        "bench": "scaling",
        "smoke": SMOKE,
        "config": {"n_a": n_a, "n_b": n_b, "n_d": n_d,
                   "gap_us": GAP_US, "layer_us": LAYER_US,
                   "ttft_slo_us": TTFT_SLO_US},
        "rows": rows,
    }
    if r["metrics"] is not None:
        doc["metrics"] = r["metrics"]
    with open(os.path.join(OUT_DIR, "BENCH_scaling.json"), "w") as f:
        json.dump(doc, f, indent=2, sort_keys=True)
        f.write("\n")
