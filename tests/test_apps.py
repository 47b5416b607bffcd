"""Integration tests for the three production systems (§4, §5, §6)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from _hypothesis_compat import given, settings, st

from repro.configs import get_config
from repro.core import Fabric
from repro.ctrl import ControlPlane
from repro.models import decode_step, init_params, prefill
from repro.moekit import MoEConfig, make_endpoints, oracle, run_moe_layer
from repro.rlweights import (ParamMeta, compute_routing, make_cluster,
                             p2p_transfer, rank0_transfer, schedule_stats,
                             verify_contents)
from repro.serving import Decoder, Prefiller, Scheduler


# ---------------------------------------------------------------------------
# §4 KvCache transfer
# ---------------------------------------------------------------------------

def _mono_generate(cfg, params, ids, n_decode):
    lg, cache = prefill(params, jnp.asarray(ids)[None], cfg,
                        max_len=len(ids) + 64, moe_mode="dense")
    toks = [int(jnp.argmax(lg[0]))]
    pos = len(ids)
    for _ in range(n_decode - 1):
        lg, cache = decode_step(params, jnp.asarray([[toks[-1]]]),
                                jnp.asarray([pos], jnp.int32), cache, cfg,
                                moe_mode="dense")
        toks.append(int(jnp.argmax(lg[0])))
        pos += 1
    return toks


@pytest.mark.slow
@pytest.mark.parametrize("nic", ["efa", "cx7"])
def test_disaggregated_equals_monolithic(nic):
    cfg = get_config("stablelm-3b").reduced()
    params = init_params(cfg, jax.random.PRNGKey(0))
    fab = Fabric(seed=3)
    ctrl = ControlPlane(fab, nic=nic, max_sweeps=64)
    Prefiller(fab, "p0", cfg, params, nic=nic, ctrl=ctrl, max_renewals=64)
    Decoder(fab, "d0", cfg, params, nic=nic, ctrl=ctrl, max_renewals=64)
    sched = Scheduler(fab, ctrl)
    ids = np.random.default_rng(0).integers(0, cfg.vocab, size=37)
    rid = sched.submit(ids, n_decode=5)
    fab.run()
    r = sched.completed[rid]
    assert r["tokens"] == _mono_generate(cfg, params, ids, 5)
    assert r["ttft_us"] > 0


@pytest.mark.slow
def test_disagg_multiple_requests_and_page_reuse():
    cfg = get_config("stablelm-3b").reduced()
    params = init_params(cfg, jax.random.PRNGKey(0))
    fab = Fabric(seed=5)
    ctrl = ControlPlane(fab, nic="efa", max_sweeps=64)
    Prefiller(fab, "p0", cfg, params, nic="efa", ctrl=ctrl, max_renewals=64)
    dec = Decoder(fab, "d0", cfg, params, nic="efa", ctrl=ctrl,
                  max_renewals=64)
    sched = Scheduler(fab, ctrl)
    rng = np.random.default_rng(1)
    rids = [sched.submit(rng.integers(0, cfg.vocab, size=20 + 3 * i),
                         n_decode=3) for i in range(3)]
    fab.run()
    for rid in rids:
        assert len(sched.completed[rid]["tokens"]) == 3
    # all pages returned to the pool
    assert len(dec.pool._free) == dec.pool.n_pages


def test_disagg_bf16_equals_monolithic():
    """bf16 KV handoff: the schema carries the wire dtype by name (bf16's
    ``dtype.str`` is ``"<V2"``, which numpy cannot cast from), and the
    disaggregated tokens match the monolithic ones at the reduced config."""
    import dataclasses
    from repro.kvlayout import schema_from_config
    from repro.launch import serve
    cfg = dataclasses.replace(get_config("stablelm-3b").reduced(),
                              param_dtype="bfloat16")
    assert {c.dtype for c in schema_from_config(cfg).components} == \
        {"bfloat16"}
    params = serve.init_serving_params(cfg)
    prompts, _ = serve.make_requests(cfg, 2, 40)
    mono = serve.monolithic(cfg, params, prompts, 4)
    done, _ = serve.disaggregated(cfg, params, prompts, 4)
    assert [r["tokens"] for r in done] == mono


def test_serving_steady_state_compiles_nothing():
    """After one warm-up request, further requests of the same shape run
    the jitted prefill/decode programs without a single compilation."""
    from repro.launch import serve
    from repro.launch.cache import CompileCounter
    cfg = serve.serving_config("stablelm-3b", full=False)
    params = serve.init_serving_params(cfg)
    prompts, _ = serve.make_requests(cfg, 3, 32)
    with CompileCounter() as warm:
        serve.monolithic(cfg, params, prompts[:1], 4)
        serve.disaggregated(cfg, params, prompts[:1], 4)
    assert warm.by_name["jit(prefill)"] and warm.by_name["jit(decode_step)"]
    with CompileCounter() as steady:
        serve.monolithic(cfg, params, prompts[1:], 4)
        serve.disaggregated(cfg, params, prompts[1:], 4)
    assert steady.count == 0, dict(steady.by_name)


def test_scheduler_drops_crashed_prefiller_from_view():
    """A crashed prefiller stops renewing its lease; the control plane
    declares it dead and the scheduler's routable view excludes it."""
    cfg = get_config("stablelm-3b").reduced()
    params = init_params(cfg, jax.random.PRNGKey(0))
    fab = Fabric(seed=6)
    ctrl = ControlPlane(fab, nic="efa", lease_us=1_000.0, sweep_us=250.0,
                        max_sweeps=64)
    p0 = Prefiller(fab, "p0", cfg, params, nic="efa", ctrl=ctrl,
                   renew_us=250.0, max_renewals=64)
    p1 = Prefiller(fab, "p1", cfg, params, nic="efa", ctrl=ctrl,
                   renew_us=250.0, max_renewals=64)
    Decoder(fab, "d0", cfg, params, nic="efa", ctrl=ctrl, max_renewals=64)
    sched = Scheduler(fab, ctrl)
    fab.loop.schedule(100.0, p0.crash)
    fab.run()
    assert ctrl.registry.record("p0") is None
    assert any(e.startswith("dead:p0") for _, e in ctrl.registry.epoch_log)
    routable = [p.peer_id for p in sched.view.routable("prefill")]
    assert routable == [p1.client.peer_id] == ["p1"]


def test_prefiller_cancellation_stops_transfers():
    cfg = get_config("stablelm-3b").reduced()
    params = init_params(cfg, jax.random.PRNGKey(0))
    fab = Fabric(seed=8)
    pf = Prefiller(fab, "p0", cfg, params, nic="efa")
    dec = Decoder(fab, "d0", cfg, params, nic="efa")
    pf.cancel(0)
    dec.submit(0, np.arange(24) % cfg.vocab, pf.address(), n_decode=2)
    fab.run()
    assert "tokens" not in dec.results.get(0, {})


# ---------------------------------------------------------------------------
# §5 RL weight transfer
# ---------------------------------------------------------------------------

@settings(max_examples=20, deadline=None)
@given(st.integers(1, 8), st.integers(1, 8), st.integers(1, 4),
       st.integers(1, 6))
def test_routing_covers_every_inference_byte(n_train, n_infer_rep, tp, n_params):
    n_infer = n_infer_rep * tp
    params = [ParamMeta(f"w{i}", (64, 8 * (i + 1)), 2) for i in range(n_params)]
    routes, sizes = compute_routing(params, n_train, n_infer, infer_tp=tp)
    # every inference rank's buffer must be covered exactly once
    for r in range(n_infer):
        need = sizes["infer"][r]
        cover = np.zeros(need, np.int32)
        for rt in routes:
            if rt.infer_rank == r:
                cover[rt.dst_off:rt.dst_off + rt.nbytes] += 1
        assert (cover == 1).all(), f"rank {r}: coverage {cover.min()}..{cover.max()}"


def test_p2p_and_rank0_move_identical_bytes():
    params = [ParamMeta(f"w{i}", (256, 256), 2) for i in range(8)]
    routes, sizes = compute_routing(params, 4, 2, infer_tp=2)
    shard = max(sizes["train"].values())
    infb = max(sizes["infer"].values())
    c1 = make_cluster(4, 2, shard, infb, nic="cx7", seed=1)
    p2p_transfer(c1, routes)
    assert verify_contents(c1, routes)
    c2 = make_cluster(4, 2, shard, infb, nic="cx7", seed=1)
    rank0_transfer(c2, routes)
    assert verify_contents(c2, routes)
    for a, b in zip(c1.infer_bufs, c2.infer_bufs):
        assert np.array_equal(a, b)


def test_p2p_beats_rank0_and_scales():
    params = [ParamMeta(f"w{i}", (512, 512), 2) for i in range(16)]
    speeds = []
    for n_train in (4, 16):
        routes, sizes = compute_routing(params, n_train, 4, infer_tp=2)
        shard = max(sizes["train"].values())
        infb = max(sizes["infer"].values())
        ca = make_cluster(n_train, 4, shard, infb, nic="cx7")
        ra = p2p_transfer(ca, routes)
        cb = make_cluster(n_train, 4, shard, infb, nic="cx7")
        rb = rank0_transfer(cb, routes)
        speeds.append(rb["total_us"] / ra["total_us"])
    assert speeds[0] > 1.5
    assert speeds[1] > speeds[0]  # the gap grows with cluster size


# ---------------------------------------------------------------------------
# §6 MoE dispatch/combine
# ---------------------------------------------------------------------------

@settings(max_examples=10, deadline=None)
@given(st.integers(0, 2**16), st.sampled_from([2, 4]), st.integers(1, 2),
       st.sampled_from([4, 9]), st.sampled_from([0, 2, 64]))
def test_moekit_matches_oracle(seed, N, k_half, T, t_priv):
    rng = np.random.default_rng(seed)
    E, R, elems = 2 * N, 2 * k_half, 16
    cfgk = MoEConfig(n_ranks=N, n_experts=E, top_k=R, max_tokens=T,
                     token_bytes=elems * 4, t_priv=max(t_priv, 1))
    fab = Fabric(seed=seed)
    eps = make_endpoints(fab, cfgk, nic="efa", gpus_per_node=2)
    tokens, eids, gates = [], [], []
    for r in range(N):
        tokens.append(rng.normal(size=(T, elems)).astype(np.float32))
        ei = np.stack([rng.choice(E, R, replace=False) for _ in range(T)]).astype(np.int32)
        eids.append(ei)
        g = np.zeros((T, E), np.float32)
        for t in range(T):
            w = rng.random(R)
            g[t, ei[t]] = w / w.sum()
        gates.append(g)
    f = lambda e, x: np.tanh(x) * (e + 1)
    res, stats = run_moe_layer(fab, eps, tokens, eids, gates, f)
    ref = oracle(tokens, eids, gates, f, E)
    for r in range(N):
        np.testing.assert_allclose(res[r], ref[r], rtol=1e-4, atol=1e-4)
    assert all(d > 0 for d in stats["dispatch_us"])


def test_moekit_multi_round():
    """Two MoE layers back to back (round-scoped imm values)."""
    rng = np.random.default_rng(3)
    N, E, R, T, elems = 2, 4, 2, 8, 8
    cfgk = MoEConfig(n_ranks=N, n_experts=E, top_k=R, max_tokens=T,
                     token_bytes=elems * 4, t_priv=2)
    fab = Fabric(seed=3)
    eps = make_endpoints(fab, cfgk, nic="cx7", gpus_per_node=2)
    for layer in range(2):
        tokens = [rng.normal(size=(T, elems)).astype(np.float32) for _ in range(N)]
        eids = [np.stack([rng.choice(E, R, replace=False) for _ in range(T)]).astype(np.int32)
                for _ in range(N)]
        gates = []
        for r in range(N):
            g = np.zeros((T, E), np.float32)
            for t in range(T):
                g[t, eids[r][t]] = 1.0 / R
            gates.append(g)
        f = lambda e, x: x + e
        res, _ = run_moe_layer(fab, eps, tokens, eids, gates, f)
        ref = oracle(tokens, eids, gates, f, E)
        for r in range(N):
            np.testing.assert_allclose(res[r], ref[r], rtol=1e-4, atol=1e-4)
