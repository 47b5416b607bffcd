"""Multi-head latent attention, the sigmoid router with a selection bias and
the untied head (Moonlight-16B-A3B, the DeepSeek-V3 block) in the program:
the absorbed decode against the decompressed attention, the router's choice
and gates, the latent cache's schema and handoff, the head, and serving
through Scheduler/Prefiller/Decoder."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.core import Fabric
from repro.ctrl import ControlPlane
from repro.kvlayout import (TransferPlan, compile_plan, fill_cache,
                            schema_from_config, stage_cache)
from repro.models import init_cache, init_params
from repro.models.attention import init_mla, mla_decode, mla_prefill
from repro.models.model import _unembed, padded_vocab
from repro.models.moe import router_topk
from repro.obs import HostSpans
from repro.serving import Decoder, KvPool, Prefiller, Scheduler

MOONLIGHT = get_config("moonlight-16b-a3b")


def tiny(dtype="float32"):
    return dataclasses.replace(MOONLIGHT.reduced(), param_dtype=dtype)


def test_absorbed_decode_equals_decompressed_attention():
    """A decode step through the latent cache (W_uk folded into the query,
    W_uv into the output) gives what the decompressed prefill gives at that
    position.  float32 on the CPU: the forms differ only in the order of
    their sums, so 1e-5 of the output's scale."""
    cfg = tiny()
    p = init_mla(jax.random.PRNGKey(1), cfg, jnp.float32)
    p["norm"] = 0.1 * jax.random.normal(jax.random.PRNGKey(2), p["norm"].shape)
    p["kv_norm"] = 0.1 * jax.random.normal(jax.random.PRNGKey(3), p["kv_norm"].shape)
    S = 19
    x = jax.random.normal(jax.random.PRNGKey(4), (2, S + 1, cfg.d_model))
    with jax.default_matmul_precision("highest"):
        full, lat_full = mla_prefill(p, x, cfg, jnp.arange(S + 1))
        _, lat = mla_prefill(p, x[:, :S], cfg, jnp.arange(S))
        cache = jnp.pad(lat, ((0, 0), (0, 5), (0, 0)))
        out, cache = mla_decode(p, x[:, S:], cfg, cache=cache,
                                positions=jnp.full((2,), S, jnp.int32))
    assert cache.shape == (2, S + 5, cfg.latent_dim)
    want, got = np.asarray(full[:, S]), np.asarray(out[:, 0])
    assert np.abs(got - want).max() < 1e-5 * np.abs(want).max()
    # the row the step wrote is the prefill's row of that token
    np.testing.assert_allclose(np.asarray(cache[:, S]), np.asarray(lat_full[:, S]),
                               rtol=1e-6, atol=1e-6)
    assert not np.asarray(cache[:, S + 1:]).any()


def test_decode_reads_only_rows_up_to_its_position():
    """Rows past the step's position are masked: filling them changes
    nothing."""
    cfg = tiny()
    p = init_mla(jax.random.PRNGKey(5), cfg, jnp.float32)
    x = jax.random.normal(jax.random.PRNGKey(6), (1, 1, cfg.d_model))
    cache = jax.random.normal(jax.random.PRNGKey(7), (1, 12, cfg.latent_dim))
    pos = jnp.asarray([6], jnp.int32)
    a, _ = mla_decode(p, x, cfg, cache=cache, positions=pos)
    b, _ = mla_decode(p, x, cfg, cache=cache.at[:, 7:].set(1e3), positions=pos)
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_bias_chooses_but_does_not_weight():
    """Expert 5 has the lowest score; a bias lifts it into the top 2.  Its
    gate is its unbiased score, renormalised with the other chosen one, and
    the gates sum to the routed scale."""
    logits = jnp.asarray([[2.0, 1.5, 1.0, 0.5, 0.0, -3.0, -1.0, -2.0]])
    bias = jnp.zeros(8).at[5].set(2.0)
    s = jax.nn.sigmoid(logits[0])
    _, ids, _ = router_topk(logits, 2, "sigmoid", jnp.zeros(8), 2.446)
    assert sorted(np.asarray(ids[0]).tolist()) == [0, 1]
    gates, ids, _ = router_topk(logits, 2, "sigmoid", bias, 2.446)
    assert sorted(np.asarray(ids[0]).tolist()) == [0, 5]
    g = dict(zip(np.asarray(ids[0]).tolist(), np.asarray(gates[0]).tolist()))
    want = 2.446 * np.asarray([s[0], s[5]]) / (s[0] + s[5])
    np.testing.assert_allclose([g[0], g[5]], want, rtol=1e-6)
    assert float(gates.sum()) == pytest.approx(2.446, rel=1e-6)


@pytest.mark.parametrize("path", ["moe_a2a", "moe_ep_psum"])
def test_expert_parallel_paths_route_by_the_sigmoid_router(path):
    """The dispatch/combine path and its replicated-token fallback make the
    routing decision of ``moe_all_experts``, bias and scale included (one
    device, so no token is dropped for capacity); the bias moves that
    decision here."""
    from repro import comm
    from repro.launch.mesh import make_mesh
    from repro.models.moe import init_moe, moe_all_experts
    cfg = tiny()
    p = init_moe(jax.random.PRNGKey(2), cfg, jnp.float32)
    p["router_bias"] = 0.3 * jax.random.normal(jax.random.PRNGKey(3), (cfg.n_routed,))
    h = jax.random.normal(jax.random.PRNGKey(4), (4, cfg.d_model))
    mesh = make_mesh((1, 1), ("data", "model"))
    ep = getattr(comm, path)
    with jax.default_matmul_precision("highest"):
        y = np.asarray(jax.jit(lambda p, h: ep(p, h, cfg, "model", mesh)[0])(p, h))
        ref = jax.jit(lambda p, h: moe_all_experts(p, h, cfg)[0])
        y_ref = np.asarray(ref(p, h))
        unbiased = np.asarray(ref(dict(p, router_bias=0 * p["router_bias"]), h))
    assert np.abs(y - y_ref).max() <= 1e-5 * np.abs(y_ref).max()
    assert np.abs(unbiased - y_ref).max() > 1e-2 * np.abs(y_ref).max()


def test_softmax_router_is_unchanged():
    logits = jax.random.normal(jax.random.PRNGKey(8), (5, 16))
    gates, ids, _ = router_topk(logits, 3)
    probs = jax.nn.softmax(logits, -1)
    top, want_ids = jax.lax.top_k(probs, 3)
    np.testing.assert_array_equal(np.asarray(ids), np.asarray(want_ids))
    np.testing.assert_allclose(np.asarray(gates),
                               np.asarray(top / top.sum(-1, keepdims=True)),
                               rtol=1e-6)


def test_schema_holds_one_latent_row_a_token_a_layer():
    bf16 = dict(n_layers=5, param_dtype="bfloat16")
    s = schema_from_config(dataclasses.replace(MOONLIGHT, **bf16))
    assert [(c.name, c.kind, c.layers, c.token_bytes) for c in s.components] == [
        ("latent0", "token", (0,), 1152), ("latent", "token", (1, 2, 3, 4), 1152)]
    assert MOONLIGHT.latent_dim == 576
    # the cell's prompts: 5,760 B a prompt token, 7.1 times less than the
    # 40,960 B of deepseek-moe-16b's k/v at the same depth
    ds = schema_from_config(dataclasses.replace(get_config("deepseek-moe-16b"),
                                                **bf16))
    for seq in (1024, 2048, 4096):
        assert TransferPlan(s, seq).write_bytes == 5760 * seq
        assert TransferPlan(ds, seq).write_bytes == 40960 * seq


def test_stage_then_fill_returns_the_latent_cache_bit_for_bit():
    cfg = dataclasses.replace(tiny("bfloat16"), n_layers=3)
    schema = schema_from_config(cfg)
    S = 37
    plan = compile_plan(schema, schema, S)
    rng = np.random.default_rng(3)
    src = {k: jnp.asarray(rng.normal(size=v.shape), v.dtype)
           for k, v in init_cache(cfg, 1, plan.max_len).items()}
    assert set(src) == {"latent0", "latent"}
    fab = Fabric(seed=1)
    pool = KvPool(fab.add_engine("a", nic="efa"), schema, plan.n_slots)
    pages = pool.alloc(plan.n_slots)
    stage_cache(plan, pool, pages, src)
    got = fill_cache(plan, pool, pages, init_cache(cfg, 1, plan.max_len))
    for name in src:
        want = np.asarray(src[name])[:, :, :S].view(np.uint16)
        np.testing.assert_array_equal(got[name][:, :, :S].view(np.uint16), want)
        assert not got[name][:, :, S:].view(np.uint16).any()


def test_untied_head_is_used_and_tied_head_unchanged():
    cfg = tiny()
    p = init_params(cfg, jax.random.PRNGKey(0))
    assert p["lm_head"].shape == (cfg.d_model, padded_vocab(cfg))
    x = jax.random.normal(jax.random.PRNGKey(1), (1, 2, cfg.d_model))
    lg = _unembed(p, x, cfg)
    zero = _unembed(dict(p, lm_head=jnp.zeros_like(p["lm_head"])), x, cfg)
    assert not np.asarray(zero[..., :cfg.vocab]).any() and np.asarray(lg).any()
    # the embedding does not enter the untied logits
    np.testing.assert_array_equal(
        np.asarray(_unembed(dict(p, embed=2 * p["embed"]), x, cfg)), np.asarray(lg))
    tied = dataclasses.replace(cfg, tie_embeddings=True)
    pt = init_params(tied, jax.random.PRNGKey(0))
    assert "lm_head" not in pt
    h = np.asarray(x) * np.asarray(jax.lax.rsqrt(
        jnp.mean(x * x, -1, keepdims=True) + cfg.norm_eps))
    np.testing.assert_allclose(np.asarray(_unembed(pt, x, tied))[..., :cfg.vocab],
                               (h @ np.asarray(pt["embed"]).T)[..., :cfg.vocab],
                               rtol=1e-4, atol=1e-5)


def test_param_count():
    """Embedding and untied head 2 x 335.5 M, the dense layer 83.0 M, each
    MoE layer 584.8 M (13.8 M of it MLA): 3.09 B at the benchmark's 5
    layers, 15.96 B at the published 27."""
    d, h, v = 2048, 16, 163840
    mla = d * h * 192 + d * 576 + 512 * h * 256 + h * 128 * d
    assert mla == 13_762_560
    dense = mla + 3 * d * 11264
    moe = mla + d * 64 + 64 + (64 + 2) * 3 * d * 1408
    assert (dense, moe) == (82_968_576, 584_843_328)
    assert dataclasses.replace(MOONLIGHT, n_layers=5).param_count() == \
        2 * v * d + dense + 4 * moe == 3_093_430_528
    assert MOONLIGHT.param_count() == 2 * v * d + dense + 26 * moe == 15_959_983_744
    # a tied k/v model counts as before this count knew MLA
    assert get_config("deepseek-moe-16b").param_count() == 16_165_896_192


def test_served_equals_one_process():
    """A tiny Moonlight through Scheduler -> Prefiller -> latent handoff ->
    Decoder gives the tokens of prefill plus decode_step in one process, and
    counts the latent bytes it moved and the rows its steps read."""
    from repro.launch.serve import monolithic
    cfg = dataclasses.replace(tiny(), n_layers=3)
    params = init_params(cfg, jax.random.PRNGKey(0))
    fab = Fabric(seed=3)
    rec = HostSpans()
    fab.attach_spans(rec)
    ctrl = ControlPlane(fab, nic="efa", max_sweeps=64)
    pf = Prefiller(fab, "p0", cfg, params, nic="efa", ctrl=ctrl, max_renewals=64)
    dec = Decoder(fab, "d0", cfg, params, nic="efa", ctrl=ctrl, max_renewals=64)
    sched = Scheduler(fab, ctrl)
    ids = np.random.default_rng(0).integers(0, cfg.vocab, size=48)
    rid = sched.submit(ids, n_decode=5)
    fab.run()
    sched.check_drained()
    assert sched.completed[rid]["tokens"] == monolithic(
        cfg, params, [ids], 5, max_len=len(ids) + 13)[0]
    # 48 prompt tokens, 3 whole pages of 16: one float32 latent row a token
    # and a layer, nothing else
    assert rec.counters["kv.staged_bytes"] == rec.counters["kv.filled_bytes"] \
        == 48 * cfg.latent_dim * 4 * cfg.n_layers
    # 4 steps at positions 48..51 read 49..52 rows in each of 3 layers
    assert rec.counters["decoder.cache_rows"] == 3 * (49 + 50 + 51 + 52)
    assert len(pf.pool._free) == pf.pool.n_pages
    assert len(dec.pool._free) == dec.pool.n_pages
