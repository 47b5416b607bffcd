"""repro.kvlayout: schema derivation goldens, plan round-trips, ImmCounter
parity, exact-coverage property tests, and e2e disagg == monolithic for
every formerly guarded cache family."""

import jax
import numpy as np
import pytest
from _hypothesis_compat import given, settings, st

from repro.configs import ARCH_IDS, get_config
from repro.core import Fabric
from repro.ctrl import ControlPlane
from repro.kvlayout import (DECODE_MARGIN, KvSchema, TransferPlan,
                            compile_plan, fill_cache, handoff_max_len,
                            schema_from_config, stage_cache)
from repro.models import init_cache, init_params
from repro.serving import Decoder, KvPool, Prefiller, Scheduler


# ---------------------------------------------------------------------------
# schema derivation goldens (one per ModelConfig family)
# ---------------------------------------------------------------------------

def _schema(arch):
    return schema_from_config(get_config(arch).reduced())


def test_schema_uniform_dense():
    s = _schema("stablelm-3b")
    assert [(c.name, c.kind, c.layers) for c in s.components] == [
        ("k", "token", (0, 1)), ("v", "token", (0, 1))]
    cfg = get_config("stablelm-3b").reduced()
    assert s.component("k").token_bytes == cfg.n_kv_heads * cfg.head_dim * 4


def test_schema_gemma3_pattern_split():
    s = _schema("gemma3-1b")
    assert [(c.name, c.kind, c.layers) for c in s.components] == [
        ("lk", "ring", (0,)), ("lv", "ring", (0,)),
        ("sk", "token", (1,)), ("sv", "token", (1,))]
    cfg = get_config("gemma3-1b").reduced()
    lk = s.component("lk")
    assert lk.window == cfg.window
    # ring transfers min(max_len, window) slots regardless of prompt length
    assert lk.tokens(4, handoff_max_len(4)) == cfg.window


def test_schema_vlm_cross():
    s = _schema("llama-3.2-vision-90b")
    assert [(c.name, c.kind) for c in s.components] == [
        ("lk", "token"), ("lv", "token"), ("sk", "fixed"), ("sv", "fixed")]
    cfg = get_config("llama-3.2-vision-90b").reduced()
    assert s.component("sk").fixed_tokens == cfg.vision_seq
    # cross K/V extent is vision-determined, independent of the prompt
    assert s.component("sk").tokens(3, handoff_max_len(3)) == cfg.vision_seq


def test_schema_ssm_and_hybrid():
    s = _schema("mamba2-780m")
    assert [(c.name, c.kind, c.layers) for c in s.components] == [
        ("conv", "blob", (0, 1)), ("ssd", "blob", (0, 1))]
    cfg = get_config("mamba2-780m").reduced()
    assert s.component("ssd").blob_bytes == (
        cfg.ssm_nheads * cfg.ssm_headdim * cfg.ssm_state * 4)
    h = _schema("zamba2-1.2b")
    assert [(c.name, c.kind) for c in h.components] == [
        ("conv", "blob"), ("ssd", "blob"), ("ak", "ring"), ("av", "ring")]
    # the shared-attn ring unlocks after its group's LAST mamba layer
    assert h.component("ak").layers == (1,)


def test_schema_first_k_dense():
    s = _schema("deepseek-moe-16b")
    assert [(c.name, c.layers) for c in s.components] == [
        ("k0", (0,)), ("v0", (0,)), ("k", (1,)), ("v", (1,))]


def test_schema_wire_roundtrip_and_mismatch():
    for arch in ARCH_IDS:
        s = _schema(arch)
        assert KvSchema.from_wire(s.to_wire()) == s
    a, b = _schema("gemma3-1b"), _schema("stablelm-3b")
    assert a.mismatch(a) is None
    assert "component sets differ" in a.mismatch(b)
    assert "no KvSchema" in a.mismatch(None)
    c = schema_from_config(get_config("gemma3-1b").reduced(), page_tokens=8)
    assert "page_tokens" in a.mismatch(c)
    with pytest.raises(ValueError, match="incompatible"):
        compile_plan(a, b, 16)


def test_schema_matches_init_cache_shapes():
    """Every component's byte geometry equals the model's actual cache
    arrays — the schema IS init_cache, declaratively."""
    for arch in ARCH_IDS:
        cfg = get_config(arch).reduced()
        s = _schema(arch)
        S = 11
        ml = handoff_max_len(S)
        cache = init_cache(cfg, 1, ml)
        assert set(s.names()) <= set(cache.keys())
        for comp in s.components:
            arr = np.asarray(cache[comp.name])
            assert arr.shape[0] == comp.n_stack, (arch, comp.name)
            assert arr.dtype == np.dtype(comp.dtype), (arch, comp.name)
            if comp.kind == "blob":
                assert arr[0, 0].nbytes == comp.blob_bytes, (arch, comp.name)
            else:
                # token axis is 2; per-token bytes must match
                assert arr[0, 0, 0].nbytes == comp.token_bytes, (arch, comp.name)
                assert arr.shape[2] >= comp.tokens(S, ml), (arch, comp.name)
            # every producing layer is a real model layer
            assert all(0 <= l < cfg.n_layers for l in comp.layers)


# ---------------------------------------------------------------------------
# plan round-trip over the fabric: bytes conservation + ImmCounter parity
# ---------------------------------------------------------------------------

def _random_cache(cfg, max_len, rng):
    return {k: rng.normal(size=v.shape).astype(np.asarray(v).dtype)
            for k, v in init_cache(cfg, 1, max_len).items()}


@pytest.mark.parametrize("arch", ["stablelm-3b", "gemma3-1b", "mamba2-780m",
                                  "zamba2-1.2b", "deepseek-moe-16b",
                                  "llama-3.2-vision-90b"])
def test_plan_roundtrip_conserves_bytes(arch):
    """stage -> span-scatter over the simulated fabric -> fill reproduces
    every valid component byte; ImmCounter expectations match the writes
    a monolithic full-state copy would count."""
    cfg = get_config(arch).reduced()
    schema = _schema(arch)
    S = 37
    plan = compile_plan(schema, schema, S)
    rng = np.random.default_rng(7)
    src_cache = _random_cache(cfg, plan.max_len, rng)

    fab = Fabric(seed=1)
    a = fab.add_engine("a", nic="efa")
    b = fab.add_engine("b", nic="efa")
    pa, pb = KvPool(a, schema, 64), KvPool(b, schema, 64)
    src_pages, dst_pages = pa.alloc(plan.n_slots), pb.alloc(plan.n_slots)
    stage_cache(plan, pa, src_pages, src_cache)

    fired = []
    for off, count in plan.expected_counts():
        b.expect_imm_count(100 + off, count, lambda off=off: fired.append(off))
    # submit layer-by-layer (worst-case span fragmentation): per span the
    # submission is still ONE WrBatch no matter how many components ride it
    sent = 0
    for l in range(cfg.n_layers):
        before = a.batch_stats.batches
        n = plan.submit_span(a, pa.handle, src_pages, pb.desc, dst_pages,
                             100, l, l + 1)
        sent += n
        assert a.batch_stats.batches == before + (1 if n else 0)
    assert sent == plan.total_writes
    fab.run()
    # ImmCounter parity: every component completed exactly at its count
    assert sorted(fired) == [off for off, _ in plan.expected_counts()]
    for off, count in plan.expected_counts():
        assert b.counters[0].value(100 + off) == count

    got = fill_cache(plan, pb, dst_pages, init_cache(cfg, 1, plan.max_len))
    total_valid = 0
    for comp in schema.components:
        t = comp.tokens(S, plan.max_len)
        src, dst = src_cache[comp.name], got[comp.name]
        if comp.kind == "blob":
            np.testing.assert_array_equal(src, dst)
            total_valid += src.nbytes
        else:
            np.testing.assert_array_equal(src[:, :, :t], dst[:, :, :t])
            total_valid += comp.n_stack * t * comp.token_bytes
    # bytes conservation vs a monolithic copy of the same state
    assert total_valid == schema.total_bytes(S)


@settings(max_examples=30, deadline=None)
@given(st.sampled_from(["stablelm-3b", "gemma3-1b", "mamba2-780m",
                        "zamba2-1.2b", "deepseek-moe-16b",
                        "llama-3.2-vision-90b"]),
       st.integers(1, 70), st.sampled_from([4, 8, 16]))
def test_plan_covers_every_component_byte_exactly_once(arch, S, page_tokens):
    """Property: for any schema, the union of all layer spans covers every
    component's valid byte range exactly once — no slot repeated, no byte
    of any component skipped or double-written."""
    cfg = get_config(arch).reduced()
    schema = schema_from_config(cfg, page_tokens)
    plan = TransferPlan(schema, S)
    seen = set()
    per_comp = {ci: 0 for ci in range(len(schema.components))}
    for l in range(cfg.n_layers):
        for ci, slot in plan.span_writes(l, l + 1):
            assert slot not in seen            # exactly once
            seen.add(slot)
            per_comp[ci] += 1
    assert len(seen) == plan.n_slots == plan.total_writes
    for ci, comp in enumerate(schema.components):
        t = comp.tokens(S, plan.max_len)
        covered = per_comp[ci] * comp.page_len(page_tokens)
        need = comp.n_stack * comp.layer_bytes(S, plan.max_len)
        assert covered >= need                 # pages cover all valid bytes
        if comp.kind == "blob":
            assert covered == need             # blobs are exact
        else:
            # padding never exceeds one page per stack layer
            assert covered - need < comp.n_stack * comp.page_len(page_tokens)
    # expectation map totals the same writes
    assert sum(c for _, c in plan.expected_counts()) == plan.total_writes


def test_hand_wired_schema_mismatch_raises_before_any_write():
    """Peers wired without the control plane (no routing-time gate) still
    fail loudly: the prefiller validates the DispatchReq's schema before
    the first WRITE instead of hanging on unmet expectations."""
    cfg = get_config("stablelm-3b").reduced()
    fab = Fabric(seed=2)
    pf = Prefiller(fab, "p0", cfg, None, nic="efa", page_tokens=16)
    dec = Decoder(fab, "d0", cfg, None, nic="efa", page_tokens=8)
    dec.submit(0, np.arange(20) % cfg.vocab, pf.address(), n_decode=2)
    with pytest.raises(ValueError, match="page_tokens"):
        fab.run()


def test_n_decode_beyond_margin_rejected():
    cfg = get_config("stablelm-3b").reduced()
    fab = Fabric(seed=2)
    pf = Prefiller(fab, "p0", cfg, None, nic="efa")
    dec = Decoder(fab, "d0", cfg, None, nic="efa")
    with pytest.raises(ValueError, match="DECODE_MARGIN"):
        dec.submit(0, np.arange(8), pf.address(), n_decode=DECODE_MARGIN + 1)


def test_pool_shared_allocator_across_components():
    """One free list serves every component: slots are interchangeable."""
    schema = _schema("zamba2-1.2b")
    fab = Fabric(seed=0)
    e = fab.add_engine("n", nic="efa")
    pool = KvPool(e, schema, 8)
    assert pool.slot_bytes == schema.slot_bytes
    a = pool.alloc(5)
    pool.free(a)
    b = pool.alloc(8)                # drains the whole pool
    assert set(a) <= set(b)          # recycled slots serve any component
    assert pool._free == []
    with pytest.raises(RuntimeError, match="exhausted"):
        pool.alloc(1)
    pool.free(b)
    assert len(pool._free) == pool.n_pages


# ---------------------------------------------------------------------------
# e2e: disagg == monolithic for every formerly guarded family
# ---------------------------------------------------------------------------

def _mono_generate(cfg, params, ids, n_decode, vision_emb=None):
    # the launcher's reference loop — deliberately shared, but with a
    # DIFFERENT max_len than the handoff convention, proving the outputs
    # are invariant to the cache headroom (f32)
    from repro.launch.serve import monolithic
    return monolithic(cfg, params, [ids], n_decode, vision_emb,
                      max_len=len(ids) + n_decode + 8)[0]


@pytest.mark.slow
@pytest.mark.parametrize("arch", ["gemma3-1b",            # pattern-split
                                  "mamba2-780m",          # SSM
                                  "zamba2-1.2b",          # hybrid
                                  "deepseek-moe-16b",     # first-k-dense
                                  "llama-3.2-vision-90b"  # vlm cross
                                  ])
def test_disagg_equals_monolithic_all_families(arch):
    cfg = get_config(arch).reduced()
    params = init_params(cfg, jax.random.PRNGKey(0))
    rng = np.random.default_rng(0)
    vis = (rng.normal(size=(cfg.vision_seq, cfg.vision_dim))
           .astype(np.float32) if cfg.family == "vlm" else None)
    fab = Fabric(seed=3)
    ctrl = ControlPlane(fab, nic="efa", max_sweeps=64)
    pf = Prefiller(fab, "p0", cfg, params, nic="efa", ctrl=ctrl,
                   max_renewals=64)
    dec = Decoder(fab, "d0", cfg, params, nic="efa", ctrl=ctrl,
                  max_renewals=64)
    sched = Scheduler(fab, ctrl)
    ids = rng.integers(0, cfg.vocab, size=37)
    rid = sched.submit(ids, n_decode=5, vision_emb=vis)
    fab.run()
    sched.check_drained()
    r = sched.completed[rid]
    assert r["tokens"] == _mono_generate(cfg, params, ids, 5, vis)
    assert r["ttft_us"] > 0
    # hot-path contract: ONE WrBatch enqueue per completed layer span plus
    # one for the tail write, regardless of schema complexity
    assert len(pf.span_log) >= 1
    assert pf.engine.batch_stats.batches == len(pf.span_log) + 1
    assert sum(n for _, _, _, n in pf.span_log) == \
        sum(c for _, c in dec._plan(len(ids)).expected_counts())
    # nothing leaked on either side
    assert len(pf.pool._free) == pf.pool.n_pages
    assert len(dec.pool._free) == dec.pool.n_pages
