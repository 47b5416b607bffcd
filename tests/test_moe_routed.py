"""Routed-only MoE (``moe_routed_only``): the same routing and, to rounding,
the same output as the all-experts oracle, chosen by ``moe_path`` from the
static token count, and counted per request by ``decoder.routed_steps``."""

import dataclasses
import itertools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.core import Fabric
from repro.core.domain import MemoryRegion
from repro.ctrl import ControlPlane
from repro.models import model as M
from repro.models import moe
from repro.obs import HostSpans
from repro.serving import Decoder, Prefiller, Scheduler


def _cfg(name: str):
    """float32 test configurations: (E 8, k 2) and the reduced DeepSeekMoE."""
    base = dataclasses.replace(get_config("deepseek-moe-16b").reduced(),
                               param_dtype="float32")
    if name == "e8k2":
        return dataclasses.replace(base, n_routed=8, top_k=2)
    return base


@pytest.mark.parametrize("T", [1, 2, 3])
@pytest.mark.parametrize("name", ["e8k2", "deepseek-moe-16b-reduced"])
def test_routed_only_equals_all_experts(name, T):
    cfg = _cfg(name)
    p = moe.init_moe(jax.random.PRNGKey(0), cfg, jnp.float32)
    h = jax.random.normal(jax.random.PRNGKey(1), (T, cfg.d_model))
    with jax.default_matmul_precision("highest"):
        y, aux = jax.jit(lambda p, h: moe.moe_routed_only(p, h, cfg))(p, h)
        y_ref, aux_ref = jax.jit(lambda p, h: moe.moe_all_experts(p, h, cfg))(p, h)
    y, y_ref = np.asarray(y), np.asarray(y_ref)
    assert np.abs(y - y_ref).max() <= 1e-5 * np.abs(y_ref).max()
    assert float(aux) == float(aux_ref)      # same router call, same routing


@pytest.mark.parametrize("arch,T,path", [
    ("deepseek-moe-16b", 1, "routed"),       # decode: 6 of 64 experts
    ("deepseek-moe-16b", 10, "routed"),      # 60 < 64
    ("deepseek-moe-16b", 11, "all"),         # 66 > 64
    ("deepseek-moe-16b", 64, "all"),         # prefill lengths
    ("deepseek-moe-16b", 256, "all"),
    ("qwen3-moe-30b-a3b", 15, "routed"),     # 120 < 128
    ("qwen3-moe-30b-a3b", 16, "all"),        # 128 == 128
    ("e8k2", 3, "routed"),
    ("e8k2", 4, "all"),                      # T*k == E stays all-experts
])
def test_moe_path_is_routed_iff_fewer_slices_than_experts(arch, T, path):
    cfg = _cfg(arch) if arch == "e8k2" else get_config(arch)
    assert moe.moe_path(T, cfg) == path
    assert (path == "routed") == (T * cfg.top_k < cfg.n_routed)


def _oracle_decode_step(monkeypatch):
    """``decode_step`` with the all-experts path forced, in a jit of its
    own so that no program traced with the routed path is reused."""
    monkeypatch.setattr(moe, "moe_path", lambda n_tokens, cfg: "all")
    return jax.jit(lambda *a, **kw: M.decode_step(*a, **kw),
                   static_argnames=("cfg", "moe_mode"))


def test_greedy_decode_matches_all_experts_oracle(monkeypatch):
    cfg = _cfg("deepseek-moe-16b-reduced")
    assert moe.moe_path(1, cfg) == "routed"
    params = M.init_params(cfg, jax.random.PRNGKey(0))
    prompt = jax.random.randint(jax.random.PRNGKey(2), (1, 8), 0, cfg.vocab)

    def greedy(step):
        logits, cache = M.prefill_jit(params, prompt, cfg, max_len=32,
                                      moe_mode="dense")
        toks, out = [int(jnp.argmax(logits[0]))], []
        for pos in range(8, 16):
            logits, cache = step(params, jnp.asarray([[toks[-1]]]),
                                 jnp.asarray([pos], jnp.int32), cache,
                                 cfg=cfg, moe_mode="dense")
            out.append(np.asarray(logits[0]))
            toks.append(int(jnp.argmax(logits[0])))
        return toks, np.stack(out)

    with jax.default_matmul_precision("highest"):
        toks, logits = greedy(M.decode_step_jit)
        toks_ref, logits_ref = greedy(_oracle_decode_step(monkeypatch))
    assert toks == toks_ref
    assert np.abs(logits - logits_ref).max() <= 1e-4 * np.abs(logits_ref).max()


PROMPTS = (20, 37)
N_DECODE = 4


@pytest.mark.parametrize("arch,routed", [("deepseek-moe-16b", True),
                                         ("stablelm-3b", False)])
def test_routed_steps_counted_per_request(arch, routed, monkeypatch):
    """One prefiller and one decoder serve two requests with spans
    attached: an MoE model counts every decode step after the first token,
    a dense model none."""
    cfg = get_config(arch).reduced()
    params = M.init_params(cfg, jax.random.PRNGKey(0))
    monkeypatch.setattr(MemoryRegion, "_ids", itertools.count())
    fab = Fabric(seed=3)
    rec = HostSpans()
    fab.attach_spans(rec)
    ctrl = ControlPlane(fab, nic="efa", max_sweeps=64)
    Prefiller(fab, "p0", cfg, params, nic="efa", ctrl=ctrl, max_renewals=64)
    Decoder(fab, "d0", cfg, params, nic="efa", ctrl=ctrl, max_renewals=64)
    sched = Scheduler(fab, ctrl)
    rng = np.random.default_rng(0)
    rids = [sched.submit(rng.integers(0, cfg.vocab, size=n), n_decode=N_DECODE)
            for n in PROMPTS]
    fab.run()
    assert all(len(sched.completed[r]["tokens"]) == N_DECODE for r in rids)
    want = len(PROMPTS) * (N_DECODE - 1) if routed else 0
    assert rec.counters["decoder.routed_steps"] == want


def test_routed_decode_step_reads_under_half_the_bytes(monkeypatch):
    """At E 64, k 6 and small widths, the CPU compiler's cost analysis of
    the decode step counts the routed experts' slices, not all 64."""
    cfg = dataclasses.replace(_cfg("deepseek-moe-16b-reduced"),
                              n_routed=64, top_k=6)
    params = jax.eval_shape(lambda: M.init_params(cfg, jax.random.PRNGKey(0)))
    cache = jax.eval_shape(lambda: M.init_cache(cfg, 1, 32))
    args = (params, jax.ShapeDtypeStruct((1, 1), jnp.int32),
            jax.ShapeDtypeStruct((1,), jnp.int32), cache)

    def bytes_accessed(step):
        ca = step.lower(*args, cfg=cfg, moe_mode="dense").compile().cost_analysis()
        return (ca[0] if isinstance(ca, list) else ca)["bytes accessed"]

    routed = bytes_accessed(M.decode_step_jit)
    oracle = bytes_accessed(_oracle_decode_step(monkeypatch))
    assert routed < 0.5 * oracle
