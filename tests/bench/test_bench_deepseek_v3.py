"""The ``deepseek_v3`` architecture module (Moonlight-16B-A3B) and what its
cell adds: FLOP and byte counts against hand counts, the program against
the plain reference through several decode steps, the configuration's
refusals, the long-prompt mix, and the two roofline readers."""

import json
import pathlib
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import benchtest
from harness import arch, device, model, traffic
from harness.spans import Recorder

ROOT = pathlib.Path(__file__).resolve().parents[2]
CONF = model.load(ROOT / "bench" / "configs" / "moonlight-16b-a3b.json")
V3 = arch.get(CONF)
CELL = "moonlight-16b-a3b.long-prompt"

# two layers, the first dense: every count below by hand
SMALL = {"d_model": 4, "n_heads": 2, "d_ff": 8, "vocab": 10, "n_routed": 8,
         "n_shared": 1, "top_k": 2, "d_ff_expert": 3, "kv_lora_rank": 6,
         "qk_nope_head_dim": 2, "qk_rope_head_dim": 2, "v_head_dim": 3,
         "param_bytes": 2, "ffn_kinds": ["dense", "moe"]}


def test_hand_counts():
    # MLA: q 4*2*4 = 32, latent 4*(6+2) = 32, expansion 6*2*(2+3) = 60,
    # output 2*3*4 = 24 -> 148 a layer
    assert V3._attn_params(SMALL) == 148
    dense, moe = 3 * 4 * 8, 4 * 8 + 3 * 4 * 3 * (2 + 1)
    assert V3.layer_matmul_params(SMALL) == 2 * 148 + dense + moe == 532
    # prefill of 3: 6 causal pairs, per pair and head 2*(4 + 3) in each of
    # 2 layers, one logits row 2*4*10
    assert V3.prefill_flops(SMALL, 3) == 2 * 532 * 3 + 6 * 2 * 2 * 7 * 2 + 80
    # decode at position 3, absorbed: 4 rows, per row and head 2*(6+2+6)
    assert V3.decode_flops(SMALL, 3) == 2 * 532 + 4 * 2 * 2 * 14 * 2 + 80
    # bytes: attention 2*148, dense 96, 3 experts 108, head 40 weights;
    # 4 rows of 8 values in 2 layers; 2 bytes each
    assert V3.decode_bytes(SMALL, 3) == (2 * 148 + 96 + 108 + 40 + 4 * 8 * 2) * 2


def test_published_counts():
    """A decode step must read about 1.50 GB (attention 68.8 M, dense FFN
    69.2 M, 4 x 8 experts x 8.65 M, head 335.5 M) plus 1,152 B a latent
    row a layer; a prompt token costs about 0.88 GFLOP at the cell's
    mean length."""
    m = V3.dims(V3.program_config(CONF))
    w = 5 * 13_762_560 + 3 * 2048 * 11264 + 4 * 8 * 3 * 2048 * 1408 + 2048 * 163840
    assert V3.decode_bytes(m, 0) == 2 * w + 1152 * 5 == pytest.approx(1.50e9, rel=0.01)
    assert V3.decode_bytes(m, 4095) - V3.decode_bytes(m, 0) == 4095 * 1152 * 5
    per_token = V3.prefill_flops(m, 1946) / 1946
    assert per_token == pytest.approx(0.88e9, rel=0.02)


def test_configuration_keeps_the_published_widths():
    cfg = V3.program_config(CONF)
    assert (cfg.kv_lora_rank, cfg.qk_nope_head_dim, cfg.qk_rope_head_dim,
            cfg.v_head_dim, cfg.latent_dim) == (512, 128, 64, 128, 576)
    assert (cfg.router_score, cfg.routed_scaling) == ("sigmoid", 2.446)
    assert not cfg.tie_embeddings and cfg.param_dtype == "bfloat16"
    assert CONF["reduced"] == ["num_hidden_layers"] and cfg.n_layers == 5
    assert cfg.param_count() == 3_093_430_528


@pytest.mark.parametrize("key, value", [("q_lora_rank", 1536),
                                        ("scoring_func", "softmax"),
                                        ("n_group", 8),
                                        ("norm_topk_prob", False),
                                        ("rope_scaling", {"type": "yarn"})])
def test_options_the_program_lacks_are_refused(key, value):
    with pytest.raises(ValueError, match=key):
        V3.program_config(dict(CONF, **{key: value}))


@pytest.mark.parametrize("prompt", [24, 1100])
def test_program_decodes_through_the_latent_cache_as_the_reference(prompt):
    """Prefill then 6 absorbed decode steps against the decompressed
    float32 reference, on logits.  float32 at HIGHEST: the two differ in
    the order of their sums and in the absorbed form, 1e-4 of the logits'
    scale (the control departs by more than 1e-3, test_bench_check).  A
    prompt of 1,100 tokens spans three query blocks of the program's and
    the reference's attention and puts the rotary parts past 1,024."""
    from repro.models import decode_step_jit, prefill_jit
    conf = V3.tiny(CONF, 3, "small")
    cfg = V3.program_config(conf)
    assert cfg.routed_scaling == 2.446
    m = V3.dims(cfg)
    p = model.make_params(V3, cfg, 5)
    ids = np.random.default_rng(1).integers(0, cfg.vocab, prompt + 6).astype(np.int32)
    ref = np.asarray(V3.forward(p, ids, m))
    scale = np.abs(ref).max()
    with jax.default_matmul_precision("highest"):
        lg, cache = prefill_jit(p, jnp.asarray(ids[:prompt])[None], cfg,
                                max_len=prompt + 16, moe_mode="dense")
        got = [lg]
        for pos in range(prompt, prompt + 6):
            lg, cache = decode_step_jit(p, jnp.asarray([[ids[pos]]]),
                                        jnp.asarray([pos], jnp.int32), cache,
                                        cfg, moe_mode="dense")
            got.append(lg)
    for i, lg in enumerate(got[:-1]):
        assert np.abs(np.asarray(lg)[0, :cfg.vocab] - ref[prompt - 1 + i]).max() < 1e-4 * scale


def test_the_router_bias_is_drawn_and_chooses():
    """The weights carry a float32 bias per expert and layer, drawn from
    the seed, and it changes which experts the reference chooses."""
    cfg = V3.program_config(V3.tiny(CONF, 2, "published"))
    p = model.make_params(V3, cfg, 9)
    model.check_layout(cfg, p)
    b = p["layers"]["ffn"]["router_bias"]
    assert b.shape == (1, 64) and b.dtype == jnp.float32 and float(jnp.std(b)) > 0.05
    y = jax.random.normal(jax.random.PRNGKey(0), (32, cfg.d_model))
    w, m = p["layers"]["ffn"], V3.dims(cfg)
    _, with_bias = V3.route(y, w["router"][0], b[0], m, False)
    _, without = V3.route(y, w["router"][0], 0 * b[0], m, False)
    assert (np.sort(with_bias, -1) != np.sort(without, -1)).any()


def test_long_prompt_mix():
    mix = traffic.load(ROOT / "bench" / "traffic" / "long-prompt.json")
    assert mix["kind"] == "closed_loop" and mix["clients"] == mix["pool_requests"] == 16
    assert traffic.length_deck(mix["prompt_len"], mix["deck"]) == \
        [1024] * 5 + [2048] * 3 + [4096] * 2
    assert traffic.max_output(mix) == 64 and traffic.pad_to(mix) == 4224
    reqs = [next(g) for g in [traffic.requests(mix, 2 ** 31 + 3, 163840)] for _ in range(20)]
    assert np.mean([len(r.prompt) for r in reqs]) == 1945.6
    assert {r.n_out for r in reqs} == {64}


def _data(rec, red, sut_arch=V3):
    m = V3.dims(V3.program_config(CONF))
    sut = types.SimpleNamespace(arch=sut_arch, dims=m, rec=rec, t0=0.0, t_close=10.0)
    return types.SimpleNamespace(sut=sut, red=red, chips=1,
                                 peak=device.peaks("TPU v5 lite"))


def test_roofline_readers():
    """Required FLOPs (bytes) of the spans inside the window over the
    device time of the matching programs x chips x peak; None without a
    trace or without the count."""
    run = benchtest.load_run()
    mfu, hbm = run.reader("prefill_mfu.tok"), run.reader("decode_hbm.tok")
    rec = Recorder()
    rec.spans += [("model.prefill", 1.0, 2.0, {"seq": 1024}),
                  ("model.prefill", 9.5, 10.5, {"seq": 4096}),   # past the window
                  ("model.decode", 2.0, 2.1, {"pos": 1024}),
                  ("model.decode", 2.1, 2.2, {"pos": 1025})]
    red = {"modules": {"jit_prefill": 0.05, "jit_decode_step": 0.004},
           "module_calls": {"jit_prefill": 1, "jit_decode_step": 2}}
    data = _data(rec, red)
    m = data.sut.dims
    assert mfu(data) == pytest.approx(V3.prefill_flops(m, 1024) / (0.05 * 197e12) * 100)
    assert hbm(data) == pytest.approx(
        (V3.decode_bytes(m, 1024) + V3.decode_bytes(m, 1025)) / (0.004 * 819e9) * 100)
    assert mfu(_data(rec, None)) is None and hbm(_data(rec, None)) is None
    bare = types.SimpleNamespace()
    assert mfu(_data(rec, red, bare)) is None and hbm(_data(rec, red, bare)) is None


def test_cell_entries():
    spec = json.loads((ROOT / "BENCHMARK.json").read_text())
    cell = next(w for w in spec["workloads"] if w["name"] == CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == \
        ("moonlight-16b-a3b", "long-prompt", 1)
    for name in ("output_tok_per_s", "decode_step_ms.tok", "kv_stage_ms.tok",
                 "mfu.tok", "device_idle.tok", "prefill_mfu.tok", "decode_hbm.tok"):
        m = next(m for m in spec["end_to_end"] + spec["per_layer"] if m["name"] == name)
        assert CELL in m["workloads"]



def _serve_scaled(run, seed):
    """The seed's first 8 requests of the cell, served to completion at its
    tiny cut with the published routed scaling (the shared tests cut it to
    1.0): the harness after the run, ready for its readings."""
    import time
    c = run.load_cell(CELL)
    c.conf["routed_scaling_factor"] = CONF["routed_scaling_factor"]
    sut = run.system(c, seed)
    sut.setup()
    gen = traffic.requests(sut.mix, seed, sut.cfg.vocab)
    sut.t0 = time.perf_counter()
    for _ in range(8):
        sut._submit(next(gen), sut.t0)
    done = sut.sched.completed
    sut.fab.run_until(lambda: all(r["rid"] in done for r in sut.requests))
    sut.t_end = sut.t_close = time.perf_counter()
    for r in sut.requests:
        r["done"], r["tokens"] = sut.t_close, done[r["rid"]]["tokens"]
    sut.free()
    return sut, c.limits


@pytest.mark.parametrize("fault", ["control", "state"])
def test_scaled_bf16_serving_reads_faults_far_above_the_program(small_cell, fault):
    """The bf16 serving path with gates times 2.446, which the shared
    tests bypass: the float8 control on the same requests, and a decode
    step that leaves the latent cache unchanged, read at least 3 times the
    sound program on each compared number (CPU readings at this size:
    sound means up to 0.0083 and request means up to 0.029; the control
    from 0.095 and 0.14; the stale state from 0.21 and 0.36)."""
    import importlib.util
    from repro.serving import disagg
    spec = importlib.util.spec_from_file_location(
        "bench_calibrate", ROOT / "bench" / "calibrate.py")
    cal = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(cal)
    orig = disagg.decode_step_jit

    def stale(params, tokens, positions, cache, cfg, **kw):
        return orig(params, tokens, positions, cache, cfg, **kw)[0], cache
    for seed in (11, 2 ** 31 + 12, 13):
        sut, limits = _serve_scaled(small_cell, seed)
        sound = cal.readings(sut, seed, limits)
        if fault == "control":
            bad = cal.readings(sut, seed, limits, control=True)
        else:
            disagg.decode_step_jit = stale
            try:
                sut, limits = _serve_scaled(small_cell, seed)
            finally:
                disagg.decode_step_jit = orig
            bad = cal.readings(sut, seed, limits)
        for name in ("mean_logit_gap", "request_mean_gap"):
            assert bad[name] >= 3 * sound[name] and bad[name] > 0, \
                (seed, name, sound, bad)
