"""What decides ``correct``: the plain reference agrees with the program,
and a run whose timed path is broken underneath, or the float8 control in
the program's place, reads as not correct."""

import json
import pathlib
import time
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from harness import arch, model, reference, traffic

ROOT = pathlib.Path(__file__).resolve().parents[2]
SPEC = json.loads((ROOT / "BENCHMARK.json").read_text())
CONFIGS = {c["name"]: ROOT / c["file"] for c in SPEC["configs"]}
SERVING = [w["name"] for w in SPEC["workloads"]
           if model.load(CONFIGS[w["config"]])["system"] == "serving"]


def tiny_conf(name, layers=2):
    """Configuration ``name`` at CPU size with a small routing, in float32:
    (its architecture module, the cut, the program's config)."""
    conf = model.load(CONFIGS[name])
    mod = arch.get(conf)
    conf = mod.tiny(conf, layers, "small")
    return mod, conf, mod.program_config(conf)


@pytest.mark.parametrize("name", sorted(CONFIGS))
def test_reference_matches_program_in_f32(name):
    from repro.models import decode_step_jit, prefill_jit
    mod, _, cfg = tiny_conf(name, 3)
    m = mod.dims(cfg)
    p = model.make_params(mod, cfg, 7)
    ids = np.random.default_rng(0).integers(0, cfg.vocab, 24).astype(np.int32)
    with jax.default_matmul_precision("highest"):
        lg, cache = prefill_jit(p, jnp.asarray(ids)[None], cfg, max_len=40,
                                moe_mode="dense")
        lg2, _ = decode_step_jit(p, jnp.asarray([[5]]),
                                 jnp.asarray([24], jnp.int32), cache, cfg,
                                 moe_mode="dense")
    ref = np.asarray(mod.forward(p, np.append(ids, 5), m))
    scale = np.abs(ref).max()
    assert np.abs(np.asarray(lg)[0, :cfg.vocab] - ref[23]).max() < 1e-4 * scale
    assert np.abs(np.asarray(lg2)[0, :cfg.vocab] - ref[24]).max() < 1e-4 * scale
    # the control departs from the reference
    ctl = np.asarray(mod.forward(p, np.append(ids, 5), m, quant=True))
    assert np.abs(ctl - ref).max() > 1e-3 * scale


def test_served_gaps_read_the_reference():
    mod, _, cfg = tiny_conf("deepseek-moe-16b")
    m = mod.dims(cfg)
    p = model.make_params(mod, cfg, 3)
    ids = np.arange(16, dtype=np.int32)
    ref = np.asarray(mod.forward(p, ids, m))
    best = [int(ref[15].argmax())]
    worst = [int(ref[15].argmin())]
    gaps = reference.served_gaps(mod.forward, p, m,
                                 [(ids, best), (ids, worst)], 32)
    assert len(gaps) == 2 and gaps[0].max() == 0.0
    assert gaps[1][0] == pytest.approx(ref[15].max() - ref[15].min(), rel=1e-5)


def test_request_mean_gap_sees_one_request():
    """A fault in one request of many reads in full in the per-request
    number, diluted in the mean over all tokens."""
    from harness.serving import Serving
    sound = [np.full(64, 0.005) for _ in range(7)]
    stats = Serving.GAP_STATS
    one_bad = sound + [np.full(64, 0.13)]
    assert stats["request_mean_gap"](one_bad) == pytest.approx(0.13)
    assert stats["mean_logit_gap"](one_bad) < 0.03
    assert stats["logit_gap"](one_bad) == pytest.approx(0.13)


def _decode_fault(kind):
    from repro.serving import disagg
    orig = disagg.decode_step_jit

    def run(params, tokens, positions, cache, cfg, **kw):
        lg, new = orig(params, tokens, positions, cache, cfg, **kw)
        if kind == "token":              # the least likely token comes out
            return -lg, new
        return lg, cache                 # the step leaves its state unchanged
    return run


@pytest.mark.parametrize("fault", ["token", "state"])
@pytest.mark.parametrize("cell", SERVING)
def test_serving_faults_are_not_correct(small_cell, monkeypatch, cell, fault):
    from repro.serving import disagg
    monkeypatch.setattr(disagg, "decode_step_jit", _decode_fault(fault))
    out = small_cell.run(types.SimpleNamespace(
        workload=cell, seed=2 ** 31 + 5, seconds=2.0, trace=0),
        devices=jax.devices("cpu")[:1])
    assert out["correct"] is False, out["checks"]


def _calibrate():
    import importlib.util
    spec = importlib.util.spec_from_file_location(
        "bench_calibrate", ROOT / "bench" / "calibrate.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _serve(sut, n):
    """The seed's first ``n`` requests of the mix, sent at once and served
    to completion: the same requests whatever the machine's speed, where
    ``Serving.window`` serves for a time."""
    gen = traffic.requests(sut.mix, sut.seed, sut.cfg.vocab)
    sut.t0 = time.perf_counter()
    for _ in range(n):
        sut._submit(next(gen), sut.t0)
    done = sut.sched.completed
    sut.fab.run_until(lambda: all(r["rid"] in done for r in sut.requests))
    sut.t_end = sut.t_close = time.perf_counter()
    for r in sut.requests:
        r["done"], r["tokens"] = sut.t_close, done[r["rid"]]["tokens"]
    assert len(sut.completed()) == n


@pytest.mark.parametrize("cell", SERVING)
def test_control_reads_far_above_the_program(small_cell, cell):
    """The float8 control in the program's place, on the requests a run
    served: at this size its widest gap is at least 3x the program's."""
    cal = _calibrate()
    c = small_cell.load_cell(cell)
    for seed in (11, 2 ** 31 + 12, 13):
        sut = small_cell.system(c, seed)
        sut.setup()
        _serve(sut, 8)
        sut.free()
        lower = cal.readings(sut, seed, c.limits)
        upper = cal.readings(sut, seed, c.limits, control=True)
        for name in set(c.limits) & set(sut.GAP_STATS):
            assert upper[name] >= 3 * lower[name] and upper[name] > 0, \
                (seed, name, lower, upper)


@pytest.mark.parametrize("cell", SERVING)
def test_control_in_the_programs_place_is_not_correct(small_cell, monkeypatch,
                                                      cell):
    """A whole run whose served tokens are the float8 control's first
    choices, at each position of the same prompts and tokens, reads as not
    correct."""
    orig = reference.served_gaps
    monkeypatch.setattr(reference, "served_gaps",
                        lambda *a, **kw: orig(*a, **dict(kw, control=True)))
    out = small_cell.run(types.SimpleNamespace(
        workload=cell, seed=2 ** 31 + 6, seconds=3.0, trace=0),
        devices=jax.devices("cpu")[:1])
    assert out["correct"] is False, out["checks"]
