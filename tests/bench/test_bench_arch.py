"""Architecture modules (``bench/harness/arch/``): each configuration finds
its own by ``model_type``; the deepseek module computes what the harness
computed before the modules existed (weights, reference logits and FLOP
counts pinned in ``pins_deepseek.json``); a new ``model_type`` enters by
new files alone, and an unknown one fails before any weights are made."""

import json
import pathlib
import re
import shutil
import types

import jax
import numpy as np
import pytest

from harness import arch, model

ROOT = pathlib.Path(__file__).resolve().parents[2]
SPEC = json.loads((ROOT / "BENCHMARK.json").read_text())
PINS = json.loads((pathlib.Path(__file__).parent / "pins_deepseek.json").read_text())
CONTRACT = ("program_config", "dims", "param_shapes", "forward",
            "prefill_flops", "decode_flops", "tiny")


def deepseek():
    conf = model.load(ROOT / "bench" / "configs" / "deepseek-moe-16b.json")
    return arch.get(conf), conf


@pytest.mark.parametrize("entry", SPEC["configs"], ids=lambda c: c["name"])
def test_every_configuration_has_its_architecture(entry):
    mod = arch.get(model.load(ROOT / entry["file"]))
    assert pathlib.Path(mod.__file__).parent == arch.DIR
    assert all(callable(getattr(mod, name, None)) for name in CONTRACT)


def test_flop_counts_are_pinned():
    mod, conf = deepseek()
    m = mod.dims(mod.program_config(conf))
    for seq, want in PINS["prefill_flops"].items():
        got = mod.prefill_flops(m, int(seq))
        assert got == float(want) and int(got) == want, seq
    for pos, want in PINS["decode_flops"].items():
        got = mod.decode_flops(m, int(pos))
        assert got == float(want) and int(got) == want, pos


def _cut(routing):
    mod, conf = deepseek()
    cfg = mod.program_config(mod.tiny(conf, 3, routing))
    return mod, cfg, model.make_params(mod, cfg, 7)


@pytest.mark.parametrize("routing", ["small", "published"])
def test_weights_are_pinned(routing):
    """Same layout, same key per leaf: the same seed gives the same
    weights as before the move."""
    _, _, p = _cut(routing)
    flat = jax.tree_util.tree_flatten_with_path(p)[0]
    got = [[jax.tree_util.keystr(k), list(a.shape), str(a.dtype),
            float(np.asarray(a, np.float32).astype(np.float64).sum())]
           for k, a in flat]
    want = PINS[f"{routing}_leaves"]
    assert [g[:3] for g in got] == [w[:3] for w in want]
    for g, w in zip(got, want):
        assert g[3] == pytest.approx(w[3], rel=1e-9, abs=1e-9), g[0]


@pytest.mark.parametrize("routing", ["small", "published"])
def test_reference_logits_are_pinned(routing):
    mod, cfg, p = _cut(routing)
    ids = np.random.default_rng(0).integers(0, cfg.vocab, 24).astype(np.int32)
    ref = np.asarray(mod.forward(p, ids, mod.dims(cfg)), np.float64)
    v = np.random.default_rng(1).standard_normal(ref.shape[1])
    for r, (top, arg, proj) in zip(ref, PINS[f"{routing}_logits"]):
        assert r.max() == pytest.approx(top, rel=1e-6)
        assert int(r.argmax()) == arg
        assert abs(r @ v - proj) <= 1e-6 * np.linalg.norm(r) * np.linalg.norm(v)


def _checkout(tmp_path, monkeypatch, model_type, module=None):
    """A copy of the benchmark's files with one more configuration and cell,
    added as new files and entries only; ``run`` and ``arch`` look there."""
    import benchtest
    shutil.copytree(ROOT / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    arch_dir = tmp_path / "bench" / "harness" / "arch"
    if module:
        shutil.copy(arch_dir / "deepseek.py", arch_dir / f"{module}.py")
    conf = model.load(ROOT / "bench" / "configs" / "deepseek-moe-16b.json")
    conf.update(name="new-arch", model_type=model_type)
    (tmp_path / "bench" / "configs" / "new-arch.json").write_text(json.dumps(conf))
    shutil.copy(tmp_path / "bench" / "limits" / "deepseek-moe-16b.decode-batch.json",
                tmp_path / "bench" / "limits" / "new-arch.decode-batch.json")
    spec = json.loads(json.dumps(SPEC))
    spec["configs"].append(dict(spec["configs"][0], name="new-arch",
                                file="bench/configs/new-arch.json"))
    spec["workloads"].append(dict(spec["workloads"][0], name="new-arch.decode-batch",
                                  config="new-arch"))
    for m in spec["end_to_end"] + spec["per_layer"]:
        if "workloads" in m:
            m["workloads"].append("new-arch.decode-batch")
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(spec))
    run = benchtest.load_run()
    monkeypatch.setattr(run, "ROOT", tmp_path)
    monkeypatch.setattr(run, "BENCH", tmp_path / "bench")
    monkeypatch.setattr(arch, "DIR", arch_dir)
    return run


def test_a_new_model_type_enters_by_new_files(tmp_path, monkeypatch):
    """A copy of the deepseek module under a new ``model_type``, and a
    configuration naming it: the tiny cell runs through ``run.run``."""
    run = _checkout(tmp_path, monkeypatch, "deepseek_copy", "deepseek_copy")
    import benchtest
    benchtest.on_cpu(run, monkeypatch.setattr)
    suts = []
    system = run.system
    monkeypatch.setattr(run, "system", lambda c, seed: (
        suts.append(system(c, seed)), suts[-1])[1])
    out = run.run(types.SimpleNamespace(
        workload="new-arch.decode-batch", seed=2 ** 31 + 21, seconds=2.0,
        trace=0), devices=jax.devices("cpu")[:1])
    assert out["correct"] is True, out["checks"]
    assert out["attempted"] > 0 and out["metrics"]["output_tok_per_s"]["value"] > 0
    assert pathlib.Path(suts[0].arch.__file__) == \
        tmp_path / "bench" / "harness" / "arch" / "deepseek_copy.py"


def test_an_unknown_model_type_fails_before_weights(tmp_path, monkeypatch):
    run = _checkout(tmp_path, monkeypatch, "no_such_arch")
    want = re.escape(str(tmp_path / "bench" / "harness" / "arch" / "no_such_arch.py"))
    with pytest.raises(ValueError, match=want):
        run.load_cell("new-arch.decode-batch")
    made = []
    monkeypatch.setattr(model, "make_params", lambda *a, **kw: made.append(a))
    with pytest.raises(ValueError, match=want):
        run.run(types.SimpleNamespace(
            workload="new-arch.decode-batch", seed=1, seconds=1.0, trace=0),
            devices=jax.devices("cpu")[:1])
    assert made == []
    run.load_cell(SPEC["workloads"][0]["name"])      # the others still load
