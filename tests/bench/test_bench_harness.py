"""Each cell's harness path at a tiny size on the CPU: traffic, the
program, the metric arithmetic and the check; and the refusal to report
without a TPU."""

import json
import math
import os
import pathlib
import shutil
import subprocess
import sys
import types

import jax
import pytest

from harness import trace

ROOT = pathlib.Path(__file__).resolve().parents[2]
SPEC = json.loads((ROOT / "BENCHMARK.json").read_text())
CELLS = [w["name"] for w in SPEC["workloads"]]
BIG_SEED = 2 ** 31 + 99


def args(cell, traced, seconds=2.0, seed=BIG_SEED):
    return types.SimpleNamespace(workload=cell, seed=seed, seconds=seconds,
                                 trace=traced)


def with_device_plane(tr):
    """A TPU plane for a CPU trace: one op per model span, named like the
    programs and kernels the readers look for."""
    ops, mods = [], []
    for name, t, d in (e[:3] for e in trace.host_spans(tr)):
        if name == "model.prefill":
            mods.append(["jit_prefill(1)", t, d // 2])
            ops.append(["%branch_0_fun.7 = bf16[4] custom-call(), "
                        'custom_call_target="tpu_custom_call"', t, d // 4])
        elif name == "model.decode":
            mods.append(["jit_decode_step(2)", t, d // 2])
            ops.append(["%fusion.1 = f32[] fusion()", t, d // 2])
    tr["planes"].append({"name": "/device:TPU:0", "lines": [
        {"name": "XLA Ops", "events": ops},
        {"name": "XLA Modules", "events": mods}]})
    return tr


@pytest.fixture
def cpu_trace(monkeypatch):
    load = trace.load
    monkeypatch.setattr(trace, "load", lambda p: with_device_plane(load(p)))


@pytest.mark.parametrize("traced", [0, 1])
@pytest.mark.parametrize("cell", CELLS)
def test_cell_runs_tiny_on_cpu(small_cell, cpu_trace, cell, traced, capsys):
    run = small_cell
    out = run.run(args(cell, traced), devices=jax.devices("cpu")[:1])
    counters = json.loads(next(
        ln for ln in capsys.readouterr().out.splitlines()
        if ln.startswith("counters "))[len("counters "):])
    assert counters["window_compiles"] == 0, counters["window_compiled"]
    assert out["correct"] is True, out["checks"]
    assert out["attempted"] > 0 and out["failed"] == 0
    want = {m["name"] for m in run.metrics_for(SPEC, cell, bool(traced))}
    assert set(out["metrics"]) == want
    for m in out["metrics"].values():
        assert math.isfinite(m["value"]) and m["value"] >= 0
    if traced:
        assert 0 < out["device"]["busy_s"] <= out["device"]["window_s"]
        assert out["breakdown"]["device_ops"]
    assert list(out)[-1] == "checks"
    json.dumps(out)


def test_every_cell_reports_setup_and_a_layer():
    for cell in CELLS:
        e2e = {m["name"] for m in SPEC["end_to_end"]
               if "workloads" not in m or cell in m["workloads"]}
        assert "setup_s" in e2e and len(e2e) >= 2
        assert [m for m in SPEC["per_layer"] if cell in m.get("workloads", [])]


def test_every_name_has_its_file():
    for m in SPEC["end_to_end"] + SPEC["per_layer"]:
        assert (ROOT / "bench" / "metrics" / f"{m['name']}.py").exists()
    for w in SPEC["workloads"]:
        assert (ROOT / "bench" / "traffic" / f"{w['traffic']}.json").exists()
        assert (ROOT / "bench" / "limits" / f"{w['name']}.json").exists()
    for c in SPEC["configs"]:
        conf = json.loads((ROOT / c["file"]).read_text())
        assert conf["name"] == c["name"] and conf["reduced"] == c["reduced"]


def test_open_loop_serves_on_schedule(small_cell, monkeypatch):
    """The same cell under an open-loop mix: requests sent at their due
    times from the seed's schedule, served, and correct."""
    run = small_cell
    load = run.load_cell

    def open_loop(name):
        c = load(name)
        c.mix = {k: v for k, v in c.mix.items() if k != "clients"}
        c.mix.update(kind="open_loop", rate_per_s=4.0)
        return c
    monkeypatch.setattr(run, "load_cell", open_loop)
    out = run.run(args(CELLS[0], 0, seconds=3.0), devices=jax.devices("cpu")[:1])
    assert out["correct"] is True, out["checks"]
    assert out["attempted"] >= 6 and out["failed"] == 0


def _cmd(cwd):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload", CELLS[0], "--seed",
         str(BIG_SEED), "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=120)


def test_refuses_without_a_tpu():
    r = _cmd(ROOT)
    assert r.returncode == 3, r.stderr
    assert r.stdout.strip() == ""
    assert "needs a TPU" in r.stderr


def test_refuses_in_a_bare_checkout(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(ROOT / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    r = _cmd(tmp_path)
    assert r.returncode != 0 and r.stdout.strip() == ""


NAME = r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$"
UNIT = r"^[A-Za-z0-9_/%.\-]{1,16}$"


def _line(text):
    return isinstance(text, str) and 1 <= len(text) <= 200 \
        and "\n" not in text and "\t" not in text


def test_benchmark_json_shape():
    import re
    assert set(SPEC) == {"command", "paths", "run_seconds", "configs",
                         "workloads", "end_to_end", "per_layer"}
    assert all(_line(w) for w in SPEC["command"]) and len(SPEC["command"]) <= 32
    for p in SPEC["paths"]:
        assert re.match(r"^[A-Za-z0-9_.\-/]{1,200}$", p) and (ROOT / p).is_dir()
    rs = SPEC["run_seconds"]
    assert isinstance(rs, int) and 1 <= rs <= 51
    assert (2 + 14 * 24) * (rs + 60) + 24 * 2 * 90 + 1200 <= 43200
    for c in SPEC["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert re.match(NAME, c["name"]) and _line(c["source"]) and _line(c["why"])
        assert all(re.match(NAME, k) for k in c["reduced"]) and len(c["reduced"]) <= 16
        assert any(w["config"] == c["name"] for w in SPEC["workloads"])
    pairs = set()
    for w in SPEC["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert re.match(NAME, w["name"]) and re.match(NAME, w["traffic"])
        assert w["chips"] in (1, 4) and _line(w["why"])
        assert (w["config"], w["traffic"]) not in pairs
        pairs.add((w["config"], w["traffic"]))
    four = sum(w["chips"] == 4 for w in SPEC["workloads"])
    assert four <= max(1, len(SPEC["workloads"]) // 2)
    names = [m["name"] for m in SPEC["end_to_end"] + SPEC["per_layer"]]
    assert len(names) == len(set(names))
    for m in SPEC["end_to_end"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "bound", "source"}
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
    assert next(m for m in SPEC["end_to_end"] if m["name"] == "setup_s")["bound"] <= 0.25
    e2e = {m["name"] for m in SPEC["end_to_end"]}
    for m in SPEC["per_layer"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "source", "layer", "moves"}
        assert m["moves"] in e2e and _line(m["layer"])
        assert m["source"] in ("device_trace", "program_span", "program_counter", "host_clock")
    for m in SPEC["end_to_end"] + SPEC["per_layer"]:
        assert re.match(NAME, m["name"]) and re.match(UNIT, m["unit"])
        assert m["better"] in ("lower", "higher")
        if m["name"].endswith("_roofline") or "_roofline." in m["name"] or "mfu" in m["name"]:
            assert m["unit"] == "%"
    assert len((ROOT / "BENCHMARK.json").read_bytes()) <= 64 * 1024
