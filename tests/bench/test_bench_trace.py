"""The reduction from a profiler trace to device metrics, on a hand-made
trace and on one recorded here on the CPU."""

import jax
import pytest

from harness import trace


def synthetic():
    ops = [["%fusion.1 = f32[] fusion()", 100, 50],
           ["%all-to-all.2 = bf16[4] all-to-all()", 200, 100],
           ["%moe_pack.1 = u32[4] custom-call(), "
            'custom_call_target="tpu_custom_call"', 400, 50],
           ["%fusion.1 = f32[] fusion()", 1100, 50]]          # after the window
    dev = {"name": "/device:TPU:0", "lines": [
        {"name": "XLA Ops", "events": ops},
        {"name": "XLA Modules", "events": [["jit_prefill(1)", 90, 400],
                                           ["jit_decode_step(2)", 1100, 50]]}]}
    host = {"name": "/host:CPU", "lines": [{"name": "python", "events": [
        ["bench.window", 0, 1000], ["model.prefill", 80, 500],
        ["kv.stage", 600, 300]]}]}
    return {"planes": [host, dev]}


def test_busy_idle_and_gaps():
    red = trace.reduce(synthetic(), 1, {"model.prefill", "kv.stage"})
    assert red["window_s"] == pytest.approx(1000e-9)
    assert red["busy_s"] == pytest.approx(200e-9)
    gaps = dict(red["breakdown"]["idle_gaps"])
    assert gaps["kv.stage"] == pytest.approx(550e-9)
    assert gaps["model.prefill"] == pytest.approx(150e-9)
    assert gaps["host"] == pytest.approx(100e-9)
    ops = dict(red["breakdown"]["device_ops"])
    assert ops["jit_prefill/all-to-all.2"] == pytest.approx(100e-9)
    assert ops["jit_prefill/fusion.1"] == pytest.approx(50e-9)   # in window
    # spans the benchmark did not record are not named
    other = trace.reduce(synthetic(), 1, {"kv.stage"})
    assert dict(other["breakdown"]["idle_gaps"])["host"] == pytest.approx(250e-9)


def test_programs_and_ops_by_pattern():
    red = trace.reduce(synthetic(), 1)
    s, calls = trace.module_seconds(red, "prefill")
    assert (s, calls) == (pytest.approx(400e-9), 1)
    assert trace.module_seconds(red, "decode_step") == (0.0, 0)
    assert trace.op_seconds(red, "^%all-to-all") == pytest.approx(100e-9)
    assert trace.op_seconds(red, "tpu_custom_call") == pytest.approx(50e-9)
    assert trace.op_seconds(red, "tpu_custom_call", "prefill") == pytest.approx(50e-9)
    assert trace.op_seconds(red, "tpu_custom_call", "decode") == 0.0


def test_devices_are_averaged():
    tr = synthetic()
    dev1 = {"name": "/device:TPU:1", "lines": [
        {"name": "XLA Ops", "events": [["%fusion.9 = f32[] fusion()", 0, 1000]]}]}
    tr["planes"].append(dev1)
    red = trace.reduce(tr, 2)
    assert red["devices"] == 2
    assert red["busy_s"] == pytest.approx((200e-9 + 1000e-9) / 2)
    assert trace.reduce(tr, 1)["busy_s"] == pytest.approx(200e-9)


def test_op_and_module_names():
    assert trace.op_name("%moe_pack.1 = u32[4] custom-call()") == "moe_pack.1"
    assert trace.module_name("jit_decode_step(123)") == "jit_decode_step"


def test_union_and_gaps():
    assert trace.union([(5, 9), (0, 3), (2, 4), (8, 10)]) == [(0, 4), (5, 10)]
    assert trace.gaps([(0, 4), (5, 10)], 0, 12) == [(4, 5), (10, 12)]
    assert trace.clip([(0, 4), (5, 10)], 3, 6) == [(3, 4), (5, 6)]


def test_recorded_cpu_trace(tmp_path):
    f = jax.jit(lambda x: (x @ x).sum())
    x = jax.numpy.ones((64, 64))
    f(x).block_until_ready()
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    with jax.profiler.TraceAnnotation(trace.WINDOW):
        with jax.profiler.TraceAnnotation("model.prefill"):
            f(x).block_until_ready()
    jax.profiler.stop_trace()
    path = next(tmp_path.glob("**/*.xplane.pb"))
    tr = trace.load(str(path))
    lo, hi = trace.window(tr)
    assert hi > lo
    names = {e[0] for e in trace.host_spans(tr)}
    assert {"model.prefill", trace.WINDOW} <= names
    with pytest.raises(ValueError, match="no TPU device plane"):
        trace.reduce(tr, 1)


def scan_innermost(spans, t):
    """The per-gap scan the sweep replaced, kept as its oracle: every span
    for each time, the shortest open one, the first of equals."""
    best = None
    for e in spans:
        if not (e[1] <= t < e[1] + e[2]):
            continue
        if best is None or e[2] < best[2]:
            best = e
    return best[0] if best else "host"


def _agree(spans, times):
    got = trace.innermost(spans, times)
    assert got == [scan_innermost(spans, t) for t in times]
    return got


@pytest.mark.parametrize("seed", range(6))
def test_sweep_matches_the_scan_with_ties_and_nesting(seed):
    """Random spans on a coarse clock, so starts, ends and durations tie
    often; spans nest and overlap; times fall on every start and end."""
    import numpy as np
    r = np.random.default_rng(seed)
    spans = []
    for i in range(300):
        t, d = int(r.integers(0, 400)), int(r.integers(0, 40))
        spans.append([f"s{i % 7}", t, d])
    for t, d in ((50, 10), (50, 10), (55, 10), (50, 200), (0, 1000)):
        spans.append([f"tie{t}.{d}", t, d])       # exact ties, nested
    times = sorted({e[1] for e in spans} | {e[1] + e[2] for e in spans}
                   | {e[1] + e[2] - 1 for e in spans}
                   | set(int(x) for x in r.integers(-5, 1100, 500)))
    got = _agree(spans, list(r.permutation(times)))
    assert "host" in got and len(set(got)) > 3
    assert trace.innermost(spans, []) == [] and \
        trace.innermost([], [1, 2]) == ["host", "host"]


def test_sweep_matches_the_scan_on_a_recorded_trace(tmp_path):
    f = jax.jit(lambda x: (x @ x).sum())
    x = jax.numpy.ones((64, 64))
    f(x).block_until_ready()
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    with jax.profiler.TraceAnnotation(trace.WINDOW):
        for _ in range(20):
            with jax.profiler.TraceAnnotation("fabric.loop"):
                with jax.profiler.TraceAnnotation("model.decode"):
                    f(x).block_until_ready()
                with jax.profiler.TraceAnnotation("kv.stage"):
                    f(x).block_until_ready()
    jax.profiler.stop_trace()
    tr = trace.load(str(next(tmp_path.glob("**/*.xplane.pb"))))
    lo, hi = trace.window(tr)
    names = {"fabric.loop", "model.decode", "kv.stage"}
    host = [e for e in trace.host_spans(tr) if e[0] in names]
    assert len(host) == 60
    times = list(range(lo, hi, max(1, (hi - lo) // 5000)))
    times += [e[1] for e in host] + [e[1] + e[2] for e in host]
    got = _agree(host, times)
    assert names <= set(got)
