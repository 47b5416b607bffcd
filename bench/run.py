#!/usr/bin/env python3
"""Run one cell of the on-chip benchmark once.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Everything is found by name in ``BENCHMARK.json``: the cell's configuration
file, the architecture module its ``model_type`` names
(``bench/harness/arch/<model_type>.py``), its traffic mix
``bench/traffic/<mix>.json``, its correctness limits
``bench/limits/<cell>.json`` and one reader per metric,
``bench/metrics/<metric>.py``.  ``--trace 0`` reports the cell's end-to-end
metrics, ``--trace 1`` its per-layer metrics from spans and a device trace.

The last line of standard output is one JSON object (``correct``,
``attempted``, ``failed``, ``metrics``, ``device`` and, traced,
``breakdown``, then ``checks``: each compared number beside its limit).
Without a TPU, or with fewer chips than the cell asks for, the run exits 3
and prints no result.
"""

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import pathlib  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
from types import SimpleNamespace  # noqa: E402

ROOT = pathlib.Path(__file__).resolve().parents[1]
BENCH = ROOT / "bench"
CACHE = ROOT / ".jax_cache"
TRACES = ROOT / ".bench_traces"
NO_CHIP = 3


def load_cell(name: str) -> SimpleNamespace:
    """The cell ``name`` of ``BENCHMARK.json`` with its configuration,
    traffic mix and limits."""
    spec = json.loads((ROOT / "BENCHMARK.json").read_text())
    cells = {w["name"]: w for w in spec["workloads"]}
    if name not in cells:
        raise SystemExit(f"no workload {name!r} in BENCHMARK.json")
    cell = cells[name]
    conf_entry = {c["name"]: c for c in spec["configs"]}[cell["config"]]
    from harness import arch, model, traffic
    conf = model.load(ROOT / conf_entry["file"])
    arch.get(conf)           # an unknown model_type fails here, before weights
    return SimpleNamespace(
        spec=spec, cell=cell, conf=conf,
        mix=traffic.load(BENCH / "traffic" / f"{cell['traffic']}.json"),
        limits=json.loads((BENCH / "limits" / f"{name}.json").read_text()))


def metrics_for(spec: dict, cell: str, traced: bool) -> list:
    """The metrics a cell reports: its end-to-end ones, or traced, the
    per-layer ones whose end-to-end metric the cell reports."""
    def here(m):
        return "workloads" not in m or cell in m["workloads"]
    e2e = [m for m in spec["end_to_end"] if here(m)]
    if not traced:
        return e2e
    names = {m["name"] for m in e2e}
    return [m for m in spec["per_layer"] if here(m) and m["moves"] in names]


def reader(name: str):
    path = BENCH / "metrics" / f"{name}.py"
    spec = importlib.util.spec_from_file_location(
        "bench_metric_" + name.replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def configure_jax() -> None:
    import jax
    jax.config.update("jax_compilation_cache_dir", str(CACHE))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    # no eviction: an evicted program compiles again in the next run's set-up
    jax.config.update("jax_compilation_cache_max_size", -1)


def system(c, seed: int):
    from harness.serving import Serving
    kind = c.conf["system"]
    if kind == "serving":
        return Serving(c.conf, c.mix, seed)
    raise ValueError(f"unknown system {kind!r}")


def run(args, devices=None) -> dict:
    """One run; ``devices`` given skips the look for a chip (tests)."""
    import jax
    from harness import device, trace
    from harness.spans import Compiles

    c = load_cell(args.workload)
    configure_jax()
    if devices is None:
        devices = device.require(c.cell["chips"])
    dev = device.describe(devices)
    sut = system(c, args.seed)
    with Compiles() as setup_compiles:
        sut.setup()
    trace_dir = TRACES / f"{args.workload}-{args.seed}"
    if args.trace:
        sut.instrument()
        shutil.rmtree(trace_dir, ignore_errors=True)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 2
        jax.profiler.start_trace(str(trace_dir), profiler_options=opts)
    with Compiles() as window_compiles:
        with jax.profiler.TraceAnnotation(trace.WINDOW):
            sut.window(args.seconds)
    red = None
    if args.trace:
        jax.profiler.stop_trace()
        sut.uninstrument()
        path = next(trace_dir.glob("**/*.xplane.pb"))
        red = trace.reduce(trace.load(str(path)), len(devices),
                           {name for name, *_ in sut.rec.spans})
        shutil.rmtree(trace_dir, ignore_errors=True)
    dev["memory_peak_bytes"] = device.memory_peak(devices)
    data = SimpleNamespace(
        cell=c.cell, conf=c.conf, mix=c.mix, sut=sut, red=red,
        setup_s=sut.t0 - T_START, window_s=sut.t_close - sut.t0,
        chips=len(devices), peak=device.peaks(dev["kind"]))
    metrics = {}
    for m in metrics_for(c.spec, c.cell["name"], bool(args.trace)):
        value = reader(m["name"])(data)
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    counters = sut.counters()
    counters.update(setup_compiles=setup_compiles.count,
                    window_compiles=window_compiles.count,
                    window_compiled=dict(window_compiles.names))
    print("counters " + json.dumps(counters), flush=True)
    sut.free()
    checks = sut.check(args.seed, c.limits)
    correct = all(v["value"] <= v["limit"] for v in checks.values())
    out = {"correct": correct, "attempted": counters["attempted"],
           "failed": counters["failed"], "metrics": metrics,
           "device": dev}
    if red is not None:
        dev["busy_s"], dev["window_s"] = red["busy_s"], red["window_s"]
        out["breakdown"] = red["breakdown"]
    out["checks"] = checks
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    sys.path[:0] = [str(ROOT / "src"), str(BENCH)]
    from harness.device import NoChip
    try:
        out = run(args)
    except NoChip as e:
        print(f"bench: {e}", file=sys.stderr)
        return NO_CHIP
    for name, v in out["checks"].items():
        print(f"check {name}: {v['value']!r} (limit {v['limit']!r})",
              file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
