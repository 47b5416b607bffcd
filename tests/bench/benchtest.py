"""Helpers of the benchmark's tests: the harness on the path, cells cut
to CPU size."""

import importlib.util
import pathlib
import sys

ROOT = pathlib.Path(__file__).resolve().parents[2]
for p in (ROOT / "src", ROOT / "bench"):
    if str(p) not in sys.path:
        sys.path.insert(0, str(p))


def load_run():
    spec = importlib.util.spec_from_file_location("bench_run", ROOT / "bench" / "run.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def shrink(c, layers=3):
    """A cell cut to CPU size: its architecture's tiny cut with the
    published routing (same keys and code paths, tiny widths)."""
    from harness import arch
    conf = arch.get(c.conf).tiny(c.conf, layers, "published")
    mix, lim = dict(c.mix), dict(c.limits)
    mix.update(prompt_len={"16": 0.5, "32": 0.3, "64": 0.2},
               output_len={"uniform": [6, 10]})
    if mix["kind"] == "open_loop":
        mix["rate_per_s"] = 5.0
    else:
        mix["clients"] = 4
    # limits at this size, set like the chip's from readings at this size
    # (12 seeds, 3 s windows): sound runs read a mean gap up to 0.0035 and
    # a request's mean up to 0.017; the float8 control a mean from 0.020;
    # a decode step that leaves its cache unchanged a mean from 0.10 and a
    # request's mean from 0.15
    lim.update(sample_tokens=48, mean_logit_gap=0.01, request_mean_gap=0.05)
    c.conf, c.mix, c.limits = conf, mix, lim
    return c


def on_cpu(run, patch) -> None:
    """Tiny cells, the v5e peaks standing in for the CPU's, and no
    persistent compilation cache (``patch``: a setattr like
    ``monkeypatch.setattr``)."""
    from harness import device
    orig, peaks = run.load_cell, device.peaks
    patch(run, "load_cell", lambda name: shrink(orig(name)))
    patch(run, "configure_jax", lambda: None)
    patch(device, "peaks", lambda kind: peaks("TPU v5 lite"))
