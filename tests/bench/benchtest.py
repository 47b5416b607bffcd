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


# wide enough that logits spread as at full width, small enough for the CPU
TINY_SERVING = dict(hidden_size=512, num_attention_heads=4,
                    num_key_value_heads=4, intermediate_size=1024,
                    vocab_size=4096)


def shrink(c, layers=2):
    """A cell cut to CPU size: same keys and code paths, tiny widths."""
    conf, mix, lim = dict(c.conf), dict(c.mix), dict(c.limits)
    conf.update(TINY_SERVING, num_hidden_layers=layers)
    if "n_routed_experts" in conf:
        # the published routing (64 experts, top-6, 2 shared), narrow
        conf.update(moe_intermediate_size=64, num_hidden_layers=layers + 1)
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
