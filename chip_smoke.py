#!/usr/bin/env python3
"""Smoke test of the main paths on a TPU, in one process, at published widths.

    python3 chip_smoke.py             # one chip
    python3 chip_smoke.py --chips 4   # four chips: expert-parallel MoE only

One chip runs four phases:

* numerics  — the reduced stablelm-3b prefill on the TPU and on the host
              CPU; logits and KV cache must agree (``NUMERICS_RTOL``).
* serving   — ``launch/serve.py``'s monolithic and disaggregated paths
              (2 prefillers, 2 decoders, a Scheduler on the simulated
              fabric) on stablelm-3b at its published widths in bf16: one
              warm-up request, then 4 requests of 512 prompt tokens and 16
              decode tokens, which must match token for token with zero
              compilations and finite logits.
* headroom  — the serving requests decoded at the handoff cache length and
              at ``S + n + 8``: in f32 the logits must agree to
              accumulation noise, in bf16 within ``NUMERICS_RTOL``; every
              greedy step that differs is printed with its top-2 margin.
* moe       — ``moekit.run_moe_layer`` at the DeepSeek-V3 geometry (hidden
              7168, 256 experts, top-8, EP8 in one process, 128 decode
              tokens per rank, bf16 tokens) against ``moekit.oracle``, with
              the Pallas pack/combine kernels compiled for and run on the
              chip.

``--chips 4`` runs only ``comm.moe_a2a`` on a (1, 4) ("data", "model") mesh
at one qwen3-moe-30b-a3b layer's widths in bf16 against ``moe_dense`` on
one chip; the compiled program must hold the all-to-all and the Pallas
kernels.

Each phase prints its wall time and compile count.  The last line of
stdout is ``{"ok": true, "device": {...}}``; it is printed only when every
phase passed.  Without a TPU the script exits 2 and prints no result.
"""

from __future__ import annotations

import argparse
import json
import os
import pathlib
import re
import sys
import time

ROOT = pathlib.Path(__file__).resolve().parent
sys.path.insert(0, str(ROOT / "src"))

# Stated before the first chip run.  The TPU rounds f32 matmul operands to
# bf16 passes by default and the chip runs the flash kernel where the host
# runs chunked attention: 2% of the largest reference magnitude.
NUMERICS_RTOL = 2e-2
# bf16 expert outputs: each rounding is within 2**-9 relative, the gates
# sum to 1 and |f_e| <= 1 + 0.01 * 255, so the combine is within 7e-3.
MOE_ATOL = 1e-2
# moe_a2a vs moe_dense in bf16: gate and output roundings differ (~2**-8);
# a dropped token would miss a whole expert term and fail this bound.
A2A_RTOL = 2e-2
# Decode attention reduces over the whole cache, masked slots included, so
# the cache length may change only the order of that reduction.  In f32 at
# HIGHEST matmul precision that is accumulation noise (~1e-6 relative); a
# masked slot that leaked into the softmax would move logits far more.
HEADROOM_F32_RTOL = 1e-4
# In bf16 the attention output is rounded after that reduction, so the
# same reordering can move an element by 2**-8: the chip-vs-CPU limit.
HEADROOM_BF16_RTOL = NUMERICS_RTOL

SERVE_ARCH, SERVE_PROMPT, SERVE_DECODE, SERVE_REQUESTS = "stablelm-3b", 512, 16, 4


def pallas_kernels(text: str) -> set:
    """Names of the Pallas kernels in a lowered or compiled program."""
    return set(re.findall(r'kernel_name = "([^"]+)"', text))


def rel_err(got, ref) -> float:
    import numpy as np
    got, ref = np.asarray(got, np.float32), np.asarray(ref, np.float32)
    return float(np.abs(got - ref).max() / max(float(np.abs(ref).max()), 1e-30))


class Phase:
    """Prints a phase's wall time and the compilations inside it."""

    def __init__(self, name: str):
        from repro.launch.cache import CompileCounter
        self.name = name
        self.compiles = CompileCounter()

    def __enter__(self) -> "Phase":
        print(f"[{self.name}] start", flush=True)
        self.t0 = time.perf_counter()
        self.compiles.__enter__()
        return self

    def __exit__(self, exc_type, *exc) -> None:
        self.compiles.__exit__()
        status = "FAILED" if exc_type else "ok"
        print(f"[{self.name}] {status}: wall {time.perf_counter() - self.t0:.3f}s "
              f"compiles {self.compiles.count}", flush=True)


def check(cond: bool, what: str) -> None:
    if not cond:
        raise AssertionError(what)


# ---------------------------------------------------------------------------
# phases
# ---------------------------------------------------------------------------

def phase_numerics(arch: str = SERVE_ARCH, seq_len: int = 128) -> None:
    """The reduced prefill on the default device and on the host CPU."""
    import jax
    import numpy as np
    from repro.launch.serve import init_serving_params, serving_config
    from repro.models import prefill_jit

    cfg = serving_config(arch, full=False)
    params = init_serving_params(cfg)
    tokens = jax.numpy.asarray(np.random.default_rng(1).integers(
        0, cfg.vocab, (1, seq_len)), jax.numpy.int32)
    kw = dict(max_len=seq_len, moe_mode="dense")
    attn = pallas_kernels(prefill_jit.lower(params, tokens, cfg, **kw).as_text())
    print(f"  prefill attention on {jax.devices()[0].platform}: "
          f"{'Pallas ' + ', '.join(sorted(attn)) if attn else 'chunked jnp'}")
    lg, cache = prefill_jit(params, tokens, cfg, **kw)
    cpu = jax.devices("cpu")[0]
    lg_c, cache_c = prefill_jit(jax.device_put(params, cpu),
                                jax.device_put(tokens, cpu), cfg, **kw)
    errs = {"logits": rel_err(lg, lg_c)}
    errs.update({f"cache.{k}": rel_err(cache[k], cache_c[k]) for k in cache})
    for name, e in errs.items():
        print(f"  {name}: max|chip - cpu| / max|cpu| = {e:.3e} "
              f"(limit {NUMERICS_RTOL})")
        check(e <= NUMERICS_RTOL, f"{name} differs between chip and CPU")


def phase_serving(arch: str = SERVE_ARCH, full: bool = True,
                  prompt_len: int = SERVE_PROMPT, n_decode: int = SERVE_DECODE,
                  n_requests: int = SERVE_REQUESTS) -> None:
    """launch/serve.py's monolithic and disaggregated paths, warmed up."""
    import jax
    from repro.launch import serve
    from repro.launch.cache import CompileCounter
    from repro.models import prefill_jit

    cfg = serve.serving_config(arch, full=full)
    print(f"  {cfg.name}: {cfg.n_layers} layers, d_model {cfg.d_model}, "
          f"{cfg.n_heads} heads, d_ff {cfg.d_ff}, vocab {cfg.vocab}, "
          f"{cfg.param_dtype}, ~{cfg.param_count() / 1e9:.2f}B params")
    t = time.perf_counter()
    params = jax.block_until_ready(serve.init_serving_params(cfg))
    print(f"  init params: {time.perf_counter() - t:.3f}s")
    prompts, _ = serve.make_requests(cfg, n_requests + 1, prompt_len)
    lowered = prefill_jit.lower(params, prompts[0][None], cfg,
                                max_len=serve.cache_len(prompt_len, n_decode),
                                moe_mode="dense")
    attn = pallas_kernels(lowered.as_text())
    print(f"  prefill attention: "
          f"{'Pallas ' + ', '.join(sorted(attn)) if attn else 'chunked jnp'}")

    with CompileCounter() as warm:
        t = time.perf_counter()
        serve.monolithic(cfg, params, prompts[:1], n_decode)
        serve.disaggregated(cfg, params, prompts[:1], n_decode)
    print(f"  warm-up request: {time.perf_counter() - t:.3f}s, "
          f"{warm.count} compiles ({dict(warm.by_name)})")

    with CompileCounter() as steady:
        t = time.perf_counter()
        mono = serve.monolithic(cfg, params, prompts[1:], n_decode)
        t_mono = time.perf_counter() - t
        t = time.perf_counter()
        done, epoch = serve.disaggregated(cfg, params, prompts[1:], n_decode)
        t_dis = time.perf_counter() - t
    ok = sum(r["tokens"] == ref for r, ref in zip(done, mono))
    print(f"  monolithic: {n_requests} requests x {n_decode} tokens in "
          f"{t_mono:.3f}s (host wall clock, finite logits)")
    ttft = ", ".join("%.1fus" % r["ttft_us"] for r in done)
    print(f"  disaggregated: {n_requests} requests in {t_dis:.3f}s host wall "
          f"clock; simulated TTFT {ttft}")
    print(f"  disaggregated == monolithic for {ok}/{len(done)} requests "
          f"(membership epoch {epoch})")
    print(f"  compilations after warm-up: {steady.count}")
    check(ok == len(done), "disaggregated tokens differ from monolithic")
    check(steady.count == 0, f"steady-state compiles: {dict(steady.by_name)}")


def greedy_logits(cfg, params, ids, n_decode: int, max_len: int, toks=None):
    """The logits of ``n_decode`` greedy steps at cache length ``max_len``,
    as an (n_decode, vocab) f32 array, and the tokens fed back: the greedy
    ones, or ``toks`` when given (so two runs see the same inputs)."""
    import jax.numpy as jnp
    import numpy as np
    from repro.models import decode_step_jit, prefill_jit

    lg, cache = prefill_jit(params, jnp.asarray(ids)[None], cfg,
                            max_len=max_len, moe_mode="dense")
    out = [np.asarray(lg[0, :cfg.vocab], np.float32)]
    toks = list(toks) if toks is not None else [int(out[0].argmax())]
    for i in range(n_decode - 1):
        lg, cache = decode_step_jit(params, jnp.asarray([[toks[i]]]),
                                    jnp.asarray([len(ids) + i], jnp.int32),
                                    cache, cfg, moe_mode="dense")
        out.append(np.asarray(lg[0, :cfg.vocab], np.float32))
        if len(toks) < n_decode:
            toks.append(int(out[-1].argmax()))
    return np.stack(out), toks


def headroom_drift(cfg, params, prompts, n_decode: int, label: str):
    """Logits at the serving cache length (the handoff's) against the same
    steps at ``S + n_decode + 8``, fed the same tokens.  Prints every step
    whose greedy token differs, with its top-2 logit margin; returns the
    largest max|dlogit| / max|logit| and the number of such steps."""
    import numpy as np
    from repro.launch import serve

    worst, flips = 0.0, 0
    for r, ids in enumerate(prompts):
        long_len = serve.cache_len(len(ids), n_decode)
        short_len = len(ids) + n_decode + 8
        a, toks = greedy_logits(cfg, params, ids, n_decode, long_len)
        b, _ = greedy_logits(cfg, params, ids, n_decode, short_len, toks)
        scale = np.abs(a).max(-1)
        drift = np.abs(a - b).max(-1)
        worst = max(worst, float((drift / scale).max()))
        for step in np.flatnonzero(a.argmax(-1) != b.argmax(-1)):
            top2 = np.sort(a[step])[-2:]
            print(f"  {label}: request {r} step {step}: greedy token differs; "
                  f"top-2 margin {top2[1] - top2[0]:.3e}, max|dlogit| "
                  f"{drift[step]:.3e}, max|logit| {scale[step]:.3e}")
            flips += 1
    print(f"  {label}: cache {long_len} vs {short_len}: max|dlogit| / "
          f"max|logit| = {worst:.3e}; {flips} of {len(prompts) * n_decode} "
          f"greedy steps differ")
    return worst, flips


def phase_headroom(arch: str = SERVE_ARCH, prompt_len: int = SERVE_PROMPT,
                   n_decode: int = SERVE_DECODE,
                   n_requests: int = SERVE_REQUESTS, f32_layers: int = 4
                   ) -> None:
    """Greedy decoding at two cache lengths, on the serving phase's
    requests: stablelm-3b in bf16 as served, and in f32 at HIGHEST matmul
    precision at the published widths cut to ``f32_layers`` layers (the
    full depth in f32 does not fit one chip's 16 GB)."""
    import dataclasses
    import jax
    from repro.configs import get_config
    from repro.launch import serve

    cfg = serve.serving_config(arch, full=True)
    prompts = serve.make_requests(cfg, n_requests + 1, prompt_len)[0][1:]
    params = serve.init_serving_params(cfg)
    bf16, _ = headroom_drift(cfg, params, prompts, n_decode,
                             f"bf16, {cfg.n_layers} layers")
    del params
    cfg32 = dataclasses.replace(get_config(arch), n_layers=f32_layers)
    params = serve.init_serving_params(cfg32)
    with jax.default_matmul_precision("highest"):
        f32, f32_flips = headroom_drift(cfg32, params, prompts, n_decode,
                                        f"f32, {f32_layers} layers")
    print(f"  limits: f32 {HEADROOM_F32_RTOL} with no greedy step changed, "
          f"bf16 {HEADROOM_BF16_RTOL}")
    check(f32 <= HEADROOM_F32_RTOL and f32_flips == 0,
          "f32 logits depend on the cache length")
    check(bf16 <= HEADROOM_BF16_RTOL, "bf16 logits depend on the cache length")


def phase_moe(n_ranks: int = 8, n_experts: int = 256, top_k: int = 8,
              tokens_per_rank: int = 128, hidden: int = 7168) -> None:
    """moekit's host-proxy dispatch/combine layer against the oracle."""
    import jax
    import ml_dtypes
    import numpy as np
    from repro.core import Fabric
    from repro.kernels import host, ops
    from repro.launch.cache import CompileCounter
    from repro.moekit import MoEConfig, make_endpoints, oracle, run_moe_layer

    bf16 = ml_dtypes.bfloat16
    cfg = MoEConfig(n_ranks=n_ranks, n_experts=n_experts, top_k=top_k,
                    max_tokens=tokens_per_rank,
                    token_bytes=hidden * np.dtype(bf16).itemsize, t_priv=32)
    rng = np.random.default_rng(0)
    tokens, eids, gates = [], [], []
    for _ in range(n_ranks):
        tokens.append(rng.normal(size=(tokens_per_rank, hidden)).astype(bf16))
        ei = np.stack([rng.choice(n_experts, top_k, replace=False)
                       for _ in range(tokens_per_rank)]).astype(np.int32)
        w = rng.random((tokens_per_rank, top_k)).astype(np.float32)
        g = np.zeros((tokens_per_rank, n_experts), np.float32)
        np.put_along_axis(g, ei, w / w.sum(1, keepdims=True), 1)
        eids.append(ei)
        gates.append(g)

    def expert_fn(e, x):
        return np.tanh(x.astype(np.float32)) * np.float32(1 + 0.01 * e)

    check(host._accel_backend(), "moekit host path would run numpy, not Pallas")
    fab = Fabric(seed=1)
    eps = make_endpoints(fab, cfg, nic="cx7", gpus_per_node=8)
    with CompileCounter() as cc:
        res, stats = run_moe_layer(fab, eps, tokens, eids, gates, expert_fn,
                                   dtype=bf16)
    ref = oracle(tokens, eids, gates, expert_fn, n_experts)
    err = max(float(np.abs(r - o).max()) for r, o in zip(res, ref))
    ran = {k: v for k, v in cc.by_name.items() if "moe_" in k}
    print(f"  EP{n_ranks}, {n_experts} experts, top-{top_k}, "
          f"{tokens_per_rank} tokens/rank, hidden {hidden} bf16")
    print(f"  kernel programs compiled for {jax.devices()[0].platform}: {ran}")
    rows = jax.numpy.zeros((16, cfg.token_bytes), jax.numpy.uint8)
    ye = jax.numpy.zeros((16, hidden), jax.numpy.bfloat16)
    idx = jax.numpy.zeros((2, top_k), jax.numpy.int32)
    kernels = (pallas_kernels(ops.moe_pack.lower(rows, idx[0]).as_text())
               | pallas_kernels(ops.moe_combine.lower(
                   ye, idx, idx.astype(jax.numpy.float32),
                   out_dtype=np.float32).as_text()))
    print(f"  Pallas kernels in those programs: {sorted(kernels)}")
    print(f"  simulated dispatch p50 {np.median(stats['dispatch_us']):.1f}us, "
          f"combine p50 {np.median(stats['combine_us']):.1f}us (fabric "
          f"model, not a chip time)")
    print(f"  max|combine - oracle| = {err:.3e} (limit {MOE_ATOL})")
    check(ran.get("jit(moe_pack)", 0) > 0 and ran.get("jit(moe_combine)", 0) > 0,
          "the Pallas pack/combine programs did not run")
    check({"moe_pack", "moe_combine"} <= kernels, "kernels missing from programs")
    check(err <= MOE_ATOL, "moekit combine differs from the oracle")


def a2a_program(cfg, mesh):
    """The jitted expert-parallel layer and the shardings of its inputs."""
    import jax
    from jax.sharding import NamedSharding
    from jax.sharding import PartitionSpec as P
    from repro.comm import moe_a2a

    def shard(spec):
        return NamedSharding(mesh, spec)

    experts = shard(P("model", None, None))
    p_shd = {"norm": shard(P(None)), "router": shard(P(None, None)),
             "wg": experts, "wu": experts, "wd": experts}
    h_shd = shard(P(("data", "model"), None))
    fn = jax.jit(lambda p, h: moe_a2a(p, h, cfg, "model", mesh=mesh),
                 in_shardings=(p_shd, h_shd))
    return fn, p_shd, h_shd


def phase_a2a(tokens: int = 2048) -> None:
    """comm.moe_a2a over the EP axis of a (1, n) mesh vs moe_dense."""
    import dataclasses
    import jax
    import jax.numpy as jnp
    from repro.configs import get_config
    from repro.launch.mesh import make_local_mesh
    from repro.models.moe import init_moe, moe_dense

    cfg = dataclasses.replace(get_config("qwen3-moe-30b-a3b"),
                              param_dtype="bfloat16")
    n = jax.device_count()
    mesh = make_local_mesh(1, n)
    fn, p_shd, h_shd = a2a_program(cfg, mesh)
    p = jax.jit(init_moe, static_argnums=(1, 2), out_shardings=p_shd)(
        jax.random.PRNGKey(0), cfg, jnp.bfloat16)
    h = jax.jit(lambda k: jax.random.normal(k, (tokens, cfg.d_model),
                                            jnp.bfloat16),
                out_shardings=h_shd)(jax.random.PRNGKey(1))
    print(f"  {cfg.name} layer: d_model {cfg.d_model}, {cfg.n_routed} "
          f"experts, top-{cfg.top_k}, d_ff_expert {cfg.d_ff_expert}, bf16; "
          f"{tokens} tokens on mesh {dict(mesh.shape)}")
    lowered = fn.lower(p, h)
    kernels = pallas_kernels(lowered.as_text())
    compiled = lowered.compile()
    text = compiled.as_text()
    n_a2a = text.count("all-to-all(")
    print(f"  compiled program: {n_a2a} all-to-all, Pallas kernels "
          f"{sorted(kernels)}, tpu_custom_call {'tpu_custom_call' in text}")
    check(n_a2a > 0, "no all-to-all in the expert-parallel program")
    check("tpu_custom_call" in text and {"moe_pack", "moe_combine"} <= kernels,
          "Pallas pack/combine missing from the expert-parallel program")
    t = time.perf_counter()
    y, aux = compiled(p, h)
    y.block_until_ready()
    print(f"  moe_a2a step: {time.perf_counter() - t:.3f}s (first call)")

    one = jax.devices()[0]
    y_ref, aux_ref = jax.jit(moe_dense, static_argnums=2)(
        jax.device_put(p, one), jax.device_put(h, one), cfg)
    e, e_aux = rel_err(y, y_ref), rel_err(aux, aux_ref)
    print(f"  moe_a2a vs moe_dense on one chip: max|dy| / max|y| = {e:.3e}, "
          f"aux {e_aux:.3e} (limit {A2A_RTOL})")
    check(e <= A2A_RTOL and e_aux <= A2A_RTOL, "moe_a2a differs from moe_dense")


# ---------------------------------------------------------------------------

def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4),
                    help="4: run only the expert-parallel MoE phase")
    args = ap.parse_args()

    # the numerics phase runs the same program on the host CPU as well
    platforms = os.environ.get("JAX_PLATFORMS")
    if platforms and "cpu" not in platforms.split(","):
        os.environ["JAX_PLATFORMS"] = platforms + ",cpu"
    import jax
    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: needs a TPU, JAX found {dev.platform}",
              file=sys.stderr)
        return 2
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} but {len(devices)} device(s)",
              file=sys.stderr)
        return 2

    from repro.launch.cache import use_compile_cache
    cache_dir = use_compile_cache()
    print(f"device: {dev.platform} {dev.device_kind} x{len(devices)}; "
          f"jax {jax.__version__}; compile cache {cache_dir}", flush=True)

    phases = ([("a2a", phase_a2a)] if args.chips == 4 else
              [("numerics", phase_numerics), ("serving", phase_serving),
               ("headroom", phase_headroom), ("moe", phase_moe)])
    t0 = time.perf_counter()
    for name, fn in phases:
        with Phase(name):
            fn()
    print(f"all phases passed in {time.perf_counter() - t0:.3f}s", flush=True)
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
