"""Serving launcher: batched prefill + decode for any assigned arch.

Two modes:
  * monolithic  — jitted prefill + decode_step on one device
  * disagg      — the §4 disaggregated path over the simulated fabric
                  (prefillers + decoders + scheduler), verified against the
                  monolithic generation.  Works for EVERY arch family:
                  ``repro.kvlayout`` derives the cache schema (uniform,
                  pattern-split, SSM/hybrid, first-k-dense) and compiles
                  the transfer plan.

The default config is the reduced float32 variant; ``--full`` serves the
published widths in bf16.

    PYTHONPATH=src python -m repro.launch.serve --arch stablelm-3b \
        --requests 4 --prompt-len 48 --decode 8 [--disagg] [--full]
"""

from __future__ import annotations

import argparse
import dataclasses
import time
from typing import Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..configs import ARCH_IDS, get_config
from ..kvlayout import handoff_max_len
from ..models import decode_step_jit, init_params, prefill_jit
from .cache import use_compile_cache


def serving_config(arch: str, full: bool):
    """The published widths in bf16 (``full``), else the reduced f32 smoke
    config of the same family."""
    cfg = get_config(arch)
    if full:
        return dataclasses.replace(cfg, param_dtype="bfloat16")
    return cfg.reduced()


def init_serving_params(cfg, seed: int = 0):
    """Random weights from ``seed``, initialised by one compiled program."""
    return jax.jit(init_params, static_argnums=0)(cfg, jax.random.PRNGKey(seed))


def make_requests(cfg, n: int, prompt_len: int, seed: int = 0
                  ) -> Tuple[List[np.ndarray], Optional[np.ndarray]]:
    """``n`` random prompts and, for vlm archs, one shared synthetic image
    (both paths use the same one, so parity holds)."""
    rng = np.random.default_rng(seed)
    prompts = [rng.integers(0, cfg.vocab, size=prompt_len) for _ in range(n)]
    vision_emb = (rng.normal(size=(cfg.vision_seq, cfg.vision_dim))
                  .astype(np.float32) if cfg.family == "vlm" else None)
    return prompts, vision_emb


def cache_len(prompt_len: int, n_decode: int) -> int:
    """Default monolithic cache length: the disaggregated handoff's, so both
    paths decode with the same compiled programs.  Decode attention reduces
    over the whole cache; in bf16 another length can round a near-tied
    greedy token the other way (``chip_smoke.py`` measures that drift)."""
    return max(handoff_max_len(prompt_len), prompt_len + n_decode)


def _argmax(logits, cfg) -> int:
    if not bool(jnp.isfinite(logits).all()):
        raise FloatingPointError("non-finite logits")
    return int(jnp.argmax(logits[0, :cfg.vocab]))


def monolithic(cfg, params, prompts, n_decode: int, vision_emb=None, *,
               max_len: Optional[int] = None):
    """Greedy generation of ``n_decode`` tokens per prompt, one request at
    a time, in a cache of ``max_len`` tokens (default ``cache_len``);
    raises on non-finite logits."""
    ve = None if vision_emb is None else jnp.asarray(vision_emb)[None]
    outs = []
    for ids in prompts:
        lg, cache = prefill_jit(params, jnp.asarray(ids)[None], cfg,
                                max_len=(max_len or
                                         cache_len(len(ids), n_decode)),
                                moe_mode="dense", vision_emb=ve)
        toks = [_argmax(lg, cfg)]
        pos = len(ids)
        for _ in range(n_decode - 1):
            lg, cache = decode_step_jit(params, jnp.asarray([[toks[-1]]]),
                                        jnp.asarray([pos], jnp.int32), cache,
                                        cfg, moe_mode="dense")
            toks.append(_argmax(lg, cfg))
            pos += 1
        outs.append(toks)
    return outs


def disaggregated(cfg, params, prompts, n_decode: int, vision_emb=None, *,
                  nic: str = "efa") -> Tuple[List[Dict], int]:
    """Serve ``prompts`` through 2 prefillers, 2 decoders and a Scheduler
    on the simulated fabric.  Returns the per-request completion records
    (in submission order) and the final membership epoch."""
    from ..core import Fabric
    from ..ctrl import ControlPlane
    from ..serving import (Decoder, Prefiller, Scheduler,
                           disagg_unsupported_reason)
    reason = disagg_unsupported_reason(cfg)
    if reason:
        raise SystemExit(f"disagg path cannot serve '{cfg.name}': {reason}")
    # every peer's pool holds all requests at once: routing may place them
    # on one peer
    pool = dict(max_seq_len=max(len(p) for p in prompts),
                max_inflight=len(prompts))
    fab = Fabric(seed=1)
    ctrl = ControlPlane(fab, nic=nic)
    for i in range(2):
        Prefiller(fab, f"p{i}", cfg, params, nic=nic, ctrl=ctrl, **pool)
        Decoder(fab, f"d{i}", cfg, params, nic=nic, ctrl=ctrl, **pool)
    sched = Scheduler(fab, ctrl)
    rids = [sched.submit(ids, n_decode=n_decode, vision_emb=vision_emb)
            for ids in prompts]
    fab.run()
    sched.check_drained()
    return [sched.completed[rid] for rid in rids], sched.view.epoch


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", choices=ARCH_IDS, required=True)
    ap.add_argument("--requests", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=48)
    ap.add_argument("--decode", type=int, default=8)
    ap.add_argument("--disagg", action="store_true")
    ap.add_argument("--full", action="store_true",
                    help="published widths in bf16 (default: reduced f32)")
    ap.add_argument("--nic", default="efa", choices=["efa", "efa4", "cx7"])
    args = ap.parse_args()

    use_compile_cache()
    cfg = serving_config(args.arch, args.full)
    params = init_serving_params(cfg)
    prompts, vision_emb = make_requests(cfg, args.requests, args.prompt_len)

    t0 = time.time()
    mono = monolithic(cfg, params, prompts, args.decode, vision_emb)
    print(f"monolithic: {args.requests} requests x {args.decode} tokens "
          f"in {time.time() - t0:.1f}s")

    if args.disagg:
        done, epoch = disaggregated(cfg, params, prompts, args.decode,
                                    vision_emb, nic=args.nic)
        ok = 0
        for i, (r, ref) in enumerate(zip(done, mono)):
            ok += r["tokens"] == ref
            print(f"req {i}: TTFT {r['ttft_us']:8.1f}us  "
                  f"p={r['prefiller']} d={r['decoder']}  "
                  f"match={r['tokens'] == ref}")
        print(f"disaggregated == monolithic for {ok}/{len(done)} requests "
              f"(membership epoch {epoch})")
        if ok != len(done):
            raise SystemExit("disaggregated output differs from monolithic")

    for i, toks in enumerate(mono[:2]):
        print(f"sample {i}: {toks}")


if __name__ == "__main__":
    main()
