"""Distributed training launcher.

Builds the sharded train step for an (arch, shape) pair on a mesh sized to
the available devices, feeds it from the deterministic data pipeline, and
logs/checkpoints.  On this CPU container it runs reduced configs on a 1x1
mesh; on a real slice the same entrypoint runs the full configs on the
production mesh (--production).

    PYTHONPATH=src python -m repro.launch.train --arch gemma3-1b \
        --steps 20 --seq-len 128 --global-batch 4 [--reduced]
"""

from __future__ import annotations

import argparse
import dataclasses
import time

import jax
import jax.numpy as jnp

from ..checkpoint import ckpt
from ..configs import ARCH_IDS, INPUT_SHAPES, get_config
from ..configs.shapes import InputShape
from ..data import Batcher, SyntheticCorpus
from ..models import init_params
from ..optim import init_adamw
from . import steps as St
from .cache import use_compile_cache
from .mesh import make_local_mesh, make_production_mesh


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", choices=ARCH_IDS, required=True)
    ap.add_argument("--steps", type=int, default=20)
    ap.add_argument("--seq-len", type=int, default=128)
    ap.add_argument("--global-batch", type=int, default=4)
    ap.add_argument("--reduced", action="store_true", default=True)
    ap.add_argument("--full", dest="reduced", action="store_false")
    ap.add_argument("--production", action="store_true",
                    help="use the 16x16 production mesh (needs 256 devices)")
    ap.add_argument("--data", type=int, default=1)
    ap.add_argument("--model", type=int, default=1)
    ap.add_argument("--moe-mode", default="scatter",
                    choices=["dense", "scatter", "a2a"])
    ap.add_argument("--ckpt", default="")
    ap.add_argument("--log-every", type=int, default=5)
    args = ap.parse_args()

    use_compile_cache()
    cfg = get_config(args.arch)
    if args.reduced:
        cfg = cfg.reduced()
    mesh = (make_production_mesh() if args.production
            else make_local_mesh(args.data, args.model))
    shape = InputShape("cli", args.seq_len, args.global_batch, "train")
    print(f"arch={cfg.name} params~{cfg.param_count() / 1e6:.1f}M "
          f"mesh={dict(mesh.shape)} moe_mode={args.moe_mode}")

    step_fn, (p_shd, o_shd, b_shd) = St.build_train_step(
        cfg, mesh, shape, moe_mode=args.moe_mode)
    params = jax.device_put(init_params(cfg, jax.random.PRNGKey(0)), p_shd)
    opt = jax.device_put(init_adamw(params), o_shd)
    batcher = Batcher(SyntheticCorpus(cfg.vocab, seed=0),
                      args.global_batch, args.seq_len)

    t0 = time.time()
    for step in range(args.steps):
        batch = {k: jnp.asarray(v) for k, v in batcher.batch(step).items()}
        params, opt, metrics = step_fn(params, opt, batch)
        if step % args.log_every == 0 or step == args.steps - 1:
            print(f"step {step:4d}  loss {float(metrics['loss']):.4f}  "
                  f"gnorm {float(metrics['grad_norm']):.2f}  "
                  f"{time.time() - t0:.0f}s")
    if args.ckpt:
        ckpt.save(args.ckpt, {"params": params}, step=args.steps,
                  meta={"arch": cfg.name})
        print(f"checkpoint -> {args.ckpt}.npz")


if __name__ == "__main__":
    main()
