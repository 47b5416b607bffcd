"""Composable model definitions (all families, scan-stacked layers)."""

from .model import (decode_step, decode_step_jit, forward_train, init_cache,
                    init_params, loss_fn, prefill, prefill_jit)

__all__ = ["init_params", "forward_train", "loss_fn", "init_cache",
           "prefill", "decode_step", "prefill_jit", "decode_step_jit"]
