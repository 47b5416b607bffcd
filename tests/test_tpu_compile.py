"""Compile-only checks of the main-path Pallas kernels for a TPU v5e.

The kernels are compiled at real widths for a ``v5e:2x2`` topology that is
described, not attached: Mosaic refuses here what it would refuse on the
chip (unaligned slices, VMEM overflow, unsupported dtypes), at no chip time.
Nothing runs, so these tests say nothing about results or speed.

The topology is described inside a fixture, never at import: only one
process may load the TPU library, and every test worker imports this file.
"""

import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs import get_config
from repro.kernels import flash_attention as fa
from repro.kernels import moe_combine, moe_pack, ssd_scan

# DeepSeek-V3 dispatch geometry (benchmarks/bench_moe.py): hidden 7168,
# top-8, 128 decode tokens per rank; a 7392-byte fp8 token + scales row.
HIDDEN, TOP_K, DECODE_T, PREFILL_T = 7168, 8, 128, 4096
FP8_ROW_BYTES = 7168 + 56 * 4


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


def _compile(fn, one_chip, *shapes):
    specs = [jax.ShapeDtypeStruct(s, d, sharding=one_chip) for s, d in shapes]
    compiled = jax.jit(fn).lower(*specs).compile()
    assert "tpu_custom_call" in compiled.as_text()
    return compiled


@pytest.mark.parametrize("rows,width,dtype", [
    (DECODE_T, HIDDEN, jnp.float32),
    (DECODE_T, HIDDEN, jnp.bfloat16),
    (DECODE_T * TOP_K, FP8_ROW_BYTES, jnp.uint8),     # moekit host byte rows
])
def test_moe_pack_compiles(one_chip, rows, width, dtype):
    _compile(moe_pack.moe_pack, one_chip,
             ((rows, width), dtype), ((DECODE_T * TOP_K,), jnp.int32))


@pytest.mark.parametrize("T,dtype", [(DECODE_T, jnp.bfloat16),
                                     (PREFILL_T, jnp.float32)])
def test_moe_combine_compiles(one_chip, T, dtype):
    # at T=4096 in f32 a kernel that keeps ye resident in VMEM is refused
    _compile(moe_combine.moe_combine, one_chip,
             ((T * TOP_K, HIDDEN), dtype), ((T, TOP_K), jnp.int32),
             ((T, TOP_K), jnp.float32))


def test_flash_attention_compiles_at_stablelm_head_dim(one_chip):
    cfg = get_config("stablelm-3b")
    shape = ((1, cfg.n_heads, 512, cfg.head_dim), jnp.bfloat16)
    _compile(lambda q, k, v: fa.flash_attention(q, k, v, causal=True),
             one_chip, shape, shape, shape)


def test_ssd_intra_compiles_at_mamba2_widths(one_chip):
    cfg = get_config("mamba2-780m")
    bc, h, cl = 4096 // cfg.ssm_chunk, cfg.ssm_nheads, cfg.ssm_chunk
    p, n = cfg.ssm_headdim, cfg.ssm_state
    _compile(ssd_scan.ssd_intra_flat, one_chip,
             ((bc, h, cl, p), jnp.float32), ((bc, h, cl, 1), jnp.float32),
             ((bc, h, cl, n), jnp.float32), ((bc, h, cl, n), jnp.float32))
