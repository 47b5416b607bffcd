"""MoE dispatch/combine demo (paper §6): both halves of the reproduction.

1. Host-proxy protocol over the simulated fabric: routes scatter ->
   speculative private buffers -> contiguous placement -> grouped compute ->
   single-scatter combine, validated against a dense oracle.
2. TPU-native path: the same dispatch/combine as shard_map all_to_all with
   the Pallas pack/combine kernels (run with
   XLA_FLAGS=--xla_force_host_platform_device_count=8 to see it sharded).

    PYTHONPATH=src python examples/moe_dispatch_demo.py
"""

import numpy as np

from repro.core import Fabric
from repro.moekit import MoEConfig, make_endpoints, oracle, run_moe_layer

# -- 1. fabric protocol -----------------------------------------------------
N, E, R, T, elems = 8, 32, 4, 32, 64
cfg = MoEConfig(n_ranks=N, n_experts=E, top_k=R, max_tokens=T,
                token_bytes=elems * 4, t_priv=8)
fab = Fabric(seed=0)
eps = make_endpoints(fab, cfg, nic="efa", gpus_per_node=4)

rng = np.random.default_rng(0)
tokens, eids, gates = [], [], []
for r in range(N):
    tokens.append(rng.normal(size=(T, elems)).astype(np.float32))
    ei = np.stack([rng.choice(E, R, replace=False) for _ in range(T)]).astype(np.int32)
    eids.append(ei)
    g = np.zeros((T, E), np.float32)
    for t in range(T):
        w = rng.random(R)
        g[t, ei[t]] = w / w.sum()
    gates.append(g)

expert_fn = lambda e, x: np.tanh(x) * (1 + 0.1 * e)
res, stats = run_moe_layer(fab, eps, tokens, eids, gates, expert_fn)
ref = oracle(tokens, eids, gates, expert_fn, E)
for r in range(N):
    np.testing.assert_allclose(res[r], ref[r], rtol=1e-4, atol=1e-4)
print(f"fabric protocol == oracle across {N} ranks, {E} experts, top-{R}")
print(f"  dispatch p50 {np.median(stats['dispatch_us']):.1f}us  "
      f"combine p50 {np.median(stats['combine_us']):.1f}us "
      f"(EFA, 4 GPUs/node, NVLink intra-node)")

# -- 2. TPU-native shard_map path ----------------------------------------------
import jax
import jax.numpy as jnp

from repro.comm import moe_a2a, use_mesh
from repro.configs import get_config
from repro.models.moe import init_moe, moe_dense

mcfg = get_config("qwen3-moe-30b-a3b").reduced()
n_dev = jax.device_count()
if n_dev >= 4:
    from repro.launch.mesh import make_mesh
    mesh = make_mesh((n_dev // 4, 4), ("data", "model"))
    p = init_moe(jax.random.PRNGKey(0), mcfg, jnp.float32)
    h = jax.random.normal(jax.random.PRNGKey(1), (64, mcfg.d_model)) * 0.5
    y_ref, _ = moe_dense(p, h, mcfg)
    with use_mesh(mesh):
        y, _ = jax.jit(lambda p, h: moe_a2a(p, h, mcfg, "model"))(p, h)
    np.testing.assert_allclose(np.asarray(y), np.asarray(y_ref), atol=2e-5)
    print(f"shard_map all_to_all path == dense oracle on {n_dev} devices")
else:
    print(f"({n_dev} device(s): run with "
          f"XLA_FLAGS=--xla_force_host_platform_device_count=8 for the "
          f"sharded path)")
print("moe demo OK")
