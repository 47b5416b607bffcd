"""Per-kernel validation: shape/dtype sweeps + hypothesis vs ref.py oracles."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from _hypothesis_compat import given, settings, st

from repro.kernels import ops, ref


@pytest.mark.parametrize("T,D,M", [(16, 64, 16), (50, 200, 70), (128, 512, 256),
                                   (7, 33, 130)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_moe_pack_sweep(T, D, M, dtype):
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.normal(size=(T, D)), dtype)
    perm = jnp.asarray(rng.integers(-1, T, size=(M,)), jnp.int32)
    np.testing.assert_allclose(
        np.asarray(ops.moe_pack(x, perm), np.float32),
        np.asarray(ref.moe_pack(x, perm), np.float32), rtol=1e-6)


@settings(max_examples=20, deadline=None)
@given(st.integers(1, 40), st.integers(1, 70), st.integers(1, 90),
       st.integers(0, 2**16))
def test_moe_pack_property(T, D, M, seed):
    rng = np.random.default_rng(seed)
    x = jnp.asarray(rng.normal(size=(T, D)), jnp.float32)
    perm = jnp.asarray(rng.integers(-1, T, size=(M,)), jnp.int32)
    np.testing.assert_allclose(ops.moe_pack(x, perm), ref.moe_pack(x, perm),
                               rtol=1e-6)


@pytest.mark.parametrize("T,D,M,K", [(16, 64, 24, 2), (64, 300, 200, 8),
                                     (5, 130, 11, 3)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_moe_combine_sweep(T, D, M, K, dtype):
    rng = np.random.default_rng(1)
    ye = jnp.asarray(rng.normal(size=(M, D)), dtype)
    inv = jnp.asarray(rng.integers(-1, M, size=(T, K)), jnp.int32)
    gates = jnp.asarray(rng.random(size=(T, K)), jnp.float32)
    tol = 1e-5 if dtype == jnp.float32 else 5e-2
    np.testing.assert_allclose(
        np.asarray(ops.moe_combine(ye, inv, gates), np.float32),
        np.asarray(ref.moe_combine(ye, inv, gates), np.float32),
        rtol=tol, atol=tol)


def test_kernel_vjps_match_oracle_grads():
    rng = np.random.default_rng(2)
    T, D, M, K = 20, 32, 30, 3
    x = jnp.asarray(rng.normal(size=(T, D)), jnp.float32)
    perm = jnp.asarray(rng.integers(-1, T, size=(M,)), jnp.int32)
    g1 = jax.grad(lambda x: (ops.moe_pack(x, perm) ** 2).sum())(x)
    g2 = jax.grad(lambda x: (ref.moe_pack(x, perm) ** 2).sum())(x)
    np.testing.assert_allclose(g1, g2, atol=1e-5)

    ye = jnp.asarray(rng.normal(size=(M, D)), jnp.float32)
    inv = jnp.asarray(rng.integers(-1, M, size=(T, K)), jnp.int32)
    gates = jnp.asarray(rng.random(size=(T, K)), jnp.float32)
    ga = jax.grad(lambda y, g: (ops.moe_combine(y, inv, g) ** 2).sum(), (0, 1))(ye, gates)
    gb = jax.grad(lambda y, g: (ref.moe_combine(y, inv, g) ** 2).sum(), (0, 1))(ye, gates)
    np.testing.assert_allclose(ga[0], gb[0], rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(ga[1], gb[1], rtol=1e-4, atol=1e-4)


@pytest.mark.parametrize("Ps,Pd,E,P", [(8, 8, 128, 4), (32, 40, 300, 10),
                                       (4, 4, 4096, 4)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16, jnp.int32])
def test_paged_copy_sweep(Ps, Pd, E, P, dtype):
    rng = np.random.default_rng(3)
    if dtype == jnp.int32:
        src = jnp.asarray(rng.integers(0, 100, (Ps, E)), dtype)
        dst = jnp.asarray(rng.integers(0, 100, (Pd, E)), dtype)
    else:
        src = jnp.asarray(rng.normal(size=(Ps, E)), dtype)
        dst = jnp.asarray(rng.normal(size=(Pd, E)), dtype)
    sidx = jnp.asarray(rng.choice(Ps, P, replace=False), jnp.int32)
    didx = jnp.asarray(rng.choice(Pd, P, replace=False), jnp.int32)
    out = ops.paged_copy(src, sidx, dst, didx)
    expect = ref.paged_copy(src, sidx, dst, didx)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(expect))


@settings(max_examples=15, deadline=None)
@given(st.integers(1, 4), st.integers(1, 3), st.sampled_from([16, 32, 64]),
       st.integers(1, 6), st.sampled_from([8, 16]), st.sampled_from([8, 24]),
       st.integers(0, 2**16))
def test_ssd_intra_property(b, nc, cl, h, p, n, seed):
    rng = np.random.default_rng(seed)
    xw = jnp.asarray(rng.normal(size=(b, nc, cl, h, p)), jnp.float32)
    dA = -jnp.asarray(rng.random(size=(b, nc, cl, h)), jnp.float32) * 0.2
    cum = jnp.cumsum(dA, axis=2)
    Br = jnp.asarray(rng.normal(size=(b, nc, cl, h, n)), jnp.float32)
    Cr = jnp.asarray(rng.normal(size=(b, nc, cl, h, n)), jnp.float32)
    y, stt = ops.ssd_intra(xw, cum, Br, Cr)
    y_r, st_r = ref.ssd_intra(xw, cum, Br, Cr)
    np.testing.assert_allclose(y, y_r, rtol=2e-4, atol=2e-4)
    np.testing.assert_allclose(stt, st_r, rtol=2e-4, atol=2e-4)


@pytest.mark.slow
def test_ssd_kernel_inside_model():
    """End-to-end: mamba2 forward with/without the Pallas kernel agrees."""
    from repro.configs import get_config
    from repro.models import forward_train, init_params
    cfg = get_config("mamba2-780m").reduced()
    params = init_params(cfg, jax.random.PRNGKey(0))
    tokens = jax.random.randint(jax.random.PRNGKey(1), (2, 64), 0, cfg.vocab)
    l1, _ = forward_train(params, tokens, cfg, use_kernel=False, remat=False)
    l2, _ = forward_train(params, tokens, cfg, use_kernel=True, remat=False)
    np.testing.assert_allclose(np.asarray(l1), np.asarray(l2), rtol=1e-4, atol=1e-4)


@pytest.mark.parametrize("cfg", [(2, 4, 128, 64, True, 0),
                                 (1, 2, 256, 64, True, 32),
                                 (1, 1, 64, 128, False, 0)])
def test_flash_attention_vs_oracle(cfg):
    B, H, S, D, causal, win = cfg
    rng = np.random.default_rng(5)
    q = jnp.asarray(rng.normal(size=(B, H, S, D)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(B, H, S, D)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(B, H, S, D)), jnp.float32)
    out = ops.flash_attention(q, k, v, causal=causal, window=win,
                              block_q=64, block_k=64)
    exp = ref.flash_attention(q, k, v, causal=causal, window=win)
    np.testing.assert_allclose(np.asarray(out), np.asarray(exp),
                               rtol=2e-4, atol=2e-4)


@pytest.mark.slow
def test_flash_path_matches_chunked_in_model():
    """attn_prefill with the flash kernel (FORCE_FLASH) agrees with the
    chunked-jnp path across dense / GQA / windowed archs."""
    from repro.models import attention as A
    from repro.models import forward_train, init_params
    from repro.configs import get_config
    for arch in ("stablelm-3b", "granite-3-8b", "gemma3-1b"):
        cfg = get_config(arch).reduced()
        params = init_params(cfg, jax.random.PRNGKey(0))
        tokens = jax.random.randint(jax.random.PRNGKey(1), (2, 64), 0, cfg.vocab)
        l_ref, _ = forward_train(params, tokens, cfg, moe_mode="dense",
                                 remat=False)
        A.FORCE_FLASH = True
        try:
            l_flash, _ = forward_train(params, tokens, cfg, moe_mode="dense",
                                       remat=False)
        finally:
            A.FORCE_FLASH = False
        np.testing.assert_allclose(np.asarray(l_flash), np.asarray(l_ref),
                                   rtol=5e-4, atol=5e-4)


@pytest.mark.parametrize("window", [None, 16])
def test_flash_path_grads_match_chunked(window):
    """The flash kernel is forward-only: attn_prefill's custom VJP
    differentiates the chunked path, so gradients through the kernel
    (FORCE_FLASH) equal those of the platform's default path."""
    from repro.configs import get_config
    from repro.models import attention as A
    cfg = get_config("stablelm-3b").reduced()
    p = A.init_attn(jax.random.PRNGKey(0), cfg, jnp.float32)
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 32, cfg.d_model))

    def grads():
        loss = lambda p, x: (A.attn_prefill(p, x, cfg, window=window) ** 2).sum()
        return jax.jit(jax.grad(loss, (0, 1)))(p, x)

    g_ref = grads()
    A.FORCE_FLASH = True
    try:
        g_flash = grads()
    finally:
        A.FORCE_FLASH = False
    for a, b in zip(jax.tree.leaves(g_flash), jax.tree.leaves(g_ref)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-5, atol=1e-5)
