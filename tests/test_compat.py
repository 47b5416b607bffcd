"""The installed jax's mesh and shard_map API, as the repo uses it.

The repo targets one jax (pinned in pyproject.toml): meshes come from
``repro.launch.mesh.make_mesh`` with Auto axis types, and shard_map is
``jax.shard_map`` with ``check_vma``."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import AxisType
from jax.sharding import PartitionSpec as P

from repro.launch.cache import REPO_CACHE, use_compile_cache
from repro.launch.mesh import make_mesh


def test_mesh_carries_auto_axis_types():
    mesh = make_mesh((1, 1), ("data", "model"))
    assert mesh.axis_types == (AxisType.Auto, AxisType.Auto)


def test_make_mesh_builds_named_axes():
    mesh = make_mesh((1, 1), ("data", "model"))
    assert mesh.axis_names == ("data", "model")
    assert dict(mesh.shape) == {"data": 1, "model": 1}


def test_launch_mesh_module_imports_and_builds():
    from repro.launch.mesh import make_local_mesh
    mesh = make_local_mesh(1, 1)
    assert mesh.axis_names == ("data", "model")


def test_shard_map_runs_and_matches():
    mesh = make_mesh((1,), ("x",))
    f = jax.shard_map(lambda a: a * 2, mesh=mesh, in_specs=P(None),
                      out_specs=P(None), check_vma=False)
    out = jax.jit(f)(jnp.arange(4.0))
    np.testing.assert_allclose(np.asarray(out), np.arange(4.0) * 2)


@pytest.mark.parametrize("env_dir", ["/elsewhere/jax-cache", None])
def test_compile_cache_dir(monkeypatch, env_dir):
    """``$JAX_COMPILATION_CACHE_DIR`` wins and is left to jax; otherwise
    the cache is the fixed ``<repo>/.jax_cache``."""
    before = jax.config.jax_compilation_cache_dir
    if env_dir is None:
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    else:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", env_dir)
    try:
        got = use_compile_cache()
        if env_dir is None:
            assert got == str(REPO_CACHE) == jax.config.jax_compilation_cache_dir
            assert REPO_CACHE.name == ".jax_cache"
            assert (REPO_CACHE.parent / "pyproject.toml").exists()
        else:
            assert got == env_dir
            assert jax.config.jax_compilation_cache_dir == before
    finally:
        jax.config.update("jax_compilation_cache_dir", before)
