"""repro.runtime.compat: the mesh facade over ``jax.set_mesh`` /
``get_abstract_mesh`` / ``jax.shard_map``, the placement of JAX's
persistent compilation cache, and the launchers' platform check."""
import types

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec

from repro.runtime import compat
from repro.runtime import partitioning as PT


def test_use_mesh_installs_and_restores_the_active_mesh():
    assert compat.get_active_mesh() is None
    mesh = compat.make_mesh((1,), ("data",))
    with compat.use_mesh(mesh):
        got = compat.get_active_mesh()
        assert got is not None and dict(got.shape) == {"data": 1}
    assert compat.get_active_mesh() is None


def test_get_active_mesh_present_api_wins(monkeypatch):
    fake = types.SimpleNamespace(empty=False, size=4, shape={"data": 4})
    monkeypatch.setattr(jax.sharding, "get_abstract_mesh", lambda: fake)
    assert compat.get_active_mesh() is fake


def test_shard_map_executes():
    mesh = compat.make_mesh((1,), ("d",))
    fn = compat.shard_map(
        lambda x: x * 2.0, mesh=mesh,
        in_specs=PartitionSpec("d"), out_specs=PartitionSpec("d"),
    )
    out = fn(jnp.arange(4.0))
    np.testing.assert_allclose(np.asarray(out), np.arange(4.0) * 2)


def test_logical_constraint_noop_without_mesh():
    x = jnp.ones((4, 4))
    assert PT.logical_constraint(x, ("batch", None)) is x


def test_logical_constraint_noop_on_single_device_mesh():
    mesh = compat.make_mesh((1,), ("data",))
    x = jnp.ones((4, 4))
    with compat.use_mesh(mesh):
        assert PT.logical_constraint(x, ("batch", None)) is x


def test_deprecation_shims_are_gone():
    # the PR-1 shim modules were deleted once external callers migrated;
    # their import paths must stay dead (a reintroduction would silently
    # shadow the runtime package as the canonical home)
    import importlib

    import pytest

    for name in ("repro.sharding", "repro.core.distributed",
                 "repro.launch.mesh"):
        with pytest.raises(ModuleNotFoundError):
            importlib.import_module(name)


def test_compilation_cache_honours_the_environment(monkeypatch, tmp_path):
    """With JAX_COMPILATION_CACHE_DIR set, the helper sets no directory
    of its own; unset, the cache lands at the fixed in-checkout path."""
    from pathlib import Path

    before = (jax.config.jax_compilation_cache_dir,
              jax.config.jax_persistent_cache_min_compile_time_secs,
              jax.config.jax_persistent_cache_min_entry_size_bytes)
    try:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
        jax.config.update("jax_compilation_cache_dir", None)
        assert compat.configure_compilation_cache() == str(tmp_path)
        assert jax.config.jax_compilation_cache_dir is None

        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
        want = Path(__file__).resolve().parent.parent / ".jax_cache"
        assert compat.default_compilation_cache_dir() == str(want)
        assert compat.configure_compilation_cache() == str(want)
        assert jax.config.jax_compilation_cache_dir == str(want)
    finally:
        jax.config.update("jax_compilation_cache_dir", before[0])
        jax.config.update("jax_persistent_cache_min_compile_time_secs",
                          before[1])
        jax.config.update("jax_persistent_cache_min_entry_size_bytes",
                          before[2])


def test_require_tpu_refuses_an_unasked_cpu(monkeypatch):
    """JAX on the CPU is accepted only when JAX_PLATFORMS asked for it;
    a fall-back the user did not ask for stops the launcher."""
    import pytest

    monkeypatch.setenv("JAX_PLATFORMS", "cpu")
    assert compat.require_tpu() == "cpu"
    monkeypatch.delenv("JAX_PLATFORMS")
    with pytest.raises(SystemExit, match="no TPU found"):
        compat.require_tpu()
    monkeypatch.setenv("JAX_PLATFORMS", "tpu,cpu")
    assert compat.require_tpu() == "cpu"
    fake = types.SimpleNamespace(platform="tpu")
    monkeypatch.setattr(jax, "devices", lambda *a: [fake])
    monkeypatch.delenv("JAX_PLATFORMS")
    assert compat.require_tpu() == "tpu"
