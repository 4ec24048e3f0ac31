"""One facade over JAX's mesh/sharding, executable-serialization and
compilation-cache surfaces.

Everything in ``repro`` that needs a mesh goes through this module:
``make_mesh`` (Auto axis types), ``shard_map`` (the public
``jax.shard_map``), ``use_mesh`` (``jax.set_mesh``) and
``get_active_mesh`` (``jax.sharding.get_abstract_mesh``).

It also wraps the **executable serialization** API that the persistent
AOT compile cache (``serve/aot.py``) builds on:
``jax.experimental.serialize_executable`` round-trips a
``Lowered(...).compile()`` product to bytes and back without retracing
or recompiling.

:func:`configure_compilation_cache` places JAX's own persistent
compilation cache and :func:`require_tpu` refuses a silent fall-back off
the chip; every entry point calls both once at start-up.
"""
from __future__ import annotations

import contextlib
import os
from typing import Callable, Optional

import jax
from jax.experimental import serialize_executable as _sx

# <checkout>/src/repro/runtime/compat.py -> <checkout>
_CHECKOUT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.dirname(os.path.abspath(__file__)))))

# ------------------------------------------------------ compilation cache


def default_compilation_cache_dir() -> str:
    """The fixed in-checkout cache path used when the environment names
    none.  Fixed on purpose: the path is part of the cache's key, so a
    temporary or per-process directory would never hit."""
    return os.path.join(_CHECKOUT, ".jax_cache")


def configure_compilation_cache() -> str:
    """Turn on JAX's persistent compilation cache and return its
    directory.

    When ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already reads it and
    this sets no directory; otherwise the cache goes to
    :func:`default_compilation_cache_dir`.  Either way every program
    qualifies for caching (no minimum compile time or entry size), so a
    second process finds every serving program it compiled before."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = default_compilation_cache_dir()
        jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


# ------------------------------------------------------------ platform


def require_tpu() -> str:
    """The platform JAX runs on, for an entry point that serves or trains.

    Unless ``JAX_PLATFORMS`` names the CPU (tests, CI, the interpret-mode
    kernels), the program runs on the TPU or not at all: JAX falling back
    to another platform exits the process with an error rather than
    serving quietly through the jnp reference path."""
    platform = jax.devices()[0].platform
    asked = os.environ.get("JAX_PLATFORMS", "").split(",")
    if platform != "tpu" and "cpu" not in asked:
        raise SystemExit(
            f"no TPU found (JAX runs on {platform!r}); set JAX_PLATFORMS=cpu "
            f"to run on the CPU on purpose")
    return platform


# ------------------------------------------------- executable serialization


def serialize_compiled(compiled) -> tuple:
    """Serialize one ``jax.stages.Compiled`` to ``(payload_bytes,
    in_tree, out_tree)`` — everything :func:`deserialize_compiled` needs
    to rebuild a callable executable in another process."""
    return _sx.serialize(compiled)


def deserialize_compiled(payload: bytes, in_tree, out_tree):
    """Rebuild a callable ``Compiled`` from :func:`serialize_compiled`'s
    triple.  The executable binds to this process's backend: the caller
    (``serve/aot.py``) is responsible for fingerprinting the environment
    so a payload is never loaded onto a different jax/jaxlib/backend/
    topology than it was compiled for."""
    return _sx.deserialize_and_load(payload, in_tree, out_tree)


# ------------------------------------------------------- mesh construction


def make_mesh(axis_shapes, axis_names, *, devices=None) -> jax.sharding.Mesh:
    """``jax.make_mesh`` with Auto axis types."""
    kw = {"devices": devices} if devices is not None else {}
    return jax.make_mesh(
        tuple(axis_shapes), tuple(axis_names),
        axis_types=(jax.sharding.AxisType.Auto,) * len(axis_names), **kw,
    )


def mesh_from_devices(devices, axis_names) -> jax.sharding.Mesh:
    """Build a Mesh from an explicit device array (e.g. a flattened view of
    another mesh's devices)."""
    return jax.sharding.Mesh(
        devices, tuple(axis_names),
        axis_types=(jax.sharding.AxisType.Auto,) * len(axis_names),
    )


# ------------------------------------------------------------- shard_map


def shard_map(
    f: Callable, mesh, in_specs, out_specs, check_replication: bool = False
):
    """``jax.shard_map`` with replication checking off by default."""
    return jax.shard_map(f, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=check_replication)


# ------------------------------------------------------------ active mesh


@contextlib.contextmanager
def use_mesh(mesh):
    """Install ``mesh`` as the active mesh for the dynamic extent."""
    with jax.set_mesh(mesh):
        yield mesh


def get_active_mesh() -> Optional[object]:
    """Return the active abstract mesh, or None when no mesh is set."""
    mesh = jax.sharding.get_abstract_mesh()
    if mesh is None or mesh.empty:
        return None
    return mesh
