"""repro.runtime — the execution substrate.

One import surface for everything mesh/sharding related:

  * ``compat``       — the JAX mesh API (make_mesh, shard_map, use_mesh,
                       get_active_mesh), executable serialization and the
                       persistent compilation cache
  * ``mesh``         — production / debug / flat mesh builders
  * ``partitioning`` — logical-axis rules, PartitionSpec resolution,
                       logical_constraint, sharded message passing

This package is the sole home of mesh/sharding logic; the pre-runtime
import paths (``repro.sharding``, ``repro.launch.mesh``,
``repro.core.distributed``) are gone.
"""
from repro.runtime import compat, mesh, partitioning
from repro.runtime.compat import (
    configure_compilation_cache,
    deserialize_compiled,
    get_active_mesh,
    make_mesh,
    require_tpu,
    serialize_compiled,
    shard_map,
    use_mesh,
)
from repro.runtime.mesh import (
    flatten_mesh,
    make_debug_mesh,
    make_flat_mesh,
    make_production_mesh,
)
from repro.runtime.partitioning import (
    DEFAULT_RULES,
    active_rules,
    allgather_mp_local,
    alltoall_mp_local,
    batch_rules,
    fsdp_rules,
    gnn_rules,
    logical_constraint,
    make_sharded_mp,
    resolve_spec,
    tree_shardings,
    tree_specs,
    zero1_rules,
    zero1_spec,
)

__all__ = [
    "compat",
    "mesh",
    "partitioning",
    "configure_compilation_cache",
    "deserialize_compiled",
    "get_active_mesh",
    "make_mesh",
    "require_tpu",
    "serialize_compiled",
    "shard_map",
    "use_mesh",
    "flatten_mesh",
    "make_debug_mesh",
    "make_flat_mesh",
    "make_production_mesh",
    "DEFAULT_RULES",
    "active_rules",
    "allgather_mp_local",
    "alltoall_mp_local",
    "batch_rules",
    "fsdp_rules",
    "gnn_rules",
    "logical_constraint",
    "make_sharded_mp",
    "resolve_spec",
    "tree_shardings",
    "tree_specs",
    "zero1_rules",
    "zero1_spec",
]
