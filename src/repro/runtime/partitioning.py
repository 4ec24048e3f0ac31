"""Logical-axis -> mesh-axis resolution, sharding rules, and the sharded
message-passing collectives (absorbs the old ``repro.sharding`` and the
collective helpers of ``repro.core.distributed``).

Model code annotates every parameter/cache dimension with a *logical* axis
name (params.Param).  This module turns those names into physical
PartitionSpecs for a given mesh via a rules table, enforcing:

  * a mesh axis is used at most once per tensor,
  * a dim is only sharded if its size divides evenly,
  * multi-axis rules (("pod","data") for batch) use the largest prefix
    that divides.

This is how e.g. Mixtral's 8 experts on a 16-way model axis fall back
gracefully: "experts" fails the divisibility check, and the d_ff dim picks
up the model axis instead (classic TP-within-expert) with no per-model
special cases.  The same machinery shards the GNN serving path: padded
node/edge rows carry the logical axes "nodes"/"edges" and resolve onto the
data axis of whatever mesh the engine runs under.
"""
from __future__ import annotations

import contextlib
import contextvars
from functools import partial
from typing import Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec

from repro import params as P
from repro.runtime import compat

# Candidate mesh axes per logical axis, in priority order.  A tuple value
# means "use jointly" (e.g. batch over pod x data); a list means
# "try alternatives in order".
DEFAULT_RULES: Dict[Optional[str], tuple] = {
    "batch": ("pod", "data"),
    "seq": (),
    "kv_seq": (),  # overridden to ("data",) for seq-sharded long decode
    "vocab": ("model",),
    "embed": (),
    "embed_out": (),
    "heads": ("model",),
    "heads_flat": ("model",),
    "kv_heads": ("model",),
    # head_dim stays unsharded: when kv_heads < TP width the KV projection
    # is REPLICATED (Megatron convention).  Sharding head_dim instead
    # measurably triggers involuntary GSPMD rematerialization at the
    # repeat_kv boundary (full replication + 650 GB/dev temps).
    "head_dim": (),
    "mlp": ("model",),
    "experts": ("model",),
    # MoE slot tensors: batch-rows axis used by the expert-GEMM constraint;
    # defaults to the batch mapping, overridden by hybrid FSDP+EP rules
    "moe_batch": ("pod", "data"),
    "inner": ("model",),  # mamba d_inner
    "state": (),
    "q_lora": (),
    "kv_lora": (),
    "layers": (),
    # GNN serving: padded node/edge/graph rows (see gnn_rules)
    "nodes": (),
    "edges": (),
    "graphs": (),
    None: (),
}


def resolve_spec(
    axes: Tuple[Optional[str], ...],
    shape: Tuple[int, ...],
    mesh: Mesh,
    rules: Dict[Optional[str], tuple] | None = None,
) -> PartitionSpec:
    """Map one tensor's logical axes to a PartitionSpec under ``mesh``."""
    rules = rules or DEFAULT_RULES
    used: set = set()
    spec = []
    for dim, name in zip(shape, axes):
        cands = rules.get(name, ())
        chosen: list = []
        prod = 1
        for ax in cands:
            if ax not in mesh.shape or ax in used:
                continue
            nx = mesh.shape[ax]
            if dim % (prod * nx) == 0:
                chosen.append(ax)
                prod *= nx
        if chosen:
            used.update(chosen)
            spec.append(tuple(chosen) if len(chosen) > 1 else chosen[0])
        else:
            spec.append(None)
    return PartitionSpec(*spec)


def tree_shardings(param_tree, mesh: Mesh, rules=None):
    """Param tree -> matching tree of NamedShardings."""

    def f(p: P.Param):
        shape = p.value.shape
        return NamedSharding(mesh, resolve_spec(p.axes, shape, mesh, rules))

    return jax.tree.map(f, param_tree, is_leaf=P.is_param)


def tree_specs(param_tree, mesh: Mesh, rules=None):
    def f(p: P.Param):
        return resolve_spec(p.axes, p.value.shape, mesh, rules)

    return jax.tree.map(f, param_tree, is_leaf=P.is_param)


def batch_rules(mesh: Mesh, batch: int, seq_shard: bool = False) -> dict:
    """Shape-aware rules for activations/caches.

    When the global batch cannot cover the data axis (long-context decode,
    batch=1), shard the KV-cache *sequence* dimension over data instead —
    sequence parallelism for the cache (DESIGN.md §8).
    """
    rules = dict(DEFAULT_RULES)
    dp = int(np.prod([mesh.shape.get(a, 1) for a in ("pod", "data")]))
    if batch % dp != 0 or seq_shard:
        rules["batch"] = ()
        rules["kv_seq"] = ("data",)
    return rules


def fsdp_rules(mesh: Mesh, batch: int) -> dict:
    """FSDP-style preset: data parallelism over BOTH mesh axes, parameters
    sharded over the model axis (GSPMD all-gathers each layer's weights at
    use — ZeRO-3 semantics).

    Napkin math vs Megatron-TP at global batch 256 on 16x16 (per device):
      TP:   ~6 activation all-reduces/layer x (B/dp x S x D) — O(10 s)
      FSDP: param all-gather 3x params_bytes/model_axis + grad
            reduce-scatter — O(1-4 s) for 4-30B dense models
    and the replicated-attention memory problem (MLA, 40 heads) vanishes
    because attention is sequence-local at batch-per-device <= 1.
    """
    rules = dict(DEFAULT_RULES)
    rules["batch"] = ("pod", "data", "model")
    rules["moe_batch"] = ("pod", "data", "model")  # pure FSDP: forcing EP
    # inside this layout was measured at 469 s of resharding (H2, refuted)
    rules["embed"] = ("model",)  # weight matrices: shard the embed dim
    rules["kv_seq"] = ()
    return rules


def gnn_rules(mesh: Mesh | None = None, axis: str = "data") -> dict:
    """GNN serving preset: padded node/edge rows (and the per-graph pool
    axis) shard over ``axis``.  Divisibility-aware resolution means buckets
    whose padded sizes don't divide the axis simply stay replicated.
    ``mesh`` (optional) validates that ``axis`` actually exists on it."""
    if mesh is not None and axis not in mesh.shape:
        raise ValueError(
            f"axis {axis!r} not on mesh (axes: {tuple(mesh.shape)})"
        )
    rules = dict(DEFAULT_RULES)
    rules["nodes"] = (axis,)
    rules["edges"] = (axis,)
    rules["graphs"] = (axis,)
    return rules


def zero1_spec(spec: PartitionSpec, shape, mesh: Mesh, axis: str = "data") -> PartitionSpec:
    """ZeRO-1: shard an optimizer-moment tensor over ``axis`` on its first
    dim that is unsharded and divisible — on top of whatever sharding the
    parameter already has.  Moments are only touched by the (local)
    optimizer update, so this costs one reduce-scatter/all-gather pair of
    the *gradients*, which GSPMD inserts at the update boundary."""
    if axis not in mesh.shape:
        return spec
    used = set()
    for s in spec:
        if s is None:
            continue
        used.update(s if isinstance(s, tuple) else (s,))
    if axis in used:
        return spec
    n = mesh.shape[axis]
    out = list(spec)
    for i, (dim, s) in enumerate(zip(shape, spec)):
        if s is None and dim % n == 0:
            out[i] = axis
            return PartitionSpec(*out)
    return spec


def zero1_rules(base_rules: dict) -> dict:
    """ZeRO-1-style optimizer-state sharding: moments additionally shard
    their first unsharded dim over the data axis (applied to m/v only)."""
    rules = dict(base_rules)
    for name in ("embed", "layers"):
        if not rules.get(name):
            rules[name] = ("data",)
    return rules


_ACTIVE_RULES: contextvars.ContextVar = contextvars.ContextVar(
    "repro_sharding_rules", default=None
)


@contextlib.contextmanager
def active_rules(rules: dict):
    """Install shape-aware rules for logical_constraint (set by launchers
    together with ``compat.use_mesh``)."""
    token = _ACTIVE_RULES.set(rules)
    try:
        yield
    finally:
        _ACTIVE_RULES.reset(token)


def logical_constraint(x, axes: Tuple[Optional[str], ...]):
    """with_sharding_constraint via logical axes.

    No-op unless a mesh is installed with ``compat.use_mesh`` (so CPU tests
    and single-device runs are untouched).  Used at activation boundaries
    where GSPMD's propagation otherwise *replicates compute* instead of
    inserting a collective — measured 8-16x per-device FLOPs inflation on
    the MoE expert GEMM (EXPERIMENTS.md §Perf).
    """
    mesh = compat.get_active_mesh()
    if mesh is None or mesh.empty or mesh.size == 1:
        return x
    rules = _ACTIVE_RULES.get() or DEFAULT_RULES
    spec = resolve_spec(axes, x.shape, mesh, rules)
    return jax.lax.with_sharding_constraint(x, spec)


# ---------------------------------------------------------------------------
# Multi-chip sharded message passing — the large-graph extension (§4.6) at
# scale.  The paper stores node/message buffers in DRAM and hides latency
# with a prefetcher when a graph exceeds on-chip memory; at pod scale the
# analogous limit is a graph exceeding one chip's HBM, and the answer is
# *node sharding* over a mesh axis with collective message exchange.
#
# Two exchange strategies (both built on core.scatter_gather):
#   * allgather_mp — all-gather node embeddings, compute local edges'
#     messages locally, reduce into local destinations.  Comm = O(N*F) per
#     layer; simple and bandwidth-optimal for dense-ish graphs.
#   * alltoall_mp — GenGNN's merged scatter-gather lifted to chip level:
#     each shard packs messages into per-destination capacity slots,
#     exchanges with a single all-to-all, and folds received messages into
#     its local O(N/P) aggregate buffer.  Comm = O(E/P * F).
# ---------------------------------------------------------------------------


def _resolve_num_shards(num_shards: int | None, axis_name: str) -> int:
    """Static shard count for a mapped axis: ``num_shards`` when given
    (``make_sharded_mp`` passes it from the mesh), else the axis size."""
    if num_shards is not None:
        return int(num_shards)
    return int(jax.lax.axis_size(axis_name))


def allgather_mp_local(
    x_local: jax.Array,
    src: jax.Array,
    dst: jax.Array,
    edge_mask: jax.Array,
    phi: Callable[[jax.Array], jax.Array],
    axis_name: str,
    num_shards: int | None = None,
) -> jax.Array:
    """Per-shard body: all-gather x, aggregate messages for local dst rows.

    x_local: (N/P, F). src/dst: (E/P,) *global* node ids of local edges.
    num_shards is threaded statically from the mesh by make_sharded_mp;
    direct callers on new JAX may omit it (``jax.lax.axis_size``).
    Returns (N/P, F') aggregated messages for this shard's nodes.
    """
    from repro.core import scatter_gather as sg

    num_shards = _resolve_num_shards(num_shards, axis_name)
    n_local = x_local.shape[0]
    x_global = jax.lax.all_gather(x_local, axis_name, axis=0, tiled=True)
    msgs = phi(jnp.take(x_global, src, axis=0))
    msgs = jnp.where(edge_mask[:, None], msgs, 0.0)
    # Each edge is owned by exactly one shard, but its destination may be
    # remote: segment-reduce into the *global* frame and reduce-scatter rows
    # back to their owners.
    agg_global = sg.segment_reduce(msgs, dst, n_local * num_shards, "sum")
    return jax.lax.psum_scatter(agg_global, axis_name, scatter_dimension=0, tiled=True)


def alltoall_mp_local(
    x_local: jax.Array,
    src_local: jax.Array,
    dst: jax.Array,
    edge_mask: jax.Array,
    phi: Callable[[jax.Array], jax.Array],
    axis_name: str,
    capacity: int,
    num_shards: int | None = None,
) -> jax.Array:
    """Per-shard body for the all-to-all exchange.

    Assumes edges live on the shard that owns their *source* (CSR ownership,
    which is free: the producer of a message owns it — exactly the paper's
    scatter side).  src_local: (E/P,) local row ids; dst: (E/P,) global ids.

    capacity: max messages any (src-shard -> dst-shard) pair may carry per
    layer; overflow drops (GShard semantics) — sized by the caller from the
    degree distribution, and asserted in tests.
    """
    from repro.core import scatter_gather as sg

    p = _resolve_num_shards(num_shards, axis_name)
    n_local = x_local.shape[0]
    msgs = phi(jnp.take(x_local, src_local, axis=0))
    msgs = jnp.where(edge_mask[:, None], msgs, 0.0)
    dst_shard = dst // n_local
    # carry destination-local row id alongside the payload so the receiver
    # can fold messages into its O(N/P) buffer (merged scatter-gather).
    payload = jnp.concatenate(
        [msgs, (dst % n_local).astype(msgs.dtype)[:, None]], axis=-1
    )
    slots, _, _ = sg.dispatch_to_slots(
        payload, dst_shard, p, capacity, valid=edge_mask
    )  # (P, capacity, F+1)
    received = jax.lax.all_to_all(
        slots, axis_name, split_axis=0, concat_axis=0, tiled=True
    )
    rmsg = received[..., :-1].reshape(p * capacity, -1)
    rdst = received[..., -1].reshape(p * capacity).astype(jnp.int32)
    # zero-payload slots reduce harmlessly into row 0
    return sg.segment_reduce(rmsg, rdst, n_local, "sum")


def make_sharded_mp(
    mesh, axis: str, phi: Callable, strategy: str = "allgather", capacity: int = 0
):
    """Build a shard_map-wrapped message-passing aggregate step.

    Returns fn(x, src, dst, edge_mask) -> (N, F') with x sharded on axis 0
    and edges sharded on axis 0 (ownership: 'allgather' -> any shard,
    'alltoall' -> source shard, src given shard-locally).
    """
    num_shards = int(mesh.shape[axis])
    if strategy == "allgather":
        body = partial(
            allgather_mp_local, phi=phi, axis_name=axis, num_shards=num_shards
        )
    elif strategy == "alltoall":
        if capacity <= 0:
            raise ValueError("alltoall strategy requires capacity > 0")
        body = partial(
            alltoall_mp_local, phi=phi, axis_name=axis, capacity=capacity,
            num_shards=num_shards,
        )
    else:
        raise ValueError(f"unknown strategy {strategy!r}")
    in_specs = (
        PartitionSpec(axis, None),
        PartitionSpec(axis),
        PartitionSpec(axis),
        PartitionSpec(axis),
    )
    return compat.shard_map(
        body, mesh=mesh, in_specs=in_specs, out_specs=PartitionSpec(axis, None)
    )
