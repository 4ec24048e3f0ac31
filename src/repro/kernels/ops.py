"""Public jit'd wrappers over the Pallas kernels.

Dispatch policy: on TPU backends the compiled kernels run natively; on CPU
(this container) they execute in interpret mode when explicitly requested
(tests/benchmarks) and otherwise fall back to the pure-jnp reference path,
which lowers to identical-semantics XLA ops — so the rest of the framework
is backend-agnostic.  ``mode``:

  * "auto":      kernel on TPU, reference elsewhere
  * "kernel":    force Pallas (interpret=True off-TPU)
  * "reference": force pure-jnp oracle

The ``REPRO_KERNEL_MODE`` environment variable, when set, overrides the
per-call ``mode`` globally — benches/CI force the kernel or reference path
without threading a flag through every config.  It is read at trace time:
set it before building/jitting a program (an already-compiled program does
not retrace when the variable changes).

Under a multi-device mesh (``runtime.use_mesh``, as the sharded serving
executor installs) the compiler cannot partition a Pallas kernel, so each
kernel runs per shard inside a ``shard_map``: the node MLPs split their
rows, the segment reductions split their edges and combine the partial
aggregates with one collective, and the fused megakernel splits its node
rows over a replicated edge plan.  Node and edge rows that do not divide
over the mesh are an error (``shard_map`` refuses them); every served
rung divides, as rungs are multiples of 32 nodes and 96 edges.  Only a
node MLP may see rows that do not divide, the graph readout's (G, F)
rows with two graph slots per base bucket: every device then runs it
whole.
"""
from __future__ import annotations

import functools
import os

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.obs.metrics import default_registry
from repro.kernels import ref
from repro.runtime import compat
from repro.kernels.edge_softmax import softmax_over_segments
from repro.kernels.flash_attention import flash_attention as _flash_kernel
from repro.kernels.fused_mp import fused_mp as _fused_mp_kernel
from repro.kernels.node_mlp import node_mlp as _node_mlp_kernel
from repro.kernels.quant_mlp import quant_node_mlp as _quant_mlp_kernel
from repro.kernels.segment_reduce import segment_reduce_sorted as _segment_kernel

_MODES = ("auto", "kernel", "reference")


def _on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def _mesh():
    """The active mesh when it spans several devices, else None."""
    mesh = compat.get_active_mesh()
    return mesh if mesh is not None and mesh.size > 1 else None


# how ``_per_shard`` places an operand or output: leading axis split over
# every mesh axis, or the whole array on every device
ROWS, WHOLE = "rows", "whole"


def _per_shard(fn, in_specs, out_specs, *args):
    """``fn(*args)``.  Under a multi-device mesh the compiler cannot
    partition a Pallas kernel, so ``fn`` runs once per shard in a
    ``shard_map`` with ``ROWS``/``WHOLE`` placements (a pytree prefix of
    ``args`` and of the output); inside it ``_mesh()`` names the axes
    for collectives."""
    mesh = _mesh()
    if mesh is None:
        return fn(*args)
    spec = lambda s: P(mesh.axis_names) if s == ROWS else P()
    return compat.shard_map(fn, mesh, jax.tree.map(spec, in_specs),
                            jax.tree.map(spec, out_specs))(*args)


def _mlp_rows(n: int) -> str:
    """``ROWS`` for an MLP's ``n`` rows, or ``WHOLE`` when they do not
    divide over the active mesh (the graph readout's rows)."""
    mesh = _mesh()
    return ROWS if mesh is None or n % mesh.size == 0 else WHOLE


_COMBINE = {"sum": jax.lax.psum, "sqsum": jax.lax.psum,
            "max": jax.lax.pmax, "min": jax.lax.pmin}


def _segment(values, segment_ids, num_segments: int, op: str,
             interpret: bool):
    """One segment-kernel call; under a mesh each device reduces its
    share of the sorted edges into a full partial aggregate and one
    collective combines them."""
    def local(v, ids):
        part = _segment_kernel(v, ids, num_segments, op, interpret=interpret)
        mesh = _mesh()
        return part if mesh is None else _COMBINE[op](part, mesh.axis_names)

    return _per_shard(local, (ROWS, ROWS), WHOLE, values, segment_ids)


def _record_dispatch(op: str, use_kernel: bool, interpret: bool) -> None:
    """Count one dispatch decision in the process-wide registry
    (``kernels_dispatch_total{op, path}``).

    These wrappers execute at *trace time* — once per compiled program,
    never per served request — so the counter is a census of which path
    each program actually lowered through (Pallas kernel, interpret-mode
    kernel, or jnp reference): on the chip every entry should read
    ``kernel``.  A pure-Python dict update at trace time: no new compile
    keys, nothing staged into the program."""
    path = ("interpret" if use_kernel and interpret
            else "kernel" if use_kernel
            else "reference")
    default_registry().counter("kernels_dispatch_total").inc(op=op, path=path)


def _resolve(mode: str):
    """-> (use_kernel, interpret)"""
    env = os.environ.get("REPRO_KERNEL_MODE", "")
    if env:
        if env not in _MODES:
            raise ValueError(
                f"REPRO_KERNEL_MODE={env!r} invalid; expected one of {_MODES}"
            )
        mode = env
    if mode == "reference":
        return False, False
    if mode == "kernel":
        return True, not _on_tpu()
    if mode != "auto":
        raise ValueError(f"unknown kernel mode {mode!r}; expected one of {_MODES}")
    return (True, False) if _on_tpu() else (False, False)


def segment_reduce(
    values: jax.Array,
    segment_ids: jax.Array,
    num_segments: int,
    op: str = "sum",
    mode: str = "auto",
    perm: jax.Array | None = None,
) -> jax.Array:
    """Sorted-segment reduction (MP PE). values (E,F), ids sorted.

    Operands are **pre-sorted**: ``segment_ids`` non-decreasing, coming
    from a shared ``core.layout.GraphLayout`` plan — neither the Pallas
    kernel nor the jnp reference ever sorts.  Pass ``perm`` (the plan's
    CSC permutation) when ``values`` are still in COO order; the gather
    happens here so call sites stay sort-free and plan-agnostic.
    """
    if perm is not None:
        values = jnp.take(values, perm, axis=0)
    use_kernel, interpret = _resolve(mode)
    _record_dispatch("segment_reduce", use_kernel, interpret)
    if not use_kernel:
        return ref.segment_reduce_sorted_ref(values, segment_ids, num_segments, op)
    if op == "mean":
        total = _segment(values, segment_ids, num_segments, "sum", interpret)
        ones = jnp.ones((values.shape[0], 1), values.dtype)
        count = _segment(ones, segment_ids, num_segments, "sum", interpret)
        return (total / jnp.maximum(count, 1.0)).astype(values.dtype)
    out = _segment(values, segment_ids, num_segments, op, interpret)
    if op in ("max", "min"):
        ones = jnp.ones((values.shape[0], 1), values.dtype)
        count = _segment(ones, segment_ids, num_segments, "sum", interpret)
        out = jnp.where(count > 0, out, 0.0)
    return out.astype(values.dtype)


# the fused megakernel holds the whole (N, F) source table plus gamma's
# weights resident in VMEM; a compiled dispatch above this footprint is an
# error, never a quiet switch off the chip (interpret mode — the CPU test
# path — is exempt: no real VMEM there)
_FUSED_VMEM_BUDGET = 12 * 1024 * 1024


def fused_mp(
    spec,
    ids_sorted: jax.Array,
    src_sorted: jax.Array,
    in_degree: jax.Array,
    node_mask: jax.Array,
    msrc: jax.Array,
    x_res: jax.Array,
    nop: jax.Array | None = None,
    eop: jax.Array | None = None,
    ew: jax.Array | None = None,
    w1: jax.Array | None = None,
    b1: jax.Array | None = None,
    w1_scale: jax.Array | None = None,
    w2: jax.Array | None = None,
    b2: jax.Array | None = None,
    mode: str = "auto",
    block_e: int = 256,
    block_n: int = 128,
) -> jax.Array:
    """One fused (phi, A, gamma) message-passing layer — the megakernel.

    ``spec`` is a ``core.message_passing.MPSpec``; array operands follow
    :func:`ref.fused_mp_ref` (the oracle, also the CPU production path:
    its jnp lowering keeps the gather -> phi -> reduce -> gamma chain in
    one jit scope, which is how the fused speedups in BENCH_layout.json
    are realized off-TPU).  Per-edge operands arrive in plan order; the
    plan's out-of-range padding ids do the masking.
    """
    use_kernel, interpret = _resolve(mode)
    if use_kernel and not interpret:
        resident = msrc.size * 4
        for wgt in (w1, w2):
            if wgt is not None:
                resident += wgt.size * 4
        if resident > _FUSED_VMEM_BUDGET:
            raise ValueError(
                f"fused_mp: {resident} resident bytes (source table "
                f"{tuple(msrc.shape)} plus gamma weights) exceed the "
                f"{_FUSED_VMEM_BUDGET}-byte VMEM budget; serve this bucket "
                f"unfused (docs/KERNELS.md)"
            )
    _record_dispatch("fused_mp", use_kernel, interpret)
    if not use_kernel:
        return ref.fused_mp_ref(
            spec, ids_sorted, src_sorted, in_degree, node_mask, msrc, x_res,
            nop=nop, eop=eop, ew=ew, w1=w1, b1=b1, w1_scale=w1_scale,
            w2=w2, b2=b2,
        )
    # under a mesh the node rows split; every device holds the whole edge
    # plan and source table and keeps the edges whose destination falls
    # in its rows (ids shifted by its row offset: the others land
    # outside [0, n_local) and every node block skips them)
    per_node = dict(in_degree=in_degree, node_mask=node_mask, x_res=x_res,
                    nop=nop)
    whole = dict(ids_sorted=ids_sorted, src_sorted=src_sorted, msrc=msrc,
                 eop=eop, ew=ew, w1=w1, b1=b1, w1_scale=w1_scale, w2=w2, b2=b2)
    per_node = {k: v for k, v in per_node.items() if v is not None}
    whole = {k: v for k, v in whole.items() if v is not None}

    def local(per_node, whole):
        ids = whole.pop("ids_sorted")
        mesh = _mesh()
        if mesh is not None:
            n_local = per_node["in_degree"].shape[0]
            ids = ids - jax.lax.axis_index(mesh.axis_names) * n_local
        return _fused_mp_kernel(
            spec, ids, whole.pop("src_sorted"), per_node.pop("in_degree"),
            per_node.pop("node_mask"), whole.pop("msrc"),
            per_node.pop("x_res"), **per_node, **whole,
            block_e=block_e, block_n=block_n, interpret=interpret,
        )

    return _per_shard(local, (ROWS, WHOLE), ROWS, per_node, whole)


def node_mlp(
    x: jax.Array,
    w: jax.Array,
    b: jax.Array,
    activation: str = "relu",
    mode: str = "auto",
) -> jax.Array:
    """Fused linear+bias+activation (NE PE)."""
    use_kernel, interpret = _resolve(mode)
    _record_dispatch("node_mlp", use_kernel, interpret)
    if not use_kernel:
        return ref.node_mlp_ref(x, w, b, activation)
    kernel = functools.partial(_node_mlp_kernel, activation=activation,
                               interpret=interpret)
    rows = _mlp_rows(x.shape[0])
    return _per_shard(kernel, (rows, WHOLE, WHOLE), rows, x, w, b)


def quant_node_mlp(
    x_q: jax.Array,
    w_q: jax.Array,
    scale: jax.Array,
    b: jax.Array,
    activation: str = "relu",
    row_scale: jax.Array | None = None,
    mode: str = "auto",
) -> jax.Array:
    """Quantized fused linear (int8 NE PE): int32 accumulate + requantize.

    x_q (M, K) int8, w_q (K, N) int8, scale (N,)/() f32, row_scale
    (M, 1) f32 or None (dynamic per-node scales), b (N,) f32.
    """
    use_kernel, interpret = _resolve(mode)
    _record_dispatch("quant_node_mlp", use_kernel, interpret)
    if not use_kernel:
        return ref.quant_node_mlp_ref(x_q, w_q, scale, b, activation,
                                      row_scale=row_scale)
    if row_scale is None:
        row_scale = jnp.ones((x_q.shape[0], 1), jnp.float32)
    kernel = functools.partial(_quant_mlp_kernel, activation=activation,
                               interpret=interpret)
    rows = _mlp_rows(x_q.shape[0])
    return _per_shard(
        lambda x, w, s, b_, r: kernel(x, w, s, b_, row_scale=r),
        (rows, WHOLE, WHOLE, WHOLE, rows), rows,
        x_q, w_q, scale, b, row_scale)


def edge_softmax(
    logits: jax.Array,
    segment_ids: jax.Array,
    num_segments: int,
    mode: str = "auto",
    perm: jax.Array | None = None,
) -> jax.Array:
    """Per-destination softmax over sorted edges (GAT).

    ``segment_ids`` are pre-sorted (a shared layout plan); ``perm``
    gathers COO-order ``logits`` into plan order first — the sort itself
    never happens here, on either the Pallas or the reference path.
    """
    if perm is not None:
        logits = jnp.take(logits, perm, axis=0)
    use_kernel, interpret = _resolve(mode)
    _record_dispatch("edge_softmax", use_kernel, interpret)
    if not use_kernel:
        return ref.edge_softmax_ref(logits, segment_ids, num_segments)
    reduce = functools.partial(_segment, interpret=interpret)
    return softmax_over_segments(reduce, logits, segment_ids, num_segments)


def flash_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    causal: bool = True,
    window: int | None = None,
    mode: str = "auto",
    block_q: int = 128,
    block_k: int = 128,
    segment_ids: jax.Array | None = None,
    name: str | None = None,
) -> jax.Array:
    """Blockwise GQA attention; with ``segment_ids`` ((B, S), -1 for a
    row that attends nowhere) a query sees only keys of its own segment.
    ``name`` labels the kernel's op in the program and the device trace."""
    use_kernel, interpret = _resolve(mode)
    _record_dispatch("flash_attention", use_kernel, interpret)
    if not use_kernel:
        return ref.flash_attention_ref(q, k, v, causal=causal, window=window,
                                       segment_ids=segment_ids)
    return _flash_kernel(
        q, k, v, causal=causal, window=window,
        block_q=block_q, block_k=block_k, interpret=interpret,
        segment_ids=segment_ids, name=name,
    )
