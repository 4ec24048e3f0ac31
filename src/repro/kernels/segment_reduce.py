"""Pallas TPU kernel for the Message-Passing PE: blocked segment reduction.

GenGNN's MP PE folds each message into its destination's partial aggregate
(merged scatter-gather, O(N) buffer).  The TPU-native expression, given
edges sorted by destination (the CSC layout produced on device by
``core.graph.coo_to_compressed``):

  * grid = (node_blocks, edge_blocks); the output block for node tile i
    stays resident in VMEM while the (sequential) edge-block dimension
    streams message tiles HBM -> VMEM.  Pallas's grid pipeline
    double-buffers the next edge tile during the current tile's compute —
    this is the paper's *prefetcher* (§4.6), expressed structurally.
  * sum/mean/sqsum aggregate via a one-hot (TE, TN) matmul on the MXU:
    partial = onehot^T @ messages — turning irregular scatter into dense
    systolic work (the hardware-adaptation decision recorded in DESIGN.md).
  * max/min aggregate via a sequential per-edge accumulate (VPU), mirroring
    the paper's per-edge MP loop; sum-family ops stay on the matmul path.
  * because ids are sorted, an edge block overlaps a node block only if
    their id ranges intersect; non-overlapping cells skip compute via
    ``pl.when`` (the block-sparse early-out).

Block shapes default to (TE=256/512, TN=128, F tiles of 128) — multiples of
the (8, 128) VREG tile and the 128x128 MXU.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

# identity element written to empty rows by the finalizer in ops.py
_FILL = {"max": -1e30, "min": 1e30}


def _kernel_matmul(ids_ref, vals_ref, out_ref, *, tn: int, op: str, num_segments: int):
    """sum/mean/sqsum path: one-hot MXU matmul, accumulated over edge blocks."""
    i = pl.program_id(0)  # node block
    j = pl.program_id(1)  # edge block (sequential, innermost)

    @pl.when(j == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    ids = ids_ref[...][:, 0]  # (TE,)
    lo = i * tn
    te = ids.shape[0]
    # static block bounds: ``ids[-1]`` lowers to dynamic_slice, which the
    # TPU lowering rejects
    first, last = ids_ref[0, 0], ids_ref[te - 1, 0]
    overlap = (first < lo + tn) & (last >= lo) & (first < num_segments)

    @pl.when(overlap)
    def _accumulate():
        vals = vals_ref[...].astype(jnp.float32)  # (TE, F)
        if op == "sqsum":
            vals = vals * vals
        local = ids - lo
        onehot = (local[:, None] == jax.lax.iota(jnp.int32, tn)[None, :]) & (
            ids[:, None] < num_segments
        )
        partial = jax.lax.dot_general(
            onehot.astype(jnp.float32),
            vals,
            (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )  # (TN, F)
        out_ref[...] += partial


def _kernel_extremum(ids_ref, vals_ref, out_ref, *, tn: int, op: str, num_segments: int):
    """max/min path: sequential per-edge accumulate (the paper's MP loop)."""
    i = pl.program_id(0)
    j = pl.program_id(1)
    fill = _FILL[op]

    @pl.when(j == 0)
    def _init():
        out_ref[...] = jnp.full_like(out_ref, fill)

    lo = i * tn
    te = ids_ref.shape[0]
    first, last = ids_ref[0, 0], ids_ref[te - 1, 0]
    overlap = (first < lo + tn) & (last >= lo) & (first < num_segments)

    @pl.when(overlap)
    def _accumulate():
        def body(e, _):
            # per-edge scalars and rows come straight from the refs: a
            # dynamic index into a loaded vector does not lower on the TPU
            seg = ids_ref[e, 0]
            row = seg - lo
            in_block = (row >= 0) & (row < tn) & (seg < num_segments)
            safe = jnp.clip(row, 0, tn - 1)
            cur = out_ref[pl.ds(safe, 1), :]
            val = vals_ref[pl.ds(e, 1), :].astype(jnp.float32)
            new = jnp.maximum(cur, val) if op == "max" else jnp.minimum(cur, val)
            out_ref[pl.ds(safe, 1), :] = jnp.where(in_block, new, cur)
            return ()

        jax.lax.fori_loop(0, te, body, ())


@functools.partial(
    jax.jit, static_argnames=("num_segments", "op", "block_e", "block_n", "interpret")
)
def segment_reduce_sorted(
    values: jax.Array,
    segment_ids: jax.Array,
    num_segments: int,
    op: str = "sum",
    block_e: int = 256,
    block_n: int = 128,
    interpret: bool = False,
) -> jax.Array:
    """Blocked segment reduction over sorted ids.  See module docstring.

    values (E, F) with E % block_e == 0 handled by internal padding;
    num_segments padded up to a block_n multiple internally.
    Returns (num_segments, F) f32; empty-segment rows are 0 for sum-family
    and ±FILL for max/min (finalized to 0 by ops.segment_reduce_pallas).
    """
    e, f = values.shape
    e_pad = -(-e // block_e) * block_e
    n_pad = -(-num_segments // block_n) * block_n
    if e_pad != e:
        values = jnp.pad(values, ((0, e_pad - e), (0, 0)))
        segment_ids = jnp.pad(
            segment_ids, (0, e_pad - e), constant_values=num_segments
        )
    ids2d = segment_ids.astype(jnp.int32).reshape(e_pad, 1)
    grid = (n_pad // block_n, e_pad // block_e)
    kernel = _kernel_matmul if op in ("sum", "mean", "sqsum") else _kernel_extremum
    kop = "sum" if op == "mean" else op
    out = pl.pallas_call(
        functools.partial(kernel, tn=block_n, op=kop, num_segments=num_segments),
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_e, 1), lambda i, j: (j, 0)),
            pl.BlockSpec((block_e, f), lambda i, j: (j, 0)),
        ],
        out_specs=pl.BlockSpec((block_n, f), lambda i, j: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((n_pad, f), jnp.float32),
        interpret=interpret,
    )(ids2d, values)
    return out[:num_segments]
