"""Pallas TPU megakernel: one fused (phi, A, gamma) message-passing pass.

GenGNN's central dataflow claim (paper §3.3–3.4) is that message
transformation, aggregation, and node update run as ONE on-chip pipeline —
intermediate edge/node tensors never spill off-chip.  The unfused
reproduction lowers every layer to gather -> phi -> segment-reduce ->
gamma as separate XLA ops that each round-trip HBM; this kernel is the
paper's pipeline expressed as a single ``pallas_call``:

  * grid = (node_blocks, edge_blocks), edge dimension innermost and
    sequential — the output/aggregate block for node tile ``i`` stays
    resident in VMEM while edge tiles stream HBM -> VMEM (Pallas
    double-buffers the next tile during compute: the §4.6 prefetcher);
  * the source-operand table ``msrc`` (N, F) is held whole in VMEM and
    gathered per edge (the paper's node-feature BRAM) — phi is applied on
    the gathered tile, so messages are *produced and consumed* in VMEM;
  * sum-family aggregators (sum / sqsum / wsum) accumulate through a
    one-hot (TE, TN) MXU matmul; max/min run the paper's per-edge MP loop
    on the VPU — both into per-op VMEM scratch, exactly as
    ``kernels/segment_reduce.py`` does standalone;
  * because ids are sorted (the shared ``core.layout.GraphLayout`` plan),
    an edge block overlaps a node block only if their id ranges intersect
    — non-overlapping grid cells skip all work via ``pl.when``;
  * on the LAST edge block the node update gamma runs in-place on the
    VMEM aggregates: GCN's normalized self-loop add, GIN's 2-layer MLP,
    PNA's scaler tower, DGN's directional derivative — and for
    ``precision="int8"`` the gamma matmul quantizes its input per row,
    accumulates int8 x int8 -> int32 on the MXU, and requantizes in the
    same fused tail (W8A8 with the quantize/requant *inside* the pass).

The layer contract arrives as a declarative ``core.message_passing.MPSpec``
(duck-typed: this module never imports ``core``); the pure-jnp oracle is
``kernels/ref.fused_mp_ref``; dispatch (backend policy, VMEM budget
check) lives in ``kernels/ops.fused_mp``.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_FILL = {"max": -1e30, "min": 1e30}
# must match kernels/ref._ROW_EPS (== quant.qconfig._EPS)
_ROW_EPS = 1e-8


def _gamma_linear(t, w1_ref, b1_ref, s1_ref, precision: str):
    """gamma's first linear + relu on a resident (TN, K) tile.

    int8: per-row exact-range quantize -> int8 x int8 -> int32 MXU
    accumulate -> fused requant ``acc * (row_scale * w_scale) + b``;
    the same expression as the oracle's, so the integer accumulations
    agree exactly and the f32 tails agree op-for-op.
    """
    if precision == "int8":
        rs = jnp.maximum(
            jnp.max(jnp.abs(t), axis=-1, keepdims=True), _ROW_EPS
        ) / 127.0
        q = jnp.clip(jnp.round(t / rs), -128.0, 127.0)
        acc = jax.lax.dot_general(
            q.astype(jnp.int8),
            w1_ref[...],
            (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.int32,
        )
        y = acc.astype(jnp.float32) * (rs * s1_ref[...]) + b1_ref[...]
    else:
        y = jax.lax.dot_general(
            t,
            w1_ref[...],
            (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        ) + b1_ref[...]
    return jnp.maximum(y, 0.0)


def _fused_kernel(
    ids_ref, src_ref, msrc_ref, eop_ref, ew_ref, xres_ref, nop_ref,
    deg_ref, mask_ref, w1_ref, b1_ref, s1_ref, w2_ref, b2_ref,
    out_ref, msg_ref, *acc_refs,
    spec, tn: int, te: int, n_e: int, num_segments: int,
):
    i = pl.program_id(0)  # node block
    j = pl.program_id(1)  # edge block (sequential, innermost)

    @pl.when(j == 0)
    def _init():
        for op, acc in zip(spec.ops, acc_refs):
            if op in _FILL:
                acc[...] = jnp.full_like(acc, _FILL[op])
            else:
                acc[...] = jnp.zeros_like(acc)

    lo = i * tn
    # static block bounds: ``ids[-1]`` lowers to dynamic_slice, which the
    # TPU lowering rejects
    first, last = ids_ref[0, 0], ids_ref[te - 1, 0]
    overlap = (first < lo + tn) & (last >= lo) & (first < num_segments)

    @pl.when(overlap)
    def _accumulate():
        # gather + phi: messages are produced into VMEM scratch and never
        # leave the chip — the paper's merged scatter-gather.  Per-edge
        # scalars are read from the refs inside the loops: a dynamic index
        # into a loaded vector does not lower on the TPU.
        n_rows = msrc_ref.shape[0]

        def gather(e, _):
            s = jnp.clip(src_ref[e, 0], 0, n_rows - 1)
            msg_ref[pl.ds(e, 1), :] = msrc_ref[pl.ds(s, 1), :]
            return ()

        jax.lax.fori_loop(0, te, gather, ())
        if spec.phi == "add_relu":
            msg_ref[...] = jnp.maximum(msg_ref[...] + eop_ref[...], 0.0)
        msg = msg_ref[...]

        ids = ids_ref[...][:, 0]  # (TE,)
        local = ids - lo
        onehot = (
            (local[:, None] == jax.lax.iota(jnp.int32, tn)[None, :])
            & (ids[:, None] < num_segments)
        ).astype(jnp.float32)
        for op, acc in zip(spec.ops, acc_refs):
            if op in ("max", "min"):
                continue
            if op == "sum":
                vals = msg
            elif op == "sqsum":
                vals = msg * msg
            else:  # wsum
                vals = msg * ew_ref[...]
            acc[...] += jax.lax.dot_general(
                onehot, vals, (((0,), (0,)), ((), ())),
                preferred_element_type=jnp.float32,
            )
        for op, acc in zip(spec.ops, acc_refs):
            if op not in ("max", "min"):
                continue

            def extremum(e, _, acc=acc, op=op):
                seg = ids_ref[e, 0]
                row = seg - lo
                in_block = (row >= 0) & (row < tn) & (seg < num_segments)
                safe = jnp.clip(row, 0, tn - 1)
                cur = acc[pl.ds(safe, 1), :]
                val = msg_ref[pl.ds(e, 1), :]
                new = jnp.maximum(cur, val) if op == "max" else jnp.minimum(cur, val)
                acc[pl.ds(safe, 1), :] = jnp.where(in_block, new, cur)
                return ()

            jax.lax.fori_loop(0, te, extremum, ())

    @pl.when(j == n_e - 1)
    def _finalize():
        deg = deg_ref[...]  # (TN, 1) f32
        c = jnp.maximum(deg, 1.0)
        agg = {}
        for op, acc in zip(spec.ops, acc_refs):
            v = acc[...]
            if op in ("max", "min"):
                v = jnp.where(deg > 0, v, 0.0)
            agg[op] = v
        x_res = xres_ref[...]
        if spec.gamma == "gcn":
            out = (agg["sum"] + x_res) * nop_ref[...]
        elif spec.gamma == "gin":
            h = _gamma_linear(
                x_res + agg["sum"], w1_ref, b1_ref, s1_ref, spec.precision
            )
            out = jax.lax.dot_general(
                h, w2_ref[...], (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32,
            ) + b2_ref[...]
        elif spec.gamma == "pna":
            nop = nop_ref[...]  # (TN, 3) degree scalers
            mean = agg["sum"] / c
            std = jnp.sqrt(jnp.maximum(agg["sqsum"] / c - mean * mean, 0.0))
            agg4 = jnp.concatenate(
                [mean, std, agg["max"], agg["min"]], axis=-1
            )
            tower = jnp.concatenate(
                [agg4 * nop[:, 0:1], agg4 * nop[:, 1:2], agg4 * nop[:, 2:3]],
                axis=-1,
            )
            out = _gamma_linear(tower, w1_ref, b1_ref, s1_ref, spec.precision)
            out = out + x_res
        else:  # dgn
            mean = agg["sum"] / c
            dx = jnp.abs(agg["wsum"] - x_res * nop_ref[...])
            tower = jnp.concatenate([x_res, mean, dx], axis=-1)
            out = _gamma_linear(tower, w1_ref, b1_ref, s1_ref, spec.precision)
            out = out + x_res
        out_ref[...] = jnp.where(mask_ref[...] > 0, out, 0.0)


def _pad_rows(a, rows):
    return a if a.shape[0] == rows else jnp.pad(
        a, ((0, rows - a.shape[0]), (0, 0))
    )


@functools.partial(
    jax.jit, static_argnames=("spec", "block_e", "block_n", "interpret")
)
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
    block_e: int = 256,
    block_n: int = 128,
    interpret: bool = False,
) -> jax.Array:
    """One fused message-passing layer over the sorted edge plan.

    Operand contract is :func:`kernels.ref.fused_mp_ref`'s (the oracle);
    ``spec`` is a hashable static (``core.message_passing.MPSpec``).
    Edge count pads up to a ``block_e`` multiple (padding ids get the
    out-of-range value N, exactly like the plan's own padding rows) and
    node rows pad up to a ``block_n`` multiple (masked out; sliced off on
    return) — ragged shapes are handled here, not by callers.
    """
    n = in_degree.shape[0]
    e = ids_sorted.shape[0]
    f = msrc.shape[1]
    e_pad = -(-e // block_e) * block_e
    n_pad = -(-n // block_n) * block_n
    if e_pad != e:
        ids_sorted = jnp.pad(ids_sorted, (0, e_pad - e), constant_values=n)
        src_sorted = jnp.pad(src_sorted, (0, e_pad - e))
    ids2d = ids_sorted.astype(jnp.int32).reshape(e_pad, 1)
    src2d = src_sorted.astype(jnp.int32).reshape(e_pad, 1)
    deg2d = _pad_rows(in_degree.astype(jnp.float32).reshape(n, 1), n_pad)
    mask2d = _pad_rows(node_mask.astype(jnp.float32).reshape(n, 1), n_pad)
    # the source table may hold more rows than the nodes updated here
    # (a mesh shard updates its own rows from the whole table)
    msrc = _pad_rows(msrc.astype(jnp.float32), max(msrc.shape[0], n_pad))
    x_res = _pad_rows(x_res.astype(jnp.float32), n_pad)
    nop = (
        jnp.zeros((n_pad, 1), jnp.float32) if nop is None
        else _pad_rows(nop.astype(jnp.float32), n_pad)
    )
    eop = (
        jnp.zeros((e_pad, 1), jnp.float32) if eop is None
        else _pad_rows(eop.astype(jnp.float32), e_pad)
    )
    ew = (
        jnp.zeros((e_pad, 1), jnp.float32) if ew is None
        else _pad_rows(ew.astype(jnp.float32), e_pad)
    )
    if w1 is None:
        w1 = jnp.zeros((1, 1), jnp.float32)
        b1 = jnp.zeros((1,), jnp.float32)
    if w1_scale is None:
        w1_scale = jnp.ones((w1.shape[1],), jnp.float32)
    if w2 is None:
        w2 = jnp.zeros((1, 1), jnp.float32)
        b2 = jnp.zeros((1,), jnp.float32)
    b1_2d = b1.astype(jnp.float32).reshape(1, -1)
    s1_2d = w1_scale.astype(jnp.float32).reshape(1, -1)
    b2_2d = b2.astype(jnp.float32).reshape(1, -1)
    w2 = w2.astype(jnp.float32)
    if spec.precision != "int8":
        w1 = w1.astype(jnp.float32)

    if spec.gamma == "gcn":
        f_out = x_res.shape[1]
    elif spec.gamma == "gin":
        f_out = w2.shape[1]
    else:  # pna / dgn: lin1 output + residual
        f_out = w1.shape[1]

    grid = (n_pad // block_n, e_pad // block_e)
    kernel = functools.partial(
        _fused_kernel, spec=spec, tn=block_n, te=block_e,
        n_e=grid[1], num_segments=n,
    )
    full = lambda a: pl.BlockSpec(a.shape, lambda i, j: (0, 0))
    by_e = lambda a: pl.BlockSpec((block_e, a.shape[1]), lambda i, j: (j, 0))
    by_n = lambda a: pl.BlockSpec((block_n, a.shape[1]), lambda i, j: (i, 0))
    out = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            by_e(ids2d), by_e(src2d), full(msrc), by_e(eop), by_e(ew),
            by_n(x_res), by_n(nop), by_n(deg2d), by_n(mask2d),
            full(w1), full(b1_2d), full(s1_2d), full(w2), full(b2_2d),
        ],
        out_specs=pl.BlockSpec((block_n, f_out), lambda i, j: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((n_pad, f_out), jnp.float32),
        scratch_shapes=[pltpu.VMEM((block_e, f), jnp.float32)]
        + [pltpu.VMEM((block_n, f), jnp.float32) for _ in spec.ops],
        interpret=interpret,
    )(ids2d, src2d, msrc, eop, ew, x_res, nop, deg2d, mask2d,
      w1, b1_2d, s1_2d, w2, b2_2d)
    return out[:n]
