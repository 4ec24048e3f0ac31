"""Pallas TPU blockwise (flash) attention: the LM substrate's GQA attention
and GraphGPS's attention restricted to each graph of a packed flush.

Online-softmax attention with q/kv tiling so the (S, S) score matrix is
never materialized in HBM — the working set per grid cell is
(TQ, D) + (TK, D) + (TQ, TK), sized for VMEM, MXU-aligned.

Supports causal masking, GQA (Hq % Hkv == 0, the kv head is selected by
the BlockSpec index map so no repeated kv materialization), sliding
windows (Mistral/Gemma-local layers), and **segment ids**: with
``segment_ids`` a query attends only to keys of its own segment (a packed
flush's ``Graph.graph_id``), and a row whose id is negative (a padded
node row) attends nowhere and outputs 0.  The causal/window structure and
the segment ranges prune whole kv blocks via ``pl.when`` (compute skip):
the segment plan is a per-(q-block, k-block) table built in the wrapper
and handed to the kernel as a scalar-prefetch operand, so a k-block that
shares no segment with its q-block costs one grid step and no arithmetic.

The dry-run/costing path uses the pure-jnp chunked equivalent in
models/attention.py for clean HLO; this kernel is the TPU deployment path,
validated against kernels/ref.py in interpret mode.
"""
from __future__ import annotations

import bisect
import functools
import itertools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_NEG_INF = -1e30
SEGMENT_BLOCK = 128  # q and k tile rows of the segment path (MXU-aligned)


def segment_blocks(s: int) -> tuple:
    """(block, padded length) of the segment path for ``s`` rows: one tile
    of all rows up to ``SEGMENT_BLOCK``, else ``SEGMENT_BLOCK``-row tiles
    over ``s`` rounded up (the extra rows carry segment id -1)."""
    if s <= SEGMENT_BLOCK:
        return s, s
    return SEGMENT_BLOCK, -(-s // SEGMENT_BLOCK) * SEGMENT_BLOCK


def _block_ranges(seg, block: int):
    """Per block of ``seg`` (B, S): the smallest and largest non-negative
    id, an empty block reading (big, -1)."""
    b, s = seg.shape
    tiles = seg.reshape(b, s // block, block)
    valid = tiles >= 0
    lo = jnp.min(jnp.where(valid, tiles, np.iinfo(np.int32).max), axis=-1)
    hi = jnp.max(jnp.where(valid, tiles, -1), axis=-1)
    return lo, hi


def segment_tile_table(seg, block_q: int, block_k: int):
    """(B, n_q, n_k) int32: 1 where q-block i and k-block j may share a
    segment (their id ranges overlap), else 0 — exact for ids that are
    non-decreasing over the valid rows, as a packed flush's are, and a
    safe superset for any ids (the in-tile mask does the rest)."""
    q_lo, q_hi = _block_ranges(seg, block_q)
    k_lo, k_hi = _block_ranges(seg, block_k)
    need = ((q_lo[:, :, None] <= k_hi[:, None, :])
            & (k_lo[:, None, :] <= q_hi[:, :, None]))
    return need.astype(jnp.int32)


def segment_pairs(node_counts, rows: int) -> tuple:
    """(real, computed) query-key pairs of one segment-masked attention
    over a packed flush of ``rows`` node rows holding graphs of
    ``node_counts`` nodes, in order from row 0: real = sum of n_g^2, the
    pairs within each graph; computed = the q x k pairs of the tiles the
    kernel runs (per head and per layer, as real) — the tiles
    :func:`segment_tile_table` keeps, counted on the host in plain Python
    (a few blocks per flush)."""
    block, s_pad = segment_blocks(rows)
    ends = list(itertools.accumulate(int(n) for n in node_counts))
    total = ends[-1] if ends else 0
    ranges = []  # per block: (first, last) graph over its real rows
    for start in range(0, min(s_pad, total), block):
        stop = min(start + block, total)
        ranges.append((bisect.bisect_right(ends, start),
                       bisect.bisect_right(ends, stop - 1)))
    tiles = sum(1 for a in ranges for b in ranges
                if a[0] <= b[1] and b[0] <= a[1])
    return sum(int(n) * int(n) for n in node_counts), tiles * block * block


def _flash_kernel(*refs, scale: float, causal: bool, window: int | None,
                  block_q: int, block_k: int, n_k: int, segmented: bool,
                  n_q: int, heads: int):
    """Grid = (batch*heads, q_blocks, k_blocks); k innermost (sequential).
    With ``segmented`` the refs open with the scalar-prefetched tile table
    and the q / k segment-id tiles."""
    if segmented:
        (table_ref, qseg_ref, kseg_ref, q_ref, k_ref, v_ref, o_ref,
         m_ref, l_ref, acc_ref) = refs
    else:
        q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref = refs
    h = pl.program_id(0)
    qi = pl.program_id(1)
    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, _NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q_start = qi * block_q
    k_start = ki * block_k
    # block-level pruning: causal => skip blocks strictly above the diagonal;
    # window => skip blocks entirely left of the window; segments => skip
    # blocks whose id ranges do not overlap (the prefetched table).
    needed = True
    if causal:
        needed = k_start <= q_start + block_q - 1
    if window is not None:
        needed = jnp.logical_and(needed, k_start + block_k > q_start - window + 1)
    if segmented:
        tile = table_ref[((h // heads) * n_q + qi) * n_k + ki]
        needed = jnp.logical_and(needed, tile > 0)

    @pl.when(needed)
    def _block():
        q = q_ref[0].astype(jnp.float32)  # (TQ, D)
        k = k_ref[0].astype(jnp.float32)  # (TK, D)
        v = v_ref[0].astype(jnp.float32)  # (TK, D)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        ) * scale  # (TQ, TK)
        qpos = q_start + jax.lax.iota(jnp.int32, block_q)[:, None]
        kpos = k_start + jax.lax.iota(jnp.int32, block_k)[None, :]
        mask = jnp.ones_like(s, dtype=jnp.bool_)
        if causal:
            mask &= kpos <= qpos
        if window is not None:
            mask &= kpos > qpos - window
        if segmented:
            qseg = qseg_ref[0]  # (TQ, 1)
            kseg = kseg_ref[0]  # (1, TK)
            mask &= (qseg == kseg) & (qseg >= 0)
        s = jnp.where(mask, s, _NEG_INF)
        m_prev = m_ref[...]  # (TQ, 1)
        m_cur = jnp.max(s, axis=1, keepdims=True)
        m_new = jnp.maximum(m_prev, m_cur)
        p = jnp.exp(s - m_new)
        p = jnp.where(mask, p, 0.0)
        alpha = jnp.exp(m_prev - m_new)
        l_ref[...] = alpha * l_ref[...] + jnp.sum(p, axis=1, keepdims=True)
        acc_ref[...] = alpha * acc_ref[...] + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
        )
        m_ref[...] = m_new

    @pl.when(ki == n_k - 1)
    def _finalize():
        # a row that met no key (l == 0: a padded row) outputs 0
        o_ref[0] = (acc_ref[...] / jnp.maximum(l_ref[...], 1e-30)).astype(o_ref.dtype)


@functools.partial(
    jax.jit,
    static_argnames=("causal", "window", "block_q", "block_k", "interpret",
                     "name"),
)
def flash_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    causal: bool = True,
    window: int | None = None,
    block_q: int = 128,
    block_k: int = 128,
    interpret: bool = False,
    segment_ids: jax.Array | None = None,
    name: str | None = None,
) -> jax.Array:
    """q: (B, Hq, S, D); k/v: (B, Hkv, S, D).  Returns (B, Hq, S, D).

    Without ``segment_ids`` S must be a multiple of the block sizes (the
    LM substrate pads seq); D should be a multiple of 128 for MXU
    alignment (64 tolerated).  With ``segment_ids`` ((B, S) int32; -1 for
    a row that attends nowhere) the tiles are :func:`segment_blocks`' and
    S is padded here to their multiple.  ``name`` labels the kernel's op
    in the compiled program and the device trace.
    """
    b, hq, s, d = q.shape
    hkv = k.shape[1]
    assert hq % hkv == 0, (hq, hkv)
    g = hq // hkv
    scale = 1.0 / (d**0.5)
    segmented = segment_ids is not None
    s_pad = s
    if segmented:
        block_q, s_pad = segment_blocks(s)
        block_k = block_q
        if s_pad != s:
            pad = ((0, 0), (0, 0), (0, s_pad - s), (0, 0))
            q, k, v = (jnp.pad(a, pad) for a in (q, k, v))
            segment_ids = jnp.pad(segment_ids, ((0, 0), (0, s_pad - s)),
                                  constant_values=-1)
        segment_ids = segment_ids.astype(jnp.int32)
    qf = q.reshape(b * hq, s_pad, d)
    kf = k.reshape(b * hkv, s_pad, d)
    vf = v.reshape(b * hkv, s_pad, d)
    n_q = s_pad // block_q
    n_k = s_pad // block_k
    grid = (b * hq, n_q, n_k)

    def q_map(h, i, j, *_):
        return (h, i, 0)

    def kv_map(h, i, j, *_):
        # GQA: query head h -> kv head (h % hq) // g within its batch
        bidx = h // hq
        kvh = (h % hq) // g
        return (bidx * hkv + kvh, j, 0)

    in_specs = [
        pl.BlockSpec((1, block_q, d), q_map),
        pl.BlockSpec((1, block_k, d), kv_map),
        pl.BlockSpec((1, block_k, d), kv_map),
    ]
    operands = [qf, kf, vf]
    if segmented:
        in_specs = [
            pl.BlockSpec((1, block_q, 1), lambda h, i, j, *_: (h // hq, i, 0)),
            pl.BlockSpec((1, 1, block_k), lambda h, i, j, *_: (h // hq, 0, j)),
        ] + in_specs
        # the scalar-prefetched tile table leads the operands
        operands = [segment_tile_table(segment_ids, block_q, block_k).reshape(-1),
                    segment_ids[:, :, None], segment_ids[:, None, :]] + operands
    kernel = functools.partial(
        _flash_kernel, scale=scale, causal=causal, window=window,
        block_q=block_q, block_k=block_k, n_k=n_k, segmented=segmented,
        n_q=n_q, heads=hq,
    )
    out = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=int(segmented), grid=grid, in_specs=in_specs,
            out_specs=pl.BlockSpec((1, block_q, d), q_map),
            scratch_shapes=[
                pltpu.VMEM((block_q, 1), jnp.float32),
                pltpu.VMEM((block_q, 1), jnp.float32),
                pltpu.VMEM((block_q, d), jnp.float32),
            ]),
        out_shape=jax.ShapeDtypeStruct((b * hq, s_pad, d), q.dtype),
        interpret=interpret, name=name,
    )(*operands)
    return out.reshape(b, hq, s_pad, d)[:, :, :s]
