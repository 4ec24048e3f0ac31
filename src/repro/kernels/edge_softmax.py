"""Edge softmax for GAT (§4.2) — composition of blocked segment kernels.

GAT normalizes attention logits over each destination's in-edges.  With
sorted (CSC) edges this is two segment reductions (max, then sum of
shifted exponentials) plus an edge-parallel normalize:

    w_e = exp(l_e - max_{e' in seg(e)} l_{e'}) / sum_{e'} exp(...)

The reductions run on the blocked Pallas segment kernel; the gather of the
per-segment statistics back to edges and the elementwise tail are plain
VPU work that XLA fuses.  A dedicated fused single-pass kernel is possible
(carrying running max/sum like flash attention) but measurement on the
blocked layout showed both reductions are DMA-bound on the same edge
stream, so the two-pass form costs one extra stream of the logits — the
paper makes the same call by reusing its generic MP machinery for GAT.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels.segment_reduce import segment_reduce_sorted


def softmax_over_segments(reduce, logits, segment_ids, num_segments: int):
    """The edge softmax with ``reduce(values, ids, num_segments, op)`` as
    its segment reduction (``kernels.ops`` passes its mesh-aware one)."""
    valid = segment_ids < num_segments
    seg_max = reduce(logits, segment_ids, num_segments, "max")
    ids_safe = jnp.minimum(segment_ids, num_segments - 1)
    shifted = logits.astype(jnp.float32) - seg_max[ids_safe]
    z = jnp.where(valid[:, None], jnp.exp(shifted), 0.0)
    seg_sum = reduce(z, segment_ids, num_segments, "sum")
    w = z / jnp.maximum(seg_sum[ids_safe], 1e-30)
    return jnp.where(valid[:, None], w, 0.0).astype(logits.dtype)


@functools.partial(jax.jit, static_argnames=("num_segments", "interpret"))
def edge_softmax(
    logits: jax.Array,
    segment_ids: jax.Array,
    num_segments: int,
    interpret: bool = False,
) -> jax.Array:
    """logits: (E, H) sorted by segment; returns per-segment softmax weights."""
    reduce = functools.partial(segment_reduce_sorted, interpret=interpret)
    return softmax_over_segments(reduce, logits, segment_ids, num_segments)
