"""Graph data representation (paper §3.2).

GenGNN takes *raw COO edge streams* with zero host-side preprocessing and
converts to CSR/CSC *on device*, once per graph, reused across all layers.
This module is the TPU/JAX analogue: every conversion below is pure-jnp,
jit-compatible, and runs on the accelerator.

Static shapes: real-time streams contain graphs of varying size, so graphs
are padded to bucketed (N_pad, E_pad) capacities (recompilation happens per
bucket, not per graph). ``node_mask`` / ``edge_mask`` distinguish real
entries; padding edges point at a dedicated sink node (the last padded row)
so they never contaminate real aggregates.
"""
from __future__ import annotations

import dataclasses
from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np


@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class Graph:
    """A (possibly batched, padded) graph in COO form.

    Attributes:
      node_feat:  (N_pad, F) float node features.
      edge_index: (2, E_pad) int32; row 0 = src, row 1 = dst.
      edge_feat:  (E_pad, D) float edge features (D may be 0).
      node_mask:  (N_pad,) bool, True for real nodes.
      edge_mask:  (E_pad,) bool, True for real edges.
      graph_id:   (N_pad,) int32 graph membership for batched pooling.
      n_graph:    () int32 number of real graphs in the batch.
    """

    node_feat: jax.Array
    edge_index: jax.Array
    edge_feat: jax.Array
    node_mask: jax.Array
    edge_mask: jax.Array
    graph_id: jax.Array
    n_graph: jax.Array

    @property
    def num_nodes(self) -> int:
        return self.node_feat.shape[0]

    @property
    def num_edges(self) -> int:
        return self.edge_index.shape[1]

    @property
    def src(self) -> jax.Array:
        return self.edge_index[0]

    @property
    def dst(self) -> jax.Array:
        return self.edge_index[1]


@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class CSRGraph:
    """Adjacency in compressed form, produced on device from COO.

    ``order="csr"``: edges sorted by src (out-edges contiguous per node),
    the layout required by the paper's merged scatter-gather (§3.4).
    ``order="csc"``: edges sorted by dst (in-edges contiguous), the layout
    for the gather-only variant.  ``perm`` maps sorted-edge position ->
    original COO position so edge features can be gathered lazily.
    """

    offsets: jax.Array  # (N_pad + 1,) int32 row offsets
    perm: jax.Array  # (E_pad,) int32 permutation into COO arrays
    src_sorted: jax.Array  # (E_pad,) int32
    dst_sorted: jax.Array  # (E_pad,) int32
    degree: jax.Array  # (N_pad,) int32 out-degree (csr) / in-degree (csc)


def _segment_starts_to_offsets(ids_sorted: jax.Array, num_segments: int) -> jax.Array:
    """Row offsets from sorted segment ids via searchsorted (O(N log E))."""
    probe = jnp.arange(num_segments + 1, dtype=ids_sorted.dtype)
    return jnp.searchsorted(ids_sorted, probe, side="left").astype(jnp.int32)


@partial(jax.jit, static_argnames=("order",))
def coo_to_compressed(graph: Graph, order: str = "csr") -> CSRGraph:
    """On-device COO -> CSR/CSC conversion (paper's on-chip converter).

    Runs once per streamed graph; the result is reused by every GNN layer.
    Stable sort keeps deterministic edge order for reproducibility.
    Padding edges carry key ``N_pad`` and therefore sort to the end.
    """
    n_pad = graph.num_nodes
    key_row = 0 if order == "csr" else 1
    keys = jnp.where(graph.edge_mask, graph.edge_index[key_row], n_pad)
    perm = jnp.argsort(keys, stable=True).astype(jnp.int32)
    src_sorted = jnp.take(graph.edge_index[0], perm)
    dst_sorted = jnp.take(graph.edge_index[1], perm)
    keys_sorted = jnp.take(keys, perm)
    offsets = _segment_starts_to_offsets(keys_sorted, n_pad)
    degree = (offsets[1:] - offsets[:-1]).astype(jnp.int32)
    return CSRGraph(
        offsets=offsets,
        perm=perm,
        src_sorted=src_sorted,
        dst_sorted=dst_sorted,
        degree=degree,
    )


def in_degree(graph: Graph) -> jax.Array:
    """(N_pad,) in-degree over real edges (on device)."""
    ones = graph.edge_mask.astype(jnp.int32)
    return jax.ops.segment_sum(ones, graph.dst, num_segments=graph.num_nodes)


def out_degree(graph: Graph) -> jax.Array:
    ones = graph.edge_mask.astype(jnp.int32)
    return jax.ops.segment_sum(ones, graph.src, num_segments=graph.num_nodes)


# ---------------------------------------------------------------------------
# Host-side construction helpers (test/data-pipeline use; not in the jit path)
# ---------------------------------------------------------------------------


def from_numpy(
    senders: np.ndarray,
    receivers: np.ndarray,
    node_feat: np.ndarray,
    edge_feat: Optional[np.ndarray] = None,
    n_pad: Optional[int] = None,
    e_pad: Optional[int] = None,
) -> Graph:
    """Build a single padded ``Graph`` from raw COO numpy arrays."""
    return jax.device_put(host_from_numpy(senders, receivers, node_feat,
                                          edge_feat, n_pad=n_pad, e_pad=e_pad))


def host_from_numpy(
    senders: np.ndarray,
    receivers: np.ndarray,
    node_feat: np.ndarray,
    edge_feat: Optional[np.ndarray] = None,
    n_pad: Optional[int] = None,
    e_pad: Optional[int] = None,
) -> Graph:
    """:func:`from_numpy` with numpy leaves: nothing touches the device."""
    n = node_feat.shape[0]
    e = senders.shape[0]
    n_pad = n_pad or n
    e_pad = e_pad or e
    if n_pad < n or e_pad < e:
        raise ValueError(f"padding too small: ({n_pad},{e_pad}) < ({n},{e})")
    f = node_feat.shape[1]
    d = 0 if edge_feat is None else edge_feat.shape[1]
    nf = np.zeros((n_pad, f), dtype=node_feat.dtype)
    nf[:n] = node_feat
    ef = np.zeros((e_pad, max(d, 1)), dtype=np.float32)
    if edge_feat is not None:
        ef[:e, :d] = edge_feat
    ei = np.full((2, e_pad), n_pad - 1 if n_pad > n else 0, dtype=np.int32)
    ei[0, :e] = senders
    ei[1, :e] = receivers
    node_mask = np.arange(n_pad) < n
    edge_mask = np.arange(e_pad) < e
    graph_id = np.where(node_mask, 0, 0).astype(np.int32)
    return Graph(
        node_feat=nf,
        edge_index=ei,
        edge_feat=ef,
        node_mask=node_mask,
        edge_mask=edge_mask,
        graph_id=graph_id,
        n_graph=np.asarray(1, np.int32),
    )


def host_batch_graphs(graphs: list, n_pad: int, e_pad: int) -> Graph:
    """Pack a list of small host graphs into one padded batch (jraph-style)
    whose leaves are numpy arrays: nothing touches the device.

    Node ids are shifted per graph; padding edges point at the final padded
    node which belongs to no real graph.  This is the TPU-efficient serving
    mode; batch-size-1 streaming (the paper's real-time mode) is the special
    case of a single graph per batch.  ``core.batching`` builds the whole
    pack-time payload on these arrays and crosses to the device once.
    """
    nfs, eis, efs, gids = [], [], [], []
    offset = 0
    for gi, g in enumerate(graphs):
        s, r, nf, ef = g
        nfs.append(nf)
        eis.append(np.stack([s + offset, r + offset]))
        efs.append(ef if ef is not None else np.zeros((len(s), 1), np.float32))
        gids.append(np.full((nf.shape[0],), gi, np.int32))
        offset += nf.shape[0]
    n = offset
    e = sum(x.shape[1] for x in eis)
    if n_pad < n or e_pad < e:
        raise ValueError(f"padding too small: ({n_pad},{e_pad}) < ({n},{e})")
    f = nfs[0].shape[1]
    d = efs[0].shape[1]
    nf = np.zeros((n_pad, f), np.float32)
    nf[:n] = np.concatenate(nfs)
    ei = np.full((2, e_pad), n_pad - 1, np.int32)
    ei[:, :e] = np.concatenate(eis, axis=1)
    ef = np.zeros((e_pad, d), np.float32)
    ef[:e] = np.concatenate(efs)
    gid = np.full((n_pad,), len(graphs), np.int32)  # padding -> out-of-range id
    gid[:n] = np.concatenate(gids)
    return Graph(
        node_feat=nf,
        edge_index=ei,
        edge_feat=ef,
        node_mask=np.arange(n_pad) < n,
        edge_mask=np.arange(e_pad) < e,
        graph_id=gid,
        n_graph=np.asarray(len(graphs), np.int32),
    )


def batch_graphs(graphs: list, n_pad: int, e_pad: int) -> Graph:
    """:func:`host_batch_graphs` followed by one ``jax.device_put``: the
    padded batch with device leaves (same shapes, dtypes and bits)."""
    return jax.device_put(host_batch_graphs(graphs, n_pad, e_pad))
