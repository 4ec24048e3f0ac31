"""Multi-graph packing: many small graphs -> one padded ``Graph``.

GenGNN streams heterogeneous graphs through one generic engine; FlowGNN
(the successor) shows the throughput win comes from keeping that stream
*dense* — variable-size graphs are concatenated into a shared padded
buffer so one compiled program amortizes dispatch over many requests.

A ``BucketBudget`` is the static capacity of one packed program:
``(N_pad, E_pad, G_pad)`` — total node rows, total edge rows, and graph
slots.  ``pack_graphs`` concatenates raw COO graphs against a budget
(node ids shifted per graph, ``graph_id`` recording membership) and
returns the padded ``Graph`` plus a ``PackMeta`` that makes the unpack
side *exact*: per-graph outputs are recovered by slot (graph-level) or by
node-offset slicing (node-level), never by masking heuristics.

Everything here is host-side (numpy) construction — the packed ``Graph``
enters the jit boundary exactly like a single padded graph does, so the
engine's compiled buckets are reused across packed batches.
``pack_prepared`` builds the whole pack-time payload on the host, writes
it into one buffer (``core.wire``) and crosses to the device with one
``jax.device_put`` of that one array.
"""
from __future__ import annotations

import dataclasses
from typing import List, Optional, Sequence, Tuple

import jax
import numpy as np

from repro.core import graph as G
from repro.core import layout as LY
from repro.obs.trace import annotate

# a raw host graph: (senders, receivers, node_feat[, edge_feat])
RawGraph = tuple


@dataclasses.dataclass(frozen=True, order=True)
class BucketBudget:
    """Static capacity of one packed program (compiled-shape key)."""

    n_pad: int  # total padded node rows
    e_pad: int  # total padded edge rows
    g_pad: int  # graph slots (sizes the pooled / per-graph buffers)

    def admits(self, n_used: int, e_used: int, g_used: int,
               n: int, e: int) -> bool:
        """Would a graph of (n nodes, e edges) still fit?"""
        return (
            g_used + 1 <= self.g_pad
            and n_used + n <= self.n_pad
            and e_used + e <= self.e_pad
        )


@dataclasses.dataclass(frozen=True)
class PackMeta:
    """Exact bookkeeping for unpacking a packed batch.

    ``node_counts[i]`` / ``edge_counts[i]`` are graph i's real sizes;
    ``node_offsets`` are the cumulative starts, so graph i's nodes occupy
    rows [node_offsets[i], node_offsets[i+1]) of the packed arrays.
    """

    budget: BucketBudget
    node_counts: Tuple[int, ...]
    edge_counts: Tuple[int, ...]

    @property
    def num_graphs(self) -> int:
        return len(self.node_counts)

    @property
    def node_offsets(self) -> Tuple[int, ...]:
        return tuple(np.concatenate([[0], np.cumsum(self.node_counts)]))


def graph_sizes(raw: RawGraph) -> Tuple[int, int]:
    """(num_nodes, num_edges) of a raw COO tuple."""
    s, _, nf = raw[0], raw[1], raw[2]
    return nf.shape[0], s.shape[0]


def pack_graphs(graphs: Sequence[RawGraph], budget: BucketBudget) -> Tuple[G.Graph, PackMeta]:
    """Concatenate raw graphs into one padded ``Graph`` against ``budget``.

    Node ids are shifted per graph; padding edges point at the final padded
    node, which belongs to no real graph, so they never contaminate real
    aggregates (same invariant as single-graph padding).  The ``Graph``'s
    leaves are numpy arrays (:func:`repro.core.graph.host_batch_graphs`).
    """
    if not graphs:
        raise ValueError("pack_graphs needs at least one graph")
    sizes = [graph_sizes(g) for g in graphs]
    n_tot = sum(n for n, _ in sizes)
    e_tot = sum(e for _, e in sizes)
    if len(graphs) > budget.g_pad or n_tot > budget.n_pad or e_tot > budget.e_pad:
        raise ValueError(
            f"pack of {len(graphs)} graphs ({n_tot} nodes, {e_tot} edges) "
            f"exceeds budget {budget}"
        )
    gs = [(g[0], g[1], g[2], g[3] if len(g) > 3 else None) for g in graphs]
    packed = G.host_batch_graphs(gs, n_pad=budget.n_pad, e_pad=budget.e_pad)
    meta = PackMeta(
        budget=budget,
        node_counts=tuple(n for n, _ in sizes),
        edge_counts=tuple(e for _, e in sizes),
    )
    return packed, meta


def pack_layout(packed: G.Graph) -> LY.GraphLayout:
    """Emit the packed batch's ``GraphLayout`` plan at pack time.

    Host-side ``np.argsort(kind="stable")`` over the same masked keys the
    device path uses, so the plan is bit-identical to one built on device
    — but the compiled forward program that receives it contains **zero**
    sort ops (the paper's convert-once-at-ingest, §3.4).  Built from the
    host arrays of :func:`pack_graphs` with numpy leaves
    (:func:`repro.core.layout.host_layout`); :func:`pack_prepared` puts
    it on the device together with the batch.
    """
    return LY.host_layout(packed)


def pack_prepared(
    graphs: Sequence[RawGraph],
    budget: BucketBudget,
    eigvecs: Optional[Sequence[np.ndarray]] = None,
    with_layout: bool = True,
):
    """Pack raw graphs and emit the whole pack-time payload as one
    ``serve.executor.PreparedBatch``: padded graph, packed eigenvectors,
    host-built ``GraphLayout`` plan, bucket key and warm signature.

    This is the packed mode's *prepare* stage, run at pack time so the
    compiled flush program receives everything ready-made (zero on-device
    sorts; the paper's convert-once-at-ingest, §3.4).  Returns
    ``(prepared, meta)`` — ``meta`` is the exact unpack bookkeeping.

    Every leaf is built in numpy, the warm signature included, and written
    into one int32 buffer; that buffer is the one array the batch's
    ``jax.device_put`` moves to the default device, with no device round
    trip in between (a put pays per array, not per byte).  The served
    program splits it at the manifest's static offsets.  The layout plan
    is built under the ``repro.layout`` profiler span.
    """
    from repro.serve import executor as X  # deferred: serve imports core

    packed, meta = pack_graphs(graphs, budget)
    eig = None if eigvecs is None else pack_eigvecs(eigvecs, meta)
    layout = None
    if with_layout:
        with annotate("layout", rung=budget.g_pad // 2):
            layout = pack_layout(packed)
    prep = X.prepared(
        packed, eig, layout,
        ("packed", budget.n_pad, budget.e_pad, budget.g_pad), budget.g_pad,
    )
    return jax.device_put(prep), meta


def pack_eigvecs(eigvecs: Sequence[np.ndarray], meta: PackMeta) -> np.ndarray:
    """Concatenate per-graph node vectors (e.g. DGN's Laplacian eigenvector)
    into the packed (N_pad,) layout; padding rows are zero."""
    out = np.zeros((meta.budget.n_pad,), np.float32)
    off = 0
    for vec, n in zip(eigvecs, meta.node_counts):
        out[off : off + n] = np.asarray(vec, np.float32)[:n]
        off += n
    return out


def unpack_outputs(
    outputs: np.ndarray,
    meta: PackMeta,
    level: str = "graph",
) -> List[np.ndarray]:
    """Exact inverse of packing for model outputs.

    ``level="graph"``: outputs is (G_pad, F) — slot i belongs to graph i.
    ``level="node"``: outputs is (N_pad, F) — slice by node offsets.
    Returns one array per real graph; padding slots/rows are dropped.
    """
    outputs = np.asarray(outputs)
    if level == "graph":
        return [outputs[i : i + 1] for i in range(meta.num_graphs)]
    if level == "node":
        offs = meta.node_offsets
        return [outputs[offs[i] : offs[i + 1]] for i in range(meta.num_graphs)]
    raise ValueError(f"unknown level {level!r}; expected 'graph' or 'node'")
