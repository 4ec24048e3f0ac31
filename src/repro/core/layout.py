"""The shared graph-layout plan: one sort per graph, reused everywhere.

The paper's central dataflow claim (§3.4) is that the COO edge stream is
converted to a destination-ordered layout **once per graph** and that every
layer of every model then consumes the converted form.  Before this module
the conversion was re-derived inside each aggregation call: GCN/GIN sorted
once per layer, PNA/DGN four-plus times per layer (one per aggregator /
weighted reduce), and GAT ran its own per-layer sort for the edge softmax —
5 to 20+ O(E log E) device sorts per forward pass over a graph whose edge
order never changes.

``GraphLayout`` is that conversion reified as a pytree:

  * ``perm``        (E_pad,) int32 — stable argsort of the masked
                    destination ids (padding edges carry key ``N_pad`` and
                    sort to the end).  This is the CSC permutation.
  * ``ids_sorted``  (E_pad,) int32 — destination ids in sorted order;
                    padding rows hold ``N_pad`` (out of range), which JAX
                    segment ops *drop* — validity is encoded in the ids, so
                    downstream consumers never re-mask message values.
  * ``offsets``     (N_pad+1,) int32 — per-destination row offsets
                    (searchsorted over ``ids_sorted``); the CSC offset
                    array a future blocked Pallas aggregation kernel needs.
  * ``src_sorted``  (E_pad,) int32 — source ids in sorted-edge order
                    (GAT gathers its messages with this directly).
  * ``in_degree``   (N_pad,) int32 — real-edge in-degree (exact integer
                    counts; feeds GCN norms and PNA scalers).

plus lazily-attached **model-static derivatives** — values that depend only
on the graph (and, for DGN, its eigenvector input), not on the layer:

  * ``gcn_inv_sqrt``  GCN's 1/sqrt(d+1) symmetric norm,
  * ``pna_scalers``   PNA's (N, 3) [identity, amplification, attenuation],
  * ``dgn_w_e`` / ``dgn_denom`` / ``dgn_wsum``  DGN's directional weights
    computed once from the eigenvector instead of once per layer.

``build_layout`` is the ONLY place in the repository that runs the
on-device edge sort for the message-passing path (enforced by
``tools/check_no_raw_sort.py``); ``host_layout`` is its bit-identical
numpy twin used by ``core.batching`` so a packed batch's plan is emitted
at pack time, from host arrays, and the compiled forward program
contains **zero** sorts.
"""
from __future__ import annotations

import dataclasses
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import graph as G
from repro.core import scatter_gather as sg


@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class GraphLayout:
    """Destination-ordered edge plan for one (possibly packed) ``Graph``.

    Core fields are always present; derivative fields default to ``None``
    and are attached by the ``with_*`` helpers (attachment is idempotent,
    so "ensure" calls are free once the value exists).  The whole object
    is a pytree and crosses jit boundaries like any other model input.
    """

    perm: jax.Array  # (E_pad,) int32 CSC permutation into COO arrays
    ids_sorted: jax.Array  # (E_pad,) int32 dst ids, padding == N_pad
    offsets: jax.Array  # (N_pad+1,) int32 per-destination row offsets
    src_sorted: jax.Array  # (E_pad,) int32 src ids in sorted-edge order
    in_degree: jax.Array  # (N_pad,) int32 real-edge in-degree
    # -- model-static derivatives (lazily attached) --
    gcn_inv_sqrt: Optional[jax.Array] = None  # (N_pad,) f32
    pna_scalers: Optional[jax.Array] = None  # (N_pad, 3) f32
    dgn_w_e: Optional[jax.Array] = None  # (E_pad,) f32 directional weights
    dgn_denom: Optional[jax.Array] = None  # (N_pad,) f32 |dphi| in-sums
    dgn_wsum: Optional[jax.Array] = None  # (N_pad,) f32 per-dst sum of w_e

    @property
    def num_nodes(self) -> int:
        return self.in_degree.shape[0]

    @property
    def num_edges(self) -> int:
        return self.perm.shape[0]


# ---------------------------------------------------------------------------
# construction — the one sort
# ---------------------------------------------------------------------------


def build_layout(graph: G.Graph) -> GraphLayout:
    """On-device plan construction: the single O(E log E) sort per forward.

    Equivalent to the per-call ``sort_by_segment(masked_dst, N)`` every
    aggregation used to run privately — same masked keys, same stable
    argsort — so consuming the shared plan is bitwise-identical to the
    seed per-call-sort path (asserted by tests/test_layout_parity.py).
    """
    n = graph.num_nodes
    dst = jnp.where(graph.edge_mask, graph.dst, n)
    perm, ids_sorted, offsets = sg.sort_by_segment(dst, n)
    return GraphLayout(
        perm=perm,
        ids_sorted=ids_sorted,
        offsets=offsets,
        src_sorted=jnp.take(graph.src, perm),
        in_degree=G.in_degree(graph),
    )


def host_layout(graph: G.Graph) -> GraphLayout:
    """Numpy twin of :func:`build_layout` for pack-time plan emission.

    ``np.argsort(kind="stable")`` over the identical int32 keys yields the
    identical permutation to the device path, so a host-built plan drops
    into the compiled program without changing a single bit of output —
    while removing the last on-device sort from the packed forward.

    Built from host arrays and returned with numpy leaves: given the
    numpy ``Graph`` of ``core.batching`` it touches no device, and the
    plan crosses with the rest of its batch.  A device ``Graph`` still
    works; its ``edge_index`` and ``edge_mask`` are read back once.
    """
    n = graph.num_nodes
    edge_mask = np.asarray(graph.edge_mask)
    edge_index = np.asarray(graph.edge_index)
    src = edge_index[0].astype(np.int32)
    real_dst = edge_index[1]
    dst = np.where(edge_mask, real_dst, n).astype(np.int32)
    perm = np.argsort(dst, kind="stable").astype(np.int32)
    ids_sorted = dst[perm]
    offsets = np.searchsorted(
        ids_sorted, np.arange(n + 1, dtype=np.int32), side="left"
    ).astype(np.int32)
    deg = np.zeros((n,), np.int32)
    np.add.at(deg, real_dst[edge_mask], 1)
    return GraphLayout(
        perm=perm,
        ids_sorted=ids_sorted,
        offsets=offsets,
        src_sorted=src[perm],
        in_degree=deg,
    )


def ensure_layout(layout: Optional[GraphLayout], graph: G.Graph) -> GraphLayout:
    """Return ``layout`` if supplied (0 sorts) else build it (1 sort)."""
    return build_layout(graph) if layout is None else layout


# ---------------------------------------------------------------------------
# sorted-plan consumption
# ---------------------------------------------------------------------------


def edge_plan(
    layout: Optional[GraphLayout], graph: G.Graph
) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """(perm, ids_sorted, src_sorted) — from the plan, or freshly sorted.

    The ``layout is None`` branch reproduces the seed per-call-sort path
    exactly (used by the parity tests and the A/B benchmark); every
    production call site passes a layout and performs zero sorts.
    """
    if layout is not None:
        return layout.perm, layout.ids_sorted, layout.src_sorted
    n = graph.num_nodes
    dst = jnp.where(graph.edge_mask, graph.dst, n)
    perm, ids_sorted, _ = sg.sort_by_segment(dst, n)
    return perm, ids_sorted, jnp.take(graph.src, perm)


def segment_reduce(
    layout: GraphLayout,
    values: jax.Array,
    op: str = "sum",
    presorted: bool = False,
) -> jax.Array:
    """Reduce per-edge ``values`` (COO order) into per-destination rows.

    Gathers through ``perm`` (``presorted=True`` skips the gather when the
    caller already holds sorted values) and reduces with
    ``indices_are_sorted=True``.  Padding edges carry id ``N_pad`` which
    JAX segment ops drop — no value masking happens or is needed here;
    that is the plan's masking contract (see core/message_passing.py).
    """
    vals = values if presorted else jnp.take(values, layout.perm, axis=0)
    return sg.segment_reduce(
        vals, layout.ids_sorted, layout.num_nodes, op, indices_are_sorted=True
    )


# ---------------------------------------------------------------------------
# model-static derivatives (lazy, idempotent, zero sorts)
# ---------------------------------------------------------------------------


def with_gcn_norms(layout: GraphLayout) -> GraphLayout:
    """Attach GCN's symmetric norm 1/sqrt(d_in + 1) (self-loop folded in)."""
    if layout.gcn_inv_sqrt is not None:
        return layout
    deg = layout.in_degree.astype(jnp.float32) + 1.0
    return dataclasses.replace(layout, gcn_inv_sqrt=jax.lax.rsqrt(deg))


def with_pna_scalers(layout: GraphLayout, avg_degree: float) -> GraphLayout:
    """Attach PNA's (N, 3) [identity, amplification, attenuation] scalers."""
    if layout.pna_scalers is not None:
        return layout
    from repro.core import message_passing as mp

    scalers = mp.pna_scalers(None, avg_degree, degree=layout.in_degree)
    return dataclasses.replace(layout, pna_scalers=scalers)


def with_dgn_weights(
    layout: GraphLayout, graph: G.Graph, eigvec: jax.Array
) -> GraphLayout:
    """Attach DGN's directional weights, computed once from the eigenvector.

    w_ij = (phi_j - phi_i) / sum_k |phi_k - phi_i| per in-edge, plus the
    per-destination |dphi| normalizer and sum of weights — all three were
    recomputed by every DGN layer (two extra sorted reduces per layer).
    """
    if layout.dgn_w_e is not None:
        return layout
    dphi = jnp.take(eigvec, graph.src) - jnp.take(eigvec, graph.dst)
    dphi = jnp.where(graph.edge_mask, dphi, 0.0)
    denom = segment_reduce(layout, jnp.abs(dphi)[:, None], op="sum")[:, 0]
    w_e = dphi / jnp.maximum(jnp.take(denom, graph.dst), 1e-6)
    wsum = segment_reduce(layout, w_e[:, None], op="sum")[:, 0]
    return dataclasses.replace(
        layout, dgn_w_e=w_e, dgn_denom=denom, dgn_wsum=wsum
    )


def for_model(
    layout: Optional[GraphLayout],
    graph: G.Graph,
    model: str,
    avg_degree: float = 1.0,
    eigvec: Optional[jax.Array] = None,
) -> GraphLayout:
    """Ensure the plan exists and carries ``model``'s static derivatives.

    At most one sort (zero when ``layout`` was supplied); the derivative
    attachment is pure arithmetic over the cached degree / permutation.
    """
    layout = ensure_layout(layout, graph)
    if model == "gcn":
        layout = with_gcn_norms(layout)
    elif model == "pna":
        layout = with_pna_scalers(layout, avg_degree)
    elif model == "dgn" and eigvec is not None:
        layout = with_dgn_weights(layout, graph, eigvec)
    return layout
