"""Generic message-passing layer (paper §3.3, Fig. 2/3).

    x_i^{l+1} = gamma( x_i^l , A_{j in N(i)} ( phi(x_j^l, e_ij^l) ) )

The framework fixes the *dataflow* (gather messages along in-edges, reduce
per destination, transform per node) and models plug in:

  * ``phi``      message transformation, applied edge-parallel,
  * ``aggregate``one or more permutation-invariant reductions,
  * ``gamma``    node transformation (the "Node Embedding PE").

GenGNN's merged scatter-gather is realized over a shared
``core.layout.GraphLayout``: the COO->CSC conversion (the one O(E log E)
sort) happens once per graph, and every aggregation of every layer folds
its messages into the O(N) destination buffer through that single plan —
permutation invariance makes the order irrelevant (§3.4).

Masking contract
----------------
Padding-edge masking is the **layout's job**, not the caller's and not a
value-side multiply here:

  * the plan's sort keys are ``where(edge_mask, dst, N_pad)``, so padding
    edges sort to the end carrying the out-of-range id ``N_pad``;
  * JAX segment ops *drop* out-of-range ids, so padding messages never
    reach a real destination row — whatever garbage they hold;
  * callers therefore pass raw, unmasked per-edge messages, and nothing
    in this module multiplies messages by ``edge_mask`` (the seed did
    both, meaning every aggregate paid a redundant (E, F) select *and*
    several callers pre-masked on top of that).

Node-side masking stays explicit (``mp_layer`` zeroes padded node rows on
the way out) because padded node rows are *read back* by the next layer's
gather, unlike padding edges which are write-only.
"""
from __future__ import annotations

import dataclasses
from typing import Callable, Dict, Optional, Sequence

import jax
import jax.numpy as jnp

from repro.core import layout as LY
from repro.core import scatter_gather as sg
from repro.core.graph import Graph, in_degree
from repro.kernels import ops as kops

# phi(x_src, x_dst, e) -> message  (edge-parallel)
PhiFn = Callable[[jax.Array, jax.Array, jax.Array], jax.Array]
# gamma(x, aggregated) -> new x    (node-parallel)
GammaFn = Callable[[jax.Array, jax.Array], jax.Array]
# aggregate(graph, messages, layout) -> per-node aggregate (the A of §3.3)
AggregateFn = Callable[[Graph, jax.Array, Optional["LY.GraphLayout"]], jax.Array]

AGGREGATORS = ("sum", "mean", "max", "min", "std", "var")

# the megakernel's aggregator set: the accumulators it materializes in
# VMEM scratch.  mean/std are *derived* in gamma from sum/sqsum and the
# plan's cached in-degree, so they never need their own accumulator.
FUSED_AGGREGATORS = ("sum", "sqsum", "max", "min", "wsum")
FUSED_PHIS = ("copy", "add_relu")
FUSED_GAMMAS = ("gcn", "gin", "pna", "dgn")
FUSED_PRECISIONS = ("fp32", "int8")


@dataclasses.dataclass(frozen=True)
class MPSpec:
    """Declarative (phi, A, gamma) layer contract for the fused megakernel.

    Where the closure form of :func:`mp_layer` *computes* phi and gamma,
    an ``MPSpec`` *names* them — a hashable static the Pallas kernel
    (``kernels/fused_mp.py``) compiles into one VMEM-resident pass:

      phi:        "copy" (message = gathered source operand) or
                  "add_relu" (GIN: relu(x_src + edge operand))
      ops:        accumulator tuple, subset of ``FUSED_AGGREGATORS``;
                  "wsum" weights each message by a per-edge operand
                  (DGN's directional w_e) before summing
      gamma:      node-update kind — "gcn" normalized self-loop add,
                  "gin" 2-layer MLP, "pna" scaler tower + skip,
                  "dgn" directional derivative + skip
      precision:  "fp32", or "int8" to run gamma's first linear as an
                  in-kernel W8A8 boundary (per-row dynamic quantize,
                  int32 accumulate, fused requant — the
                  ``quant.qconfig`` dynamic recipe, never leaving VMEM)

    The runtime operands a spec needs (weights, per-node/per-edge
    values) travel separately — see ``kernels/ref.fused_mp_ref`` for the
    operand contract.  Models that cannot lower to this set (GAT's edge
    softmax) keep the closure form and opt out of fusion.
    """

    phi: str = "copy"
    ops: tuple = ("sum",)
    gamma: str = "gcn"
    precision: str = "fp32"

    def __post_init__(self):
        if self.phi not in FUSED_PHIS:
            raise ValueError(f"unknown phi {self.phi!r}; expected {FUSED_PHIS}")
        bad = [op for op in self.ops if op not in FUSED_AGGREGATORS]
        if bad or not self.ops:
            raise ValueError(
                f"fused aggregators {self.ops!r} must be a non-empty subset "
                f"of {FUSED_AGGREGATORS}"
            )
        if self.gamma not in FUSED_GAMMAS:
            raise ValueError(
                f"unknown gamma {self.gamma!r}; expected {FUSED_GAMMAS}"
            )
        if self.precision not in FUSED_PRECISIONS:
            raise ValueError(
                f"unknown precision {self.precision!r}; "
                f"expected {FUSED_PRECISIONS}"
            )


def gather_scatter(
    graph: Graph,
    messages: jax.Array,
    ops: Sequence[str] = ("sum",),
    layout: Optional[LY.GraphLayout] = None,
    use_sorted: bool = True,
) -> jax.Array:
    """Reduce edge messages into per-destination aggregates.

    messages: (E_pad, F) raw per-edge values in COO order — **unmasked**;
    padding-edge rows are dropped by the plan's out-of-range ids (see the
    module-level masking contract).  Returns (N_pad, len(ops) * F) with
    aggregates concatenated feature-wise (PNA-style layout).

    With ``layout`` the messages are permuted once and every op reduces
    the shared sorted stream (zero sorts).  Without one, each op runs the
    seed per-call sort path — kept for parity tests and A/B benchmarks.
    """
    if layout is not None:
        msg_sorted = jnp.take(messages, layout.perm, axis=0)
        outs = [
            LY.segment_reduce(layout, msg_sorted, op, presorted=True)
            for op in ops
        ]
        return outs[0] if len(outs) == 1 else jnp.concatenate(outs, axis=-1)
    dst = jnp.where(graph.edge_mask, graph.dst, graph.num_nodes)
    outs = []
    for op in ops:
        if use_sorted:
            outs.append(sg.sorted_segment_reduce(messages, dst, graph.num_nodes, op))
        else:
            outs.append(sg.segment_reduce(messages, dst, graph.num_nodes, op))
    return outs[0] if len(outs) == 1 else jnp.concatenate(outs, axis=-1)


def mp_layer(
    graph: Graph,
    x: jax.Array,
    phi: Optional[PhiFn] = None,
    gamma: Optional[GammaFn] = None,
    ops: Sequence[str] = ("sum",),
    edge_feat: jax.Array | None = None,
    layout: Optional[LY.GraphLayout] = None,
    aggregate: Optional[AggregateFn] = None,
    spec: Optional[MPSpec] = None,
    operands: Optional[Dict[str, jax.Array]] = None,
    mode: str = "auto",
) -> jax.Array:
    """One full message-passing layer: scatter(phi) -> A -> gamma.

    Two forms share this entry point:

    * **closure form** (``phi``/``gamma`` callables): the unfused oracle
      path — gather, transform, reduce, update as separate XLA ops.
      ``aggregate`` overrides the default multi-op ``gather_scatter``
      when a model's A(.) is richer than a concatenation of standard
      reductions (PNA's scaled tower, DGN's directional derivative); it
      receives the shared ``layout`` so custom aggregators also sort
      zero times.
    * **spec form** (``spec`` + ``operands``): the declarative contract,
      dispatched to the fused megakernel (``kernels/ops.fused_mp``) —
      the whole layer runs as one VMEM-resident pass over the plan.
      Requires a ``layout``; ``operands`` follows
      ``kernels/ref.fused_mp_ref`` (msrc/x_res/nop/eop/ew/w1/b1/...).

    ``x``: (N_pad, F) current node embeddings.  Returns (N_pad, F').
    """
    if spec is not None:
        if layout is None:
            raise ValueError(
                "fused mp_layer (spec=...) requires a GraphLayout plan; "
                "pass layout= or use the closure form"
            )
        return kops.fused_mp(
            spec, layout.ids_sorted, layout.src_sorted, layout.in_degree,
            graph.node_mask, mode=mode, **operands,
        )
    e = graph.edge_feat if edge_feat is None else edge_feat
    x_src = jnp.take(x, graph.src, axis=0)
    x_dst = jnp.take(x, graph.dst, axis=0)
    messages = phi(x_src, x_dst, e)
    if aggregate is not None:
        agg = aggregate(graph, messages, layout)
    else:
        agg = gather_scatter(graph, messages, ops=ops, layout=layout)
    out = gamma(x, agg)
    return jnp.where(graph.node_mask[:, None], out, 0.0)


# ---------------------------------------------------------------------------
# PNA degree scalers (paper §4.3)
# ---------------------------------------------------------------------------


def pna_scalers(
    graph: Optional[Graph],
    avg_degree: float,
    degree: Optional[jax.Array] = None,
) -> jax.Array:
    """(N_pad, 3) scaler matrix [1, amplification, attenuation] of [21].

    ``avg_degree`` is the mean degree seen in training data (a model
    hyperparameter, not graph preprocessing).  ``degree`` takes the
    layout's cached in-degree; without it the count is recomputed from
    ``graph`` (identical integer sums either way).
    """
    if degree is None:
        degree = in_degree(graph)
    deg = degree.astype(jnp.float32)
    logd = jnp.log(deg + 1.0)
    log_davg = jnp.log(jnp.asarray(avg_degree) + 1.0)
    amp = logd / log_davg
    att = log_davg / jnp.maximum(logd, 1e-6)
    att = jnp.where(deg > 0, att, 0.0)
    return jnp.stack([jnp.ones_like(logd), amp, att], axis=-1)


def pna_aggregate(
    graph: Graph,
    messages: jax.Array,
    avg_degree: float,
    layout: Optional[LY.GraphLayout] = None,
) -> jax.Array:
    """Full PNA tower: 4 aggregators x 3 scalers -> (N_pad, 12*F).

    With a shared layout the four reductions consume one permuted message
    stream and the scalers come off the cached degree — zero sorts; the
    seed path re-sorted the same edges four times per layer.
    """
    agg = gather_scatter(
        graph, messages, ops=("mean", "std", "max", "min"), layout=layout
    )
    n, f4 = agg.shape
    if layout is not None and layout.pna_scalers is not None:
        scalers = layout.pna_scalers
    else:
        degree = layout.in_degree if layout is not None else None
        scalers = pna_scalers(graph, avg_degree, degree=degree)
    out = agg[:, None, :] * scalers[:, :, None]  # (N, 3, 4F)
    return out.reshape(n, 3 * f4)


# ---------------------------------------------------------------------------
# GAT attention aggregation (paper §4.2) — the declared fusion opt-out
# ---------------------------------------------------------------------------


def gat_attention(
    graph: Graph,
    logits: jax.Array,
    xp: jax.Array,
    layout: Optional[LY.GraphLayout] = None,
    mode: str = "auto",
) -> jax.Array:
    """GAT's A(.): per-destination softmax + attention-weighted sum.

    ``logits``: (E, H) COO-order attention logits; ``xp``: (N, H, F)
    projected per-head features.  Returns (N, H*F).  The softmax
    normalizer couples every edge of a destination *before* any message
    can be folded in, so this A(.) does not lower to the megakernel's
    accumulator set — GAT is the documented ``MPSpec`` opt-out, and its
    two segment kernels ride the shared plan here instead (zero sorts).
    """
    n = graph.num_nodes
    perm, ids_sorted, src_sorted = LY.edge_plan(layout, graph)
    alpha = kops.edge_softmax(
        logits, ids_sorted, n, mode=mode, perm=perm
    )  # (E, H) sorted
    msg = jnp.take(xp, src_sorted, axis=0) * alpha[:, :, None]
    h_f = xp.shape[1] * xp.shape[2]
    return kops.segment_reduce(
        msg.reshape(-1, h_f), ids_sorted, n, op="sum", mode=mode
    )


# ---------------------------------------------------------------------------
# DGN directional aggregation (paper §4.4)
# ---------------------------------------------------------------------------


def dgn_directional_weights(graph: Graph, eigvec: jax.Array):
    """-> (w_e (E,), wsum (N,)) directional weights from the eigenvector.

    w_ij = (phi_j - phi_i) / sum_k |phi_k - phi_i| per in-edge, plus the
    per-destination sum of weights.  The layout caches these
    (``core.layout.with_dgn_weights``); this is the plan-less fallback,
    bit-identical to the cached values.
    """
    dphi = jnp.take(eigvec, graph.src) - jnp.take(eigvec, graph.dst)  # (E,)
    dphi = jnp.where(graph.edge_mask, dphi, 0.0)
    denom = gather_scatter(graph, jnp.abs(dphi)[:, None], ops=("sum",))[:, 0]
    w_e = dphi / jnp.maximum(jnp.take(denom, graph.dst), 1e-6)
    wsum = gather_scatter(graph, w_e[:, None], ops=("sum",))[:, 0]
    return w_e, wsum


def dgn_aggregate(
    graph: Graph,
    messages: jax.Array,
    w_e: jax.Array,
    layout: Optional[LY.GraphLayout] = None,
) -> jax.Array:
    """DGN's A(.): [mean, w-weighted sum] -> (N, 2*F) concatenated.

    ``w_e`` is the (E,) COO-order directional weight vector; both
    reductions consume the one permuted message stream when a ``layout``
    is threaded (zero sorts).
    """
    mean_agg = gather_scatter(graph, messages, ops=("mean",), layout=layout)
    wx = gather_scatter(
        graph, messages * w_e[:, None], ops=("sum",), layout=layout
    )
    return jnp.concatenate([mean_agg, wx], axis=-1)


# ---------------------------------------------------------------------------
# GatedGCN aggregation and random-walk structural encoding (GraphGPS)
# ---------------------------------------------------------------------------


def gated_aggregate(
    graph: Graph,
    gates: jax.Array,
    values: jax.Array,
    layout: Optional[LY.GraphLayout] = None,
    eps: float = 1e-6,
) -> jax.Array:
    """GatedGCN's A(.): sum_j sigma_ij * v_j / (sum_j sigma_ij + eps).

    ``gates`` (sigma) and ``values`` (the gathered source values) are
    (E, F) in COO order; both sums consume one permuted stream of the
    shared plan, as GIN's sum does (zero sorts).  Returns (N, F)."""
    f = values.shape[-1]
    sums = gather_scatter(graph, jnp.concatenate([gates * values, gates], -1),
                          ops=("sum",), layout=layout)
    return sums[:, :f] / (sums[:, f:] + eps)


def random_walk_se(graph: Graph, steps: int) -> jax.Array:
    """RWSE: (N, steps) landing probabilities diag(P^k), k = 1..steps, of
    the random walk P = D^-1 A (A[i, j] counts edges i -> j, D the
    out-degree; a node without out-edges has a zero row), as GraphGPS's
    ``get_rw_landing_probs``.  P is dense over the graph's node rows: no
    edge crosses graphs, so P is block-diagonal and each graph of a packed
    batch gets its own values.  Padding edges add nothing.

    The powers double in batches (P^(m+1..2m) = P^(1..m) P^m), so the
    ``steps - 1`` products run as about log2(steps) batched ones."""
    n = graph.num_nodes
    a = jnp.zeros((n, n), jnp.float32).at[graph.src, graph.dst].add(
        graph.edge_mask.astype(jnp.float32))
    p = a / jnp.maximum(a.sum(axis=1, keepdims=True), 1.0)
    powers = p[None]  # (m, N, N): P^1 .. P^m
    while powers.shape[0] < steps:
        m = powers.shape[0]
        powers = jnp.concatenate([powers, powers[:steps - m] @ powers[-1]])
    return jnp.diagonal(powers, axis1=1, axis2=2).T


# ---------------------------------------------------------------------------
# Global graph pooling (graph-level tasks, paper §3.3)
# ---------------------------------------------------------------------------


def global_pool(
    graph: Graph,
    x: jax.Array,
    op: str = "mean",
    num_graphs: int | None = None,
) -> jax.Array:
    """Pool node embeddings per graph id -> (num_graphs, F).

    Uses the same segment machinery; graphs in a padded batch are segments.
    ``num_graphs`` is the static graph-slot count of the batch (the packed
    bucket's G_pad).  When omitted it falls back to the conservative
    ``num_nodes`` upper bound — every graph has at least one node — which
    keeps single-graph call sites working but makes the pooled buffer
    mostly padding; batch/packed callers should always pass the real count.
    (``graph_id`` is node-indexed and already ordered, so pooling never
    needs the edge plan — no sort here in any path.)
    """
    m = graph.num_nodes if num_graphs is None else num_graphs
    gid = jnp.where(graph.node_mask, graph.graph_id, m)
    xm = jnp.where(graph.node_mask[:, None], x, 0.0)
    return sg.segment_reduce(xm, gid, m, op)
