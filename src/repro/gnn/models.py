"""The six representative GNN models (paper Table 2 / §4) and GraphGPS, on
the generic message-passing core.

Every model is expressed through the same (phi, A, gamma) triple the paper
uses, so the engine (serve/gnn_engine.py) runs all of them unchanged —
the 'generic' claim.  Configurations default to the paper's §5.1 settings:

  GCN / GIN / GIN+VN : 5 layers, dim 100, mean pool, linear head
  PNA                : 4 layers, dim 80,  mean pool, MLP head (40, 20, 1)
  DGN                : 4 layers, dim 100, mean pool, MLP head (50, 25, 1)
  GAT                : 5 layers, 4 heads x 16 features, mean pool, linear head

and GraphGPS (Rampasek et al., arXiv:2205.12454) at its ogbg-molpcba
widths: 5 GPS layers at 384 (a GatedGCN local MPNN with an edge state
carried across layers, 4-head attention within each graph, FFN
384-768-384, inference BatchNorm), RWSE of 16 random-walk steps computed
in the program, mean pool, a linear head to 128 tasks.
"""
from __future__ import annotations

import dataclasses
from typing import Callable, Optional

import jax
import jax.numpy as jnp

from repro.core import graph as G
from repro.core import layout as LY
from repro.core import message_passing as mp
from repro.gnn import layers as L
from repro.kernels import ops as kops
from repro.kernels.flash_attention import segment_pairs

# Models whose layers attend among the nodes of each graph of a packed
# batch (GPS's global attention, masked by graph).
GRAPH_ATTENTION = frozenset({"gps"})

# Models that take one Laplacian eigenvector per graph, computed on the host
# at request time (DGN's directional aggregation).
HOST_EIGVEC = frozenset({"dgn"})


@dataclasses.dataclass(frozen=True)
class GNNConfig:
    model: str = "gin"  # gcn | gin | gat | pna | dgn | gps
    num_layers: int = 5
    hidden: int = 100
    feat_dim: int = 9  # OGB mol atom features (as floats)
    edge_dim: int = 3  # OGB mol bond features
    out_dim: int = 1
    heads: int = 4  # GAT / GPS attention heads
    head_features: int = 16  # GAT / GPS per-head features
    pe_steps: int = 16  # GPS: random-walk steps of the RWSE
    pe_dim: int = 20  # GPS: RWSE projection, concatenated to the atom embedding
    ffn_hidden: int = 768  # GPS: feed-forward inner width
    avg_degree: float = 2.2  # PNA scaler constant (MolHIV train stat)
    task: str = "graph"  # graph | node
    virtual_node: bool = False
    head_hidden: tuple = ()  # () = single linear head
    kernel_mode: str = "auto"

    @property
    def width(self) -> int:
        return self.heads * self.head_features if self.model == "gat" else self.hidden

    @property
    def graph_attention(self) -> bool:
        return self.model in GRAPH_ATTENTION


def attention_pairs(cfg: GNNConfig, node_counts, rows: int) -> Optional[tuple]:
    """A packed batch's (real, computed) query-key pairs of the per-graph
    attention: within its graphs (sum of n^2) and in the tiles the
    segment-id kernel runs over its ``rows`` node rows
    (``kernels.flash_attention.segment_pairs``); None for a model without
    per-graph attention."""
    if cfg.model not in GRAPH_ATTENTION:
        return None
    return segment_pairs(node_counts, rows)


def needs_eigvec(cfg: GNNConfig) -> bool:
    """Whether a request for ``cfg`` carries a host Laplacian eigenvector."""
    return cfg.model in HOST_EIGVEC


def paper_config(model: str, virtual_node: bool = False, **kw) -> GNNConfig:
    base = dict(model=model, virtual_node=virtual_node)
    if model in ("gcn", "gin"):
        base.update(num_layers=5, hidden=100)
    elif model == "gat":
        base.update(num_layers=5, heads=4, head_features=16)
    elif model == "pna":
        base.update(num_layers=4, hidden=80, head_hidden=(40, 20))
    elif model == "dgn":
        base.update(num_layers=4, hidden=100, head_hidden=(50, 25))
    elif model == "gps":  # GraphGPS, ogbg-molpcba (arXiv:2205.12454)
        base.update(num_layers=5, hidden=384, heads=4, head_features=96,
                    out_dim=128, pe_steps=16, pe_dim=20, ffn_hidden=768)
    else:
        raise ValueError(model)
    base.update(kw)
    return GNNConfig(**base)


# ---------------------------------------------------------------------------
# init
# ---------------------------------------------------------------------------


def init(rng: jax.Array, cfg: GNNConfig) -> dict:
    if cfg.model == "gps":
        return _gps_init(rng, cfg)
    keys = iter(jax.random.split(rng, 4 + 4 * cfg.num_layers))
    w = cfg.width
    params: dict = {"encoder": L.linear_init(next(keys), cfg.feat_dim, w), "layers": []}
    for _ in range(cfg.num_layers):
        lp: dict = {}
        if cfg.model == "gcn":
            lp["lin"] = L.linear_init(next(keys), w, w)
        elif cfg.model == "gin":
            lp["edge"] = L.linear_init(next(keys), cfg.edge_dim, w)
            lp["eps"] = jnp.zeros(())
            lp["mlp"] = L.mlp_init(next(keys), (w, 2 * w, w))
        elif cfg.model == "gat":
            h, f = cfg.heads, cfg.head_features
            lp["proj"] = L.linear_init(next(keys), w, h * f)
            lp["att_src"] = L.glorot(next(keys), (h, f))
            lp["att_dst"] = L.glorot(next(keys), (h, f))
        elif cfg.model == "pna":
            lp["pre"] = L.linear_init(next(keys), w, w)
            lp["post"] = L.linear_init(next(keys), 12 * w, w)
        elif cfg.model == "dgn":
            lp["post"] = L.linear_init(next(keys), 3 * w, w)
        params["layers"].append(lp)
    if cfg.virtual_node:
        params["vn_embed"] = jnp.zeros((w,))
        vn_mlps = []
        for _ in range(cfg.num_layers - 1):
            m = L.mlp_init(next(keys), (w, 2 * w, w))
            # zero-init the VN update's output layer: the virtual-node
            # branch starts as a no-op (the sum-pool over ~25 nodes
            # otherwise amplifies magnitudes ~w^0.5 per layer; the OGB
            # reference tames this with BatchNorm, which in inference-mode
            # HLS is folded constants — zero-init is the equivalent here)
            m[-1]["w"] = jnp.zeros_like(m[-1]["w"])
            vn_mlps.append(m)
        params["vn_mlp"] = vn_mlps
    head_sizes = (w,) + tuple(cfg.head_hidden) + (cfg.out_dim,)
    params["head"] = L.mlp_init(next(keys), head_sizes)
    return params


# the centre of each GPS BatchNorm's seeded running variance: the variance
# its input has when the layers see unit-scale input (glorot weights,
# molecule graphs; norm_ff's chosen so that the residual stream keeps unit
# scale through the layers, as a trained model's statistics keep it — with
# unit variances every layer would grow it about threefold)
GPS_BN_VAR = {"pe_norm": 0.03, "bn_x": 1.7, "bn_e": 2.1, "norm_local": 2.2,
              "norm_attn": 1.2, "norm_ff": 4.5}


def _gps_init(rng: jax.Array, cfg: GNNConfig) -> dict:
    """GraphGPS parameters.  Every BatchNorm holds seeded random running
    statistics (``L.batch_norm_stats_init``), so no normalization is the
    identity by accident; the attention's q/k/v projections are one fused
    linear (PyTorch's ``in_proj``), heads split contiguously."""
    w, f = cfg.hidden, cfg.ffn_hidden
    keys = iter(jax.random.split(rng, 6 + 13 * cfg.num_layers))

    def encoder(k, d_in, d_out):  # unit-scale embeddings of unit inputs
        return {"w": jax.random.normal(k, (d_in, d_out)) / jnp.sqrt(d_in),
                "b": jnp.zeros((d_out,))}

    params: dict = {
        "atom": encoder(next(keys), cfg.feat_dim, w - cfg.pe_dim),
        "pe_norm": L.batch_norm_stats_init(next(keys), cfg.pe_steps,
                                           GPS_BN_VAR["pe_norm"]),
        "pe": encoder(next(keys), cfg.pe_steps, cfg.pe_dim),
        "bond": encoder(next(keys), cfg.edge_dim, w),
        "layers": [],
    }
    for _ in range(cfg.num_layers):
        lp = {k: L.linear_init(next(keys), w, w) for k in "ABCDE"}
        for k in ("bn_x", "bn_e", "norm_local", "norm_attn", "norm_ff"):
            lp[k] = L.batch_norm_stats_init(next(keys), w, GPS_BN_VAR[k])
        lp["attn"] = {"qkv": L.linear_init(next(keys), w, 3 * w),
                      "out": L.linear_init(next(keys), w, w)}
        lp["ff"] = L.mlp_init(next(keys), (w, f, w))
        params["layers"].append(lp)
    params["head"] = L.mlp_init(next(keys), (w,) + tuple(cfg.head_hidden)
                                + (cfg.out_dim,))
    return params


GPS_NORMS = ("bn_x", "bn_e", "norm_local", "norm_attn", "norm_ff")


def serving_params(params: dict, cfg: GNNConfig) -> dict:
    """The tree a model serves from, made once by ``Executor.register``
    (``apply`` makes it inside the program when handed ``init``'s tree).

    GraphGPS serves from 20 arrays instead of 202, since the compiler
    moves each array a program reads with copies of its own, which cost
    device ops in every flush.  In each layer the five linears that read
    the node state (A, B, D, E and the attention's q/k/v) become one
    product, and the five inference BatchNorms fold into a (5, 2, hidden)
    array of scales and shifts; then the layers stack along a leading
    axis, which ``layer_params`` indexes.  Every other model serves the
    tree it is given."""
    if cfg.model != "gps" or isinstance(params["layers"], dict):
        return params

    def packed(lp):
        proj = [lp[c] for c in "ABDE"] + [lp["attn"]["qkv"]]
        return {"x_proj": {k: jnp.concatenate([p[k] for p in proj], axis=-1)
                           for k in ("w", "b")},
                "C": lp["C"], "out": lp["attn"]["out"], "ff": lp["ff"],
                "norms": jnp.stack([L.batch_norm_fold(lp[k]) for k in GPS_NORMS])}

    layers = [packed(lp) for lp in params["layers"]]
    return {"atom": params["atom"], "pe": params["pe"], "bond": params["bond"],
            "head": params["head"], "pe_norm": L.batch_norm_fold(params["pe_norm"]),
            "layers": jax.tree.map(lambda *a: jnp.stack(a), *layers)}


def layer_params(layers, li: int):
    """Layer ``li``'s parameters from a list of layers or from layers
    stacked along a leading axis (``serving_params``)."""
    if isinstance(layers, dict):
        return jax.tree.map(lambda a: a[li], layers)
    return layers[li]


# ---------------------------------------------------------------------------
# per-model layer bodies: each is a (phi, A, gamma) triple over the generic
# ``mp.mp_layer`` dataflow, closed over the shared ``GraphLayout`` plan —
# layer bodies never sort (tools/check_no_raw_sort.py) and never call the
# scatter machinery directly (tools/check_mp_spec.py): graph-static values
# come off ``extras["layout"]`` and every reduction goes through
# ``mp.mp_layer`` / the ``mp.*_aggregate`` helpers.
#
# When the engine asks for fusion (``extras["fused"]``) a body *declares*
# its triple as an ``mp.MPSpec`` + operand dict instead of closures, and
# the whole layer runs as one megakernel pass; bodies whose parameters
# can't lower (int8-static / ap_fixed linears) silently keep the closure
# form — same numerics, unfused — and GAT opts out structurally.
# ---------------------------------------------------------------------------


def _spec_precision(lin1):
    return "int8" if lin1["kind"] == "int8" else "fp32"


def _lin1_operands(lin1):
    """fused_linear_operands dict -> the kernel's w1/b1/w1_scale triple."""
    if lin1["kind"] == "int8":
        return dict(w1=lin1["w_q"], b1=lin1["b"], w1_scale=lin1["w_scale"])
    return dict(w1=lin1["w"], b1=lin1["b"])


def _gcn_layer(g: G.Graph, x, lp, cfg, extras):
    # x' = W^T sum_{j in N(i) U {i}} x_j / sqrt((d_i+1)(d_j+1)) + b
    layout = extras["layout"]
    if layout is not None and layout.gcn_inv_sqrt is not None:
        inv_sqrt = layout.gcn_inv_sqrt
    else:
        inv_sqrt = jax.lax.rsqrt(G.in_degree(g).astype(jnp.float32) + 1.0)
    xw = L.linear_apply(lp["lin"], x, mode=cfg.kernel_mode)
    xs = xw * inv_sqrt[:, None]

    if extras.get("fused") and layout is not None:
        # the linear runs *before* aggregation (W^T and the sum commute),
        # so the fused pass is pure dataflow: gamma rescales and adds the
        # normalized self-loop — any precision of lp["lin"] is eligible
        spec = mp.MPSpec(phi="copy", ops=("sum",), gamma="gcn")
        return mp.mp_layer(
            g, xs, layout=layout, spec=spec, mode=cfg.kernel_mode,
            operands=dict(msrc=xs, x_res=xs, nop=inv_sqrt[:, None]),
        )

    def phi(x_src, x_dst, e):
        return x_src

    def gamma(xs_, agg):
        return (agg + xs_) * inv_sqrt[:, None]  # self loop folded in

    return mp.mp_layer(g, xs, phi, gamma, ops=("sum",), layout=layout)


def _gin_layer(g: G.Graph, x, lp, cfg, extras):
    # phi(x, e) = relu(x_src + edge_embed)   (paper: x + eps*m with edge emb)
    layout = extras["layout"]
    if extras.get("fused") and layout is not None:
        lin1 = L.fused_linear_operands(lp["mlp"][0])
        edge_wb = L.fused_dequant_weights(lp["edge"])
        lin2_wb = L.fused_dequant_weights(lp["mlp"][1])
        if lin1 is not None and edge_wb is not None and lin2_wb is not None:
            # edge features gather into plan order first, so the edge
            # embedding lands pre-sorted as the kernel's phi operand
            ef_sorted = jnp.take(g.edge_feat, layout.perm, axis=0)
            e_emb = kops.node_mlp(
                ef_sorted, edge_wb[0], edge_wb[1], activation="none",
                mode=cfg.kernel_mode,
            )
            spec = mp.MPSpec(
                phi="add_relu", ops=("sum",), gamma="gin",
                precision=_spec_precision(lin1),
            )
            return mp.mp_layer(
                g, x, layout=layout, spec=spec, mode=cfg.kernel_mode,
                operands=dict(
                    msrc=x, x_res=(1.0 + lp["eps"]) * x, eop=e_emb,
                    w2=lin2_wb[0], b2=lin2_wb[1], **_lin1_operands(lin1),
                ),
            )

    e_emb = L.linear_apply(lp["edge"], g.edge_feat, mode=cfg.kernel_mode)

    def phi(x_src, x_dst, e):
        return jax.nn.relu(x_src + e)

    def gamma(x_, agg):
        return L.mlp_apply(
            lp["mlp"], (1.0 + lp["eps"]) * x_ + agg, mode=cfg.kernel_mode
        )

    return mp.mp_layer(
        g, x, phi, gamma, ops=("sum",), edge_feat=e_emb, layout=layout
    )


def _gat_layer(g: G.Graph, x, lp, cfg, extras):
    """GAT's A(.) is an edge softmax, not a plain reduction: the softmax
    normalizer couples all of a destination's edges before any message can
    fold in, so GAT is the declared ``MPSpec`` opt-out (it ignores
    ``extras["fused"]``).  phi produces per-edge logits and messages and
    ``mp.gat_attention`` normalizes + reduces over the shared plan;
    gamma is the elu tail."""
    h, f = cfg.heads, cfg.head_features
    n = g.num_nodes
    xp = L.linear_apply(lp["proj"], x, mode=cfg.kernel_mode).reshape(n, h, f)
    a_src = jnp.einsum("nhf,hf->nh", xp, lp["att_src"])
    a_dst = jnp.einsum("nhf,hf->nh", xp, lp["att_dst"])
    logits = jax.nn.leaky_relu(
        jnp.take(a_src, g.src, axis=0) + jnp.take(a_dst, g.dst, axis=0), 0.2
    )  # (E, H) in COO order
    agg = mp.gat_attention(
        g, logits, xp, layout=extras["layout"], mode=cfg.kernel_mode
    )
    out = jax.nn.elu(agg)
    return jnp.where(g.node_mask[:, None], out, 0.0)


def _pna_layer(g: G.Graph, x, lp, cfg, extras):
    layout = extras["layout"]
    xp = L.linear_apply(lp["pre"], x, activation="relu", mode=cfg.kernel_mode)

    if extras.get("fused") and layout is not None:
        lin1 = L.fused_linear_operands(lp["post"])
        if lin1 is not None:
            if layout.pna_scalers is not None:
                scalers = layout.pna_scalers
            else:
                scalers = mp.pna_scalers(
                    g, cfg.avg_degree, degree=layout.in_degree
                )
            spec = mp.MPSpec(
                phi="copy", ops=("sum", "sqsum", "max", "min"), gamma="pna",
                precision=_spec_precision(lin1),
            )
            return mp.mp_layer(
                g, xp, layout=layout, spec=spec, mode=cfg.kernel_mode,
                operands=dict(
                    msrc=xp, x_res=x, nop=scalers, **_lin1_operands(lin1)
                ),
            )

    def phi(x_src, x_dst, e):
        return x_src

    def aggregate(graph, messages, layout_):
        return mp.pna_aggregate(graph, messages, cfg.avg_degree, layout=layout_)

    def gamma(xp_, tower):
        out = L.linear_apply(
            lp["post"], tower, activation="relu", mode=cfg.kernel_mode
        )
        return out + x  # skip connection (§4.3) from the layer input

    return mp.mp_layer(g, xp, phi, gamma, aggregate=aggregate, layout=layout)


def _dgn_layer(g: G.Graph, x, lp, cfg, extras):
    """mean + directional-derivative aggregation along eigenvector phi1 (§4.4).

    B_dx row i: w_ij = (phi_j - phi_i) / sum_k |phi_k - phi_i|;
    y_dx_i = | sum_j w_ij x_j  -  x_i sum_j w_ij |.

    The directional weights depend only on the graph and its eigenvector,
    so they live on the layout (computed once per forward, not per layer);
    the per-layer work is phi = x_src, A = [mean, w-weighted sum], and
    gamma assembles the |.| derivative and the post-MLP + skip.  Fused,
    the weighted sum is the kernel's "wsum" accumulator over the plan-
    ordered weights and the derivative assembles in the finalize tail.
    """
    layout = extras["layout"]
    if layout is not None and layout.dgn_w_e is not None:
        w_e, wsum = layout.dgn_w_e, layout.dgn_wsum
    else:
        w_e, wsum = mp.dgn_directional_weights(g, extras["eigvec"])

    if extras.get("fused") and layout is not None:
        lin1 = L.fused_linear_operands(lp["post"])
        if lin1 is not None:
            ew_sorted = jnp.take(w_e, layout.perm)[:, None]
            spec = mp.MPSpec(
                phi="copy", ops=("sum", "wsum"), gamma="dgn",
                precision=_spec_precision(lin1),
            )
            return mp.mp_layer(
                g, x, layout=layout, spec=spec, mode=cfg.kernel_mode,
                operands=dict(
                    msrc=x, x_res=x, nop=wsum[:, None], ew=ew_sorted,
                    **_lin1_operands(lin1),
                ),
            )

    def phi(x_src, x_dst, e):
        return x_src

    def aggregate(graph, messages, layout_):
        return mp.dgn_aggregate(graph, messages, w_e, layout=layout_)

    def gamma(x_, agg):
        d = x_.shape[-1]
        mean_agg, wx = agg[:, :d], agg[:, d:]
        dx_agg = jnp.abs(wx - x_ * wsum[:, None])
        tower = jnp.concatenate([x_, mean_agg, dx_agg], axis=-1)
        out = L.linear_apply(
            lp["post"], tower, activation="relu", mode=cfg.kernel_mode
        )
        return out + x_  # skip connection, as in PNA (§4.4)

    return mp.mp_layer(g, x, phi, gamma, aggregate=aggregate, layout=layout)


def _gps_layer(g: G.Graph, x, e, lp, cfg, extras):
    """One GraphGPS layer (arXiv:2205.12454 eq. 2-4, GraphGPS's
    ``GPSLayer`` order), i the destination and j the source of edge j->i:

      e^_ij = D x_i + E x_j + C e_ij,  sigma_ij = sigmoid(e^_ij)
      x_M   = BN(x + relu(BN(A x_i + sum_j sigma_ij B x_j
                                    / (sum_j sigma_ij + 1e-6))))
      e'    = e + relu(BN(e^_ij))
      x_T   = BN(x + MHA(x)), attention within each graph only
      x'    = BN(s + W2 relu(W1 s)),  s = x_M + x_T

    The gated sums ride the shared plan (``mp.gated_aggregate``); the
    attention is ``kernels/flash_attention`` with the packed flush's graph
    ids as segment ids.  ``lp`` is a layer of ``serving_params``: one
    product gives A x, B x, D x, E x and q/k/v, and ``lp["norms"]`` holds
    the five BatchNorms in the order of ``GPS_NORMS``.  The edge state
    ``e`` stays in COO order."""
    layout, mode, w = extras["layout"], cfg.kernel_mode, cfg.hidden
    norms = lp["norms"]
    h = L.linear_apply(lp["x_proj"], x, mode=mode)
    ax, bx, dx, ex = (h[:, i * w:(i + 1) * w] for i in range(4))
    e_hat = (jnp.take(dx, g.dst, axis=0) + jnp.take(ex, g.src, axis=0)
             + L.linear_apply(lp["C"], e, mode=mode))
    agg = mp.gated_aggregate(g, jax.nn.sigmoid(e_hat),
                             jnp.take(bx, g.src, axis=0), layout=layout)
    x_local = x + jax.nn.relu(L.affine_apply(norms[0], ax + agg))
    e = e + jax.nn.relu(L.affine_apply(norms[1], e_hat))
    x_local = L.affine_apply(norms[2], x_local)

    heads = L.segment_attention(h[:, 4 * w:], g, cfg.heads, mode=mode)
    attn = L.linear_apply(lp["out"], heads, mode=mode)
    x_attn = L.affine_apply(norms[3], x + attn)

    s = x_local + x_attn
    out = L.affine_apply(norms[4], s + L.mlp_apply(lp["ff"], s, mode=mode))
    return jnp.where(g.node_mask[:, None], out, 0.0), e


def _node_layer(fn):
    """A layer body of node state alone, lifted to the (x, e) state the
    forward carries: the edge state passes through untouched."""

    def layer(g, x, e, lp, cfg, extras):
        return fn(g, x, lp, cfg, extras), e

    return layer


# every body maps the carried state (x, e) -> (x, e); ``e`` is the edge
# state of models that update one (GPS), None for the rest
_LAYERS = {"gcn": _node_layer(_gcn_layer), "gin": _node_layer(_gin_layer),
           "gat": _node_layer(_gat_layer), "pna": _node_layer(_pna_layer),
           "dgn": _node_layer(_dgn_layer), "gps": _gps_layer}


def _encode(params: dict, g: G.Graph, cfg: GNNConfig):
    """The input state (x, e): the node encoder's embedding and, for GPS,
    ``[W_atom x || W_pe BN(RWSE)]`` with the bond embedding as the first
    edge state.  The RWSE is computed here from the graph, in the program
    (no request-time preprocessing on the host)."""
    mode = cfg.kernel_mode
    if cfg.model != "gps":
        x = L.linear_apply(params["encoder"], g.node_feat, mode=mode)
        return jnp.where(g.node_mask[:, None], x, 0.0), None
    rw = mp.random_walk_se(g, cfg.pe_steps)
    pe = L.linear_apply(
        params["pe"], L.affine_apply(params["pe_norm"], rw),
        mode=mode)
    x = jnp.concatenate(
        [L.linear_apply(params["atom"], g.node_feat, mode=mode), pe], axis=-1)
    e = L.linear_apply(params["bond"], g.edge_feat, mode=mode)
    return jnp.where(g.node_mask[:, None], x, 0.0), e


# ---------------------------------------------------------------------------
# full forward pass
# ---------------------------------------------------------------------------


def apply(
    params: dict,
    g: G.Graph,
    cfg: GNNConfig,
    eigvec: Optional[jax.Array] = None,
    num_graphs: Optional[int] = None,
    layout: Optional[LY.GraphLayout] = None,
    share_layout: bool = True,
    fused: bool = False,
) -> jax.Array:
    """Forward pass.  Returns (num_graphs, out_dim) for graph tasks or
    (N_pad, out_dim) for node tasks.  ``eigvec`` is DGN's precomputed
    Laplacian eigenvector *input* (a model input, like the paper's).

    ``num_graphs`` is the static graph-slot count (a packed bucket's G_pad
    or the serving batch size); it sizes the pooled / virtual-node buffers.
    When omitted it falls back to the ``num_nodes`` upper bound, which is
    correct but allocates one pooled row per padded node.

    ``layout`` is the shared destination-ordered edge plan (§3.4): pass
    one built at pack/ingest time for a zero-sort forward, or leave it
    ``None`` to build it here (exactly one on-device sort, amortized over
    every layer).  ``share_layout=False`` disables the plan entirely and
    reverts to the seed per-call-sort path — kept for the bitwise parity
    tests and the A/B sort-count benchmark, never for serving.

    ``fused`` lowers each layer body to its declarative ``mp.MPSpec`` and
    runs the whole (phi, A, gamma) pass through the fused megakernel
    (``kernels/fused_mp.py`` / its oracle) instead of separate gather /
    reduce / update ops.  Requires ``share_layout``; GAT and layers whose
    quantized parameters can't lower (int8-static, ap_fixed) keep the
    closure path automatically.  Off by default: the unfused path is the
    parity oracle, exactly as the per-call-sort path is for layouts.
    """
    m = g.num_nodes if num_graphs is None else num_graphs
    params = serving_params(params, cfg)
    layer_fn = _LAYERS[cfg.model]
    if share_layout:
        layout = LY.for_model(
            layout, g, cfg.model, avg_degree=cfg.avg_degree, eigvec=eigvec
        )
    else:
        layout = None
    extras = {"eigvec": eigvec, "layout": layout, "fused": fused}
    x, e = _encode(params, g, cfg)
    vn = None  # (m, w) per-graph virtual-node state
    if cfg.virtual_node:
        vn = jnp.broadcast_to(params["vn_embed"], (m, x.shape[-1]))

    for li in range(cfg.num_layers):
        if cfg.virtual_node:
            # virtual node broadcasts its state to every node of its graph
            gid = jnp.clip(g.graph_id, 0, m - 1)
            x = x + jnp.take(vn, gid, axis=0) * g.node_mask[:, None]
        x, e = layer_fn(g, x, e, layer_params(params["layers"], li), cfg, extras)
        if cfg.virtual_node and li < cfg.num_layers - 1:
            # vn_{l+1} = MLP(vn_l + sum-pool of that graph's nodes)
            pooled = mp.global_pool(g, x, op="sum", num_graphs=m)
            vn = L.mlp_apply(
                params["vn_mlp"][li], pooled + vn, mode=cfg.kernel_mode
            )

    if cfg.task == "graph":
        pooled = mp.global_pool(g, x, op="mean", num_graphs=m)
        return L.mlp_apply(params["head"], pooled, mode=cfg.kernel_mode)
    return L.mlp_apply(params["head"], x, mode=cfg.kernel_mode)


def forward_program(
    cfg: GNNConfig,
    num_graphs: Optional[int] = None,
    share_layout: bool = True,
    fused: bool = False,
) -> Callable:
    """The engine-facing program: :func:`apply` with its statics bound.

    Returns a pure ``(params, graph, eigvec, layout) -> logits`` closure —
    the positional shape every compiled serving program shares.  Built
    exactly once per compile-cache entry by ``serve.executor.Executor``
    (the only module that may wrap it in ``jax.jit``; see
    ``tools/check_engine_singlepath.py``).  ``fused`` is a program-level
    static like ``share_layout``: it changes which ops the program lowers
    to, never the positional signature.
    """

    def program(params, g: G.Graph, eigvec, layout):
        return apply(params, g, cfg, eigvec=eigvec, num_graphs=num_graphs,
                     layout=layout, share_layout=share_layout, fused=fused)

    return program
