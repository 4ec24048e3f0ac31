"""Dense-adjacency oracles for every GNN model.

The paper guarantees end-to-end correctness by cross-checking the HLS
implementation against PyTorch.  Here the engine (sparse, sorted-segment,
kernel-backed) is cross-checked against an *independent* dense formulation:
adjacency is materialized as an (N, N) matrix and every aggregation is a
dense matmul / masked reduction.  Sharing only the parameter pytrees, not
the code paths.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.graph import Graph
from repro.gnn.layers import BN_EPS
from repro.gnn.models import GNNConfig


def dense_adjacency(g: Graph) -> jax.Array:
    """(N, N) with A[dst, src] = 1 for each real edge (in-edge view)."""
    n = g.num_nodes
    a = jnp.zeros((n, n))
    vals = g.edge_mask.astype(jnp.float32)
    return a.at[g.dst, g.src].add(vals)


def _mlp(ps, x, act="relu", final="none"):
    for i, p in enumerate(ps):
        x = x @ p["w"] + p["b"]
        if i < len(ps) - 1 and act == "relu":
            x = jnp.maximum(x, 0)
        elif (i < len(ps) - 1 and act == "gelu") or (i == len(ps) - 1 and final == "gelu"):
            x = jax.nn.gelu(x)
        elif i == len(ps) - 1 and final == "relu":
            x = jnp.maximum(x, 0)
    return x


def _lin(p, x, act="none", mm=jnp.matmul):
    y = mm(x, p["w"]) + p["b"]
    if act == "relu":
        y = jnp.maximum(y, 0)
    elif act == "gelu":
        y = jax.nn.gelu(y)
    return y


def _masked_pool(g: Graph, x, op="mean"):
    n = g.num_nodes
    max_graphs = n
    gid = jnp.where(g.node_mask, g.graph_id, max_graphs)
    onehot = (gid[:, None] == jnp.arange(max_graphs)[None, :]).astype(jnp.float32)
    total = onehot.T @ x
    if op == "sum":
        return total
    count = onehot.sum(0)[:, None]
    return total / jnp.maximum(count, 1.0)


def _bn(p, x, eps):
    return (x - p["mean"]) / jnp.sqrt(p["var"] + eps) * p["gamma"] + p["beta"]


def apply_gps_dense(params, g: Graph, cfg: GNNConfig, mm=jnp.matmul) -> jax.Array:
    """GraphGPS (arXiv:2205.12454) written out plainly: per-edge gathers
    and the gated sums as one-hot matrix products, attention as dense
    (N, N) scores per head masked to each graph's real nodes, RWSE as
    explicit powers of the dense random-walk matrix.  ``mm`` computes every
    matrix product (QK^T and PV included), so a lower-precision product
    reaches all of it.  Returns (N, out_dim): row k is graph k's output."""
    n, eps = g.num_nodes, BN_EPS
    lin = functools.partial(_lin, mm=mm)
    nm = g.node_mask[:, None].astype(jnp.float32)
    em = g.edge_mask.astype(jnp.float32)
    ids = jnp.arange(n)[None, :]
    to_src = (g.src[:, None] == ids).astype(jnp.float32)  # (E, N)
    to_dst = (g.dst[:, None] == ids).astype(jnp.float32) * em[:, None]
    # RWSE: P = D^-1 A over out-edges, diag(P^k) for k = 1..pe_steps
    a = mm(to_src.T, to_dst)  # a[i, j] = number of real edges i -> j
    p = a / jnp.maximum(a.sum(1, keepdims=True), 1.0)
    walk, rw = p, [jnp.diagonal(p)]
    for _ in range(cfg.pe_steps - 1):
        walk = mm(walk, p)
        rw.append(jnp.diagonal(walk))
    pe = lin(params["pe"], _bn(params["pe_norm"], jnp.stack(rw, -1), eps))
    x = jnp.concatenate([lin(params["atom"], g.node_feat), pe], -1) * nm
    e = lin(params["bond"], g.edge_feat)
    same = (g.graph_id[:, None] == g.graph_id[None, :]) & (nm > 0) & (nm.T > 0)
    h, dh = cfg.heads, cfg.hidden // cfg.heads
    for lp in params["layers"]:
        # GatedGCN with its own residuals, edge state carried
        x_dst, x_src = mm(to_dst, x), mm(to_src, x)
        e_hat = (lin(lp["D"], x_dst) + lin(lp["E"], x_src)
                 + lin(lp["C"], e))
        sig = jax.nn.sigmoid(e_hat)
        num = mm(to_dst.T, sig * lin(lp["B"], x_src))
        den = mm(to_dst.T, sig)
        x_m = lin(lp["A"], x) + num / (den + 1e-6)
        x_m = x + jnp.maximum(_bn(lp["bn_x"], x_m, eps), 0.0)
        e = e + jnp.maximum(_bn(lp["bn_e"], e_hat, eps), 0.0)
        x_m = _bn(lp["norm_local"], x_m, eps)
        # attention within each graph, one head at a time
        qkv = lin(lp["attn"]["qkv"], x)
        heads = []
        for k in range(h):
            q = qkv[:, k * dh:(k + 1) * dh]
            kk = qkv[:, cfg.hidden + k * dh:cfg.hidden + (k + 1) * dh]
            v = qkv[:, 2 * cfg.hidden + k * dh:2 * cfg.hidden + (k + 1) * dh]
            s = jnp.where(same, mm(q, kk.T) / jnp.sqrt(float(dh)), -jnp.inf)
            s = s - jnp.max(jnp.where(same, s, -1e30), axis=1, keepdims=True)
            w = jnp.where(same, jnp.exp(s), 0.0)
            w = w / jnp.maximum(w.sum(1, keepdims=True), 1e-30)
            heads.append(mm(w, v))
        attn = lin(lp["attn"]["out"], jnp.concatenate(heads, -1))
        x_t = _bn(lp["norm_attn"], x + attn, eps)
        s = x_m + x_t
        ff = lin(lp["ff"][1], jnp.maximum(lin(lp["ff"][0], s), 0.0))
        x = _bn(lp["norm_ff"], s + ff, eps) * nm
    gid = jnp.where(g.node_mask, g.graph_id, n)
    onehot = (gid[:, None] == ids).astype(jnp.float32)
    pooled = mm(onehot.T, x) / jnp.maximum(onehot.sum(0)[:, None], 1.0)
    for i, head in enumerate(params["head"]):
        pooled = lin(head, pooled)
        if i < len(params["head"]) - 1:
            pooled = jnp.maximum(pooled, 0.0)
    return pooled


def apply_dense(params, g: Graph, cfg: GNNConfig, eigvec=None) -> jax.Array:
    if cfg.model == "gps":
        return apply_gps_dense(params, g, cfg)
    a = dense_adjacency(g)  # (N,N) in-edges: a[i, j] = j -> i
    nm = g.node_mask[:, None].astype(jnp.float32)
    x = _lin(params["encoder"], g.node_feat) * nm
    vn = None  # (max_graphs, w) per-graph virtual-node state
    if cfg.virtual_node:
        vn = jnp.broadcast_to(params["vn_embed"], (g.num_nodes, x.shape[-1]))

    for li, lp in enumerate(params["layers"]):
        if cfg.virtual_node:
            gid = jnp.clip(g.graph_id, 0, g.num_nodes - 1)
            x = x + jnp.take(vn, gid, axis=0) * nm
        if cfg.model == "gcn":
            deg = a.sum(1) + 1.0
            inv = jax.lax.rsqrt(deg)[:, None]
            xw = _lin(lp["lin"], x)
            xs = xw * inv
            x = (a @ xs + xs) * inv * nm
        elif cfg.model == "gin":
            # recompute per-edge messages densely: for each i, sum_j relu(x_j + e_ij)
            n = g.num_nodes
            e_emb = _lin(lp["edge"], g.edge_feat)
            msg = jax.nn.relu(x[g.src] + e_emb) * g.edge_mask[:, None]
            onehot = (g.dst[:, None] == jnp.arange(n)[None, :]).astype(jnp.float32)
            onehot = onehot * g.edge_mask[:, None]
            agg = onehot.T @ msg
            x = _mlp(lp["mlp"], (1.0 + lp["eps"]) * x + agg) * nm
        elif cfg.model == "gat":
            h, f = cfg.heads, cfg.head_features
            n = g.num_nodes
            xp = _lin(lp["proj"], x).reshape(n, h, f)
            a_src = jnp.einsum("nhf,hf->nh", xp, lp["att_src"])
            a_dst = jnp.einsum("nhf,hf->nh", xp, lp["att_dst"])
            logits = jax.nn.leaky_relu(
                a_src[None, :, :] + a_dst[:, None, :], 0.2
            )  # (dst, src, h)
            mask = (a > 0)[:, :, None]
            # per-edge-INSTANCE softmax (PyG semantics): multi-edges weight
            # the numerator and denominator by their multiplicity a[i,j]
            zmax = jnp.max(jnp.where(mask, logits, -jnp.inf), axis=1, keepdims=True)
            zmax = jnp.where(jnp.isfinite(zmax), zmax, 0.0)
            num = a[:, :, None] * jnp.exp(logits - zmax) * mask
            alpha = num / jnp.maximum(num.sum(axis=1, keepdims=True), 1e-30)
            out = jnp.einsum("ijh,jhf->ihf", alpha, xp).reshape(n, h * f)
            x = jax.nn.elu(out) * nm
        elif cfg.model == "pna":
            n = g.num_nodes
            xp = _lin(lp["pre"], x, act="relu")
            deg = a.sum(1)
            cnt = jnp.maximum(deg, 1.0)[:, None]
            mean = (a @ xp) / cnt
            sq = (a @ (xp * xp)) / cnt
            std = jnp.sqrt(jnp.maximum(sq - mean * mean, 0.0))
            big = jnp.where((a > 0)[:, :, None], xp[None, :, :], -jnp.inf)
            mx = jnp.where(deg[:, None] > 0, jnp.max(big, axis=1), 0.0)
            small = jnp.where((a > 0)[:, :, None], xp[None, :, :], jnp.inf)
            mn = jnp.where(deg[:, None] > 0, jnp.min(small, axis=1), 0.0)
            aggs = jnp.concatenate([mean, std, mx, mn], axis=-1)
            logd = jnp.log(deg + 1.0)
            logdavg = jnp.log(jnp.asarray(cfg.avg_degree) + 1.0)
            amp = (logd / logdavg)[:, None]
            att = jnp.where(deg > 0, logdavg / jnp.maximum(logd, 1e-6), 0.0)[:, None]
            tower = jnp.concatenate([aggs, aggs * amp, aggs * att], axis=-1)
            x = (_lin(lp["post"], tower, act="relu") + x) * nm
        elif cfg.model == "dgn":
            n = g.num_nodes
            # multiplicity-weighted (per-edge-instance) directional weights
            dphi = (eigvec[None, :] - eigvec[:, None]) * a  # [i,j] = phi_j - phi_i
            denom = jnp.abs(dphi).sum(1, keepdims=True)
            w = dphi / jnp.maximum(denom, 1e-6)
            deg = a.sum(1)
            mean = (a @ x) / jnp.maximum(deg, 1.0)[:, None]
            dx = jnp.abs(w @ x - x * w.sum(1, keepdims=True))
            tower = jnp.concatenate([x, mean, dx], axis=-1)
            x = (_lin(lp["post"], tower, act="relu") + x) * nm
        if cfg.virtual_node and li < len(params["layers"]) - 1:
            pooled = _masked_pool(g, x, op="sum")
            vn = _mlp(params["vn_mlp"][li], pooled + vn)

    if cfg.task == "graph":
        pooled = _masked_pool(g, x, op="mean")
        return _mlp(params["head"], pooled)
    return _mlp(params["head"], x)
