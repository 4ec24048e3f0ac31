"""Dense-adjacency reference forward for the GenGNN models, kept with the
benchmark so that no change to the program can move it.

A copy of the program's dense oracle (``gnn/reference.py``'s
``apply_dense``) that imports nothing of the program: a graph is a plain
dict of arrays, the configuration a plain dict, and the matrix product is
a parameter, so that the same code computes the fp32 reference and its
lower-precision control.  Adjacency is materialised as an (N, N) matrix
and every aggregation is a dense matmul or a masked reduction, so it
shares no code path with the served sparse, sorted-segment, kernel-backed
forward.

Graph dict keys: ``node_feat`` (N, F), ``src`` / ``dst`` (E,) int32,
``edge_feat`` (E, D), ``node_mask`` (N,) bool, ``edge_mask`` (E,) bool,
``graph_id`` (N,) int32.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp


def dense_adjacency(g: dict, dt) -> jax.Array:
    """(N, N) with A[dst, src] = 1 for each real edge (in-edge view)."""
    n = g["node_feat"].shape[0]
    a = jnp.zeros((n, n), dt)
    return a.at[g["dst"], g["src"]].add(g["edge_mask"].astype(dt))


def _mlp(mm, ps, x, act="relu", final="none"):
    for i, p in enumerate(ps):
        x = mm(x, p["w"]) + p["b"]
        if i < len(ps) - 1 and act == "relu":
            x = jnp.maximum(x, 0)
        elif (i < len(ps) - 1 and act == "gelu") or (i == len(ps) - 1 and final == "gelu"):
            x = jax.nn.gelu(x)
        elif i == len(ps) - 1 and final == "relu":
            x = jnp.maximum(x, 0)
    return x


def _lin(mm, p, x, act="none"):
    y = mm(x, p["w"]) + p["b"]
    if act == "relu":
        y = jnp.maximum(y, 0)
    elif act == "gelu":
        y = jax.nn.gelu(y)
    return y


def _masked_pool(mm, g: dict, x, op="mean"):
    n = x.shape[0]
    gid = jnp.where(g["node_mask"], g["graph_id"], n)
    onehot = (gid[:, None] == jnp.arange(n)[None, :]).astype(x.dtype)
    total = mm(onehot.T, x)
    if op == "sum":
        return total
    count = onehot.sum(0)[:, None]
    return total / jnp.maximum(count, 1.0)


def apply_dense(params, g: dict, cfg: dict, eigvec=None, mm=jnp.matmul):
    """Forward pass of ``cfg["model"]`` over one padded graph (or several
    packed ones, told apart by ``graph_id``).  Returns (N, out_dim): row k
    is graph k's output for a graph task, node k's for a node task.

    Parameters and inputs are cast to fp32.  ``mm`` computes every matrix
    product (``jnp.matmul``; a lower-precision product makes the control)."""
    dt = jnp.dtype(jnp.float32)
    params = jax.tree.map(lambda v: jnp.asarray(v, dt), params)
    g = dict(g, node_feat=g["node_feat"].astype(dt), edge_feat=g["edge_feat"].astype(dt))
    if eigvec is not None:
        eigvec = eigvec.astype(dt)
    model = cfg["model"]
    a = dense_adjacency(g, dt)  # (N,N) in-edges: a[i, j] = j -> i
    nm = g["node_mask"][:, None].astype(dt)
    src, dst, em = g["src"], g["dst"], g["edge_mask"]
    n = g["node_feat"].shape[0]
    x = _lin(mm, params["encoder"], g["node_feat"]) * nm
    vn = None  # (max_graphs, w) per-graph virtual-node state
    if cfg.get("virtual_node"):
        vn = jnp.broadcast_to(params["vn_embed"], (n, x.shape[-1]))

    for li, lp in enumerate(params["layers"]):
        if cfg.get("virtual_node"):
            gid = jnp.clip(g["graph_id"], 0, n - 1)
            x = x + jnp.take(vn, gid, axis=0) * nm
        if model == "gcn":
            deg = a.sum(1) + 1.0
            inv = jax.lax.rsqrt(deg)[:, None]
            xs = _lin(mm, lp["lin"], x) * inv
            x = (mm(a, xs) + xs) * inv * nm
        elif model == "gin":
            # per-edge messages, densely: for each i, sum_j relu(x_j + e_ij)
            e_emb = _lin(mm, lp["edge"], g["edge_feat"])
            msg = jax.nn.relu(x[src] + e_emb) * em[:, None].astype(dt)
            onehot = (dst[:, None] == jnp.arange(n)[None, :]).astype(dt)
            onehot = onehot * em[:, None].astype(dt)
            agg = mm(onehot.T, msg)
            x = _mlp(mm, lp["mlp"], (1.0 + lp["eps"]) * x + agg) * nm
        elif model == "gat":
            h, f = cfg["heads"], cfg["head_features"]
            xp = _lin(mm, lp["proj"], x).reshape(n, h, f)
            a_src = jnp.einsum("nhf,hf->nh", xp, lp["att_src"])
            a_dst = jnp.einsum("nhf,hf->nh", xp, lp["att_dst"])
            logits = jax.nn.leaky_relu(a_src[None, :, :] + a_dst[:, None, :], 0.2)
            mask = (a > 0)[:, :, None]
            # per-edge-instance softmax: multi-edges weight the numerator
            # and the denominator by their multiplicity a[i, j]
            zmax = jnp.max(jnp.where(mask, logits, -jnp.inf), axis=1, keepdims=True)
            zmax = jnp.where(jnp.isfinite(zmax), zmax, 0.0)
            num = a[:, :, None] * jnp.exp(logits - zmax) * mask
            alpha = num / jnp.maximum(num.sum(axis=1, keepdims=True), 1e-30)
            out = jnp.einsum("ijh,jhf->ihf", alpha, xp).reshape(n, h * f)
            x = jax.nn.elu(out) * nm
        elif model == "pna":
            xp = _lin(mm, lp["pre"], x, act="relu")
            deg = a.sum(1)
            cnt = jnp.maximum(deg, 1.0)[:, None]
            mean = mm(a, xp) / cnt
            sq = mm(a, xp * xp) / cnt
            std = jnp.sqrt(jnp.maximum(sq - mean * mean, 0.0))
            big = jnp.where((a > 0)[:, :, None], xp[None, :, :], -jnp.inf)
            mx = jnp.where(deg[:, None] > 0, jnp.max(big, axis=1), 0.0)
            small = jnp.where((a > 0)[:, :, None], xp[None, :, :], jnp.inf)
            mn = jnp.where(deg[:, None] > 0, jnp.min(small, axis=1), 0.0)
            aggs = jnp.concatenate([mean, std, mx, mn], axis=-1)
            logd = jnp.log(deg + 1.0)
            logdavg = jnp.log(jnp.asarray(cfg["avg_degree"], dt) + 1.0)
            amp = (logd / logdavg)[:, None]
            att = jnp.where(deg > 0, logdavg / jnp.maximum(logd, 1e-6), 0.0)[:, None]
            tower = jnp.concatenate([aggs, aggs * amp, aggs * att], axis=-1)
            x = (_lin(mm, lp["post"], tower, act="relu") + x) * nm
        elif model == "dgn":
            # multiplicity-weighted (per-edge-instance) directional weights
            dphi = (eigvec[None, :] - eigvec[:, None]) * a  # [i,j] = phi_j - phi_i
            denom = jnp.abs(dphi).sum(1, keepdims=True)
            w = dphi / jnp.maximum(denom, 1e-6)
            deg = a.sum(1)
            mean = mm(a, x) / jnp.maximum(deg, 1.0)[:, None]
            dx = jnp.abs(mm(w, x) - x * w.sum(1, keepdims=True))
            tower = jnp.concatenate([x, mean, dx], axis=-1)
            x = (_lin(mm, lp["post"], tower, act="relu") + x) * nm
        else:
            raise ValueError(f"unknown model {model!r}")
        if cfg.get("virtual_node") and li < len(params["layers"]) - 1:
            pooled = _masked_pool(mm, g, x, op="sum")
            vn = _mlp(mm, params["vn_mlp"][li], pooled + vn)

    if cfg.get("task", "graph") == "graph":
        return _mlp(mm, params["head"], _masked_pool(mm, g, x, op="mean"))
    return _mlp(mm, params["head"], x)
