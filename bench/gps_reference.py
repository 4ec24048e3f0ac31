"""Plain reference forward of GraphGPS, kept with the benchmark so that no
change to the program can move it.

A copy of the program's ``gnn/reference.py`` ``apply_gps_dense`` that
imports nothing of the program: a graph is a plain dict of arrays (the
keys of ``dense_reference.py``), the configuration a plain dict, and the
matrix product is a parameter, so that the same code computes the fp32
reference and its lower-precision control.  Per-edge gathers and the
gated sums are one-hot matrix products, the attention is dense (N, N)
scores per head masked to each graph's real nodes, and the RWSE is
explicit powers of the dense random-walk matrix: it shares no code path
with the served forward (sorted segments, Pallas kernels, packed flushes).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

BN_EPS = 1e-5  # PyTorch BatchNorm1d's default, as GraphGPS runs it


def _lin(mm, p, x):
    return mm(x, p["w"]) + p["b"]


def _bn(p, x, eps):
    return (x - p["mean"]) / jnp.sqrt(p["var"] + eps) * p["gamma"] + p["beta"]


def apply_gps(params, g: dict, cfg: dict, mm=jnp.matmul):
    """GraphGPS over one padded graph (or several packed ones, told apart
    by ``graph_id``).  Returns (N, out_dim): row k is graph k's output.
    ``mm`` computes every matrix product, QK^T and PV included."""
    dt = jnp.dtype(jnp.float32)
    params = jax.tree.map(lambda v: jnp.asarray(v, dt), params)
    n, eps = g["node_feat"].shape[0], BN_EPS
    nm = g["node_mask"][:, None].astype(dt)
    em = g["edge_mask"].astype(dt)
    ids = jnp.arange(n)[None, :]
    to_src = (g["src"][:, None] == ids).astype(dt)  # (E, N)
    to_dst = (g["dst"][:, None] == ids).astype(dt) * em[:, None]
    # RWSE: P = D^-1 A over out-edges, diag(P^k) for k = 1..pe_steps
    a = mm(to_src.T, to_dst)  # a[i, j] = number of real edges i -> j
    p = a / jnp.maximum(a.sum(1, keepdims=True), 1.0)
    walk, rw = p, [jnp.diagonal(p)]
    for _ in range(cfg["pe_steps"] - 1):
        walk = mm(walk, p)
        rw.append(jnp.diagonal(walk))
    pe = _lin(mm, params["pe"], _bn(params["pe_norm"], jnp.stack(rw, -1), eps))
    x = jnp.concatenate([_lin(mm, params["atom"], g["node_feat"].astype(dt)), pe],
                        -1) * nm
    e = _lin(mm, params["bond"], g["edge_feat"].astype(dt))
    gid = g["graph_id"]
    same = (gid[:, None] == gid[None, :]) & (nm > 0) & (nm.T > 0)
    w = cfg["hidden"]
    h = cfg["heads"]
    dh = w // h
    for lp in params["layers"]:
        # GatedGCN with its own residuals, edge state carried
        x_dst, x_src = mm(to_dst, x), mm(to_src, x)
        e_hat = (_lin(mm, lp["D"], x_dst) + _lin(mm, lp["E"], x_src)
                 + _lin(mm, lp["C"], e))
        sig = jax.nn.sigmoid(e_hat)
        num = mm(to_dst.T, sig * _lin(mm, lp["B"], x_src))
        den = mm(to_dst.T, sig)
        x_m = _lin(mm, lp["A"], x) + num / (den + 1e-6)
        x_m = x + jnp.maximum(_bn(lp["bn_x"], x_m, eps), 0.0)
        e = e + jnp.maximum(_bn(lp["bn_e"], e_hat, eps), 0.0)
        x_m = _bn(lp["norm_local"], x_m, eps)
        # attention within each graph, one head at a time
        qkv = _lin(mm, lp["attn"]["qkv"], x)
        heads = []
        for k in range(h):
            q = qkv[:, k * dh:(k + 1) * dh]
            kk = qkv[:, w + k * dh:w + (k + 1) * dh]
            v = qkv[:, 2 * w + k * dh:2 * w + (k + 1) * dh]
            s = jnp.where(same, mm(q, kk.T) / jnp.sqrt(float(dh)), -jnp.inf)
            s = s - jnp.max(jnp.where(same, s, -1e30), axis=1, keepdims=True)
            a_k = jnp.where(same, jnp.exp(s), 0.0)
            a_k = a_k / jnp.maximum(a_k.sum(1, keepdims=True), 1e-30)
            heads.append(mm(a_k, v))
        attn = _lin(mm, lp["attn"]["out"], jnp.concatenate(heads, -1))
        x_t = _bn(lp["norm_attn"], x + attn, eps)
        s = x_m + x_t
        ff = _lin(mm, lp["ff"][1], jnp.maximum(_lin(mm, lp["ff"][0], s), 0.0))
        x = _bn(lp["norm_ff"], s + ff, eps) * nm
    gid = jnp.where(g["node_mask"], gid, n)
    onehot = (gid[:, None] == ids).astype(dt)
    pooled = mm(onehot.T, x) / jnp.maximum(onehot.sum(0)[:, None], 1.0)
    for i, lin in enumerate(params["head"]):
        pooled = _lin(mm, lin, pooled)
        if i < len(params["head"]) - 1:
            pooled = jnp.maximum(pooled, 0.0)
    return pooled
