"""DGN (GenGNN, arXiv:2201.08475, section 5.1) as the benchmark runs it.

Beside ``dgn.json`` (the sizes): the weights, made on the device from the
seed in the layout the program's ``Executor.register`` takes; the plain
reference forward with its own Laplacian eigenvector; and the operations
and bytes one forward needs, counted from the sizes alone.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from dense_reference import apply_dense


def _linear(key, d_in: int, d_out: int) -> dict:
    kw, kb = jax.random.split(key)
    scale = jnp.sqrt(2.0 / (d_in + d_out))
    return {"w": jax.random.normal(kw, (d_in, d_out), jnp.float32) * scale,
            "b": 0.1 * jax.random.normal(kb, (d_out,), jnp.float32)}


def init_params(key, cfg: dict) -> dict:
    """Random fp32 weights: glorot-scaled matrices and small random biases.
    Call it under ``jax.jit``: one device program makes every leaf."""
    w, layers = cfg["hidden"], cfg["num_layers"]
    keys = iter(jax.random.split(key, 2 + layers))
    params = {"encoder": _linear(next(keys), cfg["feat_dim"], w), "layers": []}
    for _ in range(layers):
        params["layers"].append({"post": _linear(next(keys), 3 * w, w)})
    sizes = (w,) + tuple(cfg["head_hidden"]) + (cfg["out_dim"],)
    hk = jax.random.split(next(keys), len(sizes) - 1)
    params["head"] = [_linear(k, a, b) for k, a, b in zip(hk, sizes[:-1], sizes[1:])]
    return params


def host_inputs(senders, receivers, num_nodes: int) -> np.ndarray:
    """The molecule's Fiedler vector (eigenvector of the second-smallest
    eigenvalue of the graph Laplacian, edges made undirected and counted
    once), the direction DGN's derivative aggregator follows."""
    a = np.zeros((num_nodes, num_nodes))
    a[np.asarray(receivers), np.asarray(senders)] = 1.0
    a = np.maximum(a, a.T)
    lap = np.diag(a.sum(axis=1)) - a
    return np.linalg.eigh(lap)[1][:, 1]


def reference(params, graph: dict, cfg: dict, eigvec=None, mm=jnp.matmul):
    """The reference forward of one padded molecule: its (out_dim,) output.
    ``eigvec`` is :func:`host_inputs`' vector, zero-padded to the rows."""
    return apply_dense(params, graph, cfg, eigvec=eigvec, mm=mm)[0]


def _head_flops(cfg: dict) -> int:
    sizes = (cfg["hidden"],) + tuple(cfg["head_hidden"]) + (cfg["out_dim"],)
    return sum(2 * a * b + b for a, b in zip(sizes[:-1], sizes[1:]))


def forward_flops(nodes: int, edges: int, graphs: int, cfg: dict) -> int:
    """Operations of one forward over ``nodes`` node rows, ``edges`` edge
    rows and ``graphs`` graph slots: the directional weights once, then per
    layer the mean and the eigenvector-weighted sums, the derivative, the
    post linear with its bias, relu and skip; the mean pool and the head."""
    w, f = cfg["hidden"], cfg["feat_dim"]
    enc = nodes * (2 * f * w + w)
    weights = edges * 4 + nodes * 2  # phi_j - phi_i, |.|, sums, division
    layer = (edges * w + nodes * w  # mean over in-edges
             + edges * 2 * w  # eigenvector-weighted sum
             + nodes * 3 * w  # |wx - x * sum w|
             + nodes * (2 * 3 * w * w + 3 * w))  # post linear, bias, relu, skip
    pool = nodes * w + graphs * w
    return enc + weights + cfg["num_layers"] * layer + pool + graphs * _head_flops(cfg)


def param_count(cfg: dict) -> int:
    w, f = cfg["hidden"], cfg["feat_dim"]
    sizes = (w,) + tuple(cfg["head_hidden"]) + (cfg["out_dim"],)
    head = sum(a * b + b for a, b in zip(sizes[:-1], sizes[1:]))
    return (f * w + w) + cfg["num_layers"] * (3 * w * w + w) + head


def forward_bytes(nodes: int, edges: int, graphs: int, cfg: dict) -> int:
    """The fewest bytes one forward must move to and from device memory:
    the fp32 weights once, the graph (fp32 node and edge features, int32
    edge ends), the fp32 eigenvector and the fp32 outputs."""
    graph = (nodes * (cfg["feat_dim"] * 4 + 4)
             + edges * (cfg["edge_dim"] * 4 + 8))
    return param_count(cfg) * 4 + graph + graphs * cfg["out_dim"] * 4
