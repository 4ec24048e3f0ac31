"""GIN (GenGNN, arXiv:2201.08475, section 5.1) as the benchmark runs it.

Beside ``gin.json`` (the sizes): the weights, made on the device from the
seed in the layout the program's ``Executor.register`` takes; the plain
reference forward; and the operations and bytes one forward needs, counted
from the sizes alone, whichever kernels run it.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from dense_reference import apply_dense


def _linear(key, d_in: int, d_out: int) -> dict:
    kw, kb = jax.random.split(key)
    scale = jnp.sqrt(2.0 / (d_in + d_out))
    return {"w": jax.random.normal(kw, (d_in, d_out), jnp.float32) * scale,
            "b": 0.1 * jax.random.normal(kb, (d_out,), jnp.float32)}


def init_params(key, cfg: dict) -> dict:
    """Random fp32 weights: glorot-scaled matrices, small random biases and
    a random GIN epsilon per layer (so that no term is zero by accident).
    Call it under ``jax.jit``: one device program makes every leaf."""
    w, layers = cfg["hidden"], cfg["num_layers"]
    keys = iter(jax.random.split(key, 2 + 4 * layers))
    params = {"encoder": _linear(next(keys), cfg["feat_dim"], w), "layers": []}
    for _ in range(layers):
        params["layers"].append({
            "edge": _linear(next(keys), cfg["edge_dim"], w),
            "eps": 0.1 * jax.random.normal(next(keys), (), jnp.float32),
            "mlp": [_linear(next(keys), w, 2 * w), _linear(next(keys), 2 * w, w)],
        })
    sizes = (w,) + tuple(cfg["head_hidden"]) + (cfg["out_dim"],)
    hk = jax.random.split(next(keys), len(sizes) - 1)
    params["head"] = [_linear(k, a, b) for k, a, b in zip(hk, sizes[:-1], sizes[1:])]
    return params


def reference(params, graph: dict, cfg: dict, eigvec=None, mm=jnp.matmul):
    """The reference forward of one padded molecule: its (out_dim,) output."""
    return apply_dense(params, graph, cfg, mm=mm)[0]


def host_inputs(senders, receivers, num_nodes: int):
    """GIN needs no per-molecule input besides the graph."""
    return None


def _head_flops(cfg: dict) -> int:
    sizes = (cfg["hidden"],) + tuple(cfg["head_hidden"]) + (cfg["out_dim"],)
    return sum(2 * a * b + b for a, b in zip(sizes[:-1], sizes[1:]))


def forward_flops(nodes: int, edges: int, graphs: int, cfg: dict) -> int:
    """Operations of one forward over ``nodes`` node rows, ``edges`` edge
    rows and ``graphs`` graph slots: every multiply and add of the
    linears, the messages, the sum aggregation and the mean pool."""
    w, f, d = cfg["hidden"], cfg["feat_dim"], cfg["edge_dim"]
    enc = nodes * (2 * f * w + w)
    layer = (edges * (2 * d * w + w)  # edge embedding
             + edges * 2 * w  # message x_src + e, relu
             + edges * w  # sum over in-edges
             + nodes * 2 * w  # (1 + eps) x + agg
             + nodes * (2 * w * 2 * w + 2 * w + 2 * w)  # linear 1, bias, relu
             + nodes * (2 * 2 * w * w + w))  # linear 2, bias
    pool = nodes * w + graphs * w
    return enc + cfg["num_layers"] * layer + pool + graphs * _head_flops(cfg)


def param_count(cfg: dict) -> int:
    w, f, d = cfg["hidden"], cfg["feat_dim"], cfg["edge_dim"]
    layer = (d * w + w) + 1 + (w * 2 * w + 2 * w) + (2 * w * w + w)
    sizes = (w,) + tuple(cfg["head_hidden"]) + (cfg["out_dim"],)
    head = sum(a * b + b for a, b in zip(sizes[:-1], sizes[1:]))
    return (f * w + w) + cfg["num_layers"] * layer + head


def forward_bytes(nodes: int, edges: int, graphs: int, cfg: dict) -> int:
    """The fewest bytes one forward must move to and from device memory:
    the fp32 weights once, the graph (fp32 node and edge features, int32
    edge ends) and the fp32 outputs.  Activations are left out, as a
    kernel could keep them on chip."""
    graph = nodes * cfg["feat_dim"] * 4 + edges * (cfg["edge_dim"] * 4 + 8)
    return param_count(cfg) * 4 + graph + graphs * cfg["out_dim"] * 4
