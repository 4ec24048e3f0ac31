"""GraphGPS (arXiv:2205.12454, ogbg-molpcba) as the benchmark runs it.

Beside ``gps.json`` (the sizes): the weights, made on the device from the
seed in the layout the program's ``Executor.register`` takes; the plain
reference forward (``gps_reference.py``, RWSE included, so no per-molecule
host input); and the operations and bytes one forward needs, counted from
the sizes alone, whichever kernels run it, with the same two counts for
the attention kernel alone.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from gps_reference import apply_gps

# centre of each BatchNorm's seeded running variance: the variance its
# input has when the layers see unit-scale input (norm_ff's chosen so that
# the residual stream keeps unit scale through the layers)
BN_VAR = {"pe_norm": 0.03, "bn_x": 1.7, "bn_e": 2.1, "norm_local": 2.2,
          "norm_attn": 1.2, "norm_ff": 4.5}
NORMS = ("bn_x", "bn_e", "norm_local", "norm_attn", "norm_ff")


class _Draws:
    """Every random number of the weights from two draws, one normal and
    one uniform, handed out in order: a program of two random ops, which
    compiles in a fraction of the time ~170 separate draws take."""

    def __init__(self, key, normals: int, uniforms: int):
        kn, ku = jax.random.split(key)
        self._pools = {"normal": jax.random.normal(kn, (normals,), jnp.float32),
                       "uniform": jax.random.uniform(ku, (uniforms,), jnp.float32)}
        self._used = {"normal": 0, "uniform": 0}

    def take(self, kind: str, shape: tuple):
        n = math.prod(shape)
        at = self._used[kind]
        self._used[kind] = at + n
        return self._pools[kind][at:at + n].reshape(shape)


def _linear(draws, d_in: int, d_out: int, scale=None) -> dict:
    scale = math.sqrt(2.0 / (d_in + d_out)) if scale is None else scale
    return {"w": draws.take("normal", (d_in, d_out)) * scale,
            "b": 0.1 * draws.take("normal", (d_out,))}


def _encoder(draws, d_in: int, d_out: int) -> dict:
    """Unit-scale embedding of unit-scale inputs."""
    return _linear(draws, d_in, d_out, scale=1.0 / math.sqrt(d_in))


def _norm(draws, dim: int, var: float) -> dict:
    """Inference BatchNorm: random running mean, variance within 4/3 of
    ``var``, scale and shift, so that no norm is the identity."""
    u = draws.take("uniform", (dim,))
    return {"mean": 0.1 * draws.take("normal", (dim,)),
            "var": var * (0.75 + (4.0 / 3.0 - 0.75) * u),
            "gamma": 1.0 + 0.1 * draws.take("normal", (dim,)),
            "beta": 0.1 * draws.take("normal", (dim,))}


def init_params(key, cfg: dict) -> dict:
    """Random fp32 weights: glorot-scaled matrices in the layers and the
    head, unit-scale encoders, small random biases, seeded BatchNorm
    statistics.  Call it under ``jax.jit``: one device program makes every
    leaf."""
    w, f, layers = cfg["hidden"], cfg["ffn_hidden"], cfg["num_layers"]
    norms = cfg["pe_steps"] + len(NORMS) * w * layers  # one variance each
    draws = _Draws(key, param_count(cfg) - norms, norms)
    params = {
        "atom": _encoder(draws, cfg["feat_dim"], w - cfg["pe_dim"]),
        "pe_norm": _norm(draws, cfg["pe_steps"], BN_VAR["pe_norm"]),
        "pe": _encoder(draws, cfg["pe_steps"], cfg["pe_dim"]),
        "bond": _encoder(draws, cfg["edge_dim"], w),
        "layers": [],
    }
    for _ in range(layers):
        lp = {k: _linear(draws, w, w) for k in "ABCDE"}
        for k in NORMS:
            lp[k] = _norm(draws, w, BN_VAR[k])
        lp["attn"] = {"qkv": _linear(draws, w, 3 * w), "out": _linear(draws, w, w)}
        lp["ff"] = [_linear(draws, w, f), _linear(draws, f, w)]
        params["layers"].append(lp)
    sizes = (w,) + tuple(cfg["head_hidden"]) + (cfg["out_dim"],)
    params["head"] = [_linear(draws, a, b) for a, b in zip(sizes[:-1], sizes[1:])]
    return params


def reference(params, graph: dict, cfg: dict, eigvec=None, mm=jnp.matmul):
    """The reference forward of one padded molecule: its (out_dim,) output."""
    return apply_gps(params, graph, cfg, mm=mm)[0]


def host_inputs(senders, receivers, num_nodes: int):
    """GPS computes its RWSE from the graph inside the program: no
    per-molecule host input."""
    return None


def param_count(cfg: dict) -> int:
    w, f, pe, steps = cfg["hidden"], cfg["ffn_hidden"], cfg["pe_dim"], cfg["pe_steps"]
    enc = ((cfg["feat_dim"] + 1) * (w - pe) + 4 * steps + (steps + 1) * pe
           + (cfg["edge_dim"] + 1) * w)
    layer = (5 * (w * w + w) + 5 * 4 * w  # A..E, five norms
             + (w * 3 * w + 3 * w) + (w * w + w)  # qkv, out
             + (w * f + f) + (f * w + w))  # feed-forward
    sizes = (w,) + tuple(cfg["head_hidden"]) + (cfg["out_dim"],)
    head = sum(a * b + b for a, b in zip(sizes[:-1], sizes[1:]))
    return enc + cfg["num_layers"] * layer + head


def _pairs_flops(pairs: float, cfg: dict) -> float:
    """One layer's attention over ``pairs`` query-key pairs, all heads:
    QK^T and PV (2 x 2 per pair and feature), the softmax's max, exp and
    sum (3 per pair and head) and its division (1 per row and feature is
    left with the rows)."""
    return pairs * (4 * cfg["hidden"] + 3 * cfg["heads"])


def attention_flops(rows: int, cfg: dict) -> int:
    """Operations of the attention kernel over ``rows`` node rows as one
    dense attention, every layer: what the kernel would run with no block
    skipped (its segment skip runs fewer)."""
    return int(cfg["num_layers"] * (_pairs_flops(rows * rows, cfg)
                                    + rows * cfg["hidden"]))


def attention_bytes(rows: int, cfg: dict) -> int:
    """The fewest bytes the attention kernel must move over ``rows`` rows,
    every layer: fp32 queries, keys and values read, the output written,
    and the int32 segment ids."""
    return cfg["num_layers"] * (4 * rows * cfg["hidden"] * 4 + rows * 4)


def forward_flops(nodes: int, edges: int, graphs: int, cfg: dict) -> int:
    """Operations of one forward over ``nodes`` node rows, ``edges`` edge
    rows and ``graphs`` graph slots: the RWSE's walk-matrix powers within
    each graph, the encoders, per layer the gated edge update and sums,
    the attention within each graph, the feed-forward and the norms; the
    mean pool and the head.  Per-graph terms (the walk powers, the
    attention pairs) take the nodes as split evenly over the graphs: the
    least any split needs."""
    w, f, h = cfg["hidden"], cfg["ffn_hidden"], cfg["heads"]
    steps, pe = cfg["pe_steps"], cfg["pe_dim"]
    per_graph = nodes / graphs
    walk = graphs * (steps - 1) * 2 * per_graph ** 3 + edges
    enc = (nodes * (2 * cfg["feat_dim"] * (w - pe) + (w - pe))
           + nodes * (2 * steps + 2 * steps * pe + pe)
           + edges * (2 * cfg["edge_dim"] * w + w))
    local = (nodes * (2 * w * 4 * w + 4 * w)  # A, B, D, E
             + edges * (2 * w * w + w)  # C
             + edges * 6 * w  # two adds, sigmoid, gate, the two sums
             + nodes * 9 * w)  # eps, division, A x + agg, norm, relu, residual, norm
    edge_state = edges * 4 * w  # norm, relu, residual
    attn = (nodes * (2 * w * 3 * w + 3 * w) + _pairs_flops(nodes * per_graph, cfg)
            + nodes * w + nodes * (2 * w * w + w) + nodes * 3 * w)
    ffn = nodes * (2 * w * f + f + f + 2 * f * w + w + w + 2 * w + w)  # s, +, norm
    pool = nodes * w + graphs * w
    sizes = (w,) + tuple(cfg["head_hidden"]) + (cfg["out_dim"],)
    head = sum(2 * a * b + b for a, b in zip(sizes[:-1], sizes[1:]))
    layer = local + edge_state + attn + ffn
    return int(walk + enc + cfg["num_layers"] * layer + pool + graphs * head)


def forward_bytes(nodes: int, edges: int, graphs: int, cfg: dict) -> int:
    """The fewest bytes one forward must move to and from device memory:
    the fp32 weights once, the graph (fp32 node and edge features, int32
    edge ends) and the fp32 outputs.  Activations and the edge state are
    left out, as a kernel could keep them on chip."""
    graph = nodes * cfg["feat_dim"] * 4 + edges * (cfg["edge_dim"] * 4 + 8)
    return param_count(cfg) * 4 + graph + graphs * cfg["out_dim"] * 4
