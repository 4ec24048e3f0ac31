"""Shared building blocks for the GNN model library (the paper's 'rich
library of model-specific components', §4).

Parameters are plain nested dicts of jnp arrays (pytree-native).  Every
dense transform routes through ``kernels.ops.node_mlp`` so the NE PE
kernel/reference dispatch is uniform across models.
"""
from __future__ import annotations

from typing import Sequence

import jax
import jax.numpy as jnp

from repro.kernels import ops
from repro.quant import observers as qobs
from repro.quant import qconfig as qc


def glorot(rng, shape, dtype=jnp.float32):
    fan_in, fan_out = shape[0], shape[-1]
    scale = jnp.sqrt(2.0 / (fan_in + fan_out))
    return jax.random.normal(rng, shape, dtype) * scale


def linear_init(rng, d_in: int, d_out: int) -> dict:
    kw, _ = jax.random.split(rng)
    return {"w": glorot(kw, (d_in, d_out)), "b": jnp.zeros((d_out,))}


def linear_apply(p, x: jax.Array, activation: str = "none", mode: str = "auto"):
    """Dense transform through the NE PE.  ``p`` is either a plain
    ``{"w", "b"}`` dict (fp32 path) or a ``quant.QuantizedLinear`` (int8 /
    ap_fixed path) — the quantization transform swaps nodes in the param
    tree and every model picks the right kernel here."""
    if isinstance(p, qc.QuantizedLinear):
        return qc.quantized_linear(p, x, activation=activation, mode=mode)
    qobs.observe_linear_input(p, x)  # no-op outside quant calibration
    return ops.node_mlp(x, p["w"], p["b"], activation=activation, mode=mode)


def fused_linear_operands(p):
    """A linear layer's operand form for the fused megakernel, or ``None``.

    The megakernel's gamma matmul supports exactly two parameterizations:
    plain fp32 weights, and int8 *dynamic* W8A8 (per-row activation
    scales computed inside the kernel — no calibration state).  Returns

      {"kind": "fp32", "w", "b"}                      plain ``{"w","b"}``
      {"kind": "int8", "w_q", "w_scale", "b"}         int8-dynamic

    and ``None`` for everything else (int8-static needs calibrated
    affine activation params, "fixed" needs grid snapping on both sides
    — neither folds into the kernel's requant tail), which tells the
    layer body to fall back to the unfused closure path even when the
    engine asked for fusion.
    """
    if isinstance(p, qc.QuantizedLinear):
        if p.scheme == "int8" and p.act_mode == "dynamic":
            return {
                "kind": "int8",
                "w_q": p.w_q,
                "w_scale": jnp.broadcast_to(
                    jnp.asarray(p.w_scale, jnp.float32), (p.w_q.shape[1],)
                ),
                "b": p.b,
            }
        return None
    return {"kind": "fp32", "w": p["w"], "b": p["b"]}


def fused_dequant_weights(p):
    """f32 ``(w, b)`` view of a linear layer, or ``None`` if not expressible.

    Weight-only dequantization for the fused path's *auxiliary* linears
    (GIN's tiny edge embedding, GIN's second MLP layer): re-quantizing
    their activations inside the fused pass costs more than the matmuls
    themselves, so int8-dynamic weights run as dequantized f32 there.
    int8-static / "fixed" return ``None`` (same opt-out as
    :func:`fused_linear_operands`).
    """
    if isinstance(p, qc.QuantizedLinear):
        if p.scheme == "int8" and p.act_mode == "dynamic":
            return qc.dequantize_int8(p.w_q, p.w_scale), p.b
        return None
    return p["w"], p["b"]


def mlp_init(rng, sizes: Sequence[int]) -> list:
    """sizes = (d_in, h1, ..., d_out)."""
    keys = jax.random.split(rng, len(sizes) - 1)
    return [linear_init(k, a, b) for k, a, b in zip(keys, sizes[:-1], sizes[1:])]


def mlp_apply(ps: list, x: jax.Array, activation: str = "relu", mode: str = "auto",
              final_activation: str = "none"):
    """The paper's MLP PE: pipelined linear->act chain with fused tails."""
    for i, p in enumerate(ps):
        act = activation if i < len(ps) - 1 else final_activation
        x = linear_apply(p, x, activation=act, mode=mode)
    return x


def batch_norm_init(dim: int) -> dict:
    """Inference-mode batch norm (folded scale/shift), as the HLS code bakes
    trained BN constants into the bitstream."""
    return {"scale": jnp.ones((dim,)), "shift": jnp.zeros((dim,))}


def batch_norm_apply(p: dict, x: jax.Array) -> jax.Array:
    return x * p["scale"] + p["shift"]


def batch_norm_stats_init(rng, dim: int, var_scale: float = 1.0) -> dict:
    """Inference batch norm from running statistics (GraphGPS's
    ``BatchNorm1d`` at eval): seeded random running mean and variance,
    scale and shift, so that no normalization is the identity by
    accident.  The variance is drawn within a factor 4/3 of
    ``var_scale``, the variance the norm's input has in a network whose
    layers see unit-scale input, as a trained model's running statistics
    would be."""
    km, kv, kg, kb = jax.random.split(rng, 4)
    return {"mean": 0.1 * jax.random.normal(km, (dim,)),
            "var": var_scale * jax.random.uniform(kv, (dim,), minval=0.75,
                                                  maxval=4.0 / 3.0),
            "gamma": 1.0 + 0.1 * jax.random.normal(kg, (dim,)),
            "beta": 0.1 * jax.random.normal(kb, (dim,))}


BN_EPS = 1e-5  # PyTorch BatchNorm1d's default, as GraphGPS runs it


def batch_norm_fold(p: dict, eps: float = BN_EPS) -> jax.Array:
    """``(x - mean) / sqrt(var + eps) * gamma + beta`` as one per-feature
    affine folded from the running statistics: a (2, dim) array of the
    scale and the shift, applied by :func:`affine_apply`."""
    scale = p["gamma"] * jax.lax.rsqrt(p["var"] + eps)
    return jnp.stack([scale, p["beta"] - p["mean"] * scale])


def affine_apply(st: jax.Array, x: jax.Array) -> jax.Array:
    """``x * scale + shift`` for a folded ``(2, dim)`` affine."""
    return x * st[0] + st[1]


def segment_attention(qkv: jax.Array, g, heads: int,
                      mode: str = "auto") -> jax.Array:
    """Multi-head self-attention of each graph of a (packed) ``Graph``
    over its own nodes, from the projected ``qkv`` (N, 3W: queries, keys
    and values, heads split contiguously); returns the heads side by side
    (N, W), before the output projection.  The attention is
    ``kernels/flash_attention`` with the graph ids as segment ids; padded
    node rows attend nowhere and output 0.  Its Pallas op is named
    ``gps_attention`` in the program and the device trace."""
    n, w = qkv.shape[0], qkv.shape[1] // 3
    # one head-major copy of all three: (3, heads, N, features)
    t = qkv.reshape(n, 3, heads, w // heads).transpose(1, 2, 0, 3)
    q, k, v = t[0:1], t[1:2], t[2:3]
    seg = jnp.where(g.node_mask, g.graph_id, -1).astype(jnp.int32)[None]
    out = ops.flash_attention(q, k, v, causal=False, mode=mode,
                              segment_ids=seg, name="gps_attention")
    return out[0].transpose(1, 0, 2).reshape(n, w)
