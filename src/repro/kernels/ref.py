"""Pure-jnp oracles for every Pallas kernel in this package.

Each ``*_ref`` function defines the exact semantics its kernel must match;
tests sweep shapes/dtypes and assert allclose(kernel(interpret=True), ref).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp


def segment_reduce_sorted_ref(
    values: jax.Array, segment_ids: jax.Array, num_segments: int, op: str = "sum"
) -> jax.Array:
    """Segment reduction over *sorted* segment ids (CSR/CSC edge order).

    values: (E, F) f32/bf16; segment_ids: (E,) int32 non-decreasing, with
    out-of-range ids (>= num_segments) acting as padding.  Empty segments
    produce 0 for every op.
    """
    valid = segment_ids < num_segments
    ids = jnp.where(valid, segment_ids, num_segments)
    v = jnp.where(valid[:, None], values, 0.0).astype(jnp.float32)
    kw = dict(num_segments=num_segments + 1, indices_are_sorted=True)
    count = jax.ops.segment_sum(valid.astype(jnp.float32), ids, **kw)[:-1, None]
    if op == "sum":
        out = jax.ops.segment_sum(v, ids, **kw)[:-1]
    elif op == "mean":
        out = jax.ops.segment_sum(v, ids, **kw)[:-1] / jnp.maximum(count, 1.0)
    elif op == "sqsum":
        out = jax.ops.segment_sum(v * v, ids, **kw)[:-1]
    elif op in ("max", "min"):
        fill = -jnp.inf if op == "max" else jnp.inf
        vm = jnp.where(valid[:, None], values.astype(jnp.float32), fill)
        fn = jax.ops.segment_max if op == "max" else jax.ops.segment_min
        out = fn(vm, ids, **kw)[:-1]
        out = jnp.where(count > 0, out, 0.0)
    else:
        raise ValueError(f"unknown op {op!r}")
    return out.astype(values.dtype)


def node_mlp_ref(
    x: jax.Array, w: jax.Array, b: jax.Array, activation: str = "relu"
) -> jax.Array:
    """Fused linear + bias + activation (the Node-Embedding 'MLP PE').

    x: (M, K); w: (K, N); b: (N,).  Accumulation in f32.
    """
    y = jnp.dot(x.astype(jnp.float32), w.astype(jnp.float32)) + b.astype(jnp.float32)
    if activation == "relu":
        y = jnp.maximum(y, 0.0)
    elif activation == "gelu":
        y = jax.nn.gelu(y)
    elif activation != "none":
        raise ValueError(f"unknown activation {activation!r}")
    return y.astype(x.dtype)


# int8 x int8 partial products fit an f32 mantissa while
# |x| * |w| * K <= 128 * 127 * K < 2^24, i.e. K <= 1032 — under that bound
# an f32 GEMM over the integer-valued operands is bit-identical to an
# int32 accumulator, and on XLA:CPU (no int8 GEMM lowering) ~3x faster
# than ``dot_general(..., preferred_element_type=int32)``.
_EXACT_EMU_MAX_K = 1024


def _int8_accumulate(x_q: jax.Array, w_q: jax.Array) -> jax.Array:
    """(M, K) x (K, N) int8 matmul with exact accumulation, returned f32."""
    if x_q.shape[-1] <= _EXACT_EMU_MAX_K:
        return jnp.dot(x_q.astype(jnp.float32), w_q.astype(jnp.float32))
    return jax.lax.dot_general(
        x_q,
        w_q,
        (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.int32,
    ).astype(jnp.float32)


def quant_node_mlp_ref(
    x_q: jax.Array,
    w_q: jax.Array,
    scale: jax.Array,
    b: jax.Array,
    activation: str = "relu",
    row_scale: jax.Array | None = None,
) -> jax.Array:
    """Quantized fused linear (int8 NE PE): int32 accumulate, requantize.

    x_q: (M, K) int8; w_q: (K, N) int8; scale: (N,) or () f32 per-output-
    channel requantization factor; row_scale: (M, 1) f32 per-row factor
    (dynamic per-node scales; None -> 1); b: (N,) f32.  The accumulation
    is exact (int32, or its bit-identical f32 emulation for K <= 1024),
    so kernel and oracle agree bit-for-bit up to the f32 rescale tail.
    """
    y = _int8_accumulate(x_q, w_q) * scale.astype(jnp.float32)
    if row_scale is not None:
        y = y * row_scale.astype(jnp.float32)
    y = y + b.astype(jnp.float32)
    if activation == "relu":
        y = jnp.maximum(y, 0.0)
    elif activation == "gelu":
        y = jax.nn.gelu(y)
    elif activation != "none":
        raise ValueError(f"unknown activation {activation!r}")
    return y


# floor for the dynamic per-row activation scale — must match
# ``quant.qconfig._EPS`` so the fused requant tail reproduces the unfused
# ``quantized_linear`` dynamic recipe
_ROW_EPS = 1e-8


def _fused_gamma_linear(x, w1, b1, w1_scale, precision: str) -> jax.Array:
    """gamma's first linear + relu, fp32 or the in-pass W8A8 boundary.

    int8: exact-range symmetric per-row quantization of ``x`` (the
    ``quant.qconfig`` dynamic recipe), exact int8 accumulation
    (:func:`_int8_accumulate`), one fused requantize tail
    ``acc * (row_scale * w_scale) + b``.
    """
    if precision == "int8":
        rs = jnp.maximum(
            jnp.max(jnp.abs(x), axis=-1, keepdims=True), _ROW_EPS
        ) / 127.0
        q = jnp.clip(jnp.round(x / rs), -128.0, 127.0)
        y = _int8_accumulate(q, w1) * (rs * w1_scale.astype(jnp.float32)) + b1
    else:
        y = jnp.dot(x, w1.astype(jnp.float32)) + b1
    return jnp.maximum(y, 0.0)


def fused_mp_ref(
    spec,
    ids_sorted: jax.Array,
    src_sorted: jax.Array,
    in_degree: jax.Array,
    node_mask: jax.Array,
    msrc: jax.Array,
    x_res: jax.Array,
    nop: jax.Array | None = None,
    eop: jax.Array | None = None,
    ew: jax.Array | None = None,
    w1: jax.Array | None = None,
    b1: jax.Array | None = None,
    w1_scale: jax.Array | None = None,
    w2: jax.Array | None = None,
    b2: jax.Array | None = None,
) -> jax.Array:
    """Fused (phi, A, gamma) message-passing pass — the megakernel oracle.

    ``spec`` is a ``core.message_passing.MPSpec`` (duck-typed here to keep
    ``kernels`` import-free of ``core``): phi kind, aggregator tuple,
    gamma kind, precision.  Plan operands come straight off a
    ``core.layout.GraphLayout`` (``ids_sorted`` non-decreasing with
    padding rows holding an out-of-range id); per-edge operands
    (``eop``, ``ew``) are already in plan (sorted-edge) order.

      msrc  (N, F)  per-source message operand, gathered via src_sorted
      x_res (N, Fr) gamma's residual/self operand
      nop           per-node gamma operand: gcn (N,1) 1/sqrt(d+1);
                    pna (N,3) degree scalers; dgn (N,1) sum of w_e
      eop   (E, F)  phi="add_relu" edge operand (GIN's edge embedding)
      ew    (E, 1)  "wsum" edge weights (DGN's directional w_e)
      w1/b1[/w1_scale]  gamma's first linear (int8: w1 int8 + per-channel
                    scale — the in-pass W8A8 boundary)
      w2/b2         gamma="gin" second MLP linear (always f32 weights)

    Matches the unfused ``mp_layer`` path: empty segments contribute 0
    (mean/std divide by max(deg, 1); max/min empty rows forced to 0) and
    padded node rows are zeroed on the way out.
    """
    n = in_degree.shape[0]
    msg = jnp.take(msrc.astype(jnp.float32), src_sorted, axis=0)
    if spec.phi == "add_relu":
        msg = jnp.maximum(msg + eop.astype(jnp.float32), 0.0)
    elif spec.phi != "copy":
        raise ValueError(f"unknown phi {spec.phi!r}")
    valid = ids_sorted < n
    ids = jnp.where(valid, ids_sorted, n)
    kw = dict(num_segments=n + 1, indices_are_sorted=True)
    deg = in_degree.astype(jnp.float32)[:, None]
    c = jnp.maximum(deg, 1.0)
    agg = {}
    for op in spec.ops:
        if op == "sum":
            agg[op] = jax.ops.segment_sum(msg, ids, **kw)[:-1]
        elif op == "sqsum":
            agg[op] = jax.ops.segment_sum(msg * msg, ids, **kw)[:-1]
        elif op == "wsum":
            agg[op] = jax.ops.segment_sum(msg * ew, ids, **kw)[:-1]
        elif op in ("max", "min"):
            fill = -jnp.inf if op == "max" else jnp.inf
            vm = jnp.where(valid[:, None], msg, fill)
            fn = jax.ops.segment_max if op == "max" else jax.ops.segment_min
            agg[op] = jnp.where(deg > 0, fn(vm, ids, **kw)[:-1], 0.0)
        else:
            raise ValueError(f"unknown aggregator {op!r}")
    x_res = x_res.astype(jnp.float32)
    if spec.gamma == "gcn":
        out = (agg["sum"] + x_res) * nop
    elif spec.gamma == "gin":
        h = _fused_gamma_linear(
            x_res + agg["sum"], w1, b1, w1_scale, spec.precision
        )
        out = jnp.dot(h, w2.astype(jnp.float32)) + b2
    elif spec.gamma == "pna":
        mean = agg["sum"] / c
        std = jnp.sqrt(jnp.maximum(agg["sqsum"] / c - mean * mean, 0.0))
        agg4 = jnp.concatenate([mean, std, agg["max"], agg["min"]], axis=-1)
        tower = jnp.concatenate(
            [agg4 * nop[:, 0:1], agg4 * nop[:, 1:2], agg4 * nop[:, 2:3]],
            axis=-1,
        )
        out = _fused_gamma_linear(tower, w1, b1, w1_scale, spec.precision)
        out = out + x_res
    elif spec.gamma == "dgn":
        mean = agg["sum"] / c
        dx = jnp.abs(agg["wsum"] - x_res * nop)
        tower = jnp.concatenate([x_res, mean, dx], axis=-1)
        out = _fused_gamma_linear(tower, w1, b1, w1_scale, spec.precision)
        out = out + x_res
    else:
        raise ValueError(f"unknown gamma {spec.gamma!r}")
    return jnp.where(node_mask[:, None], out, 0.0)


def edge_softmax_ref(
    logits: jax.Array, segment_ids: jax.Array, num_segments: int
) -> jax.Array:
    """Numerically-stable per-destination softmax over sorted edges (GAT).

    logits: (E, H) attention logits per head; returns (E, H) weights that
    sum to 1 within each (segment, head); padding edges get weight 0.
    """
    valid = segment_ids < num_segments
    ids = jnp.where(valid, segment_ids, num_segments)
    kw = dict(num_segments=num_segments + 1, indices_are_sorted=True)
    lm = jnp.where(valid[:, None], logits.astype(jnp.float32), -jnp.inf)
    seg_max = jax.ops.segment_max(lm, ids, **kw)
    seg_max = jnp.where(jnp.isfinite(seg_max), seg_max, 0.0)
    z = jnp.exp(lm - seg_max[ids])
    z = jnp.where(valid[:, None], z, 0.0)
    seg_sum = jax.ops.segment_sum(z, ids, **kw)
    return (z / jnp.maximum(seg_sum[ids], 1e-30)).astype(logits.dtype)


def flash_attention_ref(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    causal: bool = True,
    window: int | None = None,
    scale: float | None = None,
    segment_ids: jax.Array | None = None,
) -> jax.Array:
    """Full (quadratic) GQA attention oracle.

    q: (B, Hq, S, D); k/v: (B, Hkv, S, D) with Hq % Hkv == 0.
    window: sliding-window size (None = full); causal mask always applied
    when ``causal``.  segment_ids: (B, S) int32 — a query sees only keys
    of its own id, and a row with a negative id sees none and outputs 0.
    """
    b, hq, s, d = q.shape
    hkv = k.shape[1]
    g = hq // hkv
    scale = scale if scale is not None else 1.0 / (d**0.5)
    kq = jnp.repeat(k, g, axis=1)
    vq = jnp.repeat(v, g, axis=1)
    logits = jnp.einsum("bhqd,bhkd->bhqk", q.astype(jnp.float32), kq.astype(jnp.float32)) * scale
    qi = jnp.arange(s)[:, None]
    ki = jnp.arange(s)[None, :]
    mask = jnp.ones((s, s), bool)
    if causal:
        mask &= ki <= qi
    if window is not None:
        mask &= ki > qi - window
    mask = jnp.broadcast_to(mask, (b, 1, s, s))
    if segment_ids is not None:
        seg = segment_ids[:, None, :, None]
        mask = mask & (seg == segment_ids[:, None, None, :]) & (seg >= 0)
    logits = jnp.where(mask, logits, -1e30)
    p = jax.nn.softmax(logits, axis=-1)
    p = jnp.where(mask, p, 0.0)  # a row with no key at all outputs 0
    out = jnp.einsum("bhqk,bhkd->bhqd", p, vq.astype(jnp.float32))
    return out.astype(q.dtype)
