"""Pallas TPU kernels for the two GenGNN processing elements + LM attention.

  segment_reduce.py  MP PE: blocked sorted-segment aggregation (one-hot MXU
                     matmul for sum-family, sequential VPU for max/min)
  node_mlp.py        NE PE: fused tiled linear+bias+activation
  edge_softmax.py    GAT per-destination softmax (built on segment_reduce)
  flash_attention.py blockwise GQA attention for the LM substrate, and
                     segment-id attention within each graph (GraphGPS)
  ops.py             jit'd dispatching wrappers (kernel / interpret / ref)
  ref.py             pure-jnp oracles (the correctness contract)
"""
from repro.kernels.ops import segment_reduce, node_mlp, edge_softmax, flash_attention

__all__ = ["segment_reduce", "node_mlp", "edge_softmax", "flash_attention"]
