"""The benchmark's molecule pool: MolHIV-statistics graphs made from a seed.

A vectorised copy of the program's ``data/pipeline.MoleculeStream``
generator, with the same distribution: node count ~ N(25.5, 12) truncated
to an integer and clipped at 4 (OGB ogbg-molhiv, arXiv:2005.00687), a
random tree (node j's parent uniform among nodes 0..j-1) plus
int(n * 0.1) ring closures between uniform node pairs, every edge stored in
both directions, 9 standard-normal float atom features per node and 3 bond
features per directed edge.  The whole pool comes out of a few numpy calls,
where the program's generator loops in Python per molecule.

The pool is flat: all node rows, then all edge rows, with per-molecule
offsets.  ``Pool.graph(i)`` hands the scheduler the raw COO tuple
``(senders, receivers, node_feat, edge_feat)`` as views, node ids local to
the molecule.
"""
from __future__ import annotations

import numpy as np

NODE_MEAN, NODE_STD, MIN_NODES = 25.5, 12.0, 4
MEAN_DEGREE = 2.2  # undirected edges per node, tree plus ring closures
FEAT_DIM, EDGE_DIM = 9, 3


class Pool:
    """``count`` molecules in flat arrays; molecule ``i`` owns node rows
    ``node_off[i]:node_off[i+1]`` and edge rows ``edge_off[i]:edge_off[i+1]``."""

    def __init__(self, nodes, edges, senders, receivers, node_feat, edge_feat):
        self.nodes = nodes
        self.edges = edges
        self.node_off = np.concatenate([[0], np.cumsum(nodes)])
        self.edge_off = np.concatenate([[0], np.cumsum(edges)])
        self.senders = senders
        self.receivers = receivers
        self.node_feat = node_feat
        self.edge_feat = edge_feat

    def __len__(self) -> int:
        return len(self.nodes)

    def graph(self, i: int) -> tuple:
        n0, n1 = self.node_off[i], self.node_off[i + 1]
        e0, e1 = self.edge_off[i], self.edge_off[i + 1]
        return (self.senders[e0:e1], self.receivers[e0:e1],
                self.node_feat[n0:n1], self.edge_feat[e0:e1])

    def graphs(self, idx) -> list:
        return [self.graph(int(i)) for i in idx]


def _segments(lengths: np.ndarray):
    """For segments of the given lengths laid end to end: each row's
    segment and its index within the segment."""
    seg = np.repeat(np.arange(len(lengths)), lengths)
    starts = np.concatenate([[0], np.cumsum(lengths)[:-1]])
    return seg, np.arange(int(lengths.sum())) - starts[seg]


def make_pool(seed: int, count: int) -> Pool:
    """``count`` molecules drawn from ``seed`` (any non-negative integer)."""
    rng = np.random.default_rng(np.random.SeedSequence([seed, 0x6d6f6c]))
    nodes = np.maximum(np.trunc(rng.normal(NODE_MEAN, NODE_STD, count)),
                       MIN_NODES).astype(np.int64)
    extra = np.maximum(np.trunc(nodes * (MEAN_DEGREE - 2.0) / 2.0), 0).astype(np.int64)
    edges = 2 * (nodes - 1) + 2 * extra
    edge_off = np.concatenate([[0], np.cumsum(edges)[:-1]])

    # tree: node j >= 1 of molecule m hangs off a uniform earlier node
    t_mol, t_k = _segments(nodes - 1)
    child = t_k + 1
    parent = rng.integers(0, child)
    # ring closures between uniform node pairs
    x_mol, x_q = _segments(extra)
    ends_a = rng.integers(0, nodes[x_mol])
    ends_b = rng.integers(0, nodes[x_mol])

    senders = np.empty(int(edges.sum()), np.int32)
    receivers = np.empty_like(senders)
    base = edge_off[t_mol] + t_k
    senders[base], receivers[base] = child, parent
    back = base + (nodes - 1)[t_mol]
    senders[back], receivers[back] = parent, child
    ring = edge_off[x_mol] + 2 * (nodes - 1)[x_mol] + x_q
    senders[ring], receivers[ring] = ends_a, ends_b
    ring_back = ring + extra[x_mol]
    senders[ring_back], receivers[ring_back] = ends_b, ends_a

    node_feat = rng.standard_normal((int(nodes.sum()), FEAT_DIM), np.float32)
    edge_feat = rng.standard_normal((int(edges.sum()), EDGE_DIM), np.float32)
    return Pool(nodes, edges, senders, receivers, node_feat, edge_feat)
