"""GraphGPS on the served path, at a small size (hidden 32, 2 layers,
2 heads): the model served through ``Executor`` + ``StreamScheduler``
against the plain reference, the segment-id attention kernel against a
masked jnp attention, the in-program RWSE against numpy matrix powers,
and the attention-pair accounting.

Tolerances: the served forward and the reference both run fp32 at matmul
precision ``highest`` but sum in different orders (sorted segment sums
against one-hot products, the kernel's online softmax against a dense
one), so they agree to a few float32 roundings of the outputs' scale:
1e-5 of the reference's RMS, about 80 units in the last place.
"""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.core.graph import batch_graphs
from repro.data.pipeline import MOLHIV, MoleculeStream
from repro.gnn.models import (
    GNNConfig, apply, attention_pairs, init, paper_config, serving_params,
)
from repro.gnn.reference import apply_dense, apply_gps_dense
from repro.kernels import ops, ref
from repro.kernels.flash_attention import (
    segment_blocks, segment_pairs, segment_tile_table,
)

SMALL = GNNConfig(model="gps", num_layers=2, hidden=32, heads=2,
                  head_features=16, out_dim=5, pe_steps=16, pe_dim=8,
                  ffn_hidden=64)
GAP_OVER_RMS = 1e-5


@pytest.fixture(autouse=True)
def _highest():
    with jax.default_matmul_precision("highest"):
        yield


def _molecules(n, seed=0):
    return [g[:4] for g in MoleculeStream(MOLHIV, seed=seed).take(n)]


def _one_by_one(params, cfg, graphs, mm=jnp.matmul):
    """The plain reference, one padded molecule at a time."""
    out = []
    for gr in graphs:
        n = gr[2].shape[0]
        n_pad = max(32, 1 << (n - 1).bit_length())
        g = batch_graphs([gr], n_pad=n_pad, e_pad=3 * n_pad)
        out.append(np.asarray(apply_gps_dense(params, g, cfg, mm=mm))[0])
    return np.stack(out)


def _serve(cfg, params, graphs):
    from repro.obs.metrics import MetricsRegistry
    from repro.serve.executor import Executor
    from repro.serve.scheduler import StreamScheduler

    ex = Executor()
    ex.register("gps", cfg, params)
    metrics = MetricsRegistry()
    sched = StreamScheduler(ex, capacity=4, max_wait_s=0.002, metrics=metrics)
    rep = sched.run(graphs, qps=0.0)
    assert rep.num_served == len(graphs)
    return np.concatenate(rep.outputs), rep, metrics


def _gap(a, b):
    return float(np.abs(a - b).max() / np.sqrt(np.mean(b ** 2)))


@pytest.mark.parametrize("kernel_mode", ["auto", "kernel"])
def test_served_gps_equals_the_plain_reference(kernel_mode):
    """Several molecules per packed flush, served through the scheduler
    (``kernel`` runs every Pallas kernel in interpret mode)."""
    cfg = GNNConfig(**{**SMALL.__dict__, "kernel_mode": kernel_mode})
    params = init(jax.random.PRNGKey(3), cfg)
    graphs = _molecules(14, seed=3)
    served, rep, _ = _serve(cfg, params, graphs)
    assert max(rep.batch_sizes) > 1
    want = _one_by_one(params, cfg, graphs)
    assert served.shape == want.shape == (len(graphs), cfg.out_dim)
    assert _gap(served, want) < GAP_OVER_RMS


def test_a_cross_graph_leak_fails_the_comparison(monkeypatch):
    """With the attention's graph mask off (every real node one segment)
    the packed flush mixes its molecules and the comparison fails."""
    from repro.gnn import layers as L

    cfg = SMALL
    params = init(jax.random.PRNGKey(4), cfg)
    graphs = _molecules(12, seed=4)
    served, _, _ = _serve(cfg, params, graphs)
    want = _one_by_one(params, cfg, graphs)
    assert _gap(served, want) < GAP_OVER_RMS

    real = L.segment_attention

    def leaky(qkv, g, heads, mode="auto"):
        import dataclasses

        return real(qkv, dataclasses.replace(g, graph_id=jnp.zeros_like(g.graph_id)),
                    heads, mode=mode)

    monkeypatch.setattr(L, "segment_attention", leaky)
    leaked, rep, _ = _serve(cfg, params, graphs)
    assert max(rep.batch_sizes) > 1
    assert _gap(leaked, want) > 100 * GAP_OVER_RMS


def test_program_reference_equals_the_dense_oracle_entry():
    """``apply_dense`` dispatches GPS to ``apply_gps_dense``, and a packed
    batch of molecules gives each its own row."""
    params = init(jax.random.PRNGKey(5), SMALL)
    graphs = _molecules(4, seed=5)
    packed = batch_graphs(graphs, n_pad=128, e_pad=384)
    got = np.asarray(apply_dense(params, packed, SMALL))[:4]
    np.testing.assert_allclose(got, _one_by_one(params, SMALL, graphs),
                               rtol=1e-5, atol=1e-5)


def test_serving_tree_packs_gps_and_leaves_other_models_alone():
    params = init(jax.random.PRNGKey(9), SMALL)
    tree = serving_params(params, SMALL)
    assert len(jax.tree.leaves(params)) == 12 + 38 * SMALL.num_layers
    assert len(jax.tree.leaves(tree)) == 20
    assert serving_params(tree, SMALL) is tree
    gin = GNNConfig(model="gin", num_layers=2, hidden=16)
    raw = init(jax.random.PRNGKey(9), gin)
    assert serving_params(raw, gin) is raw


def test_the_program_serves_alike_from_either_tree():
    """``apply`` handed ``init``'s tree makes the serving tree itself.
    Folded inside the program, the norms fuse into their neighbours in
    another order, so the two agree to float32 roundings, not bits."""
    params = init(jax.random.PRNGKey(10), SMALL)
    packed = batch_graphs(_molecules(6, seed=10), n_pad=256, e_pad=768)
    fwd = jax.jit(lambda p: apply(p, packed, SMALL, num_graphs=8))
    assert _gap(np.asarray(fwd(params)),
                np.asarray(fwd(serving_params(params, SMALL)))) < GAP_OVER_RMS


# ------------------------------------------------------------- the kernel


def _segments(counts, s):
    seg = np.full((1, s), -1, np.int32)
    seg[0, :sum(counts)] = np.repeat(np.arange(len(counts)), counts)
    return seg


@pytest.mark.parametrize("s,counts", [
    (256, [100, 60, 50]),  # graph 1 straddles rows 127/128; 46 padded rows
    (384, [120, 9, 140, 60]),  # q-block 0 and k-block 2 share no graph
    (96, [30, 40, 10]),  # one tile of all rows, padded rows at the end
])
def test_segment_attention_kernel_equals_masked_jnp(s, counts):
    rng = np.random.default_rng(s)
    q, k, v = (jnp.asarray(rng.normal(size=(1, 2, s, 24)), jnp.float32)
               for _ in range(3))
    seg = jnp.asarray(_segments(counts, s))
    got = np.asarray(ops.flash_attention(q, k, v, causal=False, mode="kernel",
                                         segment_ids=seg))
    # the jnp oracle: softmax over each row's own graph, computed directly
    logits = np.einsum("bhqd,bhkd->bhqk", q, k) / np.sqrt(24.0)
    same = (np.asarray(seg)[0][:, None] == np.asarray(seg)[0][None, :])
    same &= np.asarray(seg)[0][:, None] >= 0
    w = np.where(same, np.exp(logits - logits.max(-1, keepdims=True)), 0.0)
    w = w / np.maximum(w.sum(-1, keepdims=True), 1e-30)
    want = np.einsum("bhqk,bhkd->bhqd", w, np.asarray(v))
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)
    np.testing.assert_array_equal(got[:, :, sum(counts):], 0.0)
    np.testing.assert_allclose(
        got, np.asarray(ref.flash_attention_ref(q, k, v, causal=False,
                                                segment_ids=seg)),
        rtol=1e-5, atol=1e-5)


def test_segment_tile_table_skips_blocks_without_a_shared_graph():
    block, s_pad = segment_blocks(384)
    table = np.asarray(segment_tile_table(_segments([120, 9, 140, 60], 384),
                                          block, block))[0]
    # blocks hold graphs {0, 1} / {1, 2} / {2, 3}: the corners share none
    assert block == 128 and s_pad == 384
    np.testing.assert_array_equal(table, [[1, 1, 0], [1, 1, 1], [0, 1, 1]])
    assert segment_pairs([120, 9, 140, 60], 384) == (
        120 ** 2 + 9 ** 2 + 140 ** 2 + 60 ** 2, 7 * 128 * 128)


@pytest.mark.parametrize("rows", [32, 96, 192, 256, 512, 1024])
def test_segment_pairs_count_the_kernel_tiles(rows):
    rng = np.random.default_rng(rows)
    counts = []
    while sum(counts) < rows - 80:
        counts.append(int(rng.integers(4, 80)))
    block, s_pad = segment_blocks(rows)
    table = np.asarray(segment_tile_table(_segments(counts, s_pad), block, block))
    real, computed = segment_pairs(counts, rows)
    assert real == sum(c * c for c in counts)
    assert computed == int(table.sum()) * block * block >= real


def test_serving_counts_attention_pairs_per_flush():
    params = init(jax.random.PRNGKey(6), SMALL)
    graphs = _molecules(10, seed=6)
    _, rep, metrics = _serve(SMALL, params, graphs)
    series = metrics.counter("serve_attention_pairs_total").series()
    real = sum(v for key, v in series.items() if key == ("real",))
    computed = sum(v for key, v in series.items() if key == ("computed",))
    assert real == sum(g[2].shape[0] ** 2 for g in graphs)
    assert real <= computed


@pytest.mark.parametrize("model", ["gcn", "gin", "gat", "pna", "dgn", "gps"])
def test_attention_pairs_follow_the_models_capability(model):
    """Only a model whose layers attend within each graph has pairs to
    count; the scheduler asks the model, not its name."""
    cfg = paper_config(model)
    assert cfg.graph_attention == (model == "gps")
    got = attention_pairs(cfg, [120, 9, 140, 60], 384)
    assert got == (segment_pairs([120, 9, 140, 60], 384) if model == "gps"
                   else None)


def test_serving_a_model_without_attention_counts_no_pairs():
    from repro.obs.metrics import MetricsRegistry
    from repro.serve.executor import Executor
    from repro.serve.scheduler import StreamScheduler

    cfg = GNNConfig(model="gin", num_layers=2, hidden=16)
    ex = Executor()
    ex.register("gin", cfg, init(jax.random.PRNGKey(8), cfg))
    metrics = MetricsRegistry()
    sched = StreamScheduler(ex, capacity=4, max_wait_s=0.002, metrics=metrics)
    assert sched.run(_molecules(6, seed=8), qps=0.0).num_served == 6
    assert not metrics.counter("serve_attention_pairs_total").series()


# ------------------------------------------------------------------- RWSE


def test_in_program_rwse_equals_numpy_matrix_powers():
    from repro.core import message_passing as mp

    graphs = _molecules(5, seed=7)
    packed = batch_graphs(graphs, n_pad=256, e_pad=768)
    got = np.asarray(mp.random_walk_se(packed, 16))
    off = 0
    for s, r, nf, _ in graphs:
        n = nf.shape[0]
        a = np.zeros((n, n))
        np.add.at(a, (s, r), 1.0)
        deg = a.sum(1, keepdims=True)
        p = np.divide(a, deg, out=np.zeros_like(a), where=deg > 0)
        want = np.stack([np.diagonal(np.linalg.matrix_power(p, k))
                         for k in range(1, 17)], -1)
        np.testing.assert_allclose(got[off:off + n], want, rtol=1e-5, atol=1e-6)
        off += n
    np.testing.assert_array_equal(got[off:], 0.0)


@pytest.mark.parametrize("steps", [1, 5, 20])
def test_rwse_doubling_gives_every_step_count(steps):
    from repro.core import message_passing as mp

    graphs = _molecules(3, seed=11)
    packed = batch_graphs(graphs, n_pad=128, e_pad=384)
    got = np.asarray(mp.random_walk_se(packed, steps))
    assert got.shape == (128, steps)
    s, r, nf, _ = graphs[0]
    n = nf.shape[0]
    a = np.zeros((n, n))
    np.add.at(a, (s, r), 1.0)
    deg = a.sum(1, keepdims=True)
    p = np.divide(a, deg, out=np.zeros_like(a), where=deg > 0)
    want = np.stack([np.diagonal(np.linalg.matrix_power(p, k))
                     for k in range(1, steps + 1)], -1)
    np.testing.assert_allclose(got[:n], want, rtol=1e-5, atol=1e-6)
