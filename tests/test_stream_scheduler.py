"""Streaming scheduler: greedy pack-to-budget micro-batching, deadline
flushing, budget-ladder rung selection, compiled-bucket reuse (zero
recompiles after warmup), and mesh-sharded packed parity (subprocess)."""
import os
import subprocess
import sys

import jax
import numpy as np
import pytest

from conftest import scripted_executor
from repro.core.batching import BucketBudget, pack_graphs, unpack_outputs

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _graphs(n_graphs=10, nodes=(6, 16), feat=9, edge=3, seed=0):
    rng = np.random.default_rng(seed)
    out = []
    for _ in range(n_graphs):
        n = int(rng.integers(*nodes))
        e = int(rng.integers(n, 2 * n))
        out.append(
            (
                rng.integers(0, n, e).astype(np.int32),
                rng.integers(0, n, e).astype(np.int32),
                rng.normal(size=(n, feat)).astype(np.float32),
                rng.normal(size=(e, edge)).astype(np.float32),
            )
        )
    return out


@pytest.fixture(scope="module")
def engine():
    from repro.gnn import init
    from repro.gnn.models import paper_config
    from repro.serve.gnn_engine import GNNEngine

    cfg = paper_config("gin")
    return GNNEngine(cfg, init(jax.random.PRNGKey(0), cfg))


@pytest.fixture(scope="module")
def scheduler(engine):
    from repro.serve.scheduler import StreamScheduler

    return StreamScheduler(engine, capacity=2, max_wait_s=0.002)


# ------------------------------------------------------------------- packing


def test_pack_unpack_node_level_roundtrip():
    graphs = _graphs(3)
    budget = BucketBudget(64, 128, 4)
    packed, meta = pack_graphs(graphs, budget)
    node_feat = np.asarray(packed.node_feat)
    per_graph = unpack_outputs(node_feat, meta, level="node")
    for i, g in enumerate(graphs):
        np.testing.assert_array_equal(per_graph[i], g[2])


def test_pack_rejects_over_budget():
    graphs = _graphs(3, nodes=(30, 31))
    with pytest.raises(ValueError, match="exceeds budget"):
        pack_graphs(graphs, BucketBudget(32, 96, 8))
    with pytest.raises(ValueError, match="exceeds budget"):
        pack_graphs(_graphs(3), BucketBudget(64, 128, 2))


# ----------------------------------------------------------------- scheduler


def test_scheduler_outputs_match_per_graph_stream(engine, scheduler):
    graphs = _graphs(10)
    outs, _, _ = engine.infer_stream(graphs)
    rep = scheduler.run(graphs, qps=0.0)
    assert rep.num_requests == 10
    for i in range(10):
        np.testing.assert_allclose(rep.outputs[i], outs[i], rtol=1e-4, atol=1e-5)
    # saturation mode packs multiple graphs per flush
    assert max(rep.batch_sizes) > 1
    assert sum(rep.batch_sizes) == 10


def test_scheduler_zero_recompiles_after_warmup(engine, scheduler):
    graphs = _graphs(10, seed=1)
    scheduler.run(graphs, qps=0.0)  # warm (signatures already hot from above)
    warm_s = engine.compile_seconds
    n_buckets = len(engine._compiled)
    for qps in (0.0, 500.0, 5000.0):
        rep = scheduler.run(graphs, qps=qps)
        assert rep.compile_s == 0.0
    assert engine.compile_seconds == warm_s
    assert len(engine._compiled) == n_buckets


def test_scheduler_deadline_flushes_singletons_at_low_load(engine, scheduler):
    graphs = _graphs(5)
    # 10 qps: arrivals 100ms apart >> 2ms max-wait -> every flush is a
    # singleton driven by its deadline (CPU compute ~ms << 100ms gap)
    rep = scheduler.run(graphs, qps=10.0)
    assert rep.batch_sizes == [1] * 5
    assert rep.flush_reasons["deadline"] + rep.flush_reasons["drain"] == 5
    # each request waited out max_wait before computing
    assert float(rep.latencies_s.min()) >= scheduler.max_wait_s


def test_scheduler_budget_flush_on_overflow(engine):
    from repro.serve.scheduler import StreamScheduler

    sched = StreamScheduler(engine, capacity=2, max_wait_s=10.0)
    # 30-node graphs hit bucket (32, 96); budget (64, 192, 4) fits only two
    graphs = _graphs(5, nodes=(28, 31), seed=2)
    rep = sched.run(graphs, qps=0.0)
    assert rep.flush_reasons["budget"] >= 2
    assert max(rep.batch_sizes) == 2


def test_rung_selection_prefers_smallest_fit(engine):
    from repro.serve.scheduler import StreamScheduler, _OpenBucket, Request

    sched = StreamScheduler(engine, capacity=4)
    req = Request(rid=0, graph=_graphs(1)[0], arrival_s=0.0)
    key, ladder = sched.ladder_for(req)
    # powers of two plus 1.5x midpoints, capped at capacity
    assert [b.n_pad for b in ladder] == [k * key[0] for k in (1, 2, 3, 4)]
    bucket = _OpenBucket(ladder, 0.0, 1.0)
    bucket.add(req)
    assert bucket.rung() == ladder[0]  # one small graph -> base-size program
    # every rung is pre-warmed, so any rung choice hits a compiled program
    for b in ladder:
        assert ("packed", b.n_pad, b.e_pad, b.g_pad) in engine._compiled


def test_scheduler_accepts_edge_featureless_graphs():
    """RawGraph's '(s, r, nf[, ef])' contract: 3-tuples must stream fine
    through a model that ignores edge features."""
    from repro.gnn import init
    from repro.gnn.models import paper_config
    from repro.serve.gnn_engine import GNNEngine
    from repro.serve.scheduler import StreamScheduler

    cfg = paper_config("gcn", edge_dim=1)
    eng = GNNEngine(cfg, init(jax.random.PRNGKey(0), cfg))
    graphs = [g[:3] for g in _graphs(4, seed=5)]
    rep = StreamScheduler(eng, capacity=2).run(graphs, qps=0.0)
    assert rep.num_requests == 4
    assert all(o.shape == (1, 1) for o in rep.outputs)


def test_latencies_include_queueing_delay(engine, scheduler):
    graphs = _graphs(12, seed=4)
    rep = scheduler.run(graphs, qps=0.0)  # all queued at t=0
    # the serial executor means later flushes complete later: latency of the
    # last-served request covers all earlier compute
    assert float(rep.latencies_s.max()) >= rep.compute_s * 0.9
    assert rep.makespan_s > 0 and rep.graphs_per_s > 0


# ----------------------------------------------------------- option checks


@pytest.mark.parametrize("kwargs,run_kwargs,match", [
    (dict(capacity=0), None, "capacity must be >= 1"),
    (dict(prewarm="warm"), None, "prewarm must be 'eager' or 'lazy'"),
    (dict(admit_limit=0), None, "admit_limit must be >= 1"),
    (dict(refit_every=0), None, "refit_every must be >= 1"),
    (dict(max_rungs=1), None, r"max_rungs must be >= 2"),
    ({}, dict(priorities=[0]), r"priorities \(1\) must tag every graph \(2\)"),
], ids=["capacity", "prewarm", "admit_limit", "refit_every", "max_rungs",
        "priorities"])
def test_scheduler_rejects_invalid_options(kwargs, run_kwargs, match):
    from repro.serve.scheduler import StreamScheduler

    with pytest.raises(ValueError, match=match):
        sched = StreamScheduler(scripted_executor(), **kwargs)
        sched.run(_graphs(2), **(run_kwargs or {}))


# ------------------------------------------------------- host eigenvectors


@pytest.mark.parametrize("model,vn", [
    ("gcn", False), ("gin", False), ("gin", True), ("gat", False),
    ("pna", False), ("dgn", False), ("gps", False),
], ids=["gcn", "gin", "gin_vn", "gat", "pna", "dgn", "gps"])
def test_auto_eigvec_follows_the_model(model, vn):
    """A ``with_eigvec="auto"`` scheduler solves host eigenvectors for a
    tenant exactly when ``needs_eigvec`` names its model, and packs
    ``eigvecs=None`` otherwise."""
    from repro.gnn.models import needs_eigvec, paper_config
    from repro.serve.scheduler import StreamScheduler

    cfg = paper_config(model, virtual_node=vn)
    ex = scripted_executor()
    ex.tenants["default"].cfg = cfg
    solves, seen = [], []
    solve = ex._eigvec
    ex._eigvec = lambda *a: solves.append(a) or solve(*a)
    run = ex.run
    ex.run = lambda p, model=None: seen.append(p.eigvec) or run(p, model)
    graphs = _graphs(3, seed=5)
    rep = StreamScheduler(ex, capacity=2, with_eigvec="auto").run(graphs)
    assert rep.num_served == len(graphs) and seen
    if needs_eigvec(cfg):
        assert len(solves) == len(graphs)
        assert all(v is not None for v in seen)
    else:
        assert solves == []
        assert all(v is None for v in seen)


_SHARDED_PACKED_SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=2"
import sys
sys.path.insert(0, "src")
import jax, numpy as np
from repro import runtime as RT
from repro.gnn import init
from repro.gnn.models import paper_config
from repro.serve.gnn_engine import GNNEngine
from repro.serve.scheduler import StreamScheduler

cfg = paper_config("gin")
params = init(jax.random.PRNGKey(0), cfg)
rng = np.random.default_rng(0)
graphs = []
for _ in range(8):
    n = int(rng.integers(6, 16)); e = int(rng.integers(n, 2 * n))
    graphs.append((rng.integers(0, n, e).astype(np.int32),
                   rng.integers(0, n, e).astype(np.int32),
                   rng.normal(size=(n, cfg.feat_dim)).astype(np.float32),
                   rng.normal(size=(e, cfg.edge_dim)).astype(np.float32)))

plain = StreamScheduler(GNNEngine(cfg, params), capacity=2)
rep_plain = plain.run(graphs, qps=0.0)

mesh = RT.make_flat_mesh(2, axis="data")
sharded = StreamScheduler(GNNEngine(cfg, params, mesh=mesh), capacity=2)
rep_shard = sharded.run(graphs, qps=0.0)

for a, b in zip(rep_plain.outputs, rep_shard.outputs):
    np.testing.assert_allclose(a, b, rtol=1e-4, atol=1e-5)
print("SHARDED_PACKED_OK")
"""


def test_sharded_packed_serving_matches_unsharded():
    r = subprocess.run(
        [sys.executable, "-c", _SHARDED_PACKED_SCRIPT],
        capture_output=True, text=True, cwd=ROOT,
    )
    assert r.returncode == 0, (r.stdout[-1000:], r.stderr[-3000:])
    assert "SHARDED_PACKED_OK" in r.stdout
