"""GNN serving engine: per-bucket compile-cache bookkeeping (warm-before-
timing in both modes) and the mesh-aware sharded batched path, which must
be bit-identical to the unsharded run (2 virtual devices, subprocess)."""
import os
import subprocess
import sys

import jax
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _graphs(n_graphs=8, feat=9, edge=3, seed=0):
    rng = np.random.default_rng(seed)
    out = []
    for _ in range(n_graphs):
        n = int(rng.integers(6, 16))
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


def test_infer_batched_warms_each_signature_outside_timing(engine):
    graphs = _graphs(10)
    out, per_graph = engine.infer_batched(graphs, batch_size=4, n_pad=128, e_pad=384)
    assert out.shape == (10, 1)
    assert per_graph > 0
    key = ("batched", 128, 384, 4)
    cb = engine._compiled[key]
    assert len(cb.warm) == 1  # one trace signature, warmed exactly once
    assert cb.compile_s > 0
    assert engine.compile_seconds >= cb.compile_s
    # a second run re-uses the warm program: no new signatures, no compile
    before = cb.compile_s
    engine.infer_batched(graphs, batch_size=4, n_pad=128, e_pad=384)
    assert len(cb.warm) == 1
    assert cb.compile_s == before


def test_infer_stream_bucket_records(engine):
    graphs = _graphs(6)
    outs, lats, compile_s = engine.infer_stream(graphs)
    assert len(outs) == 6 and lats.shape == (6,)
    stream_keys = [k for k in engine._compiled if k[0] == "stream"]
    assert stream_keys, "stream buckets should be cached per (n_pad, e_pad)"
    assert compile_s > 0  # first visit to each bucket compiled untimed


def test_dgn_batched_matches_stream_eigvec(rng):
    """Batched mode must feed DGN the same per-graph Laplacian
    eigenvectors the stream mode computes (it used to pass zeros)."""
    from repro.data.pipeline import MOLHIV, MoleculeStream
    from repro.gnn import init
    from repro.gnn.models import paper_config
    from repro.serve.gnn_engine import GNNEngine

    cfg = paper_config("dgn")
    eng = GNNEngine(cfg, init(jax.random.PRNGKey(0), cfg))
    graphs = [g[:4] for g in MoleculeStream(MOLHIV, seed=2).take(4)]
    outs, _, _ = eng.infer_stream(graphs, with_eigvec=True)
    outs_b, _ = eng.infer_batched(graphs, batch_size=4, n_pad=256, e_pad=768,
                                  with_eigvec=True)
    for i in range(4):
        np.testing.assert_allclose(outs_b[i], outs[i][0], rtol=1e-4, atol=1e-5)


def test_engine_has_no_dead_eigvec_dim_param(engine):
    import inspect

    from repro.serve.gnn_engine import GNNEngine

    assert "eigvec_dim" not in inspect.signature(GNNEngine.__init__).parameters


_SHARDED_SERVE_SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=2"
import sys
sys.path.insert(0, "src")
import jax, numpy as np
from repro import runtime as RT
from repro.gnn import init
from repro.gnn.models import paper_config
from repro.serve.gnn_engine import GNNEngine

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

plain = GNNEngine(cfg, params)
out_plain, _ = plain.infer_batched(graphs, batch_size=4, n_pad=128, e_pad=384)

mesh = RT.make_flat_mesh(2, axis="data")
sharded = GNNEngine(cfg, params, mesh=mesh)
assert sharded.rules["nodes"] == ("data",)
out_shard, _ = sharded.infer_batched(graphs, batch_size=4, n_pad=128, e_pad=384)
np.testing.assert_allclose(out_plain, out_shard, rtol=1e-4, atol=1e-5)
print("SHARDED_SERVE_OK")
"""


def test_sharded_batched_serving_matches_unsharded():
    r = subprocess.run(
        [sys.executable, "-c", _SHARDED_SERVE_SCRIPT],
        capture_output=True, text=True, cwd=ROOT,
    )
    assert r.returncode == 0, (r.stdout[-1000:], r.stderr[-3000:])
    assert "SHARDED_SERVE_OK" in r.stdout


_SHARDED_KERNEL_SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
os.environ["REPRO_KERNEL_MODE"] = "kernel"
import sys
sys.path.insert(0, "src")
import jax, numpy as np
from repro import runtime as RT
from repro.data.pipeline import MOLHIV, MoleculeStream
from repro.gnn import init
from repro.gnn.models import paper_config
from repro.serve.executor import Executor
from repro.serve.scheduler import StreamScheduler

model, precision, fused = sys.argv[1], sys.argv[2], sys.argv[3] == "1"
kw = dict(heads=2, head_features=8) if model == "gat" else dict(hidden=16)
cfg = paper_config(model, num_layers=2, **kw)
params = init(jax.random.PRNGKey(0), cfg)
# eight 33-64-node molecules pack four to a (256, 768) rung: 64 node rows
# per device, fewer than the source table's 256
stream = [g[:4] for g in MoleculeStream(MOLHIV, seed=1).take(64)]
graphs = [g for g in stream if g[2].shape[0] > 32][:8] + stream[:4]
outs = []
for mesh in (None, RT.make_flat_mesh(4, axis="data")):
    ex = Executor(mesh=mesh)
    ex.register(model, cfg, params, precision=precision, fused=fused)
    rep = StreamScheduler(ex, capacity=4, with_eigvec="auto").run(graphs)
    outs.append(np.concatenate(rep.outputs))
    rungs = sorted({key[1] for key in ex._compiled})  # ("packed", N, E, G)
    assert max(r[1] for r in rungs) >= 256, rungs
np.testing.assert_allclose(outs[1], outs[0], rtol=1e-4, atol=1e-4)
print("SHARDED_KERNELS_OK")
"""


@pytest.mark.parametrize("model,precision,fused", [
    ("gin", "fp32", False),
    ("gat", "fp32", False),
    ("gin", "int8", True),
    ("pna", "fp32", True),
])
def test_sharded_packed_kernels_match_unsharded(model, precision, fused):
    """A Pallas kernel cannot be partitioned by the compiler: under a
    four-device mesh every kernel runs per shard in a ``shard_map``
    (interpret mode here) and must serve what one device serves."""
    r = subprocess.run(
        [sys.executable, "-c", _SHARDED_KERNEL_SCRIPT, model, precision,
         "1" if fused else "0"],
        capture_output=True, text=True, cwd=ROOT,
    )
    assert r.returncode == 0, (r.stdout[-1000:], r.stderr[-3000:])
    assert "SHARDED_KERNELS_OK" in r.stdout
