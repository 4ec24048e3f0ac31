"""The benchmark's copy of the dense reference (bench/dense_reference.py,
reached through bench/configs/<name>.py) equals the program's own dense
oracle, gnn.reference.apply_dense, on seeded molecules."""
import dataclasses
import importlib.util
import json
import sys
from pathlib import Path

import numpy as np
import pytest

import jax
import jax.numpy as jnp

BENCH = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(BENCH))

import molecules  # noqa: E402


def _config(name):
    spec = importlib.util.spec_from_file_location(
        f"bench_config_{name}", BENCH / "configs" / f"{name}.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod, json.loads((BENCH / "configs" / f"{name}.json").read_text())


def _padded(graph, n_pad, e_pad):
    s, r, nf, ef = graph
    n, e = nf.shape[0], len(s)
    src = np.full(e_pad, n_pad - 1, np.int32)
    dst = src.copy()
    src[:e], dst[:e] = s, r
    pad = lambda a, rows: np.concatenate(  # noqa: E731
        [a, np.zeros((rows - len(a), a.shape[1]), np.float32)])
    return {"node_feat": pad(nf, n_pad), "src": src, "dst": dst,
            "edge_feat": pad(ef, e_pad), "node_mask": np.arange(n_pad) < n,
            "edge_mask": np.arange(e_pad) < e, "graph_id": np.zeros(n_pad, np.int32)}


@pytest.mark.parametrize("name", ["gin", "dgn"])
def test_the_copy_equals_the_program_oracle(name):
    from repro.core.graph import batch_graphs
    from repro.data.pipeline import laplacian_eigvec
    from repro.gnn.models import GNNConfig
    from repro.gnn.reference import apply_dense

    model, cfg = _config(name)
    fields = {f.name for f in dataclasses.fields(GNNConfig)}
    program_cfg = GNNConfig(**{k: (tuple(v) if isinstance(v, list) else v)
                               for k, v in cfg.items() if k in fields})
    params = model.init_params(jax.random.key(11), cfg)
    pool = molecules.make_pool(11, 12)
    with jax.default_matmul_precision("highest"):
        for i in range(len(pool)):
            s, r, nf, ef = pool.graph(i)
            n = nf.shape[0]
            n_pad = max(32, 1 << (n - 1).bit_length())
            g = batch_graphs([(s, r, nf, ef)], n_pad=n_pad, e_pad=3 * n_pad)
            eig = None
            if name == "dgn":
                eig = jnp.asarray(laplacian_eigvec(s, r, n, n_pad))
            want = np.asarray(apply_dense(params, g, program_cfg, eig))[0]
            got = np.asarray(model.reference(params, _padded(pool.graph(i), n_pad, 3 * n_pad),
                                             cfg, eig))
            np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)


def test_dgn_eigenvector_matches_the_program_up_to_sign():
    from repro.data.pipeline import laplacian_eigvec

    model, _ = _config("dgn")
    pool = molecules.make_pool(5, 40)
    for i in range(len(pool)):
        s, r, nf, _ = pool.graph(i)
        mine = model.host_inputs(s, r, nf.shape[0])
        theirs = laplacian_eigvec(s, r, nf.shape[0]).astype(np.float64)
        sign = np.sign(mine @ theirs) or 1.0
        np.testing.assert_allclose(sign * mine, theirs, atol=1e-5)


def test_dgn_reference_does_not_depend_on_the_eigenvector_sign():
    model, cfg = _config("dgn")
    params = model.init_params(jax.random.key(2), cfg)
    s, r, nf, ef = molecules.make_pool(2, 1).graph(0)
    n_pad = max(32, 1 << (nf.shape[0] - 1).bit_length())
    g = _padded((s, r, nf, ef), n_pad, 3 * n_pad)
    v = np.zeros(n_pad, np.float32)
    v[: nf.shape[0]] = model.host_inputs(s, r, nf.shape[0])
    with jax.default_matmul_precision("highest"):
        a = np.asarray(model.reference(params, g, cfg, jnp.asarray(v)))
        b = np.asarray(model.reference(params, g, cfg, jnp.asarray(-v)))
    np.testing.assert_allclose(a, b, rtol=1e-6, atol=1e-6)
