"""The benchmark's GraphGPS configuration: its copy of the reference
(bench/gps_reference.py, reached through bench/configs/gps.py) equals the
program's plain reference, gnn.reference.apply_gps_dense, its weights are
the tree the program serves, and its counts agree with the sizes; the
attention readers reduce a recorded-shape trace and a flush log."""
import dataclasses
import importlib.util
import json
import sys
import types
from pathlib import Path

import numpy as np
import pytest

import jax

BENCH = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(BENCH))

import molecules  # noqa: E402


def _load(path, name):
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _config(width=None):
    """The gps module and its sizes; ``width`` shrinks the model for a CPU
    test (hidden, heads and layers only)."""
    mod = _load(BENCH / "configs" / "gps.py", "bench_config_gps")
    cfg = json.loads((BENCH / "configs" / "gps.json").read_text())
    if width:
        cfg = dict(cfg, hidden=width, head_features=width // 2, heads=2,
                   num_layers=2, ffn_hidden=2 * width, pe_dim=8, out_dim=6)
    return mod, cfg


def _program_cfg(cfg):
    from repro.gnn.models import GNNConfig

    fields = {f.name for f in dataclasses.fields(GNNConfig)}
    return GNNConfig(**{k: (tuple(v) if isinstance(v, list) else v)
                        for k, v in cfg.items() if k in fields})


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


def test_the_gps_copy_equals_the_program_reference():
    from repro.core.graph import batch_graphs
    from repro.gnn.reference import apply_gps_dense

    model, cfg = _config(width=32)
    program_cfg = _program_cfg(cfg)
    params = model.init_params(jax.random.key(12), cfg)
    pool = molecules.make_pool(12, 8)
    with jax.default_matmul_precision("highest"):
        for i in range(len(pool)):
            s, r, nf, ef = pool.graph(i)
            n_pad = max(32, 1 << (nf.shape[0] - 1).bit_length())
            g = batch_graphs([(s, r, nf, ef)], n_pad=n_pad, e_pad=3 * n_pad)
            want = np.asarray(apply_gps_dense(params, g, program_cfg))[0]
            got = np.asarray(model.reference(params, _padded(pool.graph(i), n_pad,
                                                             3 * n_pad), cfg))
            np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)


def test_gps_weights_are_the_tree_the_program_serves():
    from repro.gnn.models import init

    model, cfg = _config()
    mine = jax.eval_shape(lambda k: model.init_params(k, cfg), jax.random.key(0))
    program = jax.eval_shape(lambda k: init(k, _program_cfg(cfg)),
                             jax.random.PRNGKey(0))
    assert jax.tree.structure(mine) == jax.tree.structure(program)
    assert ([a.shape for a in jax.tree.leaves(mine)]
            == [a.shape for a in jax.tree.leaves(program)])
    total = sum(int(np.prod(a.shape)) for a in jax.tree.leaves(mine))
    assert total == model.param_count(cfg) == 9_700_940


def test_gps_counts_grow_with_the_work():
    model, cfg = _config()
    one = model.forward_flops(26, 52, 1, cfg)
    # one molecule: about 9.7M weights x 2 per node row, plus edges
    assert 2 * 9.0e6 * 26 < one < 2 * 10.5e6 * 26 * 1.5
    assert model.forward_flops(256, 768, 8, cfg) > 8 * model.forward_flops(26, 52, 1, cfg)
    assert model.attention_flops(256, cfg) == 4 * model.attention_flops(128, cfg) - (
        cfg["num_layers"] * 128 * cfg["hidden"] * 2)
    assert model.attention_bytes(256, cfg) == 2 * model.attention_bytes(128, cfg)
    assert model.host_inputs(None, None, 10) is None


def _fake_run(ops, flushes, execs, cfg, model, nodes):
    """A Run with what the attention readers look at: a trace of one
    device, a flush log of one call, a pool of node counts."""
    rids = [f.rids for f in flushes]
    first = 0
    w = types.SimpleNamespace(
        calls=[types.SimpleNamespace(first=first, flushes=flushes)],
        pool_idx=np.arange(sum(len(r) for r in rids)))
    trace = {"window": (0, 10**9), "devices": {0: {"ops": ops, "programs": execs}},
             "host": []}
    run = types.SimpleNamespace(
        trace=trace, window=w, pool=types.SimpleNamespace(nodes=np.asarray(nodes)),
        peaks={"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9},
        cell=types.SimpleNamespace(config=cfg, model=model))
    run.flushes = lambda: [(f, 0, 0) for f in flushes]
    return run


def test_attention_readers_on_a_synthetic_trace():
    model, cfg = _config()
    flushes = [types.SimpleNamespace(rids=(0, 1, 2), sig=(64, 192), rung_multiple=4),
               types.SimpleNamespace(rids=(3, 4), sig=(32, 96), rung_multiple=2)]
    nodes = [60, 70, 50, 20, 30]
    kernel = "%gps_attention.3 = f32[4,256,96]{2,1,0:T(8,128)} custom-call(%a, %b)"
    ops, t = [], 1000
    for _ in range(len(flushes) * cfg["num_layers"]):
        ops.append([kernel, t, t + 20_000])
        ops.append(["%fusion.1 = f32[256,384]{1,0} fusion(%x)", t + 20_000, t + 50_000])
        t += 100_000
    execs = [["jit_run(123)", 0, 500_000], ["jit_run(123)", 500_000, 1_000_000]]
    run = _fake_run(ops, flushes, execs, cfg, model, nodes)
    share = _load(BENCH / "metrics" / "attn_device_share.py", "m1").read(run)
    assert share == pytest.approx(10 * 20_000 / 1_000_000)
    roof = _load(BENCH / "metrics" / "attn_roofline.py", "m2").read(run)
    least = sum(max(model.attention_flops(r, cfg) / 197e12,
                    model.attention_bytes(r, cfg) / 819e9) for r in (256, 64))
    assert roof == pytest.approx(100 * least / (10 * 20e-6))
    assert 0 < roof < 100
    # a layer's op missing from the trace: the roofline is left out
    run = _fake_run(ops[2:], flushes, execs, cfg, model, nodes)
    assert _load(BENCH / "metrics" / "attn_roofline.py", "m3").read(run) is None


def test_attention_pair_share_from_the_flush_log():
    from repro.kernels.flash_attention import segment_pairs

    model, cfg = _config()
    flushes = [types.SimpleNamespace(rids=(0, 1, 2), sig=(64, 192), rung_multiple=4),
               types.SimpleNamespace(rids=(3, 4), sig=(32, 96), rung_multiple=2)]
    nodes = [60, 70, 50, 20, 30]
    run = _fake_run([], flushes, [], cfg, model, nodes)
    got = _load(BENCH / "metrics" / "attn_pair_share.py", "m4").read(run)
    a, b = segment_pairs([60, 70, 50], 256), segment_pairs([20, 30], 64)
    assert got == pytest.approx((a[0] + b[0]) / (a[1] + b[1]))
    assert 0 < got < 1
