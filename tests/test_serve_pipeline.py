"""The scheduler's response order, the threaded ``PipelinedStream``
runner, the one-put pack staging, eigvec LRU, D2H accounting, and the
``run_async`` contract.

Real-engine parity cases assert *bitwise* output equality between the
blocking stream loop and the double-buffered runner.
"""
import jax
import numpy as np
import pytest

from conftest import scripted_executor
from repro.gnn import init
from repro.gnn.models import needs_eigvec, paper_config
from repro.obs import MetricsRegistry, Tracer, export
from repro.serve.clock import RealClock, VirtualClock
from repro.serve.gnn_engine import GNNEngine
from repro.serve.pipeline import PipelinedStream
from repro.serve.scheduler import StreamScheduler

KEY = jax.random.PRNGKey(0)
MW = 0.0009765625  # max_wait_s = 2**-10, exact in float64


def graph(n=8, e=12, feat=9, edge=3, seed=0):
    rng = np.random.default_rng(seed)
    return (
        rng.integers(0, n, e).astype(np.int32),
        rng.integers(0, n, e).astype(np.int32),
        rng.normal(size=(n, feat)).astype(np.float32),
        rng.normal(size=(e, edge)).astype(np.float32),
    )


def graphs(k, seed=0, nodes=(5, 14)):
    rng = np.random.default_rng(seed)
    out = []
    for _ in range(k):
        n = int(rng.integers(*nodes))
        e = int(rng.integers(n, 2 * n))
        out.append((
            rng.integers(0, n, e).astype(np.int32),
            rng.integers(0, n, e).astype(np.int32),
            rng.normal(size=(n, 9)).astype(np.float32),
            rng.normal(size=(e, 3)).astype(np.float32),
        ))
    return out


# ----------------------------------------------------- response order


def test_fifo_response_order_under_unequal_service_times():
    """A short flush behind a long one still completes and responds after
    it (serial device): response order is flush order, never compute-time
    order."""
    ex = scripted_executor(service_s=[0.016, 0.0005, 0.0005])
    tr = Tracer(VirtualClock())
    s = StreamScheduler(ex, capacity=1, max_wait_s=MW, tracer=tr)
    rep = s.run(graphs(6, seed=9), qps=0.0)
    log = rep.flush_log
    assert len(log) >= 3
    assert [f.done_s for f in log] == sorted(f.done_s for f in log)
    # rids respond in flush order
    responds = [dict(s.attrs)["rid"] for s in tr.spans if s.name == "respond"]
    flat = [r for f in log for r in f.rids]
    assert responds == flat
    # outputs land at the right request indices regardless
    assert all(o is not None for o in rep.outputs)


# ------------------------------------------------- real-engine parity


MODELS = [("gcn", False), ("gin", False), ("gin", True), ("gat", False),
          ("pna", False), ("dgn", False)]


def _reduced_config(model, vn=False, **kw):
    base = dict(num_layers=2, virtual_node=vn)
    if model == "gat":
        base.update(heads=2, head_features=8)
    elif model in ("pna", "dgn"):
        base.update(hidden=16, head_hidden=(8,))
    else:
        base.update(hidden=16)
    base.update(kw)
    return paper_config(model, **base)


@pytest.mark.parametrize("model,vn", MODELS)
@pytest.mark.parametrize("precision", ["fp32", "int8"])
def test_pipelined_stream_bitwise_parity_all_models(model, vn, precision, rng):
    """The threaded double-buffered ``PipelinedStream`` gives the bits of
    the blocking ``infer_stream`` loop for every model x precision."""
    cfg = _reduced_config(model, vn)
    params = init(KEY, cfg)
    gs = graphs(6, seed=11)
    eig = needs_eigvec(cfg)
    eng = GNNEngine(cfg, params, buckets=((16, 32),), precision=precision)
    base, _, _ = eng.infer_stream(gs, with_eigvec=eig)
    outs, stats = PipelinedStream(eng.executor, model=eng.name,
                                  inflight=2).run(gs, with_eigvec=eig)
    assert len(outs) == len(base) and stats["peak_inflight"] <= 2
    for a, b in zip(base, outs):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b)[:1])


def test_pipelined_stream_validation_and_staging(rng):
    cfg = _reduced_config("gin")
    eng = GNNEngine(cfg, init(KEY, cfg), buckets=((16, 32),))
    with pytest.raises(ValueError, match="inflight"):
        PipelinedStream(eng.executor, inflight=0)
    with pytest.raises(ValueError, match="prepare_ahead"):
        PipelinedStream(eng.executor, inflight=2, prepare_ahead=0)
    gs = graphs(4, seed=13)
    base, _, _ = eng.infer_stream(gs)
    for kwargs in (dict(stage=False), dict(prepare_ahead=3)):
        outs, _ = PipelinedStream(eng.executor, model=eng.name,
                                  inflight=2, **kwargs).run(gs)
        for a, b in zip(base, outs):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b)[:1])


def test_pack_prepared_stage_is_bitwise_transparent(rng):
    """``pack_prepared`` stages the batch on the device in one put; served,
    it gives the bits of the same batch left on the host."""
    from repro.core.batching import BucketBudget, pack_graphs, pack_layout, pack_prepared
    from repro.serve.executor import prepared

    cfg = _reduced_config("gin")
    eng = GNNEngine(cfg, init(KEY, cfg), buckets=((16, 32),))
    gs = graphs(4, seed=17)
    budget = BucketBudget(64, 128, 8)
    staged, _ = pack_prepared(gs, budget)
    packed, _ = pack_graphs(gs, budget)
    host = prepared(packed, None, pack_layout(packed), staged.bucket_key,
                    budget.g_pad)
    assert all(isinstance(x, jax.Array) for x in jax.tree.leaves(staged))
    assert all(isinstance(x, np.ndarray) for x in jax.tree.leaves(host))
    assert staged.signature == host.signature
    out_a, _ = eng.executor.run(host, model=eng.name)
    out_b, _ = eng.executor.run(staged, model=eng.name)
    np.testing.assert_array_equal(np.asarray(out_a), np.asarray(out_b))


# ------------------------------------------- executor satellites (LRU, D2H)


def test_eigvec_lru_hits_and_misses(rng):
    from repro.serve.executor import Executor

    reg = MetricsRegistry()
    ex = Executor(buckets=((16, 32),))
    ex.attach_telemetry(metrics=reg)
    g = graph(seed=21)

    def count(result):
        m = export.metrics_snapshot(reg)["metrics"].get(
            "serve_eigvec_cache_total", {"series": []})
        for s in m["series"]:
            if s["labels"]["result"] == result:
                return s["value"]
        return 0

    v1 = ex._eigvec(g[0], g[1], g[2].shape[0], 16)
    assert count("miss") == 1 and count("hit") == 0
    v2 = ex._eigvec(g[0], g[1], g[2].shape[0], 16)
    assert count("miss") == 1 and count("hit") == 1
    np.testing.assert_array_equal(np.asarray(v1), np.asarray(v2))
    # distinct edge list (same sizes) is a different key
    g2 = graph(seed=22)
    ex._eigvec(g2[0], g2[1], g2[2].shape[0], 16)
    assert count("miss") == 2
    # same edges, different padding: also a different key
    ex._eigvec(g[0], g[1], g[2].shape[0], 32)
    assert count("miss") == 3


def test_eigvec_lru_evicts_least_recent(monkeypatch):
    from repro.serve.executor import Executor

    ex = Executor(buckets=((16, 32),))
    monkeypatch.setattr(Executor, "_EIGVEC_LRU_SIZE", 2)
    ga, gb, gc = graph(seed=31), graph(seed=32), graph(seed=33)
    for g in (ga, gb, gc):
        ex._eigvec(g[0], g[1], g[2].shape[0], 16)
    assert len(ex._eigvec_lru) == 2  # ga evicted
    ex._eigvec(gb[0], gb[1], gb[2].shape[0], 16)  # hit, refreshes gb
    ex._eigvec(ga[0], ga[1], ga[2].shape[0], 16)  # re-miss, evicts gc
    keys = list(ex._eigvec_lru)
    assert len(keys) == 2


@pytest.mark.parametrize("n_pad", [8, 16], ids=["unpadded", "padded"])
def test_eigvec_stays_on_the_host(n_pad):
    """A miss and a hit both hand back the host solve itself: a read-only
    float32 ``np.ndarray``, bitwise ``laplacian_eigvec``'s vector."""
    from repro.data.pipeline import laplacian_eigvec
    from repro.serve.executor import Executor

    ex = Executor(buckets=((16, 32),))
    g = graph(seed=41)
    want = laplacian_eigvec(g[0], g[1], g[2].shape[0], n_pad)
    miss = ex._eigvec(g[0], g[1], g[2].shape[0], n_pad)
    hit = ex._eigvec(g[0], g[1], g[2].shape[0], n_pad)
    assert hit is miss
    for vec in (miss, hit):
        assert type(vec) is np.ndarray and vec.dtype == np.float32
        np.testing.assert_array_equal(vec, want)
        assert not vec.flags.writeable
        with pytest.raises(ValueError):
            vec[0] = 1.0


def test_prepare_stream_keeps_a_device_eigvec():
    """``prepare_stream`` puts the host vector itself, so its batch keeps a
    device eigenvector leaf with the solve's values."""
    from repro.data.pipeline import laplacian_eigvec
    from repro.serve.executor import Executor

    ex = Executor(buckets=((16, 32),))
    g = graph(seed=42)
    p = ex.prepare_stream(g, with_eigvec=True)
    assert isinstance(p.eigvec, jax.Array)
    np.testing.assert_array_equal(
        np.asarray(p.eigvec), laplacian_eigvec(g[0], g[1], g[2].shape[0], 16))


def test_d2h_span_and_counter(rng):
    """Every harvested run waits under a ``device_wait`` span and
    converts outputs under a ``d2h`` span, and the copy's seconds land in
    the ``serve_d2h_seconds_total`` counter."""
    cfg = _reduced_config("gin")
    tr = Tracer(RealClock())
    reg = MetricsRegistry()
    eng = GNNEngine(cfg, init(KEY, cfg), buckets=((16, 32),))
    eng.executor.attach_telemetry(tracer=tr, metrics=reg)
    gs = graphs(4, seed=41)
    eng.infer_stream(gs)
    d2h = [s for s in tr.spans if s.name == "d2h"]
    runs = [s for s in tr.spans if s.name == "device_wait"]
    assert len(d2h) == len(runs) == len(gs)
    assert all(dict(s.attrs)["dur_s"] >= 0.0 for s in d2h)
    text = export.prometheus_text(reg)
    assert "serve_d2h_seconds_total" in text
    total = sum(dict(s.attrs)["dur_s"] for s in d2h)
    snap = export.metrics_snapshot(reg)
    val = snap["metrics"]["serve_d2h_seconds_total"]["series"][0]["value"]
    assert val == pytest.approx(total)


def test_run_async_pending_run_contract(rng):
    """``run_async`` returns an unharvested future; ``result()`` closes
    the timed region once and caches; ``run`` is exactly
    ``run_async().result()``."""
    cfg = _reduced_config("gin")
    eng = GNNEngine(cfg, init(KEY, cfg), buckets=((16, 32),))
    ex = eng.executor
    p = ex.prepare_stream(graph(seed=51))
    pr = ex.run_async(p, model=eng.name)
    assert not pr.done
    out, dt = pr.result()
    assert pr.done and dt >= 0.0
    out2, dt2 = pr.result()  # cached: same object, no re-harvest
    assert out2 is out and dt2 == dt
    out3, _ = ex.run(ex.prepare_stream(graph(seed=51)), model=eng.name)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(out3))


# ----------------------------------------------------------------- clocks


def test_real_clock_advance_to_stamps():
    c = RealClock()
    t = c.now()
    assert c.advance_to(t + 100.0) >= t  # live time cannot jump


def test_virtual_clock_advance_to_monotone():
    c = VirtualClock(1.0)
    assert c.advance_to(2.5) == 2.5
    with pytest.raises(ValueError, match="backwards"):
        c.advance_to(2.0)
