"""Pipelined execution: dispatch-ahead scheduling, the bounded in-flight
window, FIFO harvest, exact virtual-clock overlap sims, the threaded
``PipelinedStream`` runner, eigvec LRU, and D2H accounting.

Same discipline as ``tests/test_slo_sim.py`` / ``tests/test_obs.py``:
scripted arrival traces + scripted service/host-pack times on a
``VirtualClock``, binary-fraction timestamps, assertions by exact float
equality — never tolerances.  Real-engine parity cases assert *bitwise*
output equality between the serial and pipelined paths.
"""
import jax
import numpy as np
import pytest

from conftest import scripted_executor
from repro.gnn import init
from repro.gnn.models import paper_config
from repro.obs import MetricsRegistry, Tracer, export
from repro.serve.clock import RealClock, VirtualClock
from repro.serve.gnn_engine import GNNEngine
from repro.serve.pipeline import (
    PipelineConfig,
    PipelinedStream,
    as_pipeline,
    overlap_fraction,
)
from repro.serve.scheduler import StreamScheduler

KEY = jax.random.PRNGKey(0)
# binary fractions: every modeled timestamp below is exact in float64
MW = 0.0009765625  # max_wait_s = 2**-10
A1 = 0.001953125  # 2**-9
A2 = 0.00390625  # 2**-8
H = 0.0029296875  # scripted host-pack seconds = 3 * 2**-10
SVC = 0.00390625  # scripted flush compute = 2**-8


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


def flush_rows(rep, with_start=True):
    return [
        (f.rids, f.reason, f.at_s, f.start_s, f.done_s, f.compute_s)
        if with_start else (f.rids, f.reason, f.at_s, f.done_s, f.compute_s)
        for f in rep.flush_log
    ]


# ------------------------------------------------------------ config surface


def test_pipeline_config_validation():
    assert PipelineConfig().inflight == 2
    with pytest.raises(ValueError, match="inflight"):
        PipelineConfig(inflight=0)
    with pytest.raises(ValueError, match="host_cost"):
        PipelineConfig(host_cost="wall")
    with pytest.raises(ValueError, match="host_cost"):
        PipelineConfig(host_cost=-0.001)
    with pytest.raises(ValueError, match="host_cost"):
        PipelineConfig(host_cost=[0.001, -0.002])
    with pytest.raises(ValueError, match="host_cost"):
        PipelineConfig(host_cost=[])
    assert PipelineConfig(host_cost="measured").measured
    assert not PipelineConfig(host_cost=0.001).measured


def test_as_pipeline_normalization():
    assert as_pipeline(None) is None
    assert as_pipeline(False) is None
    assert as_pipeline(True) == PipelineConfig()
    assert as_pipeline(3) == PipelineConfig(inflight=3)
    cfg = PipelineConfig(inflight=4, host_cost=0.001)
    assert as_pipeline(cfg) is cfg
    with pytest.raises(ValueError, match="pipeline"):
        as_pipeline("deep")


def test_host_cost_fn_forms():
    assert PipelineConfig(host_cost=None).host_cost_fn()(7) == 0.0
    assert PipelineConfig(host_cost=H).host_cost_fn()(3) == H
    seq = PipelineConfig(host_cost=[0.001, 0.002]).host_cost_fn()
    assert [seq(0), seq(1), seq(2), seq(9)] == [0.001, 0.002, 0.002, 0.002]
    assert PipelineConfig(host_cost="measured").host_cost_fn() is None


# -------------------------------------------- serial equivalence at depth 1


def _paced_run(pipeline, slo=None):
    ex = scripted_executor(service_s=[0.004, 0.002, 0.006, 0.003])
    s = StreamScheduler(ex, capacity=2, max_wait_s=0.0015, slo_s=slo,
                        service_s=0.004, pipeline=pipeline)
    gs = graphs(12, seed=3)
    return s.run(gs, arrivals=[0.001 * i for i in range(len(gs))])


def test_depth1_free_host_cost_equals_serial():
    """``pipeline=PipelineConfig(inflight=1)`` with the default free host
    cost reproduces the serial loop exactly — same flush decisions, rids,
    reasons, completion times, latencies, and outputs.  Only ``start_s``
    is allowed to differ: serial records the modeled *device* start,
    pipelined records the *dispatch* instant."""
    ser = _paced_run(None)
    p1 = _paced_run(PipelineConfig(inflight=1))
    assert flush_rows(ser, with_start=False) == flush_rows(p1, with_start=False)
    np.testing.assert_array_equal(ser.latencies_s, p1.latencies_s)
    for a, b in zip(ser.outputs, p1.outputs):
        np.testing.assert_array_equal(a, b)
    assert ser.makespan_s == p1.makespan_s
    # dispatch instant <= modeled device start, always
    for fs, fp in zip(ser.flush_log, p1.flush_log):
        assert fp.start_s <= fs.start_s


def test_depth1_equivalence_with_slo_shedding():
    ser = _paced_run(None, slo=0.006)
    p1 = _paced_run(PipelineConfig(inflight=1), slo=0.006)
    assert [(s.rid, s.reason, s.at_s, s.projected_delay_s) for s in ser.shed] \
        == [(s.rid, s.reason, s.at_s, s.projected_delay_s) for s in p1.shed]
    assert flush_rows(ser, with_start=False) == flush_rows(p1, with_start=False)


# ------------------------------------------------- exact overlap simulation


def _overlap_sim(tracer=None, metrics=None, inflight=2, host_cost=H):
    """Three singleton deadline flushes with scripted host + service
    times — every timestamp below is hand-computed and binary-exact."""
    ex = scripted_executor(service_s=SVC)
    s = StreamScheduler(
        ex, capacity=2, max_wait_s=MW, tracer=tracer, metrics=metrics,
        pipeline=PipelineConfig(inflight=inflight, host_cost=host_cost),
    )
    rep = s.run([graph(seed=0), graph(seed=1), graph(seed=2)],
                arrivals=[0.0, A1, A2])
    return ex, rep


def test_exact_virtual_clock_overlap_sim():
    """The full modeled timeline of the worked example, by exact float
    equality.  Flush 1 *dispatches* (start_s) before flush 0 completes —
    that is the overlap the serial loop cannot express."""
    _, rep = _overlap_sim()
    # f0: deadline at 2**-10; pack H; device free -> runs immediately
    # f1: deadline at A1+MW; pack queues behind f0's pack (host_free),
    #     device queues behind f0 (device_free)
    # f2: window full at its deadline -> dispatch gate waits for f0's
    #     completion (slot), reason "drain" (stream exhausted)
    assert flush_rows(rep) == [
        ((0,), "deadline", MW, MW + H, MW + H + SVC, SVC),
        ((1,), "deadline", A1 + MW, MW + 2 * H,
         MW + H + 2 * SVC, SVC),
        ((2,), "drain", MW + H + SVC, MW + H + SVC + H,
         MW + H + 3 * SVC, SVC),
    ]
    np.testing.assert_array_equal(rep.latencies_s, [
        MW + H + SVC,
        MW + H + 2 * SVC - A1,
        MW + H + 3 * SVC - A2,
    ])
    assert rep.makespan_s == MW + H + 3 * SVC
    # the overlap itself: flush 1 dispatched strictly before flush 0 done
    f0, f1, f2 = rep.flush_log
    assert f1.start_s < f0.done_s
    # FIFO: completion (== flush-log) order is dispatch order
    assert [f.rids for f in rep.flush_log] == [(0,), (1,), (2,)]
    assert f0.done_s <= f1.done_s <= f2.done_s


def test_pipelined_sim_is_bitwise_reproducible():
    tr_a, tr_b = Tracer(VirtualClock()), Tracer(VirtualClock())
    reg_a, reg_b = MetricsRegistry(), MetricsRegistry()
    _, rep_a = _overlap_sim(tracer=tr_a, metrics=reg_a)
    _, rep_b = _overlap_sim(tracer=tr_b, metrics=reg_b)
    assert flush_rows(rep_a) == flush_rows(rep_b)
    np.testing.assert_array_equal(rep_a.latencies_s, rep_b.latencies_s)
    assert export.trace_json(tr_a) == export.trace_json(tr_b)
    assert export.prometheus_text(reg_a) == export.prometheus_text(reg_b)


def test_pipelined_trace_models_overlap():
    """The trace's pack span for flush k+1 genuinely overlaps the device
    span for flush k on the virtual timeline, and ``overlap_fraction``
    reports it; a serial run reports 0.0 (zero-width pack markers)."""
    tr = Tracer(VirtualClock())
    _overlap_sim(tracer=tr)
    packs = [s for s in tr.spans if s.name == "pack"]
    devs = [s for s in tr.spans if s.name == "device"]
    assert len(packs) == 3 and len(devs) == 3
    assert all(s.dur_s == H for s in packs)
    # pack of flush 1 inside device of flush 0
    assert packs[1].t0_s < devs[0].t1_s and packs[1].t1_s > devs[0].t0_s
    frac = overlap_fraction(tr)
    assert 0.0 < frac <= 1.0
    # hand-check: pack0 [MW, MW+H] vs device union starting at MW+H ->
    # pack0 contributes 0; packs 1 and 2 fully covered -> 2/3
    assert frac == pytest.approx(2.0 / 3.0)
    tr_ser = Tracer(VirtualClock())
    ex = scripted_executor(service_s=SVC)
    StreamScheduler(ex, capacity=2, max_wait_s=MW, tracer=tr_ser).run(
        [graph(seed=0)], arrivals=[0.0])
    assert overlap_fraction(tr_ser) == 0.0


def test_dispatch_events_and_inflight_metric():
    tr = Tracer(VirtualClock())
    reg = MetricsRegistry()
    _, rep = _overlap_sim(tracer=tr, metrics=reg)
    dispatches = [s for s in tr.spans if s.name == "dispatch"]
    assert len(dispatches) == len(rep.flush_log)
    by_attr = [dict(s.attrs) for s in dispatches]
    assert all(1 <= a["inflight"] <= 2 for a in by_attr)
    snap = export.metrics_snapshot(reg)
    assert export.validate_metrics_snapshot(snap) == len(snap["metrics"])
    text = export.prometheus_text(reg)
    assert "serve_inflight_depth 0" in text  # drained at end of run
    assert "serve_pack_ewma_seconds" in text


def test_pack_ewma_tracks_scripted_host_costs():
    """Scripted per-flush host costs fold into the per-signature pack
    EWMA with the ``svc_alpha`` coefficient — exact values."""
    ex = scripted_executor(service_s=SVC)
    s = StreamScheduler(
        ex, capacity=2, max_wait_s=MW, svc_alpha=0.5,
        pipeline=PipelineConfig(inflight=2, host_cost=[0.002, 0.004, 0.008]),
    )
    s.run([graph(seed=0), graph(seed=1), graph(seed=2)],
          arrivals=[0.0, A1, A2])
    sig = (32, 96)
    # ewma: 0.002 -> 0.5*0.002+0.5*0.004 = 0.003 -> 0.5*0.003+0.5*0.008
    assert s.pack_estimate_s(sig) == 0.5 * (0.5 * (0.002 + 0.004)) + 0.5 * 0.008
    # a fresh signature projects zero pack cost
    assert s.pack_estimate_s((64, 192)) == 0.0


def test_admission_projection_accounts_host_pack_backlog():
    """With a scripted host-pack cost the admission projection grows by
    the pack EWMA, so a tight-SLO stream sheds more than the free-host
    run at the same depth — and at depth 1 the free-host pipelined run
    sheds exactly like serial (depth 2 may legitimately differ: a bucket
    dispatching at its deadline while the device is busy changes batch
    composition versus serial, which lets late arrivals pack in)."""
    def run(pipeline):
        ex = scripted_executor(service_s=0.004)
        s = StreamScheduler(ex, capacity=1, max_wait_s=0.0005,
                            slo_s=0.0105, service_s=0.004, pipeline=pipeline)
        gs = graphs(10, seed=5)
        return s.run(gs, arrivals=[0.0008 * i for i in range(len(gs))])

    ser = run(None)
    d1 = run(PipelineConfig(inflight=1, host_cost=None))
    free = run(PipelineConfig(inflight=2, host_cost=None))
    costly = run(PipelineConfig(inflight=2, host_cost=0.004))
    assert [(s.rid, s.reason, s.at_s, s.projected_delay_s) for s in ser.shed] \
        == [(s.rid, s.reason, s.at_s, s.projected_delay_s) for s in d1.shed]
    assert len(costly.shed) > len(free.shed)
    # conservation holds in every mode
    for rep in (ser, d1, free, costly):
        assert rep.num_served + rep.num_shed == rep.num_requests


# ------------------------------------------------------ in-flight window


def test_inflight_window_bounds():
    """At depth d, flush k cannot dispatch before flush k-d completed:
    the window is a hard bound on dispatched-but-unharvested flushes."""
    for depth in (1, 2, 4):
        ex = scripted_executor(service_s=SVC)
        s = StreamScheduler(
            ex, capacity=1, max_wait_s=MW,
            pipeline=PipelineConfig(inflight=depth, host_cost=0.0001),
        )
        rep = s.run(graphs(12, seed=7), qps=0.0)  # saturation
        log = rep.flush_log
        assert len(log) >= depth + 2
        for k in range(depth, len(log)):
            assert log[k].start_s >= log[k - depth].done_s
        # ...and depth genuinely allows dispatch-ahead: some flush starts
        # before its predecessor completes whenever the window has room
        if depth >= 2:
            assert any(log[k].start_s < log[k - 1].done_s
                       for k in range(1, len(log)))


def test_fifo_response_order_under_unequal_service_times():
    """A short flush dispatched behind a long one still completes and
    responds after it (serial device + FIFO harvest): response order is
    dispatch order, never compute-time order."""
    ex = scripted_executor(service_s=[0.016, 0.0005, 0.0005])
    tr = Tracer(VirtualClock())
    s = StreamScheduler(
        ex, capacity=1, max_wait_s=MW, tracer=tr,
        pipeline=PipelineConfig(inflight=3, host_cost=None),
    )
    rep = s.run(graphs(6, seed=9), qps=0.0)
    log = rep.flush_log
    assert len(log) >= 3
    assert [f.done_s for f in log] == sorted(f.done_s for f in log)
    # rids respond in dispatch order
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
def test_pipelined_bitwise_parity_all_models(model, vn, precision, rng):
    """Pipelined outputs are bitwise-equal to serial for every model x
    precision, in both serving shapes: the packed scheduler path
    (serial loop vs pipelined loop) and the streaming path
    (``infer_stream`` vs the threaded ``PipelinedStream``)."""
    cfg = _reduced_config(model, vn)
    params = init(KEY, cfg)
    gs = graphs(6, seed=11)
    eig = model == "dgn"
    eng = GNNEngine(cfg, params, buckets=((16, 32),), precision=precision)
    # packed: same engine, serial then pipelined scheduler runs
    ser = StreamScheduler(eng, capacity=2, max_wait_s=0.002,
                          with_eigvec=eig).run(gs)
    pipe = StreamScheduler(eng, capacity=2, max_wait_s=0.002,
                           with_eigvec=eig,
                           pipeline=PipelineConfig(inflight=2)).run(gs)
    assert [f.rids for f in ser.flush_log] == [f.rids for f in pipe.flush_log]
    for a, b in zip(ser.outputs, pipe.outputs):
        np.testing.assert_array_equal(a, b)
    # stream: blocking loop vs threaded double-buffered runner
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
