"""Serving telemetry: exact span boundaries, registry counters, exporter
schemas, bitwise-deterministic traces, and the provably-free dark path.

Same discipline as ``tests/test_slo_sim.py``: every scenario scripts an
arrival trace + service times into the ``scripted_executor`` fake on a
``VirtualClock``, so every span boundary and counter value is an exact
float — assertions are equalities, never tolerances.  Timestamps are
binary fractions so the expected sums are exact in float64.
"""
import contextlib
import glob
import json
import os

import numpy as np
import pytest

from conftest import scripted_executor
from repro.obs import MetricsRegistry, NullTracer, Tracer, export
from repro.obs import trace as trace_mod
from repro.obs.metrics import ServingInstruments, default_registry
from repro.serve import scheduler as sched_mod
from repro.serve.clock import VirtualClock
from repro.serve.scheduler import StreamScheduler

MW = 0.015625  # max_wait_s = 1/64: binary-exact
SVC = 0.00390625  # scripted flush compute = 1/256
A1 = 0.001953125  # second arrival = 1/512
DONE = A1 + SVC  # budget flush completion


def graph(n=8, e=12, feat=4, seed=0):
    rng = np.random.default_rng(seed)
    return (
        rng.integers(0, n, e).astype(np.int32),
        rng.integers(0, n, e).astype(np.int32),
        rng.normal(size=(n, feat)).astype(np.float32),
        rng.normal(size=(e, 3)).astype(np.float32),
    )


def run_budget_flush(tracer=None, metrics=None):
    """Two arrivals fill one capacity-1 bucket: a single ``budget`` flush
    at the second arrival — the smallest fully-scripted lifecycle."""
    ex = scripted_executor(service_s=SVC)
    s = StreamScheduler(ex, capacity=1, max_wait_s=MW,
                        tracer=tracer, metrics=metrics)
    rep = s.run([graph(seed=0), graph(seed=1)], arrivals=[0.0, A1])
    return ex, rep


def spans_by_name(tracer, name):
    return [s for s in tracer.spans if s.name == name]


# ----------------------------------------------------- exact span timeline


def test_scripted_run_emits_exact_span_boundaries():
    tracer = Tracer(VirtualClock())
    _, rep = run_budget_flush(tracer=tracer)
    assert rep.num_served == 2 and rep.flush_reasons == {"budget": 1}

    # recorded order is deterministic: admits, then the flush's pack/
    # unpack (inside _execute), then the timeline spans + responds
    assert [(s.name, s.track) for s in tracer.spans] == [
        ("admit", "scheduler"), ("admit", "scheduler"),
        ("pack", "host"), ("unpack", "host"),
        ("queue", "scheduler"), ("queue", "scheduler"),
        ("flush", "scheduler"), ("device", "device"),
        ("respond", "scheduler"), ("respond", "scheduler"),
    ]

    a0, a1 = spans_by_name(tracer, "admit")
    assert (a0.t0_s, a0.t1_s) == (0.0, None)
    assert (a1.t0_s, a1.t1_s) == (A1, None)
    assert dict(a0.attrs)["rid"] == 0 and dict(a1.attrs)["rid"] == 1
    assert dict(a0.attrs)["tenant"] == "default"
    assert dict(a0.attrs)["bucket"] == str((32, 96))

    q0, q1 = spans_by_name(tracer, "queue")
    assert (q0.t0_s, q0.t1_s) == (0.0, A1)  # rid 0 waits for the fill
    assert (q1.t0_s, q1.t1_s) == (A1, A1)  # rid 1 triggers the flush

    # host stages are zero-duration markers at the flush instant: the
    # VirtualClock does not move during host work
    (pack,), (unpack,) = (spans_by_name(tracer, n) for n in ("pack", "unpack"))
    assert (pack.t0_s, pack.t1_s) == (A1, A1)
    assert (unpack.t0_s, unpack.t1_s) == (A1, A1)
    # the flush crosses as one array: the graph's seven leaves in 128-word
    # segments of one int32 buffer (no plan: the scripted tenant shares none)
    assert dict(pack.attrs) == {"tenant": "default", "graphs": 2, "rung": 1,
                                "put_arrays": 1, "put_bytes": 5120}

    (fl,) = spans_by_name(tracer, "flush")
    assert (fl.t0_s, fl.t1_s) == (A1, DONE)
    assert dict(fl.attrs) == {"tenant": "default", "priority": 0,
                              "reason": "budget", "graphs": 2,
                              "sig": str((32, 96)), "rung": 1}

    (dev,) = spans_by_name(tracer, "device")
    assert (dev.t0_s, dev.t1_s) == (A1, DONE)
    assert dict(dev.attrs)["compute_s"] == SVC

    r0, r1 = spans_by_name(tracer, "respond")
    assert (r0.t0_s, r1.t0_s) == (DONE, DONE)
    assert dict(r0.attrs) == {"rid": 0, "latency_s": DONE, "miss": False}
    assert dict(r1.attrs) == {"rid": 1, "latency_s": DONE - A1, "miss": False}


def test_scripted_run_counts_exactly_in_the_registry():
    reg = MetricsRegistry()
    _, rep = run_budget_flush(metrics=reg)

    lab = dict(tenant="default", priority="0")
    assert reg.get("serve_requests_total").value(**lab) == 2
    assert reg.get("serve_admitted_total").value(**lab) == 2
    assert reg.get("serve_served_total").value(**lab) == 2
    assert reg.get("serve_shed_total").total() == 0
    assert reg.get("serve_deadline_misses_total").total() == 0
    assert reg.get("serve_flushes_total").value(reason="budget") == 1
    fg = reg.get("serve_flush_graphs")
    assert (fg.count(), fg.sum()) == (1, 2.0)
    lat = reg.get("serve_request_latency_seconds")
    assert lat.count(**lab) == 2
    assert lat.sum(**lab) == DONE + (DONE - A1)
    # first observation seeds the EWMA with the measured compute verbatim
    assert reg.get("serve_service_ewma_seconds").value(sig="32x96") == SVC
    assert reg.get("serve_queue_depth").value() == 0
    assert reg.get("serve_open_buckets").value() == 0
    # the registry and the report are views over the same events
    assert reg.get("serve_served_total").total() == rep.num_served
    assert reg.get("serve_flushes_total").total() == len(rep.flush_log)


def test_shed_and_miss_events_reach_tracer_registry_and_ledger():
    """queue_full sheds + a deadline miss land as structured events, and
    the admission ledger renders *from the registry*."""
    tracer, reg = Tracer(VirtualClock()), MetricsRegistry()
    ex = scripted_executor(service_s=SVC)
    s = StreamScheduler(ex, capacity=1, max_wait_s=MW, admit_limit=1,
                        slo_s=0.001, tracer=tracer, metrics=reg)
    # rid 0 admitted; rids 1-2 shed queue_full; SLO 1ms tightens the
    # bucket deadline to 0.001, and 0.001 + SVC overruns it -> one miss
    rep = s.run([graph(seed=i) for i in range(3)], arrivals=[0.0, 0.0, 0.0])

    assert rep.num_served == 1 and rep.num_shed == 2
    assert rep.deadline_misses == 1
    assert [x.reason for x in rep.shed] == ["queue_full", "queue_full"]

    sheds = spans_by_name(tracer, "shed")
    assert [(s.t0_s, dict(s.attrs)["rid"]) for s in sheds] == [(0.0, 1), (0.0, 2)]
    assert all(dict(s.attrs)["reason"] == "queue_full" for s in sheds)
    (resp,) = spans_by_name(tracer, "respond")
    assert dict(resp.attrs)["miss"] is True

    lab = dict(tenant="default", priority="0")
    assert reg.get("serve_shed_total").value(reason="queue_full", **lab) == 2
    assert reg.get("serve_deadline_misses_total").value(**lab) == 1
    assert export.admission_line(reg) == (
        "admission: served 1  shed 2 ({'queue_full': 2}); deadline misses 1"
    )


def test_admission_line_renders_compile_warm_split_and_aot_tally():
    """Once the executor has paid untimed work, the ledger shows the
    compile/warm split and the AOT cache outcome tally."""
    reg = MetricsRegistry()
    mi = ServingInstruments(reg)
    mi.served.inc(1, tenant="default", priority="0")
    mi.compile_seconds.inc(1.25)
    mi.warm_seconds.inc(0.5)
    mi.aot_cache.inc(2, result="hit")
    mi.aot_cache.inc(1, result="miss")
    assert export.admission_line(reg) == (
        "admission: served 1  shed 0 ({}); deadline misses 0; "
        "untimed compile 1.25s + warm 0.50s; aot hit 2 miss 1 stale 0"
    )


# --------------------------------------------------- bitwise-identical trace


def test_trace_json_is_bitwise_identical_across_runs():
    docs, snaps = [], []
    for _ in range(2):
        tracer, reg = Tracer(VirtualClock()), MetricsRegistry()
        run_budget_flush(tracer=tracer, metrics=reg)
        docs.append(export.trace_json(tracer))
        snaps.append(json.dumps(reg.snapshot(), sort_keys=True))
    assert docs[0] == docs[1]
    assert snaps[0] == snaps[1]


# ------------------------------------------------------- dark path is free


SLOW_SLO = 0.125  # 1/8: generous, so the free-path scenario serves all


class CountingClock(VirtualClock):
    """A VirtualClock that counts its reads."""

    __slots__ = ("reads",)

    def __init__(self):
        super().__init__()
        self.reads = 0

    def now(self) -> float:
        self.reads += 1
        return super().now()


class RecordingAnnotation:
    """Stands in for ``jax.profiler.TraceAnnotation``: notes each name."""

    opened: list = []

    def __init__(self, name, **attrs):
        self.opened.append(name)

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False


def test_disabled_telemetry_is_provably_free(monkeypatch):
    """No tracer/registry attached: identical flush log, latencies, and
    executor call sequence — the no-op sink changes nothing.  Its spans
    still open their profiler annotations, and read no clock: a run with
    every span taken out reads the run's clock exactly as often."""
    ex_on = scripted_executor(service_s=SVC)
    ex_off = scripted_executor(service_s=SVC)
    graphs = [graph(seed=i) for i in range(6)]
    arrivals = [0.0, A1, 2 * A1, 3 * A1, MW, MW + A1]
    kw = dict(capacity=2, max_wait_s=MW, slo_s=SLOW_SLO, admit_limit=3)
    rep_on = StreamScheduler(ex_on, tracer=Tracer(VirtualClock()),
                             metrics=MetricsRegistry(), **kw).run(
        graphs, arrivals=arrivals)
    monkeypatch.setattr(trace_mod, "TraceAnnotation", RecordingAnnotation)
    monkeypatch.setattr(RecordingAnnotation, "opened", [])
    clock_off = CountingClock()
    rep_off = StreamScheduler(ex_off, clock=clock_off, **kw).run(
        graphs, arrivals=arrivals)

    assert rep_on.flush_log == rep_off.flush_log  # frozen dataclasses: exact
    assert rep_on.shed == rep_off.shed
    np.testing.assert_array_equal(rep_on.latencies_s, rep_off.latencies_s)
    assert ex_on.run_log == ex_off.run_log

    opened = RecordingAnnotation.opened
    assert opened.count("repro.run") == 1
    for stage in ("repro.flush", "repro.pack", "repro.unpack"):
        assert opened.count(stage) == len(rep_off.flush_log)
    bare = lambda *a, **k: contextlib.nullcontext()  # noqa: E731
    monkeypatch.setattr(NullTracer, "span", bare)
    monkeypatch.setattr(sched_mod, "annotate", bare)
    clock_bare = CountingClock()
    rep_bare = StreamScheduler(scripted_executor(service_s=SVC),
                               clock=clock_bare, **kw).run(
        graphs, arrivals=arrivals)
    assert rep_bare.flush_log == rep_off.flush_log
    assert clock_off.reads == clock_bare.reads > 0


def test_disabled_telemetry_adds_zero_compile_keys():
    """A real engine compiles the identical program-key set with and
    without telemetry attached — the sinks stage nothing into jit.  The
    telemetry pass doubles as the executor-accounting check: compile/
    warm/device events and counters land in the attached sinks."""
    import jax

    from repro.gnn import init
    from repro.gnn.models import paper_config
    from repro.serve.gnn_engine import GNNEngine

    cfg = paper_config("gin")
    params = init(jax.random.PRNGKey(0), cfg)
    graphs = [graph(seed=i, feat=9, e=16) for i in range(4)]

    keys = []
    for telemetry in (False, True):
        eng = GNNEngine(cfg, params)
        kw = {}
        if telemetry:
            tracer, reg = Tracer(VirtualClock()), MetricsRegistry()
            kw = dict(tracer=tracer, metrics=reg)
        rep = StreamScheduler(eng, capacity=2, max_wait_s=MW, **kw).run(
            graphs, arrivals=[0.0, A1, 2 * A1, 3 * A1])
        keys.append(set(eng._compiled))
    assert keys[0] == keys[1] and keys[0]

    # executor-side accounting from the telemetry pass: one program per
    # eager-warmed rung, warm time tracked outside the timed region, and
    # device seconds exactly the flush-compute view of the report
    assert reg.get("serve_programs_built_total").value() == len(keys[1])
    assert reg.get("serve_warms_total").value() == len(keys[1])
    assert reg.get("serve_compile_seconds_total").value() > 0
    assert reg.get("serve_device_seconds_total").value() == rep.compute_s
    assert spans_by_name(tracer, "program_build")
    assert spans_by_name(tracer, "warm")
    assert len(spans_by_name(tracer, "device_wait")) == len(rep.flush_log)


# ------------------------------------------------------ profiler stage spans


def profiler_spans(trace_dir):
    """``[name, start_ns, end_ns]`` of every ``repro.*`` host event in the
    one ``.xplane.pb`` a ``jax.profiler.trace(trace_dir)`` wrote, outer
    spans before the spans they enclose."""
    from jax.profiler import ProfileData

    (path,) = glob.glob(os.path.join(trace_dir, "plugins", "profile", "*",
                                     "*.xplane.pb"))
    spans = [[e.name, int(e.start_ns), int(e.end_ns)]
             for plane in ProfileData.from_file(path).planes
             if plane.name.startswith("/host:")
             for line in plane.lines for e in line.events
             if e.name.startswith(trace_mod.PROFILER_PREFIX)]
    return sorted(spans, key=lambda s: (s[1], -s[2]))


@pytest.mark.parametrize("model", ["gin", "dgn"])
def test_stage_spans_reach_the_profiler_trace(model, tmp_path):
    """A served run under ``jax.profiler.trace`` leaves one ``repro.flush``
    per flush, each holding its stages in serving order, all inside the
    call's ``repro.run``; the eigenvector only for DGN, and no compile
    once the ladder is warm."""
    import jax

    from repro.gnn import init
    from repro.gnn.models import paper_config
    from repro.serve.gnn_engine import GNNEngine

    cfg = paper_config(model, num_layers=2, hidden=16,
                       **({"head_hidden": (8,)} if model == "dgn" else {}))
    eng = GNNEngine(cfg, init(jax.random.PRNGKey(0), cfg))
    sched = StreamScheduler(eng, capacity=2, max_wait_s=MW, with_eigvec="auto")
    graphs = [graph(seed=i, feat=9, e=16) for i in range(5)]
    arrivals = [0.0, A1, 2 * A1, 3 * A1, MW + A1]
    sched.run(graphs, arrivals=arrivals)  # warms every rung it reaches
    with jax.profiler.trace(str(tmp_path)):
        rep = sched.run(graphs, arrivals=arrivals)
    spans = profiler_spans(str(tmp_path))

    (run,) = [s for s in spans if s[0] == "repro.run"]
    flushes = [s for s in spans if s[0] == "repro.flush"]
    assert len(flushes) == len(rep.flush_log) == 2
    stages = (["repro.eigvec"] if model == "dgn" else []) + [
        "repro.pack", "repro.layout", "repro.dispatch", "repro.device_wait",
        "repro.d2h", "repro.unpack"]
    for _, lo, hi in flushes:
        assert run[1] <= lo <= hi <= run[2]
        inside = [s for s in spans if lo <= s[1] and s[2] <= hi
                  and s[0] != "repro.flush"]
        assert [s[0] for s in inside] == stages
        by = {s[0]: s for s in inside}
        pack, layout = by["repro.pack"], by["repro.layout"]
        assert pack[1] <= layout[1] <= layout[2] <= pack[2]
        ends = [by[n][2] for n in stages if n != "repro.layout"]
        starts = [by[n][1] for n in stages if n != "repro.layout"]
        assert all(e <= s for e, s in zip(ends, starts[1:]))
    names = {s[0] for s in spans}
    assert ("repro.eigvec" in names) == (model == "dgn")
    assert "repro.compile" not in names


# ------------------------------------------------------------ kernel census


def test_kernel_dispatch_decisions_are_counted():
    from repro.kernels import ops

    reg = default_registry()
    c = reg.counter("kernels_dispatch_total")
    before = c.value(op="node_mlp", path="reference")
    x = np.zeros((4, 8), np.float32)
    w = np.zeros((8, 8), np.float32)
    b = np.zeros((8,), np.float32)
    ops.node_mlp(x, w, b, mode="reference")
    assert c.value(op="node_mlp", path="reference") == before + 1


# -------------------------------------------------------- exporter schemas


def test_registry_rejects_names_outside_the_catalog():
    reg = MetricsRegistry()
    with pytest.raises(ValueError, match="closed"):
        reg.counter("serve_totally_new_total")
    with pytest.raises(ValueError, match="counter"):
        reg.gauge("serve_requests_total")  # catalog type mismatch
    with pytest.raises(ValueError, match="labels"):
        reg.counter("serve_requests_total", labels=("tenant",))


def test_metrics_snapshot_golden_schema_and_validation():
    reg = MetricsRegistry()
    run_budget_flush(metrics=reg)
    doc = reg.snapshot()
    assert doc["schema"] == "repro-metrics/v1"
    assert export.validate_metrics_snapshot(doc) == len(doc["metrics"])
    m = doc["metrics"]["serve_served_total"]
    assert m["type"] == "counter" and m["labelnames"] == ["tenant", "priority"]
    assert m["series"] == [
        {"labels": {"tenant": "default", "priority": "0"}, "value": 2.0}
    ]
    # an unregistered name fails validation — the surface is closed
    doc["metrics"]["serve_rogue_total"] = {
        "type": "counter", "help": "", "labelnames": [], "series": []}
    with pytest.raises(ValueError, match="unregistered"):
        export.validate_metrics_snapshot(doc)


def test_prometheus_text_exposition():
    reg = MetricsRegistry()
    run_budget_flush(metrics=reg)
    text = export.prometheus_text(reg)
    assert "# HELP serve_served_total" in text
    assert "# TYPE serve_served_total counter" in text
    assert 'serve_served_total{tenant="default",priority="0"} 2' in text
    assert 'serve_flushes_total{reason="budget"} 1' in text
    # cumulative histogram with the implicit +Inf bucket == count
    assert 'serve_flush_graphs_bucket{le="2"} 1' in text
    assert 'serve_flush_graphs_bucket{le="+Inf"} 1' in text
    assert "serve_flush_graphs_sum 2" in text
    assert "serve_flush_graphs_count 1" in text


def test_trace_event_export_golden_schema():
    tracer = Tracer(VirtualClock())
    run_budget_flush(tracer=tracer)
    doc = export.trace_events(tracer)
    assert export.validate_trace_events(doc) == len(tracer.spans)
    meta = [e for e in doc["traceEvents"] if e["ph"] == "M"]
    names = {e["args"]["name"] for e in meta}
    assert "repro-serve" in names  # process row
    assert {"scheduler", "device", "host"} <= names  # one row per track
    flush = next(e for e in doc["traceEvents"]
                 if e["ph"] == "X" and e["name"] == "flush")
    assert flush["ts"] == round(A1 * 1e6, 3)
    assert flush["dur"] == round(SVC * 1e6, 3)
    assert flush["args"]["reason"] == "budget"
    respond = next(e for e in doc["traceEvents"] if e["name"] == "respond")
    assert respond["ph"] == "i" and respond["s"] == "t"
    with pytest.raises(ValueError, match="ph"):
        export.validate_trace_events(
            {"traceEvents": [{"name": "x", "ph": "B", "pid": 1, "tid": 1}]})


# ------------------------------------------------------------- svc_alpha


def test_svc_alpha_is_a_real_knob_with_exact_ewma():
    script = [SVC, 2 * SVC, 4 * SVC]
    for alpha, expect in ((0.5, None), (0.25, None), (1.0, 4 * SVC)):
        ex = scripted_executor(service_s=script)
        s = StreamScheduler(ex, capacity=1, max_wait_s=MW, svc_alpha=alpha,
                            metrics=(reg := MetricsRegistry()))
        # three isolated drain flushes: arrivals a bucket-lifetime apart
        s.run([graph(seed=i) for i in range(3)],
              arrivals=[0.0, 0.0625, 0.125])
        ewma = script[0]
        for dt in script[1:]:
            ewma = (1.0 - alpha) * ewma + alpha * dt
        if expect is not None:
            assert ewma == expect
        assert s.service_estimate_s((32, 96)) == ewma
        assert reg.get("serve_service_ewma_seconds").value(sig="32x96") == ewma
    with pytest.raises(ValueError, match="svc_alpha"):
        StreamScheduler(scripted_executor(), svc_alpha=0.0)
