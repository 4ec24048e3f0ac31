"""Serving launcher: batched prefill+decode for LM archs, or the streaming
GNN engine for the paper's models.

On the CPU set ``JAX_PLATFORMS=cpu``; otherwise the launcher exits unless
JAX finds a TPU (``runtime.compat.require_tpu``).

Examples (reduced configs):
  PYTHONPATH=src python -m repro.launch.serve --arch chatglm3-6b --reduced
  PYTHONPATH=src python -m repro.launch.serve --gnn gin --n-graphs 32
  PYTHONPATH=src python -m repro.launch.serve --gnn gin --stream \
      --n-graphs 64 --qps 2000 --max-wait-ms 2
  PYTHONPATH=src python -m repro.launch.serve --gnn gin --stream \
      --n-graphs 64 --qps 8000 --slo-ms 20 --admit-limit 32 --adapt-ladder
  PYTHONPATH=src python -m repro.launch.serve --gnn gin --stream \
      --n-graphs 64 --qps 8000 --priority 0,0,1 --slo-ms 0:10,1:50
  PYTHONPATH=src python -m repro.launch.serve --models gcn:int8,gat:fp32 \
      --n-graphs 32 --qps 1000 --slo-ms 20
  PYTHONPATH=src python -m repro.launch.serve --gnn gin --stream \
      --n-graphs 64 --aot-cache /tmp/aot --prewarm-persist
"""
import argparse
import time

import jax
import numpy as np

from repro import params as P
from repro.configs import ARCHS, get_config, get_reduced
from repro.models import lm


def serve_lm(args):
    cfg = (get_reduced if args.reduced else get_config)(args.arch)
    from repro.serve.engine import LMServer, ServeConfig

    params = P.values(lm.init_params(jax.random.PRNGKey(0), cfg))
    scfg = ServeConfig(max_batch=args.batch, prompt_len=args.prompt_len,
                       cache_len=args.cache_len, max_new_tokens=args.max_new)
    srv = LMServer(params, cfg, scfg)
    rng = np.random.default_rng(0)
    prompts = [rng.integers(1, cfg.vocab_size, rng.integers(4, args.prompt_len))
               for _ in range(args.batch)]
    extras = {}
    if cfg.family == "vlm":
        extras["patches"] = rng.normal(size=(args.batch, cfg.num_patches, cfg.d_model)).astype(np.float32)
    if cfg.family == "audio":
        extras["frames"] = rng.normal(size=(args.batch, cfg.encoder_seq, cfg.d_model)).astype(np.float32)
    out, stats = srv.generate(prompts, extras=extras or None)
    print("generated:", out[:2])
    print(f"prefill {stats['prefill_s']*1e3:.1f} ms, "
          f"decode {stats['decode_s_per_token']*1e3:.2f} ms/token")


def _slo_kwargs(args):
    """StreamScheduler admission kwargs from the CLI flags.

    ``--slo-ms`` is either one budget for every request ("20") or a
    per-QoS-class table ("0:10,1:50" -> ``slo_by_class``); ``--priority``
    cycles its classes over the stream round-robin."""
    kw = dict(admit_limit=args.admit_limit, admit_margin=args.admit_margin,
              adapt_ladder=args.adapt_ladder)
    if args.slo_ms:
        if ":" in args.slo_ms:
            kw["slo_by_class"] = {
                (None, int(cls)): float(ms) * 1e-3
                for cls, _, ms in (s.partition(":")
                                   for s in args.slo_ms.split(","))
            }
        else:
            kw["slo_s"] = float(args.slo_ms) * 1e-3
    return kw


def _priorities(args, n):
    cycle = [int(p) for p in args.priority.split(",")]
    return [cycle[i % len(cycle)] for i in range(n)]


def _aot_setup(args):
    """(aot_cache, xla_flags) from the CLI.

    ``--aot-cache DIR`` turns on the persistent executable cache;
    ``--xla-flags-file`` points at an explicit flag table (error if
    absent), otherwise the checked-in ``configs/xla_flags.json`` is used
    whenever either AOT flag is given (an absent default file is an
    empty flag set, not an error)."""
    from repro.serve.aot import AOTCache, XlaFlagConfig

    cache = AOTCache(args.aot_cache) if args.aot_cache else None
    flags = None
    if args.xla_flags_file:
        flags = XlaFlagConfig.load(args.xla_flags_file)
    elif cache is not None:
        flags = XlaFlagConfig.load()
    return cache, flags


def _report_cold_start(args, executor, scheduler, graphs, registry,
                       models=None):
    """The restart-fast probe: prewarm the bucket ladder (populating the
    AOT cache on first run, loading from it on the next), then print one
    machine-parseable line — ``bench_coldstart.py`` and the CI smoke
    step parse it.  ``cold_start_s`` counts from launcher entry to
    ladder-warm (serving-ready); interpreter/JAX import time is excluded
    (orthogonal to the cache — see docs/SERVING.md)."""
    if not args.aot_cache:
        return
    if args.prewarm_persist and scheduler is not None and graphs:
        scheduler.prewarm_ladders(graphs, models=models)
    elapsed = time.perf_counter() - args._t0
    stats = executor.aot_stats()
    print(f"cold_start_s={elapsed:.3f} aot_hit={stats['hit']} "
          f"aot_miss={stats['miss']} aot_stale={stats['stale']} "
          f"lowered={executor.lowered_count}")
    if registry is not None:
        from repro.obs.metrics import ServingInstruments

        ServingInstruments(registry).cold_start.set(elapsed)


def _telemetry(args):
    """(tracer, registry) for the stream paths.

    The registry always exists — the admission ledger is a structured
    record in it, rendered for humans by ``obs.export.admission_line``
    (no more free-floating print tallies).  Span tracing only turns on
    when ``--trace-out`` asks for the artifact."""
    from repro.obs import MetricsRegistry, Tracer
    from repro.serve.clock import VirtualClock

    registry = MetricsRegistry()
    tracer = Tracer(VirtualClock()) if args.trace_out else None
    return tracer, registry


def _emit_telemetry(args, tracer, registry):
    """Print the admission ledger from the registry; write artifacts."""
    from repro.obs import export

    print(f"  {export.admission_line(registry)}")
    if args.metrics_json:
        export.write_metrics_json(registry, args.metrics_json)
        print(f"  metrics-json -> {args.metrics_json}")
    if args.trace_out:
        export.write_trace(tracer, args.trace_out)
        print(f"  trace-out -> {args.trace_out}")


def serve_gnn_multitenant(args):
    """Serve several GNN models through ONE executor + ONE scheduler.

    ``--models gcn:int8,gat:fp32`` registers each ``model[:precision]``
    spec as a tenant on a shared ``Executor`` (shared bucket ladder,
    shared compile cache); the stream round-robins requests across the
    tenants and the scheduler routes each to its model's packed flushes.
    """
    from repro import runtime as RT
    from repro.configs.gengnn_models import get_gnn_config
    from repro.data.pipeline import MOLHIV, MoleculeStream
    from repro.gnn import init
    from repro.serve.executor import Executor
    from repro.serve.scheduler import StreamScheduler

    mesh = None
    if args.gnn_mesh > 1:
        mesh = RT.make_flat_mesh(args.gnn_mesh, axis="data")
    aot_cache, xla_flags = _aot_setup(args)
    ex = Executor(mesh=mesh, aot_cache=aot_cache, xla_flags=xla_flags)
    specs = []
    for i, spec in enumerate(args.models.split(",")):
        model, _, precision = spec.partition(":")
        precision = precision or "fp32"
        cfg = get_gnn_config(model)
        params = init(jax.random.PRNGKey(i), cfg)
        calib = None
        if precision == "int8-static":
            calib = [g[:4] for g in MoleculeStream(MOLHIV, seed=97).take(16)]
        ex.register(spec, cfg, params, precision=precision, calib_graphs=calib,
                    share_layout=not args.no_share_layout, fused=args.fused)
        specs.append(spec)
    tracer, registry = _telemetry(args)
    sched = StreamScheduler(ex, capacity=args.pack,
                            max_wait_s=args.max_wait_ms * 1e-3,
                            with_eigvec="auto", tracer=tracer,
                            metrics=registry, **_slo_kwargs(args))
    graphs = [g[:4] for g in MoleculeStream(MOLHIV, seed=0).take(args.n_graphs)]
    models = [specs[i % len(specs)] for i in range(len(graphs))]
    _report_cold_start(args, ex, sched, graphs, registry, models=models)
    rep = sched.run(graphs, qps=args.qps, models=models,
                    priorities=_priorities(args, len(graphs)))
    counts = {s: models.count(s) for s in specs}
    print(f"multi-tenant stream(qps={args.qps:g}, pack x{args.pack}, "
          f"tenants {counts}): {rep.num_requests} graphs in "
          f"{rep.makespan_s*1e3:.1f} ms virtual "
          f"({rep.graphs_per_s:.0f} graphs/s)")
    print(f"  latency ms: p50 {rep.percentile_ms(50):.2f}  "
          f"p95 {rep.percentile_ms(95):.2f}  p99 {rep.percentile_ms(99):.2f}")
    print(f"  {len(rep.batch_sizes)} flushes (reasons {dict(rep.flush_reasons)}); "
          f"{len(ex._compiled)} compiled programs, "
          f"compile {rep.compile_s:.1f}s excluded")
    _emit_telemetry(args, tracer, registry)


def serve_gnn(args):
    from repro import runtime as RT
    from repro.configs.gengnn_models import get_gnn_config
    from repro.data.pipeline import MOLHIV, MoleculeStream
    from repro.gnn import init
    from repro.gnn.models import needs_eigvec
    from repro.serve.gnn_engine import GNNEngine

    cfg = get_gnn_config(args.gnn)
    params = init(jax.random.PRNGKey(0), cfg)
    mesh = None
    if args.gnn_mesh > 1:
        # shard padded node/edge rows over a flat data axis
        mesh = RT.make_flat_mesh(args.gnn_mesh, axis="data")
    calib = None
    if args.precision == "int8-static":
        # calibration stream disjoint from the served one (seed split)
        calib = [g[:4] for g in MoleculeStream(MOLHIV, seed=97).take(16)]
    aot_cache, xla_flags = _aot_setup(args)
    eng = GNNEngine(cfg, params, mesh=mesh, precision=args.precision,
                    calib_graphs=calib,
                    share_layout=not args.no_share_layout,
                    fused=args.fused,
                    aot_cache=aot_cache, xla_flags=xla_flags)
    if eng.quant_report is not None:
        r = eng.quant_report
        print(f"[quant] {args.precision}: {r.quantized} linears quantized, "
              f"{r.kept_fp32} fp32 (skip: {list(r.skipped_paths)})")
    graphs = MoleculeStream(MOLHIV, seed=0).take(args.n_graphs)
    if args.stream:
        from repro.serve.scheduler import StreamScheduler

        tracer, registry = _telemetry(args)
        sched = StreamScheduler(
            eng, capacity=args.pack, max_wait_s=args.max_wait_ms * 1e-3,
            with_eigvec=needs_eigvec(cfg), tracer=tracer,
            metrics=registry, **_slo_kwargs(args),
        )
        _report_cold_start(args, eng.executor, sched,
                           [g[:4] for g in graphs], registry)
        rep = sched.run(graphs, qps=args.qps,
                        priorities=_priorities(args, len(graphs)))
        if rep.num_requests == 0:
            print(f"{args.gnn} stream: no graphs (--n-graphs {args.n_graphs})")
            return
        sizes = np.asarray(rep.batch_sizes)
        print(f"{args.gnn} stream(qps={args.qps:g}, max-wait {args.max_wait_ms}ms, "
              f"pack x{args.pack}"
              f"{', mesh=' + str(args.gnn_mesh) if mesh is not None else ''}): "
              f"{rep.num_requests} graphs in {rep.makespan_s*1e3:.1f} ms virtual "
              f"({rep.graphs_per_s:.0f} graphs/s)")
        print(f"  latency ms: p50 {rep.percentile_ms(50):.2f}  "
              f"p95 {rep.percentile_ms(95):.2f}  p99 {rep.percentile_ms(99):.2f}")
        print(f"  {len(sizes)} flushes (mean batch {sizes.mean():.1f}, "
              f"reasons {dict(rep.flush_reasons)}); "
              f"compile {rep.compile_s:.1f}s excluded")
        _emit_telemetry(args, tracer, registry)
        return
    if args.batched:
        outs, per_graph_s = eng.infer_batched(
            graphs, batch_size=args.batch, n_pad=args.batch * 32,
            e_pad=args.batch * 96, with_eigvec=needs_eigvec(cfg),
        )
        print(f"{args.gnn} batched(bs={args.batch}"
              f"{', mesh=' + str(args.gnn_mesh) if mesh is not None else ''}): "
              f"{len(outs)} graphs, {per_graph_s*1e6:.0f} us/graph "
              f"(compile {eng.compile_seconds:.1f}s excluded)")
        return
    outs, lats, compile_s = eng.infer_stream(
        [g[:4] for g in graphs], with_eigvec=needs_eigvec(cfg)
    )
    print(f"{args.gnn}: {len(outs)} graphs, mean {np.mean(lats)*1e6:.0f} us/graph "
          f"(p50 {np.percentile(lats,50)*1e6:.0f}, p99 {np.percentile(lats,99)*1e6:.0f}; "
          f"compile {compile_s:.1f}s excluded)")
    if args.aot_cache:
        stats = eng.executor.aot_stats()
        print(f"  aot: hit {stats['hit']} miss {stats['miss']} "
              f"stale {stats['stale']}; {eng.executor.lowered_count} fresh "
              f"compiles")


def main():
    t0 = time.perf_counter()  # cold-start epoch: launcher entry
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", choices=ARCHS)
    ap.add_argument("--gnn", choices=("gcn", "gin", "gin_vn", "gat", "pna", "dgn",
                                      "gps"))
    ap.add_argument("--models",
                    help="multi-tenant GNN serving: comma-separated "
                         "model[:precision] specs (e.g. gcn:int8,gat:fp32) "
                         "registered on one shared executor + scheduler")
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--batch", type=int, default=2)
    ap.add_argument("--prompt-len", type=int, default=16)
    ap.add_argument("--cache-len", type=int, default=64)
    ap.add_argument("--max-new", type=int, default=8)
    ap.add_argument("--n-graphs", type=int, default=16)
    ap.add_argument("--batched", action="store_true",
                    help="GNN: padded-batch mode instead of streaming")
    ap.add_argument("--stream", action="store_true",
                    help="GNN: micro-batched streaming via serve.scheduler")
    ap.add_argument("--qps", type=float, default=1000.0,
                    help="stream: offered load; <=0 means all queued at t=0")
    ap.add_argument("--max-wait-ms", type=float, default=2.0,
                    help="stream: flush a bucket at latest this long after it opens")
    ap.add_argument("--pack", type=int, default=4,
                    help="stream: packed budget = this many base buckets")
    ap.add_argument("--slo-ms", default="",
                    help="stream: per-request latency SLO; one budget "
                         "('20') or a class:ms table ('0:10,1:50'); "
                         "enables admission control (empty = best-effort, "
                         "never shed)")
    ap.add_argument("--priority", default="0",
                    help="stream: QoS classes cycled over the stream "
                         "round-robin (lower = more urgent), e.g. '0,0,1'")
    ap.add_argument("--admit-limit", type=int, default=None,
                    help="stream: bound on admitted-but-unflushed requests; "
                         "arrivals beyond it shed with reason queue_full")
    ap.add_argument("--admit-margin", type=float, default=1.0,
                    help="stream: fraction of the SLO the admission "
                         "projection may use (guard band; see "
                         "serve/scheduler.py)")
    ap.add_argument("--metrics-json", default="",
                    help="stream: write the metrics-registry snapshot "
                         "(repro-metrics/v1 JSON) here after the run")
    ap.add_argument("--trace-out", default="",
                    help="stream: write the run's Chrome/Perfetto "
                         "trace-event JSON here (the scheduler's "
                         "virtual-clock timeline; open in "
                         "ui.perfetto.dev)")
    ap.add_argument("--adapt-ladder", action="store_true",
                    help="stream: re-fit each signature's bucket-rung "
                         "geometry to the observed flush-size histogram")
    ap.add_argument("--gnn-mesh", type=int, default=1,
                    help="GNN: shard node/edge rows over this many devices")
    ap.add_argument("--fused", action="store_true",
                    help="GNN: lower eligible layers through the fused "
                         "(phi, A, gamma) megakernel — one pass for "
                         "message transform, aggregation, and node update "
                         "(GAT and int8-static/fixed params keep the "
                         "unfused path; see docs/KERNELS.md)")
    ap.add_argument("--no-share-layout", action="store_true",
                    help="GNN: disable the shared GraphLayout plan and "
                         "re-sort edges inside every aggregation (the "
                         "pre-layout behaviour; A/B benchmarking only)")
    ap.add_argument("--aot-cache", default="",
                    help="GNN: persistent AOT compile-cache directory — "
                         "serialized executables survive restarts; a warm "
                         "cache restores the whole bucket ladder without "
                         "one fresh compile (docs/SERVING.md)")
    ap.add_argument("--prewarm-persist", action="store_true",
                    help="GNN stream: warm every (tenant, signature) "
                         "bucket ladder before serving, populating "
                         "--aot-cache so the next restart serves in "
                         "milliseconds")
    ap.add_argument("--xla-flags-file", default="",
                    help="explicit XLA flag table (repro-xla-flags/v1 "
                         "JSON, written by tools/autotune_xla.py); "
                         "default: the checked-in configs/xla_flags.json "
                         "when --aot-cache is on")
    ap.add_argument("--precision",
                    choices=("fp32", "int8", "int8-static", "fixed"),
                    default="fp32",
                    help="GNN serving arithmetic: fp32; int8 (dynamic "
                         "per-node activation scales); int8-static "
                         "(calibrated per-tensor scales); or the paper's "
                         "ap_fixed<W,I> emulation")
    args = ap.parse_args()
    args._t0 = t0
    from repro.runtime import configure_compilation_cache, require_tpu

    require_tpu()
    configure_compilation_cache()
    if args.models:
        serve_gnn_multitenant(args)
    elif args.gnn:
        serve_gnn(args)
    else:
        assert args.arch, "--arch or --gnn or --models required"
        serve_lm(args)


if __name__ == "__main__":
    main()
