"""Benchmark driver — one section per paper table/figure.

Prints ``name,us_per_call,derived`` CSV lines and, per section, writes the
same rows machine-readably as ``BENCH_<section>.json`` (schema: name,
config, metrics, timestamp — see benchmarks/bench_io.py) so the perf
trajectory is tracked across PRs.  Sections:
  fig7   per-model GNN inference latency (engine vs dense-SpMM, stream vs batch)
  stream packed micro-batched streaming vs one-graph mode (QPS sweep)
  slo    SLO-aware admission: overload sweep (p99 holds, goodput plateaus)
  fig8   large-graph DGN (Cora/CiteSeer/PubMed sizes)
  fig9   NE/MP pipelining speed-ups (sweep + MolHIV + virtual node)
  table4 per-model resource footprint (params/FLOPs/bytes/VMEM tiles)
  quant  fp32 vs int8/ap_fixed: logit error + packed throughput
  layout shared GraphLayout plan: sort counts + stream latency + recompiles
  multitenant  shared Executor vs N separate engines (warm time, programs)
  coldstart  AOT cache: cold vs warm-disk restart (subprocess), flag deltas
             — runs alone: its children need the accelerator, which a
             process that has already used JAX holds
  roofline  per-(arch x shape x mesh) dry-run roofline terms

The default runs every section but ``coldstart``.
"""
import importlib
import sys

_MODULES = {
    "fig7": "bench_fig7_latency",
    "fig8": "bench_fig8_large_graph",
    "fig9": "bench_fig9_pipeline",
    "table4": "bench_table4_resources",
    "stream": "bench_stream_throughput",
    "slo": "bench_slo",
    "quant": "bench_quant",
    "layout": "bench_layout",
    "multitenant": "bench_multitenant",
    "coldstart": "bench_coldstart",
    "roofline": "bench_roofline",
}


def main() -> None:
    sections = sys.argv[1:] or [
        "fig9", "table4", "fig8", "fig7", "stream", "slo", "quant",
        "layout", "multitenant", "roofline"
    ]
    unknown = [s for s in sections if s not in _MODULES]
    if unknown:
        raise SystemExit(f"unknown sections {unknown}; choose from "
                         f"{sorted(_MODULES)}")
    if "coldstart" in sections and len(sections) > 1:
        raise SystemExit("coldstart runs alone: its restarted children "
                         "need the accelerator, and the other sections "
                         "would hold it in this process")
    if sections != ["coldstart"]:
        from repro.runtime import configure_compilation_cache

        configure_compilation_cache()
    from benchmarks.bench_io import write_bench_json

    for s in sections:
        mod = importlib.import_module(f"benchmarks.{_MODULES[s]}")
        print(f"# --- {s} ---", flush=True)
        rows = mod.main()
        if rows and not getattr(mod, "WRITES_OWN_BENCH", False):
            write_bench_json(s, rows, config={"argv": sys.argv[1:]})


if __name__ == '__main__':
    main()
