"""Streaming throughput: packed micro-batching vs one-graph-at-a-time.

The paper's real-time mode (batch-size-1 ``infer_stream``) pays one full
program dispatch per molecule; the scheduler packs a live stream into
shared padded buckets so the dispatch amortizes.  This bench sweeps
offered load (QPS) and reports, per point, the sustained throughput and
per-request latency percentiles — the latency-vs-throughput curve in
docs/SERVING.md is generated this way.

Acceptance checks (asserted when run standalone, reported-only when run
through the ``benchmarks.run`` driver so one noisy box can't abort the
other figure sections):
  * at equal base bucket sizes, packed streaming sustains >= 2x the
    graphs/sec of one-graph ``infer_stream`` (compute-time basis);
  * after the warmup pass, a second full sweep triggers zero recompiles
    (``engine.compile_seconds`` does not move).

  PYTHONPATH=src python benchmarks/bench_stream_throughput.py [n_graphs]
"""
from __future__ import annotations

import sys

import jax
import numpy as np

from repro.data.pipeline import MOLHIV, MoleculeStream
from repro.gnn import init
from repro.gnn.models import paper_config
from repro.serve.gnn_engine import GNNEngine
from repro.serve.scheduler import StreamScheduler

MODEL = "gin"
CAPACITY = 16
MAX_WAIT_S = 0.002


def run(n_graphs: int = 64, strict: bool = True):
    cfg = paper_config(MODEL)
    params = init(jax.random.PRNGKey(0), cfg)
    eng = GNNEngine(cfg, params)
    graphs = MoleculeStream(MOLHIV, seed=0).take(n_graphs)

    # -- baseline: the paper's one-graph real-time mode (same buckets);
    # sustained graphs/sec = n / total compute, best of two passes to keep
    # a noisy-CPU spike from skewing the comparison
    _, lats_a, _ = eng.infer_stream([g[:4] for g in graphs])
    _, lats_b, _ = eng.infer_stream([g[:4] for g in graphs])
    base_gps = len(graphs) / float(min(np.sum(lats_a), np.sum(lats_b)))

    sched = StreamScheduler(eng, capacity=CAPACITY, max_wait_s=MAX_WAIT_S)

    # -- warmup pass: compiles every packed signature untimed
    sched.run(graphs, qps=0.0)
    warm_compile_s = eng.compile_seconds

    # -- saturation point: everything queued at t=0, pure compute
    # throughput (best of two passes, same noise rationale as above)
    sat = None
    for _ in range(2):
        rep = sched.run(graphs, qps=0.0)
        if sat is None or rep.compute_s < sat.compute_s:
            sat = rep
    packed_gps = sat.num_requests / sat.compute_s

    rows = [{
        "name": f"stream_{MODEL}_saturated",
        "graphs_per_s": round(packed_gps, 1),
        "derived": {
            "baseline_stream_gps": round(base_gps, 1),
            "amortization_x": round(packed_gps / base_gps, 2),
            "mean_batch": round(float(np.mean(sat.batch_sizes)), 2),
        },
    }]

    # -- offered-load sweep: latency vs throughput around the knee
    for frac in (0.25, 0.5, 1.0, 2.0):
        qps = frac * packed_gps
        rep = sched.run(graphs, qps=qps)
        rows.append({
            "name": f"stream_{MODEL}_qps{frac:g}x",
            "graphs_per_s": round(rep.graphs_per_s, 1),
            "derived": {
                "offered_qps": round(qps, 1),
                "p50_ms": round(rep.percentile_ms(50), 2),
                "p95_ms": round(rep.percentile_ms(95), 2),
                "p99_ms": round(rep.percentile_ms(99), 2),
                "mean_batch": round(float(np.mean(rep.batch_sizes)), 2),
                "flush_reasons": dict(rep.flush_reasons),
            },
        })

    # -- acceptance: amortization and zero recompiles after warmup
    amortized = packed_gps >= 2.0 * base_gps
    no_recompiles = eng.compile_seconds == warm_compile_s
    if strict:
        assert amortized, (
            f"packed streaming {packed_gps:.0f} graphs/s < 2x baseline {base_gps:.0f}"
        )
        assert no_recompiles, (
            f"recompiles after warmup: compile_seconds moved "
            f"{warm_compile_s:.3f} -> {eng.compile_seconds:.3f}"
        )
    elif not (amortized and no_recompiles):
        print(f"# WARNING: acceptance not met (amortized={amortized}, "
              f"no_recompiles={no_recompiles})")
    rows[0]["derived"]["recompile_s_after_warmup"] = round(
        eng.compile_seconds - warm_compile_s, 3
    )
    return rows


def main(strict: bool = False):
    # tolerate the benchmarks.run driver leaving its section name in argv
    digits = [a for a in sys.argv[1:] if a.isdigit()]
    n = int(digits[0]) if digits else 64
    rows = run(n, strict=strict)
    for row in rows:
        print(f"{row['name']},{row['graphs_per_s']},{row['derived']}")
    return rows


if __name__ == "__main__":
    main(strict=True)
