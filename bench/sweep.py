"""Find a cell's knee: the highest Poisson rate served without a growing
backlog, by one sweep over several rates in one process on the chip.

    python3 bench/sweep.py --workload gin.stream --rates 300,400,500 --seconds 8

Set-up is the cell's (``run.py``); then, for each rate, the open-loop
client of ``client.py`` runs its window at that rate.  Per rate one JSON
line: the rate served (requests over the window), p50 and p95 latency, the
mean latency of the first and last quarter of the requests, and how long
after the last arrival the window closed (``drain_ms``).  Below the knee
the last quarter waits as long as the first and the drain is about one
call; above it both grow with the window.
"""
from __future__ import annotations

import argparse
import json
import os
import sys

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import run as R  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True, help="comma-separated requests/s")
    ap.add_argument("--seconds", type=float, default=8.0)
    ap.add_argument("--seed", type=int, default=1)
    args = ap.parse_args(argv)

    cell = R.load_cell(args.workload, False)
    jax, _ = R.start_jax(cell.chips, False)
    sys.path.insert(0, os.path.join(R.ROOT, "src"))
    import client
    import molecules
    from repro.runtime import configure_compilation_cache

    configure_compilation_cache()
    jax.config.update("jax_default_matmul_precision", cell.config["matmul_precision"])
    pool = molecules.make_pool(args.seed, cell.traffic["pool"])
    ex, sched, _ = R.build_server(jax, cell, args.seed, pool)
    lowered = R.warm(ex, sched, pool, cell.traffic)
    for rate in (float(r) for r in args.rates.split(",")):
        due = client.arrivals(rate, args.seconds, args.seed)
        w = client.open_loop(sched, pool, due, args.seconds, R.span_maker(False))
        lat = (w.done_s - w.due_s) * 1e3
        q = max(1, len(lat) // 4)
        print(json.dumps({
            "rate_per_s": rate, "served_per_s": len(lat) / w.end_s,
            "p50_ms": float(np.percentile(lat, 50)),
            "p95_ms": float(np.percentile(lat, 95)),
            "first_quarter_ms": float(lat[:q].mean()),
            "last_quarter_ms": float(lat[-q:].mean()),
            "drain_ms": (w.end_s - w.due_s[-1]) * 1e3,
            "graphs_per_call": len(lat) / len(w.calls),
            "compiled": ex.lowered_count - lowered}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
