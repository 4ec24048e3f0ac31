"""The readings that the limit on ``max_gap_over_rms`` is set from.

For each seed, in one process on the chip: set up the cell as ``run.py``
does, serve a short window of its traffic, and compare a sample of the
served outputs with the fp32 reference on the host CPU (the program's
reading).  Then put the reference itself in the program's place, computed
on the chip at a lower matmul precision, and compare it the same way:

* ``high``: three bfloat16 passes per fp32 product, the next precision
  below the ``highest`` the configurations state (the control);
* ``default``: one bfloat16 pass, what JAX does on a TPU when nothing is
  asked for.

    python3 bench/control.py --workload gin.screen --seeds 101-112 --seconds 3

One JSON line per seed, then a summary line with the largest program
reading and the smallest control reading.
"""
from __future__ import annotations

import argparse
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import run as R  # noqa: E402


def seeds(text: str) -> list:
    lo, _, hi = text.partition("-")
    return list(range(int(lo), int(hi or lo) + 1))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True, help="first-last, e.g. 101-112")
    ap.add_argument("--seconds", type=float, default=3.0)
    args = ap.parse_args(argv)

    cell = R.load_cell(args.workload, False)
    jax, devices = R.start_jax(cell.chips, False)
    sys.path.insert(0, os.path.join(R.ROOT, "src"))
    import client
    import molecules
    from repro.runtime import configure_compilation_cache

    configure_compilation_cache()
    jax.config.update("jax_default_matmul_precision", cell.config["matmul_precision"])
    cpu, chip = jax.devices("cpu")[0], devices[0]
    rows = []
    for seed in seeds(args.seeds):
        pool = molecules.make_pool(seed, cell.traffic["pool"])
        ex, sched, params = R.build_server(jax, cell, seed, pool)
        R.warm(ex, sched, pool, cell.traffic)
        span = R.span_maker(False)
        if cell.traffic["loop"] == "closed":
            w = client.closed_loop(sched, pool, cell.traffic["submission"],
                                   args.seconds, span)
        else:
            due = client.arrivals(cell.traffic["rate_per_s"], args.seconds, seed)
            w = client.open_loop(sched, pool, due, args.seconds, span)
        del ex, sched
        sample = R.sample_requests(w, pool, seed)
        graphs = pool.graphs(w.pool_idx[sample])
        served = R.np_stack([w.outputs[k] for k in sample])
        ref = R.reference_outputs(jax, cell.model, cell.config, params, graphs, cpu)
        row = {"seed": seed, "compared": len(sample),
               "program": R.compare(served, ref)}
        for precision in ("high", "default"):
            ctl = R.reference_outputs(jax, cell.model, cell.config, params,
                                      graphs, chip, precision=precision)
            row[precision] = R.compare(ctl, ref)
        rows.append(row)
        print(json.dumps(row), flush=True)
    print(json.dumps({"workload": args.workload, "seeds": len(rows),
                      "program_max": max(r["program"] for r in rows),
                      "high_min": min(r["high"] for r in rows),
                      "default_min": min(r["default"] for r in rows)}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
