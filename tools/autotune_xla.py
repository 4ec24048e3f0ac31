"""Offline XLA flag sweep — benchmark candidate compiler-option sets per
model x bucket and commit the winners to ``src/repro/configs/xla_flags.json``.

The saxml ``llm_xla_flags.py`` pattern: latency-relevant XLA flags are
swept *offline* against the real serving programs, and only measured
winners are committed to a checked-in table the serving stack applies at
program-build time (``Executor._compiler_options`` ->
``Lowered.compile(compiler_options=...)``).  Serving never experiments;
it replays decisions this tool made.  The resolved flag set's hash folds
into the AOT cache fingerprint (``serve/aot.py``), so committing new
winners self-invalidates exactly the cached executables whose flags
changed — no manual cache flush.

Method, per model x bucket:

  1. every candidate set is *validated* by a try-compile first — an
     option the backend rejects (XLA raises INVALID_ARGUMENT for unknown
     names and unparsable values) is dropped with a note, never
     committed;
  2. the survivor sets (plus the empty default) compile the model's real
     packed program and run ``--reps`` timed executions on a
     representative molecule batch; the per-set score is the *minimum*
     latency (robust to scheduler noise);
  3. a candidate only wins if it beats the default by more than
     ``--threshold`` (default 2%) — ties go to the default, so the
     committed table stays minimal and a flag that merely doesn't hurt
     is never pinned.

Numerics-sensitive options (fast-math family) are deliberately absent
from the candidate pools: a winner must never change served outputs,
only how fast they are produced.

  PYTHONPATH=src python tools/autotune_xla.py --models gin,gcn
  PYTHONPATH=src python tools/autotune_xla.py --smoke --out /tmp/flags.json
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

import jax  # noqa: E402
import numpy as np  # noqa: E402

# ---------------------------------------------------------------------------
# candidate pools (per backend)
# ---------------------------------------------------------------------------

# CPU: scheduler/codegen toggles only — every option here was probed to
# be accepted by this jaxlib pin's compiler_options surface, and none
# change numerics (fast-math and fast-min-max are excluded on purpose).
CPU_CANDIDATES = {
    "thunk-runtime-off": {"xla_cpu_use_thunk_runtime": False},
    "concurrency-sched": {
        "xla_cpu_enable_concurrency_optimized_scheduler": True,
    },
    "vec-width-512": {"xla_cpu_prefer_vector_width": 512},
    "single-thread-eigen": {"xla_cpu_multi_thread_eigen": False},
}

# TPU: the saxml llm_xla_flags.py latency set — scoped vmem sizing plus
# async collectives (a no-op for single-chip GNN serving, decisive for
# sharded meshes).  Validated by try-compile like everything else.
TPU_CANDIDATES = {
    "scoped-vmem-96m": {"xla_tpu_scoped_vmem_limit_kib": 98304},
    "async-collectives": {
        "xla_enable_async_all_gather": True,
        "xla_enable_async_collective_permute": True,
    },
    "latency-hiding": {
        "xla_latency_hiding_scheduler_rerun": 1,
    },
}


def candidate_sets(backend: str) -> dict:
    if backend == "cpu":
        return dict(CPU_CANDIDATES)
    if backend == "tpu":
        return dict(TPU_CANDIDATES)
    return {}


# ---------------------------------------------------------------------------
# the sweep
# ---------------------------------------------------------------------------


def _workload(model: str, budget, n_graphs: int = 4):
    """(cfg, params, prepared) — one representative packed batch of real
    molecule graphs at the serving budget."""
    from repro.configs.gengnn_models import get_gnn_config
    from repro.core import batching as B
    from repro.data.pipeline import MOLHIV, MoleculeStream
    from repro.gnn import init
    from repro.gnn.models import needs_eigvec

    cfg = get_gnn_config(model)
    params = init(jax.random.PRNGKey(0), cfg)
    graphs = [g[:4] for g in MoleculeStream(MOLHIV, seed=7).take(n_graphs)]
    need_eig = needs_eigvec(cfg)
    eigvecs = None
    if need_eig:
        from repro.data.pipeline import laplacian_eigvec

        eigvecs = [laplacian_eigvec(s, r, nf.shape[0], nf.shape[0])
                   for s, r, nf, _ in graphs]
    prep, _ = B.pack_prepared(graphs, budget, eigvecs=eigvecs,
                              with_layout=True)
    return cfg, params, prep


def _validate(candidates: dict) -> tuple:
    """(accepted, rejected) — try-compile a trivial program under every
    candidate set; the backend's own INVALID_ARGUMENT is the filter."""
    import jax.numpy as jnp

    probe = jax.jit(lambda x: x @ x + 1.0).lower(jnp.ones((4, 4)))
    accepted, rejected = {}, {}
    for name, flags in candidates.items():
        try:
            probe.compile(compiler_options=dict(flags))
            accepted[name] = flags
        except Exception as err:  # noqa: BLE001 - the filter, not a failure
            rejected[name] = f"{type(err).__name__}: {str(err)[:120]}"
    return accepted, rejected


def _measure(model: str, budget, flag_sets: dict, reps: int) -> dict:
    """min-latency seconds per flag-set name for one model x budget,
    each measured on a fresh Executor (no cross-set compile reuse)."""
    from repro.serve.aot import XlaFlagConfig
    from repro.serve.executor import Executor

    results = {}
    for name, flags in flag_sets.items():
        ex = Executor(
            xla_flags=XlaFlagConfig(default=dict(flags)) if flags else None
        )
        cfg, params, prep = _workload(model, budget)
        ex.register(model, cfg, params)
        ex.warm(prep, model=model)
        best = float("inf")
        for _ in range(reps):
            t0 = time.perf_counter()
            ex.run(prep, model=model)
            best = min(best, time.perf_counter() - t0)
        results[name] = best
    return results


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--models", default="gcn,gin",
                    help="comma-separated model names to tune")
    ap.add_argument("--reps", type=int, default=20,
                    help="timed executions per candidate (score = min)")
    ap.add_argument("--pack", type=int, default=4,
                    help="packed budget = this many base (32,96) buckets")
    ap.add_argument("--threshold", type=float, default=0.02,
                    help="minimum fractional win over the default compile "
                         "for a candidate to be committed")
    ap.add_argument("--out", default="",
                    help="output table path (default: the checked-in "
                         "src/repro/configs/xla_flags.json)")
    ap.add_argument("--dry-run", action="store_true",
                    help="sweep and report, write nothing")
    ap.add_argument("--smoke", action="store_true",
                    help="CI mode: one model, 3 reps, tiny threshold "
                         "checks the machinery end to end")
    args = ap.parse_args()
    if args.smoke:
        args.models, args.reps = args.models.split(",")[0], 3

    from repro.core.batching import BucketBudget
    from repro.serve.aot import (XlaFlagConfig, default_flags_path,
                                 environment_fingerprint)

    backend = jax.default_backend()
    accepted, rejected = _validate(candidate_sets(backend))
    for name, why in sorted(rejected.items()):
        print(f"[drop] {name}: rejected by {backend} backend ({why})")
    print(f"backend {backend}: {len(accepted)} candidate sets "
          f"({', '.join(sorted(accepted)) or 'none'}) + default")

    budget = BucketBudget(n_pad=32 * args.pack, e_pad=96 * args.pack,
                          g_pad=2 * args.pack)
    bucket_str = f"packed|{budget.n_pad}|{budget.e_pad}|{budget.g_pad}"
    models_out: dict = {}
    provenance: dict = {"tool": "tools/autotune_xla.py", "reps": args.reps,
                        "threshold": args.threshold, "backend": backend,
                        "bucket": bucket_str, "measurements": {},
                        "rejected": rejected}
    for model in args.models.split(","):
        sets = {"default": {}}
        sets.update(accepted)
        scores = _measure(model, budget, sets, args.reps)
        base = scores["default"]
        ranked = sorted(scores.items(), key=lambda kv: kv[1])
        provenance["measurements"][model] = {
            k: round(v * 1e6, 1) for k, v in ranked  # us, for the record
        }
        win_name, win_s = ranked[0]
        gain = (base - win_s) / base if base > 0 else 0.0
        line = "  ".join(f"{k}={v*1e6:.0f}us" for k, v in ranked)
        print(f"{model} @ {bucket_str}: {line}")
        if win_name != "default" and gain > args.threshold:
            models_out[model] = {"buckets": {bucket_str: dict(sets[win_name])}}
            print(f"  -> commit {win_name} ({gain*100:.1f}% faster)")
        else:
            print(f"  -> default wins (best alternative "
                  f"{gain*100:+.1f}%, threshold {args.threshold*100:.0f}%)")

    table = XlaFlagConfig(default={}, models=models_out)
    out = args.out or default_flags_path()
    if args.dry_run:
        print(f"dry run: would write {out}")
        return 0
    table.save(out, env=environment_fingerprint(), provenance=provenance)
    print(f"wrote {out} ({len(models_out)} model entries)")
    return 0


if __name__ == "__main__":
    sys.exit(main())
