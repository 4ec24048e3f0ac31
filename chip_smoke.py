"""Smoke test of the GNN serving path on the TPU.

Drives the path users run (``Executor.register`` + ``StreamScheduler.run``,
what ``launch/serve.py --models`` and ``--gnn --stream`` use) once per
phase, at the paper's widths (``configs.gengnn_models.get_gnn_config``),
with random weights from ``--seed``:

* all six models fp32 on the default (unfused) path, gin int8
  (``quant_node_mlp``), and gcn/gin/pna/dgn through the fused megakernel
  (gin in int8);
* GraphGPS (``gps``) at its ogbg-molpcba widths, served and checked at
  matmul precision ``highest``, as its benchmark configuration states;
* each phase serves ``--graphs`` MolHIV-statistics molecules, all queued
  at once so every flush packs several graphs, twice: the first pass
  compiles the ladder rungs the stream uses, the second must compile
  nothing;
* every served output is compared with the dense oracle
  ``gnn.reference.apply_dense`` run on the host CPU device;
* the kernel census (``kernels_dispatch_total``) must show only
  ``path="kernel"``: no interpret mode and no jnp reference on the chip.

``--chips 4`` runs only the mesh-sharded packed path
(``Executor(mesh=make_flat_mesh(4))``), fp32, for gat, pna and pna fused,
and compares each with the same stream served on one chip.

Usage (from the root of a checkout; exits non-zero without a TPU):

    python3 chip_smoke.py            # one chip
    python3 chip_smoke.py --phases gps  # some one-chip phases only
    python3 chip_smoke.py --chips 4  # the four-chip mesh phase

The last line of standard output is one JSON object:
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": N}}``.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.abspath(__file__))

# (phase name, model, precision, fused)
PHASES = (
    ("gcn", "gcn", "fp32", False),
    ("gin", "gin", "fp32", False),
    ("gin_vn", "gin_vn", "fp32", False),
    ("gat", "gat", "fp32", False),
    ("pna", "pna", "fp32", False),
    ("dgn", "dgn", "fp32", False),
    ("gin_int8", "gin", "int8", False),
    ("gcn_fused", "gcn", "fp32", True),
    ("gin_int8_fused", "gin", "int8", True),
    ("pna_fused", "pna", "fp32", True),
    ("dgn_fused", "dgn", "fp32", True),
    ("gps", "gps", "fp32", False),
)
# phases served and checked at matmul precision "highest" (fp32 products
# at fp32 accuracy), against a tolerance of fp32 rounding compounded over
# the model's ~30 dependent products
HIGHEST = {"gps": 1e-4}
# (phase name, model, fused) of the four-chip run: between them the node
# MLPs, GAT's edge softmax, PNA's sum/max/min segment reductions with
# their collectives, and the fused kernel's split of node rows
MESH_PHASES = (
    ("gat", "gat", False),
    ("pna", "pna", False),
    ("pna_fused", "pna", True),
)
CAPACITY = 4  # top rung = 4 base buckets, 8 graph slots
MAX_WAIT_S = 0.002

# fp32 tolerance on max |served - ref| / (|ref| + 1), the metric of
# tests/test_gnn_models.py.  XLA on the TPU runs f32 matmuls as one
# bfloat16 pass by default (the probe below prints what this device
# does): each input is rounded to 8 mantissa bits (unit roundoff 2^-9),
# and the deepest model chains about a dozen dependent matmuls, so the
# error may compound to ~12 x 2 x 2^-9 = 4.7e-2 relative to the
# activations' scale.
TOL_FP32 = 5e-2
# the one-chip and four-chip runs execute the same arithmetic at the same
# precision; only the reduction order of the sharded sums differs
TOL_MESH = 1e-3
# int8 outputs against the fp32 oracle: the serving acceptance rule of
# benchmarks/bench_quant.py (mean absolute logit error within 10% of the
# mean |fp32 logit|, floor 0.02)
INT8_MAE_REL, INT8_MAE_FLOOR = 0.10, 0.02


def _log(msg: str) -> None:
    print(msg, flush=True)


def _rel_err(a, b) -> float:
    import numpy as np

    return float((np.abs(a - b) / (np.abs(b) + 1.0)).max())


def _graphs(n: int, seed: int, large: int = 0):
    """``n`` MolHIV-statistics molecules; with ``large``, that many of them
    have more than 32 nodes, so flushes of them fill the (256, 768) rung."""
    from repro.data.pipeline import MOLHIV, MoleculeStream

    if not large:
        return [g[:4] for g in MoleculeStream(MOLHIV, seed=seed).take(n)]
    stream = [g[:4] for g in MoleculeStream(MOLHIV, seed=seed).take(8 * n)]
    big = [g for g in stream if g[2].shape[0] > 32][:large]
    return big + [g for g in stream if g[2].shape[0] <= 32][:n - len(big)]


def _reference(cfg, params, graphs, cpu, chunk: int = 8):
    """``apply_dense`` on the host CPU device, ``chunk`` graphs per dense
    batch (DGN gets the same host eigenvectors the executor computes)."""
    import jax
    import numpy as np

    from repro.core.graph import batch_graphs
    from repro.data.pipeline import laplacian_eigvec
    from repro.gnn.models import needs_eigvec
    from repro.gnn.reference import apply_dense

    dense = jax.jit(apply_dense, static_argnums=2)
    params = jax.device_put(params, cpu)
    outs = []
    for i in range(0, len(graphs), chunk):
        part = graphs[i:i + chunk]
        n = sum(g[2].shape[0] for g in part)
        e = sum(len(g[0]) for g in part)
        n_pad, e_pad = -(-n // 128) * 128, -(-e // 128) * 128
        g = jax.device_put(batch_graphs(part, n_pad=n_pad, e_pad=e_pad), cpu)
        eig = None
        if needs_eigvec(cfg):
            vec = np.zeros((n_pad,), np.float32)
            off = 0
            for s, r, nf, _ in part:
                k = nf.shape[0]
                vec[off:off + k] = laplacian_eigvec(s, r, k, k)
                off += k
            eig = jax.device_put(vec, cpu)
        outs.append(np.asarray(dense(params, g, cfg, eig))[:len(part)])
    return np.concatenate(outs)


def _serve(ex, name, graphs):
    """Serve ``graphs`` twice through a fresh scheduler on ``ex``; returns
    (outputs, report of the second pass, lowered after the first)."""
    import numpy as np

    from repro.serve.scheduler import StreamScheduler

    sched = StreamScheduler(ex, capacity=CAPACITY, max_wait_s=MAX_WAIT_S,
                            with_eigvec="auto")
    sched.run(graphs, qps=0.0)  # compiles the rungs this stream uses
    lowered_warm = ex.lowered_count
    rep = sched.run(graphs, qps=0.0)
    if rep.num_served != len(graphs):
        raise RuntimeError(f"{name}: served {rep.num_served} of {len(graphs)}")
    if ex.lowered_count != lowered_warm:
        raise RuntimeError(
            f"{name}: {ex.lowered_count - lowered_warm} programs compiled "
            f"inside the served stream")
    return np.concatenate(rep.outputs), rep, lowered_warm


def _probe_matmul_precision(tpu) -> str:
    """Relative error of an f32 matmul on the device against float64, for
    XLA's dot and for the Pallas ``node_mlp`` kernel."""
    import jax
    import numpy as np

    from repro.kernels.node_mlp import node_mlp

    rng = np.random.default_rng(0)
    a = rng.normal(size=(256, 256)).astype(np.float32)
    b = rng.normal(size=(256, 256)).astype(np.float32)
    want = a.astype(np.float64) @ b.astype(np.float64)
    scale = np.abs(a).astype(np.float64) @ np.abs(b).astype(np.float64)
    da, db = jax.device_put(a, tpu), jax.device_put(b, tpu)
    zero = jax.device_put(np.zeros(256, np.float32), tpu)
    xla = np.asarray(jax.jit(lambda x, y: x @ y)(da, db))
    pallas = np.asarray(node_mlp(da, db, zero, activation="none"))
    e_xla = float((np.abs(xla - want) / scale).max())
    e_pal = float((np.abs(pallas - want) / scale).max())
    return (f"xla_dot={e_xla:.3e} pallas_node_mlp={e_pal:.3e} "
            f"(one bf16 pass ~ 2^-9 = 1.953e-03, f32 ~ 6e-08)")


def _census_ok() -> bool:
    from repro.obs.metrics import default_registry

    series = default_registry().counter("kernels_dispatch_total").series()
    _log(f"kernel census: {sorted((k, v) for k, v in series.items())}")
    return bool(series) and all(path == "kernel" for _, path in series)


def run_one_chip(args, tpu, cpu) -> bool:
    import jax
    import numpy as np

    from repro.configs.gengnn_models import get_gnn_config
    from repro.gnn import init
    from repro.serve.executor import Executor

    _log(f"f32 matmul error on {tpu.device_kind}: {_probe_matmul_precision(tpu)}")
    graphs = _graphs(args.graphs, args.seed)
    ok = True
    compile_total = 0.0
    for name, model, precision, fused in PHASES:
        if args.phases and name not in args.phases.split(","):
            continue
        t0 = time.perf_counter()
        cfg = get_gnn_config(model)
        params = init(jax.random.PRNGKey(args.seed), cfg)
        ex = Executor()
        ex.register(name, cfg, params, precision=precision, fused=fused)
        with jax.default_matmul_precision("highest" if name in HIGHEST else None):
            served, rep, lowered = _serve(ex, name, graphs)
            ref = _reference(cfg, params, graphs, cpu)
        finite = bool(np.isfinite(served).all())
        shape_ok = served.shape == ref.shape == (len(graphs), cfg.out_dim)
        if precision == "int8":
            mae = float(np.abs(served - ref).mean())
            tol = max(INT8_MAE_FLOOR, INT8_MAE_REL * float(np.abs(ref).mean()))
            err, what = mae, "mae"
        else:
            err, tol, what = (_rel_err(served, ref), HIGHEST.get(name, TOL_FP32),
                              "max_rel_err")
        good = finite and shape_ok and err <= tol
        ok &= good
        compile_total += ex.compile_seconds
        sizes = rep.batch_sizes
        _log(f"phase {name}: {'ok' if good else 'FAIL'} {what}={err:.3e} "
             f"tol={tol:.3e} finite={finite} shape={served.shape} "
             f"flushes={len(sizes)} graphs_per_flush={np.mean(sizes):.2f} "
             f"programs_built={len(ex._compiled)} lowered_after_warmup={lowered} "
             f"lowered_after_served={ex.lowered_count} "
             f"compile_s={ex.compile_seconds:.2f} warm_s={ex.warm_seconds:.2f} "
             f"phase_s={time.perf_counter() - t0:.1f}")
    census = _census_ok()
    _log(f"compile_s_total={compile_total:.2f} kernel_census_ok={census}")
    return ok and census


def _shard_devices(sharding) -> list:
    return sorted(d.id for d in sharding.device_set)


def _mesh_phase(name, model, fused, graphs, mesh, args, cpu) -> bool:
    import jax
    import numpy as np

    from repro.configs.gengnn_models import get_gnn_config
    from repro.core.batching import BucketBudget, pack_prepared
    from repro.gnn import init
    from repro.serve.executor import Executor

    cfg = get_gnn_config(model)
    params = init(jax.random.PRNGKey(args.seed), cfg)

    one = Executor()
    one.register(name, cfg, params, fused=fused)
    out_one, _, _ = _serve(one, f"{name}_one_chip", graphs)

    four = Executor(mesh=mesh)
    tenant = four.register(name, cfg, params, fused=fused)
    out_four, rep, lowered = _serve(four, f"{name}_four_chips", graphs)
    # ("packed", nodes, edges, graph slots) of every program served
    rungs = sorted({key[1][1:] for key in four._compiled})

    err = _rel_err(out_four, out_one)
    ref_err = _rel_err(out_four, _reference(cfg, params, graphs, cpu))
    ok = bool(np.isfinite(out_four).all()) and err <= TOL_MESH \
        and ref_err <= TOL_FP32 and max(n for n, _, _ in rungs) >= 256

    # where one packed flush's data lives: the program's inputs, the rows
    # it shards inside (logical_constraint over the mesh), and its outputs
    budget = BucketBudget(n_pad=32 * CAPACITY, e_pad=96 * CAPACITY,
                          g_pad=2 * CAPACITY)
    small = [g for g in graphs if g[2].shape[0] <= 32][:4]
    prep, _ = pack_prepared(small, budget)
    pending = four.run_async(prep)
    exe = four._program(tenant, prep.bucket_key, prep.num_graphs) \
        .executables[(tenant.params_sig,) + prep.signature]
    in_devs = sorted({tuple(_shard_devices(s))
                      for s in jax.tree.leaves(exe.input_shardings)})
    out = pending._out
    out_shards = [(s.device.id, tuple(s.data.shape))
                  for s in out.addressable_shards]
    pending.result()
    text = exe.as_text()
    row_sharded = text.count("devices=[4,1]") + text.count("devices=[4]")
    collectives = {k: text.count(k) for k in
                   ("all-reduce", "all-gather", "reduce-scatter",
                    "collective-permute", "all-to-all")}
    # the output lives on all four devices and the devices exchange
    # partial results: the work did not all sit on device 0
    spread = len({d for d, _ in out_shards}) == 4 \
        and sum(collectives.values()) > 0
    ok &= spread
    _log(f"four-chip {name}: {'ok' if ok else 'FAIL'} "
         f"max_rel_err_vs_one_chip={err:.3e} tol={TOL_MESH:.1e} "
         f"max_rel_err_vs_oracle={ref_err:.3e} tol={TOL_FP32:.1e} "
         f"flushes={len(rep.batch_sizes)} rungs={rungs} "
         f"lowered_after_warmup={lowered} compile_s={four.compile_seconds:.2f}")
    _log(f"four-chip {name} shards: input device sets {in_devs}; "
         f"row-sharded values in the program {row_sharded}; "
         f"output shards (device, shape) {out_shards}; "
         f"collectives {collectives}")
    return ok


def run_four_chips(args, devices, cpu) -> bool:
    from repro import runtime as RT

    if len(devices) < 4:
        _log(f"--chips 4 needs four devices, found {len(devices)}")
        return False
    mesh = RT.make_flat_mesh(4, axis="data")
    # half the molecules large: the stream reaches a rung whose node rows
    # per device (64) are fewer than the whole source table's (256)
    graphs = _graphs(args.graphs, args.seed, large=args.graphs // 2)
    ok = True
    for name, model, fused in MESH_PHASES:
        ok &= _mesh_phase(name, model, fused, graphs, mesh, args, cpu)
    return _census_ok() and ok


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--graphs", type=int, default=32,
                    help="molecules served per phase")
    ap.add_argument("--phases", default="",
                    help="comma-separated one-chip phases to run (default all)")
    args = ap.parse_args(argv)

    # the oracle runs on the host CPU device next to the TPU
    platforms = os.environ.get("JAX_PLATFORMS", "")
    if platforms and "cpu" not in platforms.split(","):
        os.environ["JAX_PLATFORMS"] = platforms + ",cpu"
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"chip_smoke: no TPU (found {devices[0].platform}); nothing run",
              file=sys.stderr)
        return 2
    sys.path.insert(0, os.path.join(ROOT, "src"))
    from repro.runtime import configure_compilation_cache

    _log(f"compilation cache: {configure_compilation_cache()}")
    cpu = jax.devices("cpu")[0]
    t0 = time.perf_counter()
    if args.chips == 4:
        ok = run_four_chips(args, devices, cpu)
    else:
        ok = run_one_chip(args, devices[0], cpu)
    _log(f"total_s={time.perf_counter() - t0:.1f}")
    if not ok:
        print("chip_smoke: a phase failed", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": devices[0].platform, "kind": devices[0].device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
