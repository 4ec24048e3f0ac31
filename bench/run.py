"""Run one benchmark cell once; the last line of standard output is the result.

    python3 bench/run.py --workload gin.screen --seed 7 --seconds 15 --trace 0

The cell is an entry of ``workloads`` in ``BENCHMARK.json``.  It names a
configuration, found as ``bench/configs/<config>.json`` (the sizes the
program runs) and ``bench/configs/<config>.py`` (weights from the seed,
the plain reference, operation and byte counts), and a traffic mix, found
as ``bench/traffic/<traffic>.json`` (read by ``client.py``).  Each metric
is read by ``bench/metrics/<name>.py``, or by the file of the part of its
name before the first dot; ``--trace 0`` reports the cell's end-to-end
metrics, ``--trace 1`` its per-layer metrics from a profiled window.

What runs is the program's served path: one ``Executor`` tenant and a
``StreamScheduler`` with its own defaults.  Set-up (timed from the start of
this file) makes the weights on the device in one jitted call, makes the
molecule pool, and warms every ladder rung the pool reaches; the window
then runs for ``--seconds`` on the wall clock and must compile nothing.
After the window a sample of the served outputs, drawn from the seed, is
compared with the configuration's reference on the host CPU.

Without a TPU (or with fewer chips than the cell asks for) the run exits
with code 3 and prints no result.  ``--cpu`` rehearses a cell on the CPU
instead: then only the metrics that count work, not time, are printed.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()  # set-up is timed from here

import argparse  # noqa: E402
import contextlib  # noqa: E402
import dataclasses  # noqa: E402
import gc  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)
SAMPLE = 2048  # served outputs compared with the reference per run
LARGEST = 16  # the largest molecules served always join the sample
BLOCK = 128  # molecules per jitted reference call
TRACE_DIR = os.path.join(ROOT, ".bench_trace")
CACHE_DIR = os.path.join(ROOT, ".jax_cache")
COUNT_SOURCES = ("program_counter",)  # the metrics a CPU run may report


class NoChip(Exception):
    """No accelerator, or fewer chips than the cell asks for."""


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def load_module(path: str, name: str):
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def load_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


@dataclasses.dataclass
class Cell:
    """A cell with everything its files say."""

    name: str
    chips: int
    config_name: str
    config: dict
    model: object  # the configuration's module
    traffic_name: str
    traffic: dict
    metrics: list  # BENCHMARK.json entries reported by this cell at this --trace


def load_cell(name: str, trace: bool) -> Cell:
    bench = load_json(os.path.join(ROOT, "BENCHMARK.json"))
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise SystemExit(f"no workload {name!r}; have {sorted(cells)}")
    w = cells[name]
    config = next(c for c in bench["configs"] if c["name"] == w["config"])
    cfg = load_json(os.path.join(ROOT, config["file"]))
    model = load_module(os.path.join(BENCH, "configs", f"{w['config']}.py"),
                        f"bench_config_{w['config']}")
    traffic = load_json(os.path.join(BENCH, "traffic", f"{w['traffic']}.json"))
    listed = bench["per_layer"] if trace else bench["end_to_end"]
    metrics = [m for m in listed if name in m.get("workloads", [name])]
    return Cell(name, w["chips"], w["config"], cfg, model, w["traffic"],
                traffic, metrics)


def metric_reader(name: str):
    """``bench/metrics/<name>.py``, else the file of the name's first part."""
    for stem in (name, name.split(".")[0]):
        path = os.path.join(BENCH, "metrics", f"{stem}.py")
        if os.path.exists(path):
            return load_module(path, f"bench_metric_{stem.replace('.', '_')}").read
    raise FileNotFoundError(f"no reader for metric {name!r} under bench/metrics")


# ------------------------------------------------------------------ set-up


def start_jax(chips: int, cpu: bool):
    """Import JAX with the compile cache in the checkout; check the chip.

    A configuration's module has already imported JAX, which read its
    environment then, so the settings go through ``jax.config``, before
    any backend starts or any program compiles."""
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    import jax

    # named in the environment too, so configure_compilation_cache() keeps it
    os.environ["JAX_COMPILATION_CACHE_DIR"] = CACHE_DIR
    jax.config.update("jax_compilation_cache_dir", CACHE_DIR)
    platforms = jax.config.jax_platforms
    if not cpu and platforms and "cpu" not in platforms.split(","):
        # the reference runs on the host CPU beside the chip
        jax.config.update("jax_platforms", platforms + ",cpu")
    devices = jax.devices()
    want = "cpu" if cpu else "tpu"
    if devices[0].platform != want:
        raise NoChip(f"JAX found {devices[0].platform}, not {want}")
    if len(devices) < chips:
        raise NoChip(f"the cell needs {chips} chips, JAX found {len(devices)}")
    return jax, devices[:chips]


def seed_words(seed: int, tag: int, n: int = 2):
    import numpy as np

    return np.random.SeedSequence([seed, tag]).generate_state(n, np.uint32)


def build_server(jax, cell: Cell, seed: int, pool):
    """Weights on the device in one jitted call, one tenant, the scheduler."""
    from repro.gnn.models import GNNConfig
    from repro.serve.executor import Executor
    from repro.serve.scheduler import StreamScheduler

    cfg = cell.config
    fields = {f.name for f in dataclasses.fields(GNNConfig)}
    program_cfg = GNNConfig(**{k: (tuple(v) if isinstance(v, list) else v)
                               for k, v in cfg.items() if k in fields})
    make = jax.jit(lambda k: cell.model.init_params(jax.random.wrap_key_data(k), cfg))
    params = jax.block_until_ready(make(seed_words(seed, 0x77)))
    ex = Executor()
    ex.register(cell.config_name, program_cfg, params, precision=cfg["precision"],
                share_layout=cfg["share_layout"], fused=cfg["fused"])
    sched = StreamScheduler(ex, with_eigvec="auto")
    return ex, sched, params


def warm(ex, sched, pool, traffic: dict) -> int:
    """Warm every ladder rung of every base bucket the pool reaches, then
    serve a few untimed calls shaped like the window's, from the end of
    the pool.  Returns the executor's count of compiled programs."""
    import numpy as np

    firsts = {}
    for i, (n, e) in enumerate(zip(pool.nodes, pool.edges)):
        firsts.setdefault(ex.bucket_for(int(n), int(e)), i)
    sched.prewarm_ladders(pool.graphs(sorted(firsts.values())))
    tail = np.arange(len(pool) - 256, len(pool))
    per_call = traffic.get("submission", 8)
    for lo in range(0, len(tail), per_call):
        sched.run(pool.graphs(tail[lo:lo + per_call]), qps=0.0)
    for i in tail[:8]:
        sched.run(pool.graphs([i]), qps=0.0)
    return ex.lowered_count


# -------------------------------------------------------------- reference


def padded_molecule(graph: tuple, n_pad: int, e_pad: int) -> dict:
    import numpy as np

    s, r, nf, ef = graph
    n, e = nf.shape[0], len(s)
    node_feat = np.zeros((n_pad, nf.shape[1]), np.float32)
    node_feat[:n] = nf
    edge_feat = np.zeros((e_pad, ef.shape[1]), np.float32)
    edge_feat[:e] = ef
    src = np.full(e_pad, n_pad - 1, np.int32)
    dst = src.copy()
    src[:e], dst[:e] = s, r
    return {"node_feat": node_feat, "src": src, "dst": dst, "edge_feat": edge_feat,
            "node_mask": np.arange(n_pad) < n, "edge_mask": np.arange(e_pad) < e,
            "graph_id": np.zeros(n_pad, np.int32)}


def reference_outputs(jax, model, cfg: dict, params, graphs: list, device,
                      precision: str = "highest", mm=None) -> "np.ndarray":
    """The configuration's reference over ``graphs``, ``BLOCK`` molecules
    per call, each padded to the smallest of 32/64/128/... node rows, on
    ``device`` at JAX matmul ``precision`` (a control passes a lower one,
    or its own matrix product ``mm``)."""
    import jax.numpy as jnp
    import numpy as np

    mm = mm or jnp.matmul
    fn = jax.jit(jax.vmap(lambda p, g, v: model.reference(p, g, cfg, v, mm=mm),
                          in_axes=(None, 0, 0)))
    params = jax.device_put(params, device)
    rows = [max(32, 1 << (g[2].shape[0] - 1).bit_length()) for g in graphs]
    out = np.zeros((len(graphs), cfg["out_dim"]), np.float64)
    for n_pad in sorted(set(rows)):
        idx = [i for i, r in enumerate(rows) if r == n_pad]
        for lo in range(0, len(idx), BLOCK):
            part = idx[lo:lo + BLOCK]
            part = part + [part[0]] * (BLOCK - len(part))
            mols = [padded_molecule(graphs[i], n_pad, 3 * n_pad) for i in part]
            batch = {k: np.stack([m[k] for m in mols]) for k in mols[0]}
            vec = np.zeros((BLOCK, n_pad), np.float32)
            for j, i in enumerate(part):
                s, r, nf, _ = graphs[i]
                v = model.host_inputs(s, r, nf.shape[0])
                if v is not None:
                    vec[j, :nf.shape[0]] = v
            with jax.default_device(device), jax.default_matmul_precision(precision):
                y = np.asarray(fn(params, batch, vec), np.float64)
            take = idx[lo:lo + BLOCK]
            out[take] = y[:len(take)]
    return out


def compare(served: "np.ndarray", ref: "np.ndarray") -> float:
    """The widest gap between a served output and the reference's, over the
    root mean square of the reference outputs compared."""
    import numpy as np

    scale = float(np.sqrt(np.mean(ref ** 2)))
    return float(np.max(np.abs(served - ref)) / max(scale, 1e-30))


def np_stack(outputs: list) -> "np.ndarray":
    """Served outputs as one (n, out_dim) float64 array."""
    import numpy as np

    return np.stack([np.asarray(o, np.float64).reshape(-1) for o in outputs])


def sample_requests(window, pool, seed: int) -> list:
    """Served requests to compare: ``SAMPLE`` drawn from the seed, plus
    the ``LARGEST`` largest molecules served."""
    import numpy as np

    served = np.flatnonzero(~np.isnan(window.done_s))
    rng = np.random.default_rng(np.random.SeedSequence([seed, 0x636d70]))
    pick = rng.choice(served, size=min(SAMPLE, len(served)), replace=False)
    nodes = pool.nodes[window.pool_idx[served]]
    big = served[np.argsort(-nodes, kind="stable")[:LARGEST]]
    return sorted(set(pick.tolist()) | set(big.tolist()))


# ---------------------------------------------------------------- the run


@dataclasses.dataclass
class Run:
    """What the metric readers read (``bench/metrics/*.py``)."""

    cell: Cell
    window: object  # client.Window
    pool: object  # molecules.Pool
    setup_s: float
    peaks: dict  # the device's row of peaks.json (None on the CPU)
    trace: dict = None  # devtrace.extract() plus "window" (ns), on --trace 1

    def served(self):
        """Indices of the window's requests whose outputs came back."""
        import numpy as np

        return np.flatnonzero(~np.isnan(self.window.done_s))

    def flushes(self):
        """(FlushRecord, real nodes, real edges) of each flush of the
        window, in the order the device ran them."""
        w = self.window
        for call in w.calls:
            for f in call.flushes:
                idx = w.pool_idx[[call.first + r for r in f.rids]]
                yield (f, int(self.pool.nodes[idx].sum()),
                       int(self.pool.edges[idx].sum()))


def span_maker(tracing: bool):
    if not tracing:
        return lambda name: contextlib.nullcontext()
    from jax.profiler import TraceAnnotation

    return TraceAnnotation


def run(args) -> dict:
    import numpy as np

    cell = load_cell(args.workload, bool(args.trace))
    if not os.path.isdir(os.path.join(ROOT, "src", "repro")):
        raise SystemExit("the program (src/repro) is not in this checkout")
    jax, devices = start_jax(cell.chips, args.cpu)
    sys.path.insert(0, os.path.join(ROOT, "src"))
    import client
    import devtrace
    import molecules
    from repro.runtime import configure_compilation_cache

    configure_compilation_cache()
    jax.config.update("jax_default_matmul_precision", cell.config["matmul_precision"])
    kind = devices[0].device_kind
    peaks = None if args.cpu else load_json(os.path.join(BENCH, "peaks.json")).get(kind)
    if not args.cpu and peaks is None:
        raise SystemExit(f"no peaks for device kind {kind!r} in bench/peaks.json")

    t_jax = time.perf_counter()
    pool = molecules.make_pool(args.seed, args.pool or cell.traffic["pool"])
    t_pool = time.perf_counter()
    ex, sched, params = build_server(jax, cell, args.seed, pool)
    t_params = time.perf_counter()
    lowered = warm(ex, sched, pool, cell.traffic)
    due = None
    if cell.traffic["loop"] == "open":
        due = client.arrivals(cell.traffic["rate_per_s"], args.seconds, args.seed)
    gc.collect()
    gc.freeze()
    setup_s = time.perf_counter() - T_START
    log(f"set-up {setup_s:.2f} s: jax and chip {t_jax - T_START:.2f}, pool "
        f"{t_pool - t_jax:.2f}, weights and tenant {t_params - t_pool:.2f}, "
        f"rungs and warm calls {T_START + setup_s - t_params:.2f} "
        f"({ex.compile_seconds:.2f} compiling, {lowered} programs)")

    span = span_maker(bool(args.trace))
    if args.trace:
        shutil.rmtree(TRACE_DIR, ignore_errors=True)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(TRACE_DIR, profiler_options=opts)
    if cell.traffic["loop"] == "closed":
        window = client.closed_loop(sched, pool, cell.traffic["submission"],
                                    args.seconds, span)
    else:
        window = client.open_loop(sched, pool, due, args.seconds, span)
    compiled_in_window = ex.lowered_count - lowered
    memory = [d.memory_stats() for d in devices]
    peak_bytes = (max(m["peak_bytes_in_use"] for m in memory)
                  if all(m and "peak_bytes_in_use" in m for m in memory) else None)
    trace = None
    if args.trace:
        jax.profiler.stop_trace()
        trace = devtrace.extract(devtrace.xplane_path(TRACE_DIR),
                                 platform=devices[0].platform.upper())
        trace["window"] = devtrace.window(trace)
        shutil.rmtree(TRACE_DIR, ignore_errors=True)
    gc.unfreeze()
    del ex, sched

    # correctness: a seeded sample of what the window served
    t_ref = time.perf_counter()
    sample = sample_requests(window, pool, args.seed)
    served = np_stack([window.outputs[k] for k in sample])
    ref = reference_outputs(jax, cell.model, cell.config, params,
                            pool.graphs(window.pool_idx[sample]),
                            jax.devices("cpu")[0])
    gap = compare(served, ref)
    slow = sorted(window.calls, key=lambda c: c.start_s - c.end_s)[:3]
    log("slowest calls: " + "; ".join(
        f"at {c.start_s:.3f} s {1e3 * (c.end_s - c.start_s):.1f} ms, {c.count} "
        f"graphs, {len(c.flushes)} flushes, executor "
        f"{1e3 * sum(f.compute_s for f in c.flushes):.1f} ms" for c in slow))
    log(f"window {window.end_s:.2f} s, {len(window.done_s)} requests, "
        f"{sum(len(c.flushes) for c in window.calls)} flushes; reference over "
        f"{len(sample)} of them {time.perf_counter() - t_ref:.2f} s")
    unserved = int(np.sum(np.isnan(window.done_s)))
    nonfinite = int(sum(1 for o in window.outputs
                        if o is not None and not np.all(np.isfinite(o))))
    limit = cell.config["check"]["max_gap_over_rms"]
    checks = {
        "max_gap_over_rms": {"value": gap, "limit": limit},
        "compiled_in_window": {"value": compiled_in_window, "limit": 0},
        "unserved": {"value": unserved, "limit": 0},
        "nonfinite": {"value": nonfinite, "limit": 0},
    }
    correct = all(c["value"] <= c["limit"] for c in checks.values())

    result = Run(cell, window, pool, setup_s, peaks, trace)
    metrics = {}
    for m in cell.metrics:
        if args.cpu and m["source"] not in COUNT_SOURCES:
            continue
        value = metric_reader(m["name"])(result)
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    device = {"platform": devices[0].platform, "kind": kind, "count": len(devices),
              "memory_peak_bytes": peak_bytes}
    out = {"correct": correct, "attempted": len(window.done_s),
           "failed": unserved + nonfinite, "metrics": metrics, "device": device}
    if trace is not None and trace["window"] is not None and not args.cpu:
        lo, hi = trace["window"]
        used = [trace["devices"].get(d.id, {"ops": []}) for d in devices]
        busy = [devtrace.busy_ns(d["ops"], lo, hi) for d in used]
        device["busy_s"] = sum(busy) / len(busy) / 1e9
        device["window_s"] = (hi - lo) / 1e9
        ops = used[0]["ops"]
        named = devtrace.labelled(ops, used[0]["programs"])
        out["breakdown"] = {"device_ops": devtrace.top_ops(named, lo, hi),
                            "idle_gaps": devtrace.idle_gaps(ops, trace["host"], lo, hi)}
    out["checks"] = checks
    for name, c in checks.items():
        log(f"check {name}: {c['value']!r} (limit {c['limit']!r})")
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--cpu", action="store_true",
                    help="rehearse on the CPU (reports no time, rate or share)")
    ap.add_argument("--pool", type=int, default=0,
                    help="molecules in the pool (default: the traffic file's)")
    args = ap.parse_args(argv)
    if args.seed < 0:
        ap.error("--seed must be a whole number >= 0")
    try:
        out = run(args)
    except NoChip as e:
        log(f"bench: {e}; nothing run")
        return 3
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
