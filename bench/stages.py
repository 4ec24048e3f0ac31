"""Break a cell's flushes down by the program's own stage spans, from one
traced window on the chip.

    python3 bench/stages.py --workload dgn.screen --seed 7 --seconds 20

Set-up and window are the cell's (``run.py``), and the window runs under
the JAX profiler as a ``--trace 1`` run does.  The program opens a
``repro.<stage>`` annotation around each host stage of a serving call
(``run``, ``flush``, ``eigvec``, ``pack``, ``layout``, ``dispatch``,
``compile``, ``device_wait``, ``d2h``, ``unpack``; ``repro.obs.trace``),
so the stages lie in the trace on the device's clock.  One JSON line:

* ``stage_ms``: per flush, the milliseconds of each stage, and of the
  scheduler's own work (``sched``: ``run`` less ``flush``, that is
  admission, bucketing, the event loop); and the same as the per-layer
  readings ``sched_``, ``eigvec_``, ``pack_``, ``layout_``,
  ``dispatch_ms_per_flush`` and ``unpack_ms_per_flush`` (``d2h`` plus
  ``unpack``);
* ``eager_calls_per_flush``: device programs other than the served
  ``jit_run`` that start inside a flush, the host prepare's round trips;
* ``unattributed_idle_share``: the share of the device's idle time inside
  the serving calls (``bench.serve``) that no stage span covers;
* ``idle_gaps``: the longest idle stretches, each named by the span that
  is innermost over the largest part of it, with that part's share;
* from the same run, ``host_ms_per_flush`` and ``exec_ms_per_flush`` as
  the benchmark's readers take them, and ``account``: the stages plus the
  scheduler's own time over their sum, which is 1 when the spans cover
  every serving call.

A per-flush number needs one ``repro.flush`` span per flush of the
window's flush log; where the counts differ, or the program opens no such
spans, it is left out.  ``--save PATH`` also writes a trimmed extract of
the trace (the last three flushes of one call and the first three of the
next, near the middle of the window) for the tests.  ``--cpu`` rehearses
on the CPU, where the trace has no device ops.
"""
from __future__ import annotations

import argparse
import bisect
import json
import os
import shutil
import sys

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import devtrace  # noqa: E402
import run as R  # noqa: E402

PREFIX = "repro."
PROGRAM = "jit_run"  # the served program (Executor._program jits `run`)
# stage spans nested in a flush; `layout` lies inside `pack`, `compile`
# inside `dispatch`
STAGES = ("eigvec", "pack", "layout", "dispatch", "compile", "device_wait",
          "d2h", "unpack")
# the stages that together with the scheduler's own time make up a call
ACCOUNT = ("eigvec", "pack", "dispatch", "device_wait", "d2h", "unpack")


def program_spans(path: str) -> list:
    """``[name, start_ns, end_ns]`` of the program's ``repro.*`` host
    spans in one xplane file, in order of start."""
    from jax.profiler import ProfileData

    spans = [[e.name, int(e.start_ns), int(e.end_ns)]
             for plane in ProfileData.from_file(path).planes
             if plane.name.startswith("/host:")
             for line in plane.lines for e in line.events
             if e.name.startswith(PREFIX)]
    return sorted(spans, key=lambda s: (s[1], -s[2]))


def clipped_ns(spans, name: str, lo: int, hi: int) -> list:
    """Nanoseconds inside [lo, hi) of each span called ``name``."""
    return [e - s for s, e in devtrace._clipped(
        [sp for sp in spans if sp[0] == name], lo, hi)]


def intersect(a, b) -> list:
    """Overlaps of two sorted lists of disjoint (start, end) intervals."""
    out, j = [], 0
    for s, e in a:
        while j < len(b) and b[j][1] <= s:
            j += 1
        k = j
        while k < len(b) and b[k][0] < e:
            out.append((max(s, b[k][0]), min(e, b[k][1])))
            k += 1
    return out


def covered_ns(intervals, cover) -> int:
    """Nanoseconds of the sorted, disjoint ``intervals`` that lie inside
    the union of the ``cover`` intervals."""
    return sum(e - s for s, e in intersect(intervals, devtrace._union(list(cover))))


def flushes(trace: dict) -> list:
    """The ``repro.flush`` spans that start inside the window."""
    lo, hi = trace["window"]
    return [sp for sp in trace.get("program", [])
            if sp[0] == PREFIX + "flush" and lo <= sp[1] < hi]


def stage_ms(trace: dict) -> dict:
    """Milliseconds per flush of each stage and of the scheduler's own
    work, over the window's flush spans; {} without any."""
    n = len(flushes(trace))
    if not n:
        return {}
    lo, hi = trace["window"]
    spans = trace["program"]
    ms = {st: sum(clipped_ns(spans, PREFIX + st, lo, hi)) / n / 1e6
          for st in ("run", "flush") + STAGES}
    ms["sched"] = ms["run"] - ms["flush"]
    return ms


def eager_calls_per_flush(trace: dict):
    """Device programs other than the served one that start inside a
    flush span, per flush (None without flush spans or device)."""
    inside = flushes(trace)
    if not inside or not trace["devices"]:
        return None
    programs = trace["devices"][min(trace["devices"])]["programs"]
    starts = [a for _, a, _ in inside]
    calls = 0
    for name, s, _ in programs:
        i = bisect.bisect_right(starts, s) - 1
        if i >= 0 and s < inside[i][2] and not name.startswith(PROGRAM):
            calls += 1
    return calls / len(inside)


def idle(trace: dict) -> list:
    """The device's idle stretches of the window, as (start, end)."""
    lo, hi = trace["window"]
    ops = trace["devices"][min(trace["devices"])]["ops"]
    gaps, at = [], lo
    for s, e in devtrace._union(devtrace._clipped(ops, lo, hi)):
        if s > at:
            gaps.append((at, s))
        at = max(at, e)
    if hi > at:
        gaps.append((at, hi))
    return gaps


def unattributed_idle_share(trace: dict):
    """The share of the device's idle time inside the serving calls that
    no stage span covers (None without stage spans or device)."""
    if not flushes(trace) or not trace["devices"]:
        return None
    lo, hi = trace["window"]
    serving = [h for h in trace["host"] if h[0] == "bench.serve"]
    gaps = intersect(idle(trace), devtrace._union(devtrace._clipped(serving, lo, hi)))
    total = sum(e - s for s, e in gaps)
    if total <= 0:
        return None
    names = {PREFIX + st for st in STAGES}
    stages = [(s, e) for name, s, e in trace["program"] if name in names]
    return 1.0 - covered_ns(gaps, stages) / total


def holder(spans, s: int, e: int):
    """The span innermost over the largest part of [s, e), and that part's
    share of it: each instant belongs to the shortest span open then."""
    inside = [(a, b, name) for name, a, b in spans if b > s and a < e]
    cuts = sorted({s, e} | {max(a, s) for a, _, _ in inside}
                  | {min(b, e) for _, b, _ in inside})
    held = {}
    for lo, hi in zip(cuts, cuts[1:]):
        open_ = [(b - a, name) for a, b, name in inside if a <= lo and hi <= b]
        name = min(open_)[1] if open_ else "host: no span"
        held[name] = held.get(name, 0) + hi - lo
    name = min(held, key=lambda n: (-held[n], n))
    return name, held[name] / (e - s)


def idle_gaps(trace: dict, k: int = 10) -> list:
    """The ``k`` longest idle stretches of the window, longest first, as
    ``[span, seconds, share]``: the span (harness or program) innermost
    over the largest part of the stretch, and that part's share."""
    spans = [h for h in trace["host"] if h[0] != "bench.window"] + trace.get("program", [])
    gaps = sorted(idle(trace), key=lambda g: (g[0] - g[1], g[0]))[:k]
    out = []
    for s, e in gaps:
        name, share = holder(spans, s, e)
        out.append([name, (e - s) / 1e9, share])
    return out


def reduce(trace: dict, logged: int) -> dict:
    """Everything this file reports from one trace whose window logged
    ``logged`` flushes."""
    traced = len(flushes(trace))
    out = {"flushes_traced": traced, "flushes_logged": logged}
    if traced != logged or not traced:
        return out
    ms = stage_ms(trace)
    out["stage_ms"] = ms
    for st in ("sched", "eigvec", "pack", "layout", "dispatch"):
        out[f"{st}_ms_per_flush"] = ms[st]
    out["unpack_ms_per_flush"] = ms["d2h"] + ms["unpack"]
    out["stage_sum_ms_per_flush"] = ms["sched"] + sum(ms[st] for st in ACCOUNT)
    out["eager_calls_per_flush"] = eager_calls_per_flush(trace)
    out["unattributed_idle_share"] = unattributed_idle_share(trace)
    if trace["devices"]:
        out["idle_gaps"] = idle_gaps(trace)
    return out


def trimmed(trace: dict, about: str, keep: int = 3):
    """The stretch of ``trace`` from the end of the flush before the last
    ``keep`` flushes of one serving call to the start of the flush after
    the first ``keep`` of the next, at the call boundary nearest the
    middle of the window; events overlapping it are kept whole and the
    window span is cut to it.  None when no boundary has room."""
    fl = flushes(trace)
    serves = [h for h in trace["host"] if h[0] == "bench.serve"]

    def call_of(f):
        return next((i for i, (_, a, b) in enumerate(serves) if a <= f[1] < b), -1)

    calls = [call_of(f) for f in fl]
    lo_w, hi_w = trace["window"]
    mid = (lo_w + hi_w) // 2
    cuts = [i for i in range(keep + 1, len(fl) - keep)
            if calls[i] != calls[i - 1]]
    if not cuts:
        return None
    i = min(cuts, key=lambda j: abs(fl[j][1] - mid))
    lo, hi = fl[i - keep - 1][2], fl[i + keep][1]

    def keep_(events):
        return [ev for ev in events if ev[2] > lo and ev[1] < hi]

    host = [["bench.window", lo, hi]] + [h for h in keep_(trace["host"])
                                         if h[0] != "bench.window"]
    return {"about": about, "host": host, "program": keep_(trace["program"]),
            "devices": {k: {key: keep_(v) for key, v in d.items()}
                        for k, d in trace["devices"].items()}}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument("--seconds", type=float, default=20.0)
    ap.add_argument("--save", default="", help="write a trimmed extract here")
    ap.add_argument("--cpu", action="store_true", help="rehearse on the CPU")
    ap.add_argument("--pool", type=int, default=0,
                    help="molecules in the pool (default: the traffic file's)")
    args = ap.parse_args(argv)

    cell = R.load_cell(args.workload, True)
    jax, devices = R.start_jax(cell.chips, args.cpu)
    sys.path.insert(0, os.path.join(R.ROOT, "src"))
    import client
    import molecules
    from repro.runtime import configure_compilation_cache

    configure_compilation_cache()
    jax.config.update("jax_default_matmul_precision", cell.config["matmul_precision"])
    pool = molecules.make_pool(args.seed, args.pool or cell.traffic["pool"])
    ex, sched, _ = R.build_server(jax, cell, args.seed, pool)
    lowered = R.warm(ex, sched, pool, cell.traffic)
    trace_dir = os.path.join(R.ROOT, ".bench_stages")
    shutil.rmtree(trace_dir, ignore_errors=True)
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(trace_dir, profiler_options=opts)
    span = R.span_maker(True)
    if cell.traffic["loop"] == "closed":
        w = client.closed_loop(sched, pool, cell.traffic["submission"],
                               args.seconds, span)
    else:
        due = client.arrivals(cell.traffic["rate_per_s"], args.seconds, args.seed)
        w = client.open_loop(sched, pool, due, args.seconds, span)
    jax.profiler.stop_trace()
    path = devtrace.xplane_path(trace_dir)
    trace = devtrace.extract(path, platform=devices[0].platform.upper())
    trace["program"] = program_spans(path)
    trace["window"] = devtrace.window(trace)
    shutil.rmtree(trace_dir, ignore_errors=True)

    run = R.Run(cell, w, pool, 0.0, None, trace)
    logged = sum(len(c.flushes) for c in w.calls)
    out = {"workload": args.workload, "seed": args.seed,
           "compiled_in_window": ex.lowered_count - lowered,
           "host_ms_per_flush": R.metric_reader("host_ms_per_flush")(run),
           "exec_ms_per_flush": R.metric_reader("exec_ms_per_flush")(run)}
    out.update(reduce(trace, logged))
    if "stage_sum_ms_per_flush" in out and out["host_ms_per_flush"] is not None:
        out["account"] = out["stage_sum_ms_per_flush"] / (
            out["host_ms_per_flush"] + out["exec_ms_per_flush"])
    if trace["devices"]:
        lo, hi = trace["window"]
        out["harness_idle_gaps"] = devtrace.idle_gaps(
            trace["devices"][min(trace["devices"])]["ops"], trace["host"], lo, hi)
    if args.save:
        part = trimmed(trace, f"trimmed from a bench/stages.py run of "
                       f"{args.workload} on one {devices[0].device_kind} (seed "
                       f"{args.seed}): the last three flushes of one call and "
                       f"the first three of the next; events are "
                       f"[name, start_ns, end_ns]")
        if part is not None:
            with open(args.save, "w") as f:
                json.dump(part, f, separators=(",", ":"))
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
