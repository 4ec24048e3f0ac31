"""forward_roofline: the least time of each traced flush's forward over the
device time of those programs, as a share (%).

The least time of one flush is the larger of its operations over the
chip's bf16 peak and its bytes over the HBM bandwidth (bench/peaks.json),
both counted by the configuration's forward_flops / forward_bytes at the
flush's rung (padded node rows, edge rows and graph slots) and published
widths, whichever kernels run it.  The device time is the duration of each
execution of the served program in the trace (the ``XLA Modules`` line),
matched to the flush log in order; when the counts differ the metric is
left out."""
import devtrace

PROGRAM = "jit_run"  # Executor._program jits a function named `run`


def read(run):
    t = run.trace
    if not t or not t["window"] or not t["devices"] or not run.peaks:
        return None
    lo, hi = t["window"]
    device = t["devices"][min(t["devices"])]
    execs = devtrace.programs_in(device["programs"], lo, hi, PROGRAM)
    flushes = [f for f, _, _ in run.flushes()]
    if not execs or len(execs) != len(flushes):
        return None
    cfg, model, peaks = run.cell.config, run.cell.model, run.peaks
    least = 0.0
    for f in flushes:
        k = f.rung_multiple
        rows, edges, slots = f.sig[0] * k, f.sig[1] * k, 2 * k
        least += max(model.forward_flops(rows, edges, slots, cfg) / peaks["bf16_flops_per_s"],
                     model.forward_bytes(rows, edges, slots, cfg) / peaks["hbm_bytes_per_s"])
    busy = sum(e - s for s, e in execs) / 1e9
    return 100.0 * least / busy if busy > 0 else None
