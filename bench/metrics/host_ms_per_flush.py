"""host_ms_per_flush: (wall time inside StreamScheduler.run - the sum of
the flushes' FlushRecord.compute_s) over flushes, in ms.  The host's part
of each flush: admission, packing, the layout plan, DGN's eigenvector,
unpacking."""


def read(run):
    inside = sum(c.end_s - c.start_s for c in run.window.calls)
    compute = [f.compute_s for f, _, _ in run.flushes()]
    return (inside - sum(compute)) / len(compute) * 1e3 if compute else None
