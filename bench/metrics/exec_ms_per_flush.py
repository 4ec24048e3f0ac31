"""exec_ms_per_flush: mean FlushRecord.compute_s in ms, the executor's own
span from dispatch to block_until_ready of each flush."""


def read(run):
    compute = [f.compute_s for f, _, _ in run.flushes()]
    return sum(compute) / len(compute) * 1e3 if compute else None
