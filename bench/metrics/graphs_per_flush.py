"""graphs_per_flush: graphs served over flushes, from the scheduler's
flush log (every flush of the window)."""


def read(run):
    sizes = [len(f.rids) for f, _, _ in run.flushes()]
    return sum(sizes) / len(sizes) if sizes else None
