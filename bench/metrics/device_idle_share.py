"""device_idle_share: 1 - (union of the device's op intervals inside the
window) / window, from the profiler trace, averaged over the chips."""
import devtrace


def read(run):
    t = run.trace
    if not t or not t["window"] or not t["devices"]:
        return None
    lo, hi = t["window"]
    busy = [devtrace.busy_ns(d["ops"], lo, hi) for d in t["devices"].values()]
    return 1.0 - sum(busy) / len(busy) / (hi - lo)
