"""p95_ms: 95th percentile over the window's served requests of (wall time
the serving call returned the request's output - the request's due
time).  Read in the traced run beside the bounded p50: on one chip the
host's multi-second stalls, in about one run of seven, move it too far
for a bound."""
import numpy as np


def read(run):
    w, k = run.window, run.served()
    return float(np.percentile(w.done_s[k] - w.due_s[k], 95) * 1e3) if len(k) else None
