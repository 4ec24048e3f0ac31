"""p50_ms: median over the window's served requests of (wall time the
serving call returned the request's output - the request's due time)."""
import numpy as np


def read(run):
    w, k = run.window, run.served()
    return float(np.percentile(w.done_s[k] - w.due_s[k], 50) * 1e3) if len(k) else None
