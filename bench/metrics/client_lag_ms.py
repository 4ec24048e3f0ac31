"""client_lag_ms: 95th percentile of how late the open-loop client handed
over a request that came due while it was waiting (host clock).  A
starved client would read as a fast server; this says it was not."""
import numpy as np


def read(run):
    lags = run.window.lags_s
    return float(np.percentile(lags, 95) * 1e3) if lags else None
