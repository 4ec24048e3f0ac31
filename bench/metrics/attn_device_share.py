"""attn_device_share: the attention kernel's device time over the device
time of the served programs (``jit_run`` executions) in the window, from
the profiler trace.  The kernel's ops are those attn_roofline reads (HLO
instruction named ``gps_attention``); the metric is left out when the
trace holds none of them."""
import devtrace

KERNEL = "gps_attention"
PROGRAM = "jit_run"  # Executor._program jits a function named `run`


def read(run):
    t = run.trace
    if not t or not t["window"] or not t["devices"]:
        return None
    lo, hi = t["window"]
    device = t["devices"][min(t["devices"])]
    kernel = sum(e - s for name, s, e in device["ops"]
                 if devtrace.op_label(name).startswith(KERNEL + ".") and lo <= s < hi)
    total = sum(e - s for s, e in devtrace.programs_in(device["programs"], lo, hi, PROGRAM))
    return kernel / total if kernel > 0 and total > 0 else None
