"""attn_roofline: the least time of the attention kernel's work in each
traced flush over the device time of its ops, as a share (%).

The least time of one flush is the larger of the kernel's operations over
the chip's bf16 peak and its bytes over the HBM bandwidth
(bench/peaks.json), both counted by the configuration's attention_flops /
attention_bytes at the flush's padded node rows (base bucket rows x rung
multiple), every layer.  The device time is the duration of every op the
kernel ran in the window: the ops whose HLO instruction is named
``gps_attention`` (the ``name=`` of its ``pallas_call``), one per layer and
flush; when their count is not num_layers x flushes the metric is left out,
as it is for a configuration without an attention kernel."""
import devtrace

KERNEL = "gps_attention"


def kernel_ops(run):
    """(start_ns, end_ns) of the attention kernel's ops in the window, or
    None without a trace."""
    t = run.trace
    if not t or not t["window"] or not t["devices"]:
        return None
    lo, hi = t["window"]
    device = t["devices"][min(t["devices"])]
    return [(s, e) for name, s, e in device["ops"]
            if devtrace.op_label(name).startswith(KERNEL + ".") and lo <= s < hi]


def read(run):
    model, cfg, peaks = run.cell.model, run.cell.config, run.peaks
    if not peaks or not hasattr(model, "attention_flops"):
        return None
    ops = kernel_ops(run)
    flushes = [f for f, _, _ in run.flushes()]
    if not ops or not flushes or len(ops) != cfg["num_layers"] * len(flushes):
        return None
    least = 0.0
    for f in flushes:
        rows = f.sig[0] * f.rung_multiple
        least += max(model.attention_flops(rows, cfg) / peaks["bf16_flops_per_s"],
                     model.attention_bytes(rows, cfg) / peaks["hbm_bytes_per_s"])
    busy = sum(e - s for s, e in ops) / 1e9
    return 100.0 * least / busy if busy > 0 else None
