"""step_mfu: the model operations of the real molecules served (their real
nodes and edges at the published widths, counted by the configuration's
forward_flops) per second of the window, as a share (%) of the chip's
bf16 peak (bench/peaks.json)."""


def read(run):
    if not run.peaks or run.window.end_s <= 0:
        return None
    cfg, model, pool = run.cell.config, run.cell.model, run.pool
    idx = run.window.pool_idx[run.served()]
    flops = sum(model.forward_flops(int(n), int(e), 1, cfg)
                for n, e in zip(pool.nodes[idx], pool.edges[idx]))
    return 100.0 * flops / run.window.end_s / run.peaks["bf16_flops_per_s"]
