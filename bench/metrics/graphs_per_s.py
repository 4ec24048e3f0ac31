"""graphs_per_s: graphs whose outputs came back, over the wall time from
the window start to the end of the last completed submission (client
side, host clock)."""


def read(run):
    served = len(run.served())
    return served / run.window.end_s if served and run.window.end_s > 0 else None
