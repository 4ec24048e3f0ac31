"""pad_share: node rows dispatched but padding over node rows dispatched:
each flush's rung (base bucket rows x rung multiple) against the real
nodes of its molecules, from the flush log."""


def read(run):
    rows = real = 0
    for f, nodes, _ in run.flushes():
        rows += f.sig[0] * f.rung_multiple
        real += nodes
    return (rows - real) / rows if rows else None
