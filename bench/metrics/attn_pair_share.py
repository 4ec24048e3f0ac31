"""attn_pair_share: query-key pairs within the served graphs over the
pairs the attention kernel computes, over every flush of the window.

Per flush, from the flush log and the pool: real = sum of n_g^2 over its
molecules; computed = the q x k pairs of the tiles the segment-id
attention kernel runs over the flush's padded node rows (base bucket rows
x rung multiple) with its molecules packed in order, as the program counts
them (``repro.kernels.flash_attention.segment_pairs``, what the scheduler
adds to ``serve_attention_pairs_total``).  So the computed count is the
program's own claim about its tiling, recomputed here: a change to
``segment_pairs`` or to the kernel's block size moves this share whether
or not the tiles the device runs changed (only the program's CPU test of
``segment_pairs`` against ``segment_tile_table`` ties the two).  Left out
when the program has no such count."""


def read(run):
    try:
        from repro.kernels.flash_attention import segment_pairs
    except ImportError:
        return None
    w, nodes = run.window, run.pool.nodes
    real = computed = 0
    for call in w.calls:
        for f in call.flushes:
            counts = nodes[w.pool_idx[[call.first + r for r in f.rids]]]
            r, c = segment_pairs(counts, f.sig[0] * f.rung_multiple)
            real += r
            computed += c
    return real / computed if computed else None
