"""Time the parts of ``core.batching.pack_prepared`` on one device.

A flush's pack stage builds the padded batch, the packed eigenvector, the
layout plan and the warm signature in numpy, writes them into one int32
buffer (``core.wire``), then crosses to the device with one
``jax.device_put`` of that buffer.  This script times each part apart, for
a flush of 1 and of 6 MolHIV-statistics molecules (DGN's inputs, so all
13 leaves are written), and beside them the put of the same 13 leaves as
a pytree, the form before the buffer.  Every time is the median of
``--reps`` calls, in ms:

* ``pad``: ``pack_graphs`` (numpy padding); ``eig``: ``pack_eigvecs``;
  ``layout``: ``pack_layout``; ``prepared``: ``PreparedBatch``, its
  buffer and its signature;
* ``put``: ``jax.device_put`` of the 13 leaves as a pytree, returned
  without waiting; ``put_wait``: the same put, then
  ``jax.block_until_ready``;
* ``one_buffer_put_wait``: a put and wait of one uint8 array holding the
  bytes of all the leaves (joined beforehand, outside the timing);
* ``wire_put_wait``: ``pack_prepared``'s own put, of the
  ``PreparedBatch`` whose one leaf is the int32 buffer, then the wait;
* ``pack_prepared``: the whole call, then ``jax.block_until_ready``.

The last line of standard output is one JSON object.  Run it from the
root of a checkout, on the device to be measured:

    PYTHONPATH=src python3 tools/pack_put_cost.py [--reps 500] [--seed 0]
"""
from __future__ import annotations

import argparse
import json
import statistics
import time

import jax
import numpy as np

from repro.core.batching import (
    BucketBudget, pack_eigvecs, pack_graphs, pack_layout, pack_prepared,
)
from repro.data.pipeline import MOLHIV, MoleculeStream, laplacian_eigvec
from repro.serve.executor import prepared

BASE = (32, 96)  # the default ladder's base bucket (nodes, edges)


def _rung(graphs) -> BucketBudget:
    """The smallest rung of the base bucket's ladder (1x, 2x, 3x, 4x, 6x, ...
    with two graph slots per multiple) that holds ``graphs``."""
    n = sum(g[2].shape[0] for g in graphs)
    e = sum(g[0].shape[0] for g in graphs)
    for m in (1, 2, 3, 4, 6, 8, 12, 16):
        if n <= BASE[0] * m and e <= BASE[1] * m and len(graphs) <= 2 * m:
            return BucketBudget(BASE[0] * m, BASE[1] * m, 2 * m)
    raise ValueError(f"{len(graphs)} graphs of {n} nodes fit no rung")


def _median_ms(fn, reps: int) -> float:
    fn()
    times = []
    for _ in range(reps):
        t0 = time.perf_counter()
        fn()
        times.append(time.perf_counter() - t0)
    return round(statistics.median(times) * 1e3, 4)


def measure(graphs, reps: int) -> dict:
    budget = _rung(graphs)
    eigs = [laplacian_eigvec(g[0], g[1], g[2].shape[0]) for g in graphs]
    packed, meta = pack_graphs(graphs, budget)
    eig = pack_eigvecs(eigs, meta)
    layout = pack_layout(packed)
    key = ("packed", budget.n_pad, budget.e_pad, budget.g_pad)
    prep = prepared(packed, eig, layout, key, budget.g_pad)
    tree = (packed, eig, layout)
    leaves = jax.tree.leaves(tree)
    joined = np.concatenate([np.ascontiguousarray(x).view(np.uint8).ravel()
                             for x in leaves])
    return {
        "rung": [budget.n_pad, budget.e_pad, budget.g_pad],
        "leaves": len(leaves),
        "bytes": int(joined.size),
        "wire_bytes": int(prep.wire.nbytes),
        "pad": _median_ms(lambda: pack_graphs(graphs, budget), reps),
        "eig": _median_ms(lambda: pack_eigvecs(eigs, meta), reps),
        "layout": _median_ms(lambda: pack_layout(packed), reps),
        "prepared": _median_ms(
            lambda: prepared(packed, eig, layout, key, budget.g_pad), reps),
        "put": _median_ms(lambda: jax.device_put(tree), reps),
        "put_wait": _median_ms(
            lambda: jax.block_until_ready(jax.device_put(tree)), reps),
        "one_buffer_put_wait": _median_ms(
            lambda: jax.block_until_ready(jax.device_put(joined)), reps),
        "wire_put_wait": _median_ms(
            lambda: jax.block_until_ready(jax.device_put(prep)), reps),
        "pack_prepared": _median_ms(
            lambda: jax.block_until_ready(pack_prepared(graphs, budget, eigs)[0]),
            reps),
    }


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--reps", type=int, default=500)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()
    stream = [g[:4] for g in MoleculeStream(MOLHIV, seed=args.seed).take(6)]
    out = {"device": jax.devices()[0].device_kind}
    for k in (1, 6):
        out[f"graphs_{k}"] = measure(stream[:k], args.reps)
    print(json.dumps(out))


if __name__ == "__main__":
    main()
