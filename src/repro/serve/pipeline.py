"""Host/device pipelining: bounded prepare-ahead + dispatch-ahead.

GenGNN's serving claim is that preprocessing-free inference keeps the
accelerator busy on a live stream; FlowGNN gets there by overlapping data
movement with compute across its queues.  The scheduler's serial loop is
the opposite: ``Executor.run`` blocks inside its event loop, and every
prepare stage (padding, ``pack_layout``, the Laplacian eigensolve) runs
on the host *between* device executions — at capacity the device idles
while the host packs, and the host idles while the device runs.

:class:`PipelinedStream` is the live overlap runner: a double-buffered
executor-level loop in which a single worker thread runs the
``prepare_*`` stage (pad + layout + eigvec) for request k+1 and stages it
onto the device with ``jax.device_put`` while the device runs request k;
the caller thread dispatches via :meth:`Executor.run_async` (no
``block_until_ready``) and harvests completions strictly FIFO through a
bounded in-flight window (default depth 2).  No served path uses it yet;
it is the template for dispatch-ahead inside the scheduler's one loop.

This module is the **only** place in ``serve/`` + ``obs/`` allowed to
touch ``threading`` / ``concurrent.futures``
(``tools/check_engine_singlepath.py`` enforces it, the same way it pins
the ``time`` module to the executor + clock).

Thread discipline: exactly one worker, and it only *prepares*; dispatch,
harvest, and every executor-cache mutation stay on the caller thread.
Because the device executes dispatches in order, completions are FIFO by
construction — harvesting the window front preserves per-request
response order even though dispatch k+1 happens before k completes.
"""
from __future__ import annotations

import collections
import concurrent.futures
from typing import Callable, List, Optional, Sequence, Tuple

import jax
import numpy as np

from repro.serve.executor import Executor, PendingRun, PreparedBatch


class PipelinedStream:
    """Double-buffered streaming through one executor tenant.

    One worker thread prepares (and device-stages) batches ahead of the
    dispatch loop; the caller thread dispatches with
    :meth:`Executor.run_async` and harvests the bounded in-flight window
    strictly FIFO.  ``prepare_ahead`` bounds how many prepared batches
    may wait staged on the device (default: the in-flight depth — one
    buffer filling while one drains is the classic double buffer).

    stage:  ``jax.device_put`` each prepared batch in the worker, so the
            dispatch-time H2D copy is off the critical path.
    """

    def __init__(self, executor: Executor, model: Optional[str] = None,
                 inflight: int = 2, prepare_ahead: Optional[int] = None,
                 stage: bool = True):
        if inflight < 1:
            raise ValueError(f"inflight must be >= 1, got {inflight}")
        if prepare_ahead is not None and prepare_ahead < 1:
            raise ValueError(f"prepare_ahead must be >= 1, got {prepare_ahead}")
        self.executor = executor
        self.model = model
        self.inflight = inflight
        self.prepare_ahead = prepare_ahead if prepare_ahead is not None else inflight
        self.stage = stage

    def _prepare(self, raw, with_eigvec: bool,
                 prepare: Optional[Callable]) -> PreparedBatch:
        p = (prepare(raw) if prepare is not None
             else self.executor.prepare_stream(raw, with_eigvec=with_eigvec))
        return jax.device_put(p) if self.stage else p

    def run(self, raws: Sequence[tuple], with_eigvec: bool = False,
            prepare: Optional[Callable] = None,
            ) -> Tuple[List[np.ndarray], dict]:
        """Stream ``raws`` through the pipeline; returns ``(outputs,
        stats)`` with outputs in request order (FIFO is asserted by
        construction: the window is harvested front-first).

        ``prepare`` overrides the per-item prepare stage (default:
        ``prepare_stream``); it runs on the worker thread, so it must
        not touch executor compile/warm state — the ``prepare_*`` family
        is host-side construction only, which is exactly why it can
        overlap the device.
        """
        clock = self.executor.clock
        t_start = clock.now()
        outputs: List[np.ndarray] = []
        times: List[float] = []
        window: "collections.deque[PendingRun]" = collections.deque()
        peak_inflight = 0

        def harvest_one() -> None:
            out, dt = window.popleft().result()
            outputs.append(out)
            times.append(dt)

        with concurrent.futures.ThreadPoolExecutor(max_workers=1) as pool:
            prepared: "collections.deque" = collections.deque()
            it = iter(raws)

            def top_up() -> None:
                while len(prepared) < self.prepare_ahead:
                    try:
                        raw = next(it)
                    except StopIteration:
                        return
                    prepared.append(
                        pool.submit(self._prepare, raw, with_eigvec, prepare)
                    )

            top_up()
            while prepared:
                p = prepared.popleft().result()
                top_up()  # refill the prepare queue before dispatching
                if len(window) >= self.inflight:
                    harvest_one()
                window.append(self.executor.run_async(p, model=self.model))
                peak_inflight = max(peak_inflight, len(window))
            while window:
                harvest_one()
        wall_s = clock.now() - t_start
        device_s = float(sum(times))
        return outputs, {
            "wall_s": wall_s,
            "device_s": device_s,
            "per_run_s": times,
            "peak_inflight": peak_inflight,
            "graphs_per_s": len(outputs) / max(wall_s, 1e-12),
        }
