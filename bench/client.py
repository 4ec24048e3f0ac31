"""The benchmark's client: one generator that reads a traffic file and
drives the scheduler on the wall clock.

A traffic file (``bench/traffic/<name>.json``) sets:

* ``loop``: ``"closed"`` (one client, each submission waits for the one
  before) or ``"open"`` (Poisson arrivals at a fixed rate, whatever the
  server does);
* ``submission``: molecules per submission of a closed loop;
* ``rate_per_s``: mean arrival rate of an open loop;
* ``pool``: molecules in the pool made from the seed (replayed in order).

Open loop: the client hands every request that has come due to one
``StreamScheduler.run(graphs, qps=0)`` call, then repeats; when none is
due it waits for the next arrival.  A request's latency runs from its due
time to the return of the call that served it.  The client's own lag is
how late it handed over a request that came due while it was waiting.

Each phase of the loop runs inside a span (``bench.wait``, ``bench.serve``,
``bench.generate``, all inside ``bench.window``), which the caller turns
into ``jax.profiler.TraceAnnotation``s when it traces.
"""
from __future__ import annotations

import dataclasses
import time
from typing import Callable, List

import numpy as np

now = time.perf_counter


@dataclasses.dataclass
class Call:
    """One ``StreamScheduler.run`` call of the window."""

    start_s: float  # relative to the window start
    end_s: float
    first: int  # requests [first, first + count) of the window
    count: int
    flushes: list  # the call's FlushRecords, in execution order


@dataclasses.dataclass
class Window:
    start: float  # perf_counter at the window start
    seconds: float  # the window asked for
    pool_idx: np.ndarray  # (R,) molecule of each request
    due_s: np.ndarray  # (R,) when each request came due, from the start
    done_s: np.ndarray  # (R,) when its output came back (nan: never)
    outputs: List[np.ndarray]  # (R,) served outputs (None: never)
    calls: List[Call]
    lags_s: List[float]  # client lag of requests that came due while idle
    pool_size: int = 0

    def grow(self, r: int) -> None:
        """Make room for at least ``r`` requests (closed loop)."""
        have = len(self.pool_idx)
        if r <= have:
            return
        r = max(r, 2 * have)
        self.pool_idx = np.arange(r) % self.pool_size
        self.due_s = np.concatenate([self.due_s, np.full(r - have, np.nan)])
        self.done_s = np.concatenate([self.done_s, np.full(r - have, np.nan)])
        self.outputs.extend([None] * (r - have))

    def trim(self, r: int) -> None:
        """Keep the first ``r`` requests: those the window offered."""
        self.pool_idx, self.due_s, self.done_s = \
            self.pool_idx[:r], self.due_s[:r], self.done_s[:r]
        del self.outputs[r:]

    @property
    def end_s(self) -> float:
        """End of the last completed call, from the window start."""
        return self.calls[-1].end_s if self.calls else 0.0


def arrivals(rate_per_s: float, seconds: float, seed: int) -> np.ndarray:
    """Poisson arrival times in [0, seconds): one fixed set of exponential
    gaps (drawn from the rate and the window alone), put in an order drawn
    from the seed.  Every seed offers the same number of requests and the
    same gaps, so a seed changes which requests meet, not how much work
    the window holds."""
    rng = np.random.default_rng(np.random.SeedSequence([0x617272]))
    gaps = rng.exponential(1.0 / rate_per_s, int(rate_per_s * seconds * 1.5 + 64))
    while gaps.sum() < seconds:
        gaps = np.concatenate([gaps, rng.exponential(1.0 / rate_per_s, len(gaps))])
    gaps = gaps[:int(np.searchsorted(np.cumsum(gaps), seconds))]
    order = np.random.default_rng(np.random.SeedSequence([seed, 0x617272]))
    return np.cumsum(order.permutation(gaps))


def _sleep_until(t: float) -> None:
    """Sleep to within a millisecond of ``t``, then spin to it."""
    while True:
        left = t - now()
        if left <= 0:
            return
        if left > 0.002:
            time.sleep(left - 0.001)


def _serve(sched, pool, w: Window, lo: int, hi: int, t0: float, span) -> None:
    """Serve requests [lo, hi) of the window in one call."""
    with span("bench.generate"):
        graphs = pool.graphs(w.pool_idx[lo:hi])
    with span("bench.serve"):
        c0 = now()
        rep = sched.run(graphs, qps=0.0)
        c1 = now()
    for k, out in zip(range(lo, hi), rep.outputs):
        w.outputs[k] = out
        if out is not None:
            w.done_s[k] = c1 - t0
    w.calls.append(Call(c0 - t0, c1 - t0, lo, hi - lo, list(rep.flush_log)))


def closed_loop(sched, pool, submission: int, seconds: float,
                span: Callable) -> Window:
    """Submissions of ``submission`` molecules, all queued at once, back to
    back until ``seconds`` have passed; the last one runs to its end."""
    w = Window(0.0, seconds, np.zeros(0, np.int64), np.zeros(0), np.zeros(0),
               [], [], [], pool_size=len(pool))
    w.grow(64 * submission)
    k = 0
    t0 = w.start = now()
    with span("bench.window"):
        while now() - t0 < seconds:
            w.grow(k + submission)
            w.due_s[k:k + submission] = now() - t0
            _serve(sched, pool, w, k, k + submission, t0, span)
            k += submission
    w.trim(k)
    return w


def open_loop(sched, pool, due_s: np.ndarray, seconds: float,
              span: Callable) -> Window:
    """Poisson requests due at ``due_s``: each call serves every request
    that has come due; the window closes when the last one returns."""
    r = len(due_s)
    w = Window(0.0, seconds, np.arange(r) % len(pool), due_s, np.full(r, np.nan),
               [None] * r, [], [], pool_size=len(pool))
    k = 0
    t0 = w.start = now()
    with span("bench.window"):
        while k < r:
            if due_s[k] > now() - t0:
                with span("bench.wait"):
                    _sleep_until(t0 + due_s[k])
                w.lags_s.append(now() - t0 - due_s[k])
            j = int(np.searchsorted(due_s, now() - t0, side="right"))
            j = min(max(j, k + 1), r)
            _serve(sched, pool, w, k, j, t0, span)
            k = j
    return w
