"""Span tracer for the serving stack — time only through an injected Clock.

Telemetry in a scheduler whose correctness is *defined* by deterministic
timestamps must not introduce a second time source: a ``time.time()``
inside a span would make two runs of the same scripted trace differ, and
``tools/check_engine_singlepath.py`` would rightly fail the build.  So a
:class:`Tracer` is constructed around the same injectable
``serve.clock.Clock`` the scheduler runs on, and every implicit
timestamp (``span`` enter/exit, ``event`` with no explicit instant) is a
``clock.now()`` read.  Under a ``VirtualClock`` simulation the emitted
spans are therefore a bitwise-deterministic function of the input trace
— ``tests/test_obs.py`` asserts two invocations of the same scripted
stream serialize to *identical* Chrome trace-event JSON.

Two recording styles, matching the two kinds of serving time:

* **Host stages** (pack, unpack, calibration) happen *now*: wrap them in
  ``with tracer.span("pack", tenant=..., graphs=...)``.  On a live
  ``RealClock`` the span measures real host time; on a ``VirtualClock``
  time does not move during host work, so the span is an exact
  zero-duration marker at the virtual instant — still deterministic.
* **Timeline stages** (queue wait, device occupancy) are *computed* by
  the event loop (``start_s = max(at_s, device_free)``), possibly in the
  future relative to ``clock.now()``: record them with explicit
  boundaries via :meth:`Tracer.record`.

The default sink everywhere is :data:`NULL_TRACER`, which records
nothing — no list append, no clock read; its ``span`` keeps only the
profiler annotation below (call sites guard in-memory attr construction
on ``tracer.enabled``).  Telemetry disabled is provably free: the
scheduler emits the identical flush log and the executor builds the
identical compile-key set with and without a live tracer attached, and
the dark path's spans add no clock read (``tests/test_obs.py`` pins
all three).

Spans carry a ``track`` (one Perfetto thread row per track:
``scheduler`` / ``device`` / ``host`` / ``executor``) and sorted
``attrs`` tuples so serialization order never depends on dict insertion
order.  Export lives in ``obs/export.py``.

**Profiler sink.**  Every host-stage ``span`` — on a live ``Tracer`` and
on ``NULL_TRACER`` alike — also opens a ``jax.profiler.TraceAnnotation``
named ``repro.<name>`` with the span's attrs.  While a JAX profiler runs
(``jax.profiler.trace`` / ``start_trace`` / ``start_server``) the stage
lands in its ``.xplane.pb`` on the same clock as the device's ops; while
none runs the annotation is inert (about a microsecond per span) and
reads no clock of ours.  Code that holds no tracer (``core/batching.py``)
and stages the in-memory timeline already models with ``record`` (a
flush) open the same annotation, without an in-memory span, through
:func:`annotate`.  ``record`` and ``event`` are timeline-only: they
write nothing to the profiler.
"""
from __future__ import annotations

import dataclasses
from typing import List, Optional, Tuple

from jax.profiler import TraceAnnotation

PROFILER_PREFIX = "repro."  # host-stage spans in a profiler trace


def annotate(name: str, **attrs) -> TraceAnnotation:
    """The profiler half of a host-stage span alone: a
    ``TraceAnnotation`` named ``repro.<name>`` carrying ``attrs`` (scalar
    values).  For code that holds no tracer, and for stages the in-memory
    timeline records through ``Tracer.record`` instead."""
    return TraceAnnotation(PROFILER_PREFIX + name, **attrs)


def _freeze_attrs(attrs: dict) -> Tuple[tuple, ...]:
    """Attrs as a sorted, hashable tuple — deterministic serialization
    order regardless of keyword order at the call site."""
    return tuple(sorted(attrs.items()))


@dataclasses.dataclass(frozen=True)
class Span:
    """One closed span: ``[t0_s, t1_s]`` on the tracer's clock timeline.

    Instant events are spans with ``t1_s is None`` (Perfetto ``ph: "i"``);
    closed spans export as complete events (``ph: "X"``)."""

    name: str
    t0_s: float
    t1_s: Optional[float]
    track: str = "scheduler"
    attrs: Tuple[tuple, ...] = ()

    @property
    def dur_s(self) -> float:
        return 0.0 if self.t1_s is None else self.t1_s - self.t0_s


class _LiveSpan:
    """Context manager recording one span on exit (exceptions included —
    a failed stage still shows up in the trace, with its real duration),
    inside the stage's profiler annotation."""

    __slots__ = ("_tracer", "_name", "_track", "_attrs", "_t0", "_profiled")

    def __init__(self, tracer: "Tracer", name: str, track: str, attrs: dict):
        self._tracer = tracer
        self._name = name
        self._track = track
        self._attrs = attrs
        self._t0 = 0.0
        self._profiled = annotate(name, **attrs)

    def note(self, **attrs) -> None:
        """Add attrs known only inside the stage (a duration measured on
        another clock) to the in-memory span; the profiler's copy keeps
        the attrs it opened with."""
        self._attrs.update(attrs)

    def __enter__(self):
        self._profiled.__enter__()
        self._t0 = self._tracer.clock.now()
        return self

    def __exit__(self, *exc):
        self._tracer.record(self._name, self._t0, self._tracer.clock.now(),
                            track=self._track, **self._attrs)
        self._profiled.__exit__(*exc)
        return False


class Tracer:
    """Collects spans/events; all implicit time reads go through the one
    injected ``clock`` (``serve.clock.Clock`` protocol — only ``now()``
    is required)."""

    enabled = True

    def __init__(self, clock):
        self.clock = clock
        self.spans: List[Span] = []

    def span(self, name: str, track: str = "host", **attrs) -> _LiveSpan:
        """Measure a host stage happening *now*:
        ``with tracer.span("pack", tenant=..., bucket=...)`` — in memory
        on this tracer's clock, and as ``repro.pack`` in a running
        profiler."""
        return _LiveSpan(self, name, track, attrs)

    def record(self, name: str, t0_s: float, t1_s: float,
               track: str = "scheduler", **attrs) -> None:
        """Record a closed span with explicit boundaries (the event loop's
        computed timeline stages: queue wait, device occupancy)."""
        self.spans.append(Span(name=name, t0_s=float(t0_s), t1_s=float(t1_s),
                               track=track, attrs=_freeze_attrs(attrs)))

    def event(self, name: str, t_s: Optional[float] = None,
              track: str = "scheduler", **attrs) -> None:
        """Record an instant event at ``t_s`` (default: the clock's now)."""
        at = self.clock.now() if t_s is None else float(t_s)
        self.spans.append(Span(name=name, t0_s=at, t1_s=None, track=track,
                               attrs=_freeze_attrs(attrs)))

    def clear(self) -> None:
        self.spans.clear()


class NullTracer:
    """The default sink: records nothing and never reads a clock.
    ``span`` keeps only the profiler annotation (inert unless a profiler
    runs); every other method is a no-op.  Call sites gate any in-memory
    attr-building work on ``tracer.enabled``."""

    enabled = False
    spans: Tuple[()] = ()

    def span(self, name: str, track: str = "host", **attrs) -> TraceAnnotation:
        return annotate(name, **attrs)

    def record(self, name: str, t0_s: float, t1_s: float,
               track: str = "scheduler", **attrs) -> None:
        pass

    def event(self, name: str, t_s: Optional[float] = None,
              track: str = "scheduler", **attrs) -> None:
        pass

    def clear(self) -> None:
        pass


NULL_TRACER = NullTracer()
