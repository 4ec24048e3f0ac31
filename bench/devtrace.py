"""Reduction of a ``jax.profiler`` trace to the benchmark's device numbers.

Two steps, kept apart so that the second can be tested on a small recorded
trace without a chip:

1. :func:`extract` reads the ``.xplane.pb`` file the profiler wrote and
   keeps what the reduction needs, as plain lists: per device, its op
   events (the ``XLA Ops`` line of each ``/device:TPU:<k>`` plane) and its
   program events (``XLA Modules``); from the host, the spans the harness
   opened with ``jax.profiler.TraceAnnotation`` (names starting
   ``bench.``).  Every event is ``[name, start_ns, end_ns]`` on the trace's
   one clock.
2. :func:`busy_ns`, :func:`top_ops`, :func:`idle_gaps` and
   :func:`programs_in` reduce those lists over a window.

Busy time is the union of a device's op intervals inside the window, so
ops that overlap (a copy beside a fusion) count once.  An idle gap is a
stretch of the window with no op running; it is named by the host span
that covers most of it, which says what the host was doing meanwhile.
"""
from __future__ import annotations

import glob
import os
from typing import Dict, List, Optional, Sequence, Tuple

Event = List  # [name, start_ns, end_ns]

OPS_LINE = "XLA Ops"
PROGRAMS_LINE = "XLA Modules"
HOST_PREFIX = "bench."


def xplane_path(trace_dir: str) -> str:
    """The one ``.xplane.pb`` file a ``start_trace(trace_dir)`` wrote."""
    found = glob.glob(os.path.join(trace_dir, "plugins", "profile", "*",
                                   "*.xplane.pb"))
    if len(found) != 1:
        raise FileNotFoundError(
            f"expected one .xplane.pb under {trace_dir}, found {found}")
    return found[0]


def extract(path: str, platform: str = "TPU") -> dict:
    """``{"devices": {k: {"ops": [...], "programs": [...]}}, "host": [...]}``
    from one xplane file (see the module docstring)."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    devices: Dict[int, dict] = {}
    host: List[Event] = []
    prefix = f"/device:{platform}:"
    for plane in data.planes:
        if plane.name.startswith(prefix):
            k = int(plane.name[len(prefix):].split(" ")[0])
            lines = {line.name: line for line in plane.lines}
            devices[k] = {
                key: [[e.name, int(e.start_ns), int(e.end_ns)]
                      for e in lines[name].events] if name in lines else []
                for key, name in (("ops", OPS_LINE), ("programs", PROGRAMS_LINE))
            }
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith(HOST_PREFIX):
                        host.append([e.name, int(e.start_ns), int(e.end_ns)])
    host.sort(key=lambda e: e[1])
    return {"devices": devices, "host": host}


def window(trace: dict, name: str = "bench.window") -> Optional[Tuple[int, int]]:
    """(start_ns, end_ns) of the harness's window span, or None."""
    spans = [e for e in trace["host"] if e[0] == name]
    return (spans[0][1], spans[0][2]) if spans else None


def _clipped(events: Sequence[Event], lo: int, hi: int) -> List[Tuple[int, int]]:
    out = []
    for _, s, e in events:
        s, e = max(s, lo), min(e, hi)
        if e > s:
            out.append((s, e))
    return out


def _union(intervals: List[Tuple[int, int]]) -> List[Tuple[int, int]]:
    merged: List[Tuple[int, int]] = []
    for s, e in sorted(intervals):
        if merged and s <= merged[-1][1]:
            if e > merged[-1][1]:
                merged[-1] = (merged[-1][0], e)
        else:
            merged.append((s, e))
    return merged


def busy_ns(ops: Sequence[Event], lo: int, hi: int) -> int:
    """Nanoseconds of [lo, hi) in which at least one op ran."""
    return sum(e - s for s, e in _union(_clipped(ops, lo, hi)))


def op_label(name: str, program: str = "") -> str:
    """A short label for an op event, whose name is its HLO instruction
    (``%fusion.12 = f32[128,100]{1,0:T(8,128)} fusion(...), ...``): the
    program's name without its hash, the instruction and its result type,
    as ``jit_run:fusion.12 f32[128,100]``."""
    instr, _, rest = name.partition(" = ")
    shape = rest.split("{")[0].split(" ")[0]
    label = f"{instr.lstrip('%')} {shape}".strip()
    return f"{program.split('(')[0]}:{label}" if program else label


def labelled(ops: Sequence[Event], programs: Sequence[Event]) -> List[Event]:
    """``ops`` renamed by :func:`op_label`, each with the program whose
    execution holds its start."""
    progs = sorted(programs, key=lambda p: p[1])
    out, j = [], 0
    for name, s, e in sorted(ops, key=lambda o: o[1]):
        while j < len(progs) and progs[j][2] <= s:
            j += 1
        inside = j < len(progs) and progs[j][1] <= s
        out.append([op_label(name, progs[j][0] if inside else ""), s, e])
    return out


def top_ops(ops: Sequence[Event], lo: int, hi: int, k: int = 10) -> list:
    """The ``k`` op names with the most device time inside [lo, hi), as
    ``[name, seconds]``, most first."""
    total: Dict[str, int] = {}
    for name, s, e in ops:
        s, e = max(s, lo), min(e, hi)
        if e > s:
            total[name] = total.get(name, 0) + (e - s)
    ranked = sorted(total.items(), key=lambda kv: (-kv[1], kv[0]))[:k]
    return [[name, ns / 1e9] for name, ns in ranked]


def _cover(spans: Sequence[Event], s: int, e: int) -> str:
    """Name of the host span that covers most of [s, e); the innermost
    (shortest) one wins a tie."""
    best, best_ns, best_len = "host: no span", 0, None
    for name, a, b in spans:
        ns = min(b, e) - max(a, s)
        if ns <= 0:
            continue
        length = b - a
        if ns > best_ns or (ns == best_ns and best_len is not None and length < best_len):
            best, best_ns, best_len = name, ns, length
    return best


def idle_gaps(ops: Sequence[Event], host: Sequence[Event], lo: int, hi: int,
              k: int = 10) -> list:
    """The ``k`` longest stretches of [lo, hi) with no op on the device, as
    ``[host span covering most of it, seconds]``, longest first.  The
    window span itself names no gap: only the spans inside it do."""
    busy = _union(_clipped(ops, lo, hi))
    gaps, at = [], lo
    for s, e in busy:
        if s > at:
            gaps.append((at, s))
        at = max(at, e)
    if hi > at:
        gaps.append((at, hi))
    inner = [h for h in host if h[0] != "bench.window"]
    gaps.sort(key=lambda g: (-(g[1] - g[0]), g[0]))
    return [[_cover(inner, s, e), (e - s) / 1e9] for s, e in gaps[:k]]


def programs_in(programs: Sequence[Event], lo: int, hi: int,
                prefix: str) -> List[Tuple[int, int]]:
    """(start_ns, end_ns) of each execution of a program whose name starts
    with ``prefix`` and that began inside [lo, hi), in order."""
    return [(s, e) for name, s, e in programs
            if name.startswith(prefix) and lo <= s < hi]
