"""The flush's wire format: every input leaf in one buffer of 32-bit words.

A ``jax.device_put`` of a pytree pays per array, not per byte: on a TPU
v5e host one put of a packed flush's 13 leaves (graph, eigenvector,
layout plan) waits ~2.4 ms, the same bytes as one array ~0.6 ms.  So a
prepared batch crosses as one contiguous int32 buffer, and the served
program slices the leaves back out at static offsets.

:func:`wire` writes the leaves of ``(graph, eigvec, layout)`` into that
buffer on the host; :func:`unwire` rebuilds them, inside a jitted program
or eagerly.  Both follow a **manifest**: one ``(path, segment)`` pair per
slot of ``Graph``, the eigenvector and ``GraphLayout``, in field order,
where ``segment`` is ``(offset, shape, dtype name)`` or ``None`` for an
absent eigenvector, plan or model static.  It is a pure function of the
leaves' shapes and dtypes (:func:`manifest_of`), so a compiled program's
input signature fixes it.

Encoding, per leaf (dtypes canonicalized as ``jax.device_put`` would):
4-byte dtypes are their own bits (``x.view(np.int32)``); ``bool`` is 0/1;
1- and 2-byte dtypes are their bits zero-extended.  Every bit pattern,
NaN payloads and ``-0.0`` included, comes back unchanged.  Each segment
starts on a multiple of :data:`ALIGN` words.
"""
from __future__ import annotations

import dataclasses
import functools
import math
from typing import Iterator, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from repro.core import graph as G
from repro.core import layout as LY

ALIGN = 128  # words; each segment starts on a multiple of this

# (field, path) of every slot of the graph and of the plan
_GRAPH = tuple((f.name, f"graph.{f.name}") for f in dataclasses.fields(G.Graph))
_LAYOUT = tuple((f.name, f"layout.{f.name}")
                for f in dataclasses.fields(LY.GraphLayout))


def _slots(graph: G.Graph, eigvec, layout) -> Iterator[tuple]:
    """``(path, leaf or None)`` for every slot, in manifest order."""
    for name, path in _GRAPH:
        yield path, getattr(graph, name)
    yield "eigvec", eigvec
    for name, path in _LAYOUT:
        yield path, None if layout is None else getattr(layout, name)


@functools.lru_cache(maxsize=None)
def _wire_dtype(dtype: np.dtype) -> Tuple[np.dtype, str]:
    """The dtype a leaf of ``dtype`` crosses as, and its name (memoized:
    a dtype's name is slow to read, and a flush reads one per leaf)."""
    dtype = np.dtype(jax.dtypes.canonicalize_dtype(dtype))
    if dtype != np.bool_ and dtype.itemsize not in (1, 2, 4):
        raise TypeError(f"no wire encoding for dtype {dtype}")
    return dtype, dtype.name


def manifest_of(graph: G.Graph, eigvec=None, layout=None) -> tuple:
    """The manifest of ``(graph, eigvec, layout)``: ``(path, segment)`` per
    slot, ``segment = (offset, shape, dtype name)`` or ``None``."""
    out, offset = [], 0
    for path, leaf in _slots(graph, eigvec, layout):
        if leaf is None:
            out.append((path, None))
            continue
        shape = tuple(leaf.shape)
        out.append((path, (offset, shape, _wire_dtype(leaf.dtype)[1])))
        offset += _span(shape)
    return tuple(out)


def _span(shape: tuple) -> int:
    """Words a segment of ``shape`` takes, rounded up to :data:`ALIGN`."""
    return -(-math.prod(shape) // ALIGN) * ALIGN


def wire_words(manifest: tuple) -> int:
    """Length of the buffer ``manifest`` describes, in int32 words."""
    size = 0
    for _, seg in manifest:
        if seg is not None:
            off, shape, _ = seg
            size = off + _span(shape)
    return size


def wire(graph: G.Graph, eigvec=None, layout=None) -> Tuple[np.ndarray, tuple]:
    """``(buffer, manifest)``: every leaf's bits in one host int32 array.

    Host leaves are copied in; a device leaf is read back first (the
    serving path hands over numpy leaves only, so it reads nothing back).
    """
    manifest = manifest_of(graph, eigvec, layout)
    buf = np.zeros((wire_words(manifest),), np.int32)
    for (_, seg), (_, leaf) in zip(manifest, _slots(graph, eigvec, layout)):
        if seg is None:
            continue
        x = np.asarray(leaf)
        dtype = _wire_dtype(x.dtype)[0]
        x = (x if x.dtype == dtype else x.astype(dtype)).reshape(-1)
        if x.dtype == np.bool_:
            words = x
        elif x.dtype.itemsize == 4:
            words = x.view(np.int32)
        else:
            words = x.view(f"u{x.dtype.itemsize}")
        buf[seg[0] : seg[0] + x.size] = words
    return buf, manifest


def _leaf(buf, seg: tuple):
    off, shape, name = seg
    dtype = jnp.dtype(name)
    words = lax.slice(buf, (off,), (off + math.prod(shape),))
    if dtype == jnp.bool_:
        x = words != 0
    elif dtype == jnp.int32:
        x = words
    elif dtype.itemsize == 4:
        x = lax.bitcast_convert_type(words, dtype)
    else:
        narrow = jnp.dtype(f"uint{8 * dtype.itemsize}")
        x = lax.bitcast_convert_type(words.astype(narrow), dtype)
    return x.reshape(shape)


def unwire(buf, manifest: tuple) -> Tuple[G.Graph, Optional[jax.Array],
                                          Optional[LY.GraphLayout]]:
    """Rebuild ``(graph, eigvec, layout)`` from ``buf`` as ``manifest``
    describes it: static slices, bitcasts and reshapes, so inside a jitted
    program it adds no host work; called eagerly it runs them on the
    buffer's device."""
    leaves = {path: None if seg is None else _leaf(buf, seg)
              for path, seg in manifest}
    graph = G.Graph(**{n: leaves[path] for n, path in _GRAPH})
    layout = None
    if leaves["layout.perm"] is not None:
        layout = LY.GraphLayout(**{n: leaves[path] for n, path in _LAYOUT})
    return graph, leaves["eigvec"], layout
