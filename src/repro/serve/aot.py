"""Persistent AOT compile cache + XLA flag configuration — kill the warm-up.

GenGNN/FlowGNN amortize program construction by burning the message-passing
dataflow into the bitstream once; every later request runs against finished
hardware.  The TPU/XLA analogue of "the bitstream already exists" is an
**ahead-of-time compiled executable persisted across process restarts**:
the first process pays trace + lower + compile exactly once per
``(program, bucket, signature)`` and serializes the finished executable to
disk; a restarted server deserializes it in milliseconds and is serving
before a single ``jax.jit`` trace has happened.  This module owns that
disk format; ``serve/executor.py`` is the only consumer (its
``_warm`` consults the cache before compiling and writes back on miss).

Three pieces:

* :func:`environment_fingerprint` — the invalidation key.  A serialized
  executable is machine code for one exact (jax, jaxlib, backend,
  device kind, topology, XLA flag set); loading it anywhere else is at
  best a crash and at worst silent wrong numerics.  Every cache entry
  embeds the fingerprint of the environment that produced it, and a
  mismatched load is reported as ``stale`` (distinct from ``miss``) and
  recompiled + overwritten in place — flag-set changes from the
  autotuner self-invalidate the same way.
* :class:`AOTCache` — one file per entry under a root directory, named
  by the SHA-256 of the *logical* key (program key, bucket key, slot
  count, trace signature), each holding a pickled record of
  ``{fingerprint, key, payload, in_tree, out_tree}``.  Writes are
  atomic (tempfile + rename) so a crashed writer can never leave a
  half-entry; corrupted or unreadable entries degrade to a plain miss
  (fresh compile, overwrite) — never an exception on the serving path.
* :class:`XlaFlagConfig` — the checked-in flag table
  (``src/repro/configs/xla_flags.json``) that ``tools/autotune_xla.py``
  writes: per-model (and per-bucket) XLA compiler options, applied by
  the executor at program-build time via ``Lowered.compile(
  compiler_options=...)`` — the saxml ``llm_xla_flags.py`` pattern of
  sweeping latency-relevant flags offline and committing the winners.
  The resolved flag set's hash folds into the fingerprint, so retuning
  invalidates exactly the entries whose flags changed.
"""
from __future__ import annotations

import dataclasses
import hashlib
import json
import os
import pickle
import tempfile
from typing import Dict, Optional

import jax

from repro import runtime as RT

__all__ = [
    "AOTCache",
    "XlaFlagConfig",
    "default_flags_path",
    "environment_fingerprint",
    "flags_hash",
    "model_label",
]

_SCHEMA = "repro-aot/v1"
_FLAGS_SCHEMA = "repro-xla-flags/v1"
ENTRY_SUFFIX = ".aotx"


# ---------------------------------------------------------------------------
# fingerprinting
# ---------------------------------------------------------------------------


def flags_hash(flags: Optional[Dict[str, object]]) -> str:
    """Canonical short hash of one XLA flag set (sorted-key JSON), the
    fingerprint component the autotuner moves when it commits winners."""
    blob = json.dumps(flags or {}, sort_keys=True, default=str)
    return hashlib.sha256(blob.encode()).hexdigest()[:16]


def environment_fingerprint(flags: Optional[Dict[str, object]] = None) -> dict:
    """Everything a serialized executable is only valid under: jax/jaxlib
    versions, backend platform, device kind, device/process topology, and
    the XLA flag set the program was compiled with.  Deterministic and
    JSON-able; equality is the cache's validity test."""
    import jaxlib

    devices = jax.devices()
    return {
        "schema": _SCHEMA,
        "jax": jax.__version__,
        "jaxlib": jaxlib.__version__,
        "backend": jax.default_backend(),
        "device_kind": devices[0].device_kind if devices else "none",
        "num_devices": len(devices),
        "process_count": jax.process_count(),
        "flags": flags_hash(flags),
    }


def model_label(cfg) -> str:
    """The flag-table name of one model config — ``gin_vn`` is a distinct
    program from ``gin`` (``cfg.model`` alone would conflate them)."""
    return cfg.model + ("_vn" if getattr(cfg, "virtual_node", False) else "")


# ---------------------------------------------------------------------------
# the XLA flag table
# ---------------------------------------------------------------------------


def default_flags_path() -> str:
    """The checked-in flag table the autotuner maintains."""
    return os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "configs", "xla_flags.json")


def _bucket_str(bucket_key: tuple) -> str:
    return "|".join(str(x) for x in bucket_key)


@dataclasses.dataclass(frozen=True)
class XlaFlagConfig:
    """Resolved view of ``xla_flags.json``: a global default flag set,
    per-model overrides, and per-(model, bucket) overrides, merged in
    that order by :meth:`resolve`.  Values are XLA ``compiler_options``
    entries (string/bool/int, validated at autotune time — an option the
    backend rejects never reaches this table)."""

    default: Dict[str, object] = dataclasses.field(default_factory=dict)
    models: Dict[str, dict] = dataclasses.field(default_factory=dict)
    source: str = ""

    def resolve(self, model: str, bucket_key: tuple) -> Dict[str, object]:
        """The flag set for one (model, bucket) program: global default,
        overlaid with the model's default, overlaid with the exact
        bucket's entry."""
        flags = dict(self.default)
        spec = self.models.get(model)
        if spec:
            flags.update(spec.get("default", {}))
            flags.update(spec.get("buckets", {}).get(_bucket_str(bucket_key), {}))
        return flags

    @classmethod
    def load(cls, path: Optional[str] = None) -> "XlaFlagConfig":
        """Load a flag table; ``None`` means the checked-in default (an
        absent default file is an empty config, an absent *explicit*
        path is an error)."""
        explicit = path is not None
        path = path or default_flags_path()
        if not os.path.exists(path):
            if explicit:
                raise FileNotFoundError(f"XLA flag table not found: {path}")
            return cls(source=path)
        with open(path) as f:
            doc = json.load(f)
        if doc.get("schema") != _FLAGS_SCHEMA:
            raise ValueError(
                f"{path}: not a {_FLAGS_SCHEMA} document "
                f"(schema={doc.get('schema')!r})"
            )
        return cls(default=dict(doc.get("default", {})),
                   models=dict(doc.get("models", {})), source=path)

    def save(self, path: str, env: Optional[dict] = None,
             provenance: Optional[dict] = None) -> None:
        """Write the commit-the-winners document (sorted keys, stable
        across reruns on identical measurements)."""
        doc = {
            "schema": _FLAGS_SCHEMA,
            "env": env or environment_fingerprint(),
            "provenance": provenance or {},
            "default": self.default,
            "models": self.models,
        }
        with open(path, "w") as f:
            json.dump(doc, f, indent=2, sort_keys=True)
            f.write("\n")


# ---------------------------------------------------------------------------
# the persistent executable cache
# ---------------------------------------------------------------------------


class AOTCache:
    """Disk cache of serialized compiled executables, keyed by logical
    program identity and guarded by the environment fingerprint.

    ``stats`` tallies ``hit`` (deserialized and serving), ``miss``
    (absent / unreadable / corrupt — fresh compile, write-back), and
    ``stale`` (present but fingerprint-mismatched — fresh compile,
    overwrite).  The executor mirrors these into the
    ``serve_aot_cache_total{result=...}`` metric when a registry is
    attached.
    """

    def __init__(self, root: str):
        self.root = str(root)
        os.makedirs(self.root, exist_ok=True)
        self.stats: Dict[str, int] = {"hit": 0, "miss": 0, "stale": 0}
        #: outcome of the most recent :meth:`load` — the executor mirrors
        #: it into the ``serve_aot_cache_total{result=...}`` counter
        self.last_result: str = ""

    # ------------------------------------------------------------ paths

    def entry_path(self, key: tuple) -> str:
        digest = hashlib.sha256(repr(key).encode()).hexdigest()
        return os.path.join(self.root, digest + ENTRY_SUFFIX)

    def entries(self) -> list:
        """Entry files currently on disk (maintenance/introspection)."""
        return sorted(
            f for f in os.listdir(self.root) if f.endswith(ENTRY_SUFFIX)
        )

    # ------------------------------------------------------------- load

    def load(self, key: tuple, fingerprint: dict):
        """The deserialized executable for ``key`` under ``fingerprint``,
        or ``None`` (recorded as miss/stale).  Never raises on the
        serving path: an unreadable, corrupt, colliding, or
        undeserializable entry is a miss — the caller compiles fresh and
        the write-back replaces the bad entry."""
        path = self.entry_path(key)
        if not os.path.exists(path):
            return self._outcome("miss")
        try:
            with open(path, "rb") as f:
                rec = pickle.load(f)
            if not isinstance(rec, dict) or rec.get("schema") != _SCHEMA:
                raise ValueError("bad record schema")
        except Exception:  # noqa: BLE001 - corrupt/truncated file: miss
            return self._outcome("miss")
        if rec.get("key") != repr(key):  # hash collision or tamper
            return self._outcome("miss")
        if rec.get("fingerprint") != fingerprint:
            return self._outcome("stale")
        try:
            exe = RT.deserialize_compiled(
                rec["payload"], rec["in_tree"], rec["out_tree"]
            )
        except Exception:  # noqa: BLE001 - backend refused the payload
            return self._outcome("miss")
        self._outcome("hit")
        return exe

    def _outcome(self, result: str):
        self.stats[result] += 1
        self.last_result = result
        return None

    # ------------------------------------------------------------ store

    def store(self, key: tuple, fingerprint: dict, compiled) -> bool:
        """Serialize ``compiled`` under ``key``; atomic (tempfile +
        rename) so readers never observe a partial entry.  Returns False
        (and stores nothing) when the executable refuses serialization —
        serving continues uncached."""
        try:
            payload, in_tree, out_tree = RT.serialize_compiled(compiled)
        except Exception:  # noqa: BLE001 - unserializable executable
            return False
        rec = {
            "schema": _SCHEMA,
            "key": repr(key),
            "fingerprint": fingerprint,
            "payload": payload,
            "in_tree": in_tree,
            "out_tree": out_tree,
        }
        fd, tmp = tempfile.mkstemp(dir=self.root, suffix=".tmp")
        try:
            with os.fdopen(fd, "wb") as f:
                pickle.dump(rec, f)
            os.replace(tmp, self.entry_path(key))
        except Exception:  # noqa: BLE001 - disk full etc: serve uncached
            try:
                os.unlink(tmp)
            except OSError:
                pass
            return False
        return True
