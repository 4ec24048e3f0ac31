"""Machine-readable benchmark output: one ``BENCH_<name>.json`` per run.

Schema (consumed by perf-trajectory tooling; keep stable):

    {"name": str, "config": dict, "metrics": list-of-rows,
     "env": dict, "timestamp": iso8601}

``metrics`` is whatever row list the benchmark's ``run()`` produced (the
same dicts its CSV lines print).  ``env`` is the serving stack's
environment fingerprint (``serve.aot.environment_fingerprint``: jax /
jaxlib versions, backend, device kind, topology) — two BENCH files are
only comparable when their fingerprints match, and the perf-trajectory
tooling can now refuse to diff across a toolchain bump instead of
reporting it as a regression.  Output directory defaults to the current
working directory; override with ``REPRO_BENCH_DIR``.
"""
from __future__ import annotations

import json
import os
from datetime import datetime, timezone


def _environment() -> dict:
    try:
        from repro.serve.aot import environment_fingerprint

        env = dict(environment_fingerprint())
        env.pop("schema", None)
        env.pop("flags", None)  # per-program, not per-environment
        return env
    except Exception:  # noqa: BLE001 - a bench must never die on metadata
        return {}


def write_bench_json(name: str, metrics, config: dict | None = None,
                     out_dir: str | None = None, env: dict | None = None) -> str:
    """Write one BENCH file.  ``env`` stamps an environment probed
    elsewhere (a bench whose parent must not touch JAX passes its
    child's); by default this process probes its own."""
    out_dir = out_dir or os.environ.get("REPRO_BENCH_DIR", ".")
    os.makedirs(out_dir, exist_ok=True)
    path = os.path.join(out_dir, f"BENCH_{name}.json")
    payload = {
        "name": name,
        "config": config or {},
        "metrics": metrics,
        "env": env if env is not None else _environment(),
        "timestamp": datetime.now(timezone.utc).isoformat(timespec="seconds"),
    }
    with open(path, "w") as f:
        json.dump(payload, f, indent=2, default=str)
        f.write("\n")
    print(f"# wrote {path}", flush=True)
    return path
