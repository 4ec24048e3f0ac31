"""Guard: the serving stack has ONE timing/compile path — ``serve/executor.py``.

The executor refactor's invariant is that real-time reads and ``jax.jit``
program construction exist exactly once in the GNN serving stack (the
executor's warm-before-timing path), so no serving mode can quietly grow
its own compile cache or timed region again — the drift that produced
the old mode x axis matrix, where every new axis had to be hand-threaded
through ``infer_stream`` / ``infer_batched`` / ``infer_packed``
separately.  Since the SLO scheduler landed, the invariant is stricter:
scheduling logic runs entirely on the injectable ``serve/clock.py``
``Clock``, so *any* reference to the ``time`` module — including
wall-clock stamps via ``time.time`` — outside the executor and the clock
module is a determinism leak, not just a stray timer.

This checker walks every module under ``src/repro/serve/`` and fails on
any *reference* (not just call — aliasing counts) to:

  * ``time.perf_counter`` / ``time.monotonic`` / ``time.time`` (and
    their ``from time import ...`` forms) — a private timed region or a
    wall-clock read that would make scheduling non-reproducible;
  * ``jax.jit`` / bare ``jit`` (imported from jax) / ``pjit`` — a private
    compile path;

outside the sanctioned files.  Exemptions:

  * ``serve/executor.py`` — the one timing *and* compile path;
  * ``serve/clock.py`` — timing only: it wraps the real clock behind the
    injectable ``Clock`` interface (it is still checked for compile
    references — the clock must never grow a jit path);
  * ``serve/engine.py`` — compile only: the LM prefill/decode server is a
    separate serving stack with its own jitted prefill/decode programs,
    but its wall-time reads go through the injected ``Clock`` like
    everyone else's (it is still checked for timing references — the
    guard hole it used to enjoy is closed);
  * ``serve/aot.py`` — compile only: the persistent AOT cache
    deserializes finished executables (program construction by another
    name), and is — with the executor — the only serving module allowed
    near the lowering/serialization APIs.

Since the AOT cache landed, a fourth rule rides the walk: **executable
serialization is single-path**.  Any reference to
``jax.experimental.serialize_executable`` (module import, from-import of
``serialize`` / ``deserialize_and_load``, or attribute access through a
jax alias) outside ``serve/aot.py`` and ``serve/executor.py`` fails —
a module that serializes executables is a module that can quietly grow a
second persistence format with its own (unfingerprinted) invalidation
story.  The real calls live behind ``runtime/compat.py``; the
serve/obs walk keeps everyone else out.

A third rule rides the same walk: **threading is single-path too**.
Any import of ``threading`` / ``_thread`` / ``concurrent`` (including
``concurrent.futures``) outside ``serve/pipeline.py`` fails — the
``PipelinedStream`` prepare worker is the one sanctioned threading
surface, and everything else (scheduler, executor, clock, telemetry)
must stay single-threaded so VirtualClock simulations remain bitwise
deterministic.  ``serve/executor.py`` is NOT
exempt from this rule: it is walked too, with only its historical
timing/compile allowances.

The telemetry package ``src/repro/obs/`` is walked with the full rules
and no exemptions: spans and metrics may only read time through the
``Tracer``'s injected Clock, so a VirtualClock simulation stays bitwise
deterministic end to end, and the observability layer can never stage a
compile path or a worker thread of its own.

Exit code 1 with a per-reference report when anything times or compiles
out of bounds.

  python tools/check_engine_singlepath.py
"""
from __future__ import annotations

import ast
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parent.parent
SERVE = ROOT / "src" / "repro" / "serve"
OBS = ROOT / "src" / "repro" / "obs"
ALLOWED = "executor.py"  # the one timing/compile path
TIMING_EXEMPT = {"clock.py"}  # the Clock interface: timing yes, compile no
# engine.py: the LM server's own jit pair; aot.py: executable
# (de)serialization is program construction by another name
COMPILE_EXEMPT = {"engine.py", "aot.py"}
THREADING_EXEMPT = {"pipeline.py"}  # the one sanctioned threading surface
SERIALIZE_EXEMPT = {"aot.py", "executor.py"}  # the one persistence surface
TIMING_ATTRS = {"perf_counter", "monotonic", "time"}  # of the time module
TIMING_NAMES = {"perf_counter", "monotonic", "time"}  # `from time import ...`
COMPILE_ATTRS = {"jit", "pjit"}  # of the jax module chain
COMPILE_NAMES = {"jit", "pjit"}  # bare `from jax import jit`
TIMING_MODULES = {"time"}
COMPILE_MODULES = {"jax", "jax.experimental.pjit"}
# executable-serialization surface: importing the module (any form) or
# reaching it through a jax alias is how a second persistence path
# starts, so the reference itself is the violation
SERIALIZE_MODULE = "jax.experimental.serialize_executable"
SERIALIZE_ATTRS = {"serialize_executable"}  # of the jax module chain
SERIALIZE_NAMES = {"serialize", "deserialize_and_load"}
# any import of these module trees is a threading violation: you cannot
# spawn a worker without importing one of them, so banning the import
# (every form: plain, aliased, from-import, submodule) suffices
THREADING_MODULES = {"threading", "_thread", "concurrent"}


def _attr_root(node: ast.AST):
    """Leftmost Name of a dotted attribute chain (``jax.lax.sort`` -> jax)."""
    while isinstance(node, ast.Attribute):
        node = node.value
    return node.id if isinstance(node, ast.Name) else None


def _bound_names(tree: ast.AST):
    """(timing-module aliases, compile-module aliases, from-imported names)
    — ``import time as t`` / ``import jax as j`` alias the module itself,
    so attribute checks must resolve through the alias too; from-imports
    map the bound name back to its origin (``as`` renames count)."""
    time_mods, jax_mods, names = set(), set(), {}
    for node in ast.walk(tree):
        if isinstance(node, ast.Import):
            for alias in node.names:
                bound = alias.asname or alias.name.split(".")[0]
                if alias.name in TIMING_MODULES or alias.name.split(".")[0] in TIMING_MODULES:
                    time_mods.add(bound)
                if alias.name.split(".")[0] == "jax":
                    jax_mods.add(bound)
        elif isinstance(node, ast.ImportFrom):
            if node.module in TIMING_MODULES | COMPILE_MODULES | {SERIALIZE_MODULE}:
                for alias in node.names:
                    names[alias.asname or alias.name] = alias.name
    return time_mods, jax_mods, names


def _serialize_import(node: ast.AST):
    """The offending path when a node imports the executable-serialization
    module in any form (plain, aliased, or ``from jax.experimental
    import serialize_executable``)."""
    if isinstance(node, ast.Import):
        for alias in node.names:
            if alias.name == SERIALIZE_MODULE or \
                    alias.name.startswith(SERIALIZE_MODULE + "."):
                return alias.name
    elif isinstance(node, ast.ImportFrom) and node.module is not None:
        if node.module == SERIALIZE_MODULE or \
                node.module.startswith(SERIALIZE_MODULE + "."):
            return node.module
        for alias in node.names:
            if f"{node.module}.{alias.name}" == SERIALIZE_MODULE:
                return SERIALIZE_MODULE
    return None


def _threading_import(node: ast.AST):
    """The offending module path when a node imports from a banned
    threading module tree (root match: ``concurrent.futures`` counts)."""
    if isinstance(node, ast.Import):
        for alias in node.names:
            if alias.name.split(".")[0] in THREADING_MODULES:
                return alias.name
    elif isinstance(node, ast.ImportFrom) and node.module is not None:
        if node.module.split(".")[0] in THREADING_MODULES:
            return node.module
    return None


def check_module(path: Path, allow_timing: bool = False,
                 allow_compile: bool = False,
                 allow_threading: bool = False,
                 allow_serialize: bool = False) -> list[str]:
    """All violations in one module.  ``allow_timing`` skips the timing
    rules (for ``serve/clock.py``, which wraps the real clock) but never
    the compile rules; ``allow_compile`` is the inverse (for
    ``serve/engine.py``, whose prefill/decode jit pair is its own
    sanctioned surface) and never skips the timing rules;
    ``allow_threading`` skips the worker-thread import ban (for
    ``serve/pipeline.py`` only); ``allow_serialize`` skips the
    executable-serialization ban (for ``serve/aot.py`` and the
    executor) — each allowance skips nothing else."""
    try:
        rel = path.relative_to(ROOT)
    except ValueError:  # e.g. a tmp file under test
        rel = path
    try:
        tree = ast.parse(path.read_text(), filename=str(rel))
    except SyntaxError as err:  # pragma: no cover - tier-1 would fail first
        return [f"{rel}: unparsable ({err})"]
    time_mods, jax_mods, from_names = _bound_names(tree)
    errors = []
    for node in ast.walk(tree):
        bad = hint = None
        mod = _threading_import(node)
        if mod is not None and not allow_threading:
            errors.append(
                f"{rel}:{node.lineno}: import of {mod} outside "
                f"serve/pipeline.py — the pipelined prepare/dispatch worker "
                f"is the one sanctioned threading surface"
            )
            continue
        mod = _serialize_import(node)
        if mod is not None and not allow_serialize:
            errors.append(
                f"{rel}:{node.lineno}: import of {mod} outside "
                f"serve/aot.py — the AOT cache is the one executable-"
                f"persistence surface"
            )
            continue
        if isinstance(node, ast.Attribute):
            root = _attr_root(node)
            if node.attr in TIMING_ATTRS and root in time_mods:
                bad, hint = f"time.{node.attr} timing", "timing"
            elif node.attr in COMPILE_ATTRS and root in jax_mods:
                bad, hint = f"jax.{node.attr} program construction", "compile"
            elif node.attr in SERIALIZE_ATTRS and root in jax_mods:
                bad, hint = (f"jax...{node.attr} executable serialization",
                             "serialize")
        elif isinstance(node, ast.Name):
            origin = from_names.get(node.id)
            if origin in TIMING_NAMES:
                bad, hint = f"{origin} timing", "timing"
            elif origin in COMPILE_NAMES:
                bad, hint = f"{origin} program construction", "compile"
            elif origin in SERIALIZE_NAMES:
                bad, hint = f"{origin} executable serialization", "serialize"
        if bad is None or (hint == "timing" and allow_timing) \
                or (hint == "compile" and allow_compile) \
                or (hint == "serialize" and allow_serialize):
            continue
        fix = ("route timestamps through an injected serve/clock.py Clock"
               if hint == "timing"
               else "persist executables through serve/aot.py's AOTCache"
               if hint == "serialize"
               else "route through the Executor's warm/run pipeline instead")
        errors.append(
            f"{rel}:{node.lineno}: {bad} outside serve/executor.py — {fix}"
        )
    return errors


def main() -> int:
    errors = []
    checked = 0
    for path in sorted(SERVE.glob("*.py")):
        checked += 1
        # the executor is the sanctioned timing/compile path but gets no
        # threading pass — it is walked like everyone else for that rule
        sanctioned = path.name == ALLOWED
        errors.extend(check_module(
            path,
            allow_timing=sanctioned or path.name in TIMING_EXEMPT,
            allow_compile=sanctioned or path.name in COMPILE_EXEMPT,
            allow_threading=path.name in THREADING_EXEMPT,
            allow_serialize=path.name in SERIALIZE_EXEMPT,
        ))
    for path in sorted(OBS.glob("*.py")):
        checked += 1
        errors.extend(check_module(path))
    for e in errors:
        print(f"ERROR: {e}")
    if not errors:
        print(f"engine-singlepath check OK ({checked} serve/ + obs/ modules "
              f"share the executor's one timing/compile/threading path)")
    return 1 if errors else 0


if __name__ == "__main__":
    sys.exit(main())
