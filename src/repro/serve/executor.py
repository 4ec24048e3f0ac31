"""The serving executor: one composable pipeline under every mode and tenant.

GenGNN's thesis is one generic message-passing structure serving a diverse
and growing set of models.  The serving stack had drifted the other way:
every new axis (mesh, packing, precision, layout) was hand-threaded through
``infer_stream`` / ``infer_batched`` / ``infer_packed`` separately, so the
cost of the next axis grew with the number of modes.  This module collapses
that mode x axis matrix into a pipeline of small stages:

    prepare  ->  constrain  ->  warm  ->  run
    (pad / eigvec /  (shard rows    (compile un-   (the one timed
     layout / sig)    over mesh)     timed, once     execution)
                                     per signature)

* **prepare** — the ``prepare_stream`` / ``prepare_batched`` /
  ``prepare_packed`` family turns raw input into a ``PreparedBatch``:
  padded graph + optional eigenvector + optional layout plan + the static
  bucket key and warm signature.  All host-side; one family subsumes the
  per-mode padding/eigvec/layout/signature code the engine used to
  duplicate.
* **constrain** — logical-axis sharding of the padded node/edge rows over
  the executor mesh (no-op without one), applied inside the compiled step.
* **warm** — every distinct trace signature executes once untimed before
  it may be timed; compilation never leaks into a reported latency.  One
  signature function (:func:`trace_signature`, keyed on every input leaf's
  shape+dtype) covers all modes — the stream mode's old two-field
  signature missed mid-stream dtype changes.  The warm stage is split in
  two accounted halves: **compile** (trace + lower + XLA compile — or an
  AOT disk-cache load, see below) and **warm** (the one untimed device
  execution), tracked separately as ``compile_seconds`` /
  ``warm_seconds`` so the AOT cache's effect is measurable — a disk hit
  eliminates the compile half, never the warm half.
* **run** — the single timed region in the serving stack.  Durations are
  read through the executor's injected ``serve.clock.Clock`` (default
  ``RealClock``, i.e. ``time.perf_counter``); substituting a stepping
  clock makes even compute durations deterministic under test.
  ``tools/check_engine_singlepath.py`` keeps this the only place real
  time is measured: every reference to the ``time`` module outside this
  file and ``serve/clock.py`` fails the guard.

On top of the pipeline the executor is **multi-tenant**:
``register(name, cfg, params, precision=...)`` admits several GNN models —
each with its own precision and layout settings — into one bucket ladder
and one compile cache.  Programs are keyed by ``(program_key, bucket_key,
num_graphs)`` where ``program_key = (cfg, precision, share_layout)``:
tenants that share an architecture share compiled programs (params are
runtime arguments, never baked in), while warm signatures carry each
tenant's parameter-tree signature so one tenant's warmth is never
mistaken for another's.  ``serve.gnn_engine.GNNEngine`` remains the
single-tenant facade; ``serve.scheduler.StreamScheduler`` routes tagged
requests to tenants and dispatches packed flushes per tenant.

**AOT persistence.**  With ``aot_cache=`` (a ``serve.aot.AOTCache``),
every signature's compiled executable is consulted on disk before
compiling — keyed by ``(program_key, bucket_key, num_graphs, signature)``
plus the environment fingerprint (jax/jaxlib version, backend, device
kind, topology, XLA flag set) — and written back on miss, so a restarted
process deserializes finished machine code instead of retracing and
recompiling ~10s of programs.  ``xla_flags=`` (a ``serve.aot.
XlaFlagConfig``, normally the checked-in autotuner table) supplies
per-(model, bucket) XLA ``compiler_options`` applied at program build;
the resolved set folds into the fingerprint so retuned flags
self-invalidate exactly the entries they affect.  A flag set the backend
rejects is an error: the program is never quietly compiled with other
options than the ones asked for.

**Telemetry.**  The executor accepts ``tracer=`` / ``metrics=`` sinks
(``repro.obs``; the scheduler attaches its own via
:meth:`Executor.attach_telemetry`) and reports program builds, warm
executions (with their untimed cost), and timed device seconds — the
compile/warm events of the request lifecycle in docs/OBSERVABILITY.md.
Both default off; disabled telemetry adds no compile keys and no time
reads (the instrumentation stamps the *tracer's* clock, never a second
real-time source — this module's injected ``clock`` remains the single
place real time is measured).
"""
from __future__ import annotations

import contextlib
import dataclasses
from collections import OrderedDict
from typing import Callable, Dict, Optional, Sequence, Set, Tuple

import jax
import numpy as np

from repro import runtime as RT
from repro.serve.aot import (
    AOTCache, XlaFlagConfig, environment_fingerprint, model_label,
)
from repro.obs.metrics import MetricsRegistry, ServingInstruments
from repro.obs.trace import NULL_TRACER, Tracer
from repro.serve.clock import Clock, RealClock
from repro.core import batching as B
from repro.core import graph as G
from repro.core import layout as LY
from repro.core import wire as W
from repro.gnn import models as M

DEFAULT_BUCKETS: Sequence[tuple] = ((32, 96), (64, 192), (128, 384), (256, 768))


# ---------------------------------------------------------------------------
# the prepared-batch pytree and the one warm-signature function
# ---------------------------------------------------------------------------


def trace_signature(graph: G.Graph, eigvec=None, layout=None) -> tuple:
    """The warm/compile signature of one prepared input: its wire manifest
    (``core.wire.manifest_of``), which holds the presence of the optional
    operands and the (shape, dtype) and buffer offset of **every** leaf.

    This is the single signature function for every mode.  The stream mode
    used to key warmth on ``("eig", with_eigvec)`` alone, so a mid-stream
    dtype change (int edge features after float ones in the same bucket)
    recompiled inside the timed region; keying on the leaves closes that.
    The leading ``"wire"`` keeps it apart from the signatures of programs
    that took the leaves one by one, in the AOT cache too.
    """
    return ("wire", W.manifest_of(graph, eigvec, layout))


def params_signature(params) -> tuple:
    """Structural signature of a parameter tree (treedef + leaf
    shapes/dtypes).  Part of every warm signature so tenants sharing a
    compiled program never inherit each other's warmth across a parameter
    structure change (e.g. differently-calibrated int8-static trees)."""
    leaves, treedef = jax.tree.flatten(params)
    return (str(treedef),) + tuple(
        (tuple(getattr(v, "shape", ())), str(getattr(v, "dtype", type(v).__name__)))
        for v in leaves
    )


@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class PreparedBatch:
    """One batch, fully staged for the executor: the padded (possibly
    packed) graph, its optional eigenvector input and layout plan, written
    into one buffer of int32 words (``core.wire``), plus the static facts —
    the buffer's manifest, bucket key, graph-slot count, warm signature.

    Produced by the ``prepare_*`` family (and by
    ``core.batching.pack_prepared`` at pack time); consumed by
    :meth:`Executor.warm` / :meth:`Executor.run`.  A pytree whose one data
    leaf is ``wire``, so a ``jax.device_put`` of it moves one array; the
    served program splits it.  ``graph`` / ``eigvec`` / ``layout`` read the
    leaves back, eagerly, for callers outside the program.
    """

    wire: jax.Array
    manifest: tuple = dataclasses.field(metadata=dict(static=True))
    bucket_key: tuple = dataclasses.field(metadata=dict(static=True))
    num_graphs: int = dataclasses.field(metadata=dict(static=True))
    signature: tuple = dataclasses.field(metadata=dict(static=True))

    def unwire(self):
        """``(graph, eigvec, layout)`` sliced out of the buffer."""
        return W.unwire(self.wire, self.manifest)

    @property
    def graph(self) -> G.Graph:
        return self.unwire()[0]

    @property
    def eigvec(self) -> Optional[jax.Array]:
        return self.unwire()[1]

    @property
    def layout(self) -> Optional[LY.GraphLayout]:
        return self.unwire()[2]


def prepared(graph: G.Graph, eigvec, layout, bucket_key: tuple,
             num_graphs: int) -> PreparedBatch:
    """Assemble a host ``PreparedBatch``: write the leaves into one numpy
    buffer and compute its warm signature.  Callers put it on the device
    (one array); a device leaf handed in is read back first."""
    buf, manifest = W.wire(graph, eigvec, layout)
    return PreparedBatch(
        wire=buf, manifest=manifest, bucket_key=bucket_key,
        num_graphs=num_graphs, signature=("wire", manifest),
    )


# ---------------------------------------------------------------------------
# compile-cache record + tenant registry
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class _CompiledBucket:
    """Per-program compile-cache record: the jitted program plus
    warm-signature bookkeeping.  ``num_graphs`` is recorded (and part of
    the cache key) — the old engine's ``_bucket(key, num_graphs=...)``
    silently kept the first call's value on a cache hit.

    ``executables`` maps each warmed trace signature to its AOT
    executable (freshly ``lower().compile()``-d or deserialized from the
    disk cache); execution dispatches through it, with ``fn`` (the jit
    wrapper) kept as the lowering source and the fallback path.  The old
    single ``compile_s`` is split: ``compile_s`` is trace+lower+compile
    (or disk-load) seconds, ``warm_s`` the first-run device warm —
    separately visible so the AOT cache's effect (it eliminates only the
    first half) is measurable."""

    fn: Callable
    num_graphs: Optional[int]
    warm: Set[tuple] = dataclasses.field(default_factory=set)
    executables: Dict[tuple, Callable] = dataclasses.field(default_factory=dict)
    compile_s: float = 0.0
    warm_s: float = 0.0
    lowered_count: int = 0  # fresh trace+lower+compiles (0 on pure AOT hits)


@dataclasses.dataclass
class Tenant:
    """One registered model: its config, (possibly quantized) params, and
    the derived signatures that route it through the shared machinery."""

    name: str
    cfg: M.GNNConfig
    params: dict
    precision: str = "fp32"
    share_layout: bool = True
    fused: bool = False
    quant_report: Optional[object] = None
    params_sig: tuple = ()

    @property
    def program_key(self) -> tuple:
        """Compiled programs are shared between tenants with equal keys:
        the computation depends on (cfg, precision-structure, layout
        sharing, megakernel fusion), never on the parameter *values*."""
        return (self.cfg, self.precision, self.share_layout, self.fused)


# ---------------------------------------------------------------------------
# the executor
# ---------------------------------------------------------------------------


class Executor:
    """The single compile-cache / warm / timing / mesh-scope path that every
    serving mode and every tenant runs through.

    One executor owns one bucket ladder (``buckets``), one optional mesh,
    one compile cache, and any number of registered tenants.  The
    single-tenant ``GNNEngine`` facade registers exactly one; multi-model
    serving registers several and routes by name.
    """

    def __init__(
        self,
        buckets: Sequence[tuple] = DEFAULT_BUCKETS,
        mesh=None,
        rules: Optional[dict] = None,
        clock: Optional[Clock] = None,
        tracer: Optional[Tracer] = None,
        metrics: Optional[MetricsRegistry] = None,
        aot_cache: Optional[AOTCache] = None,
        xla_flags: Optional[XlaFlagConfig] = None,
    ):
        self.buckets = sorted(buckets)
        self.mesh = mesh
        # the one place real time is measured in the serving stack; a test
        # can inject a stepping clock for deterministic compute durations
        self.clock = clock if clock is not None else RealClock()
        if rules is None and mesh is not None:
            rules = RT.gnn_rules(mesh)
        self.rules = rules
        # persistent AOT compile cache + per-program XLA flag table
        self.aot = aot_cache
        self.xla_flags = xla_flags
        self._env_fp_base: Optional[dict] = None  # lazy (touches devices)
        self._flags_cache: Dict[tuple, Dict[str, object]] = {}
        self.tenants: Dict[str, Tenant] = {}
        self._compiled: Dict[tuple, _CompiledBucket] = {}
        # host eigvec memo: (edge bytes, n, n_pad) -> computed vector
        self._eigvec_lru: "OrderedDict[tuple, np.ndarray]" = OrderedDict()
        # telemetry sinks: dark by default (the no-op tracer / no registry
        # costs nothing and adds no compile keys); the scheduler attaches
        # its own sinks here so compile/warm/device events share them
        self.tracer = tracer if tracer is not None else NULL_TRACER
        self.metrics = metrics
        self._mi = ServingInstruments(metrics) if metrics is not None else None

    def attach_telemetry(self, tracer: Optional[Tracer] = None,
                         metrics: Optional[MetricsRegistry] = None) -> None:
        """Adopt telemetry sinks after construction (the scheduler passes
        its own through here).  Sinks this executor already carries are
        kept — first attachment wins, so two schedulers sharing one
        executor never silently split its compile/warm accounting."""
        if tracer is not None and not self.tracer.enabled:
            self.tracer = tracer
        if metrics is not None and self.metrics is None:
            self.metrics = metrics
            self._mi = ServingInstruments(metrics)

    # ---------------------------------------------------------- tenants

    def register(
        self,
        name: str,
        cfg: M.GNNConfig,
        params: dict,
        precision: str = "fp32",
        calib_graphs: Optional[Sequence[tuple]] = None,
        qconfig=None,
        share_layout: bool = True,
        fused: bool = False,
    ) -> Tenant:
        """Admit a model into the shared machinery.  ``precision`` selects
        the serving arithmetic ("fp32", "int8", "int8-static", "fixed");
        the model's serving tree (``models.serving_params``) and any
        quantization are made once here and every mode then serves the
        transformed tree.  ``fused`` lowers eligible layers through the
        ``kernels.ops.fused_mp`` megakernel (requires a layout plan —
        layers without one, and opt-outs like GAT, keep the unfused path).
        Like ``share_layout`` it is program-level static: part of
        ``program_key``, never of the bucket/warm signatures, so flipping
        it adds programs but never recompiles inside a timed region.
        Tenants with an equal ``program_key`` share compiled programs;
        params and warm state never cross tenants."""
        if name in self.tenants:
            raise ValueError(f"tenant {name!r} already registered")
        params = M.serving_params(params, cfg)
        quant_report = None
        if precision != "fp32":
            from repro.quant import apply as QA

            qcfg = qconfig or QA.precision_qconfig(precision)
            if (qcfg.scheme == "int8" and qcfg.act_mode == "static"
                    and not calib_graphs):
                raise ValueError(
                    "static-activation int8 needs calib_graphs (raw COO "
                    "tuples) to calibrate activation ranges"
                )
            params, quant_report = QA.quantize_model(
                params, cfg, calib_graphs or (), qcfg
            )
        tenant = Tenant(
            name=name, cfg=cfg, params=params, precision=precision,
            share_layout=share_layout, fused=fused,
            quant_report=quant_report,
            params_sig=params_signature(params),
        )
        self.tenants[name] = tenant
        return tenant

    def tenant(self, model: Optional[str] = None) -> Tenant:
        """Resolve a tenant by name; ``None`` means the sole tenant."""
        if model is not None:
            try:
                return self.tenants[model]
            except KeyError:
                raise KeyError(
                    f"no tenant {model!r}; registered: {sorted(self.tenants)}"
                ) from None
        if len(self.tenants) == 1:
            return next(iter(self.tenants.values()))
        raise KeyError(
            f"model name required: {len(self.tenants)} tenants registered "
            f"({sorted(self.tenants)})"
        )

    # --------------------------------------------------------- plumbing

    @property
    def compile_seconds(self) -> float:
        """Total trace+lower+compile (or AOT disk-load) time across all
        programs — the half of the historical "warm-up" the AOT cache
        eliminates.  Excluded from every reported latency."""
        return sum(cb.compile_s for cb in self._compiled.values())

    @property
    def warm_seconds(self) -> float:
        """Total first-run device-warm time across all programs — the
        one untimed execution per signature, paid even on an AOT cache
        hit.  Excluded from every reported latency."""
        return sum(cb.warm_s for cb in self._compiled.values())

    @property
    def untimed_seconds(self) -> float:
        """compile + warm: the historical single "compile_seconds"
        total (everything excluded from reported latencies)."""
        return self.compile_seconds + self.warm_seconds

    @property
    def lowered_count(self) -> int:
        """Fresh trace+lower+compile constructions across all programs —
        exactly 0 in a process that served every signature from the AOT
        disk cache (the restart-safe fast path)."""
        return sum(cb.lowered_count for cb in self._compiled.values())

    # ------------------------------------------------------ AOT plumbing

    def _fingerprint(self, flags: Dict[str, object]) -> dict:
        """Environment fingerprint with this program's resolved flag set
        folded in (base part computed once — it touches jax.devices())."""
        if self._env_fp_base is None:
            self._env_fp_base = environment_fingerprint()
        from repro.serve.aot import flags_hash

        fp = dict(self._env_fp_base)
        fp["flags"] = flags_hash(flags)
        return fp

    def _compiler_options(self, tenant: Tenant, bucket_key: tuple) -> dict:
        """The XLA compiler options for one (model, bucket) program,
        resolved once and memoized."""
        if self.xla_flags is None:
            return {}
        key = (model_label(tenant.cfg), bucket_key)
        flags = self._flags_cache.get(key)
        if flags is None:
            flags = self._flags_cache[key] = self.xla_flags.resolve(*key)
        return flags

    def aot_stats(self) -> Dict[str, int]:
        """Disk-cache outcome tally (zeros when no cache is attached)."""
        return dict(self.aot.stats) if self.aot is not None \
            else {"hit": 0, "miss": 0, "stale": 0}

    def _mesh_scope(self):
        """Context under which programs trace/run: installs the executor's
        mesh + rules so logical_constraint resolves; nullcontext otherwise."""
        if self.mesh is None:
            return contextlib.nullcontext()
        stack = contextlib.ExitStack()
        stack.enter_context(RT.use_mesh(self.mesh))
        stack.enter_context(RT.active_rules(self.rules))
        return stack

    def _constrain_graph(self, g: G.Graph) -> G.Graph:
        """Shard the padded node/edge rows over the executor mesh."""
        lc = RT.logical_constraint
        return dataclasses.replace(
            g,
            node_feat=lc(g.node_feat, ("nodes", None)),
            edge_index=lc(g.edge_index, (None, "edges")),
            edge_feat=lc(g.edge_feat, ("edges", None)),
            node_mask=lc(g.node_mask, ("nodes",)),
            edge_mask=lc(g.edge_mask, ("edges",)),
            graph_id=lc(g.graph_id, ("nodes",)),
        )

    def _constrain_layout(self, layout: LY.GraphLayout) -> LY.GraphLayout:
        """Shard the plan's edge-order arrays like the edge rows they
        index (offsets is (N+1,) and stays replicated)."""
        lc = RT.logical_constraint
        return dataclasses.replace(
            layout,
            perm=lc(layout.perm, ("edges",)),
            ids_sorted=lc(layout.ids_sorted, ("edges",)),
            src_sorted=lc(layout.src_sorted, ("edges",)),
            in_degree=lc(layout.in_degree, ("nodes",)),
        )

    def bucket_for(self, n: int, e: int) -> tuple:
        """Smallest configured (N_pad, E_pad) bucket holding (n, e)."""
        for nb, eb in self.buckets:
            if n <= nb and e <= eb:
                return nb, eb
        raise ValueError(
            f"graph ({n},{e}) exceeds largest bucket {self.buckets[-1]}"
        )

    def _program(self, tenant: Tenant, bucket_key: tuple,
                 num_graphs: Optional[int]) -> _CompiledBucket:
        """The compiled program for (tenant-architecture, bucket, slots).

        ``num_graphs`` is part of the cache key — two calls that share a
        bucket but size their pooled buffers differently must never share
        a program (the old engine's closure captured the first call's
        value).  The forward itself comes from the one program builder,
        ``gnn.models.forward_program``; this is the only place in the
        serving stack that constructs a jitted program.
        """
        key = (tenant.program_key, bucket_key, num_graphs)
        cb = self._compiled.get(key)
        if cb is None:
            program = M.forward_program(
                tenant.cfg, num_graphs=num_graphs,
                share_layout=tenant.share_layout, fused=tenant.fused,
            )

            @jax.jit
            def run(params, p: PreparedBatch):
                # the flush crosses as one buffer (p's only data leaf);
                # its leaves come back here, at the manifest's offsets
                g, eigvec, layout = p.unwire()
                g = self._constrain_graph(g)
                if eigvec is not None:
                    eigvec = RT.logical_constraint(eigvec, ("nodes",))
                if layout is not None:
                    layout = self._constrain_layout(layout)
                return program(params, g, eigvec, layout)

            cb = _CompiledBucket(fn=run, num_graphs=num_graphs)
            self._compiled[key] = cb
            if self._mi is not None:
                self._mi.programs_built.inc()
            if self.tracer.enabled:
                self.tracer.event("program_build", track="executor",
                                  tenant=tenant.name, bucket=str(bucket_key),
                                  num_graphs=num_graphs)
        if cb.num_graphs != num_graphs:  # pragma: no cover - key carries it
            raise AssertionError(
                f"compile-cache record for {key} carries num_graphs="
                f"{cb.num_graphs}, requested {num_graphs}"
            )
        return cb

    def _compile(self, cb: _CompiledBucket, tenant: Tenant,
                 p: PreparedBatch, flags: dict) -> Callable:
        """Fresh trace + lower + XLA compile of one signature's program,
        with the resolved XLA compiler options applied.  A flag set the
        backend rejects raises: serving on other options than the table
        names would hide what runs on the device."""
        lowered = cb.fn.lower(tenant.params, p)
        cb.lowered_count += 1
        if flags:
            return lowered.compile(compiler_options=dict(flags))
        return lowered.compile()

    def _executable(self, cb: _CompiledBucket, sig: tuple, tenant: Tenant,
                    p: PreparedBatch) -> Callable:
        """The ready-to-run executable for one signature: the AOT disk
        cache first (fingerprint-checked; hit/miss/stale accounted), a
        fresh compile with write-back otherwise."""
        flags = self._compiler_options(tenant, p.bucket_key)
        exe = None
        if self.aot is not None:
            key = (repr(tenant.program_key), p.bucket_key, p.num_graphs, sig)
            exe = self.aot.load(key, self._fingerprint(flags))
            if self._mi is not None:
                self._mi.aot_cache.inc(result=self.aot.last_result or "hit")
            if self.tracer.enabled:
                self.tracer.event("aot_load", track="executor",
                                  tenant=tenant.name, bucket=str(p.bucket_key),
                                  result=self.aot.last_result or "hit")
        if exe is None:
            exe = self._compile(cb, tenant, p, flags)
            if self.aot is not None:
                self.aot.store(key, self._fingerprint(flags), exe)
        return exe

    def _warm(self, cb: _CompiledBucket, sig: tuple, tenant: Tenant,
              p: PreparedBatch) -> float:
        """Make ``sig`` servable through this program: build (or load
        from the AOT cache) its executable, then execute once untimed —
        so neither compilation nor first-run warm can ever leak into a
        reported latency.  The two halves are accounted separately
        (``compile_s`` / ``warm_s``); returns total seconds spent (0.0
        when already warm).  The build and the warm execution are the
        ``compile`` span."""
        if sig in cb.warm:
            return 0.0
        with self.tracer.span("compile", track="executor", tenant=tenant.name,
                              bucket=str(p.bucket_key)):
            t0 = self.clock.now()
            exe = self._executable(cb, sig, tenant, p)
            cb.executables[sig] = exe
            compile_dt = self.clock.now() - t0
            t1 = self.clock.now()
            jax.block_until_ready(exe(tenant.params, p))
            warm_dt = self.clock.now() - t1
        cb.warm.add(sig)
        cb.compile_s += compile_dt
        cb.warm_s += warm_dt
        if self._mi is not None:
            self._mi.warms.inc()
            self._mi.compile_seconds.inc(compile_dt)
            self._mi.warm_seconds.inc(warm_dt)
        if self.tracer.enabled:
            self.tracer.event("warm", track="executor",
                              bucket=str(p.bucket_key), dur_s=warm_dt,
                              compile_s=compile_dt)
        return compile_dt + warm_dt

    # ---------------------------------------------------------- prepare

    def prepare_stream(self, raw: tuple, with_eigvec: bool = False) -> PreparedBatch:
        """Stage one raw COO graph for batch-size-1 streaming: pad into the
        smallest bucket; no layout plan (the compiled step converts COO
        once on device — the single timed sort of the forward)."""
        s, r, nf, ef = raw[:4]
        nb, eb = self.bucket_for(nf.shape[0], len(s))
        g = G.host_from_numpy(s, r, nf, ef, n_pad=nb, e_pad=eb)
        eig = self._eigvec(s, r, nf.shape[0], nb) if with_eigvec else None
        return jax.device_put(prepared(g, eig, None, ("stream", nb, eb), 1))

    def prepare_batched(self, chunk: Sequence[tuple], batch_size: int,
                        n_pad: int, e_pad: int,
                        with_eigvec: bool = False) -> PreparedBatch:
        """Stage one fixed-size padded batch: concatenate the chunk's raw
        graphs, build per-graph eigenvectors at the packed node offsets
        (host-side, before the timed region)."""
        gs = [(g[0], g[1], g[2], g[3]) for g in chunk]
        g = G.host_batch_graphs(gs, n_pad=n_pad, e_pad=e_pad)
        eig = None
        if with_eigvec:
            eig = np.zeros((n_pad,), np.float32)
            off = 0
            for s, r, nf, _ in gs:
                n = nf.shape[0]
                eig[off : off + n] = self._eigvec(s, r, n, n)
                off += n
        return jax.device_put(prepared(
            g, eig, None, ("batched", n_pad, e_pad, batch_size), batch_size))

    def prepare_packed(self, packed: G.Graph, budget, eigvec=None,
                       layout=None, model: Optional[str] = None) -> PreparedBatch:
        """Stage one already-packed multi-graph batch (``core.batching``).

        ``layout`` is normally the plan the packer emitted at pack time
        (zero on-device sorts in the flushed program); when absent and the
        tenant shares layouts, the host plan is built here — the plan
        always travels with its batch, never a sort inside the program.
        The leaves (``core.batching.pack_graphs``' numpy graph, the host
        plan) cross to the device as one buffer in one put, before any
        timed region; a device graph is read back to be written into it.
        """
        if eigvec is not None:
            eigvec = np.asarray(eigvec, np.float32)
        if layout is None and self.tenant(model).share_layout:
            layout = B.pack_layout(packed)
        return jax.device_put(prepared(
            packed, eigvec, layout,
            ("packed", budget.n_pad, budget.e_pad, budget.g_pad), budget.g_pad,
        ))

    def has_program(self, bucket_key: tuple, num_graphs: int,
                    model: Optional[str] = None) -> bool:
        """Whether a compiled program already exists for this tenant's
        architecture at (bucket, slots) — the scheduler's eager-prewarm
        skip check."""
        key = (self.tenant(model).program_key, bucket_key, num_graphs)
        return key in self._compiled

    # --------------------------------------------------------- warm/run

    def _harvest(self, out, tenant: Tenant, p: PreparedBatch,
                 t0: float) -> Tuple[np.ndarray, float]:
        """Complete one dispatched execution: wait for the device (the
        ``device_wait`` span), close the timed region, then convert the
        outputs device-to-host (the ``d2h`` span; the D2H copy used to
        hide outside every measurement).  The copy's seconds on this
        executor's clock feed ``serve_d2h_seconds_total`` and the
        in-memory ``d2h`` span's ``dur_s``; those clock reads are gated
        on a live sink so the dark path stays free."""
        tr, mi = self.tracer, self._mi
        with tr.span("device_wait", track="executor", tenant=tenant.name):
            out = jax.block_until_ready(out)
        dt = self.clock.now() - t0
        accounted = mi is not None or tr.enabled
        with tr.span("d2h", track="executor", tenant=tenant.name) as copy:
            if accounted:
                t2 = self.clock.now()
            host = np.asarray(out)
            if accounted:
                d2h = self.clock.now() - t2
                if tr.enabled:
                    copy.note(dur_s=d2h)
        if mi is not None:
            mi.device_seconds.inc(dt)
            mi.d2h_seconds.inc(d2h)
        return host, dt

    def run_async(self, p: PreparedBatch,
                  model: Optional[str] = None) -> "PendingRun":
        """Dispatch one execution without waiting for it: warm the
        signature (untimed, as ever), open the timed region, hand the
        program to the device, and return a :class:`PendingRun`
        immediately — JAX's async dispatch keeps computing while the
        caller packs the next flush.  ``PendingRun.result()`` harvests
        the outputs and closes the timed region; the in-flight window is
        the *caller's* responsibility (``serve/pipeline.py`` bounds it).
        Program lookup, warm check and enqueue are the ``dispatch`` span."""
        with self.tracer.span("dispatch", track="executor", slots=p.num_graphs):
            tenant = self.tenant(model)
            cb = self._program(tenant, p.bucket_key, p.num_graphs)
            sig = (tenant.params_sig,) + p.signature
            with self._mesh_scope():
                self._warm(cb, sig, tenant, p)
                # dispatch through the signature's AOT executable (fresh or
                # deserialized); cb.fn remains the lowering source/fallback
                fn = cb.executables.get(sig, cb.fn)
                t0 = self.clock.now()
                out = fn(tenant.params, p)
        return PendingRun(self, out, tenant, p, t0)

    def run(self, p: PreparedBatch,
            model: Optional[str] = None) -> Tuple[np.ndarray, float]:
        """The one timed execution path.  Warms the signature first (un-
        timed, recorded in ``compile_seconds``), then runs and returns
        ``(outputs, seconds)`` — dispatch plus an immediate harvest, so
        serial callers see the exact historical contract while the async
        path stays the single implementation."""
        return self.run_async(p, model=model).result()

    def warm(self, p: PreparedBatch, model: Optional[str] = None) -> float:
        """Compile/warm this batch's signature without a timed execution
        (the scheduler pre-warms budget-ladder rungs with this).  Returns
        seconds spent (0.0 when already warm); also tracked in
        ``compile_seconds``."""
        tenant = self.tenant(model)
        cb = self._program(tenant, p.bucket_key, p.num_graphs)
        with self._mesh_scope():
            return self._warm(cb, (tenant.params_sig,) + p.signature,
                              tenant, p)

    # ------------------------------------------------------------- misc

    _EIGVEC_LRU_SIZE = 128

    def _eigvec(self, s, r, n, n_pad):
        """First non-trivial Laplacian eigenvector — DGN's *input* (the
        paper passes precomputed eigenvectors as a parameter; for synthetic
        streams we compute it on the host as part of data generation).

        Memoized: a small LRU keyed by (edge-list bytes, n, n_pad) — a
        live stream revisits graph shapes constantly (molecule streams
        repeat molecules; benchmarks replay the same take), and the host
        eigensolve is the most expensive single prepare stage, so
        repeated shapes must not re-pay it.  Hits/misses land in the
        ``serve_eigvec_cache_total`` counter when a registry is attached.

        The vector is a read-only host ``np.ndarray``: a packed flush
        copies it into its eigenvector leaf and puts that once with the
        rest of the batch, so a solve costs no device crossing of its own.
        Callers that want it on the device put it themselves.
        """
        s_arr = np.ascontiguousarray(s)
        r_arr = np.ascontiguousarray(r)
        key = (s_arr.tobytes(), r_arr.tobytes(), int(n), int(n_pad))
        cached = self._eigvec_lru.get(key)
        if cached is not None:
            self._eigvec_lru.move_to_end(key)
            if self._mi is not None:
                self._mi.eigvec_cache.inc(result="hit")
            return cached
        from repro.data.pipeline import laplacian_eigvec

        vec = laplacian_eigvec(s, r, n, n_pad)
        vec.flags.writeable = False
        self._eigvec_lru[key] = vec
        if len(self._eigvec_lru) > self._EIGVEC_LRU_SIZE:
            self._eigvec_lru.popitem(last=False)
        if self._mi is not None:
            self._mi.eigvec_cache.inc(result="miss")
        return vec


class PendingRun:
    """One dispatched-but-unharvested execution: the future
    :meth:`Executor.run_async` hands back.

    ``result()`` blocks until the device finishes, closes the timed
    region (``dt`` spans dispatch to completion-harvest on the
    executor's clock), converts the outputs to host memory under the
    ``d2h`` span and accounting, and caches — a second call returns the
    same ``(outputs, seconds)`` without touching the device again.
    ``done`` flips once harvested (the in-flight bookkeeping hook)."""

    __slots__ = ("_executor", "_out", "_tenant", "_prepared", "_t0", "_result")

    def __init__(self, executor: Executor, out, tenant: Tenant,
                 prepared: PreparedBatch, t0: float):
        self._executor = executor
        self._out = out
        self._tenant = tenant
        self._prepared = prepared
        self._t0 = t0
        self._result: Optional[Tuple[np.ndarray, float]] = None

    @property
    def done(self) -> bool:
        return self._result is not None

    def result(self) -> Tuple[np.ndarray, float]:
        if self._result is None:
            self._result = self._executor._harvest(
                self._out, self._tenant, self._prepared, self._t0
            )
            self._out = None  # drop the device buffers once harvested
        return self._result
