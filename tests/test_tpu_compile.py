"""Chip-compiler rehearsals: the main path's Pallas kernels and the whole
served forward of each model, on one chip and sharded over a 2x2 mesh,
compiled for a described (not attached) TPU v5e at the paper's widths
and the top packed rung (1024 nodes, 3072 edges).

Nothing runs: these compiles show what the chip's compiler refuses
(unsupported primitives in the Mosaic lowering, unaligned slices, VMEM
overruns) without spending chip time.  Each test asserts that the
kernel reached the compiled program as a ``tpu_custom_call``.

The topology is described inside a module-scoped fixture, never while
a module is imported: only one process at a time may load the TPU
library, so under several test workers only the worker that runs this
file may touch it.  JAX's persistent compilation cache is off around
these compiles (an entry compiled for a described chip cannot be read
back without one).
"""
import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import SingleDeviceSharding

N, E = 1024, 3072  # top packed rung: 4 x the (256, 768) base bucket
HIDDEN = {"gcn": 100, "gin": 100, "pna": 80, "dgn": 100}


@pytest.fixture(scope="module")
def topo():
    import os

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - no TPU compiler installed
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def no_persistent_cache():
    from jax.experimental.compilation_cache import compilation_cache

    before = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", before)
    compilation_cache.reset_cache()


@pytest.fixture
def spec_of(one_chip, no_persistent_cache):
    def make(shape, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    return make


def _compiled_text(fn, *shapes) -> str:
    return jax.jit(fn).lower(*shapes).compile().as_text()


# ----------------------------------------------------------------- kernels


def test_node_mlp_compiles_for_v5e(spec_of):
    from repro.kernels.node_mlp import node_mlp

    text = _compiled_text(lambda x, w, b: node_mlp(x, w, b),
                          spec_of((N, 100)), spec_of((100, 100)),
                          spec_of((100,)))
    assert "tpu_custom_call" in text


def test_quant_node_mlp_compiles_for_v5e(spec_of):
    from repro.kernels.quant_mlp import quant_node_mlp

    text = _compiled_text(
        lambda x, w, s, r, b: quant_node_mlp(x, w, s, b, row_scale=r),
        spec_of((N, 100), jnp.int8), spec_of((100, 100), jnp.int8),
        spec_of((100,)), spec_of((N, 1)), spec_of((100,)),
    )
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("op,width", [("sum", 100), ("max", 80)])
def test_segment_reduce_compiles_for_v5e(spec_of, op, width):
    from repro.kernels.segment_reduce import segment_reduce_sorted

    text = _compiled_text(
        lambda v, ids: segment_reduce_sorted(v, ids, N, op),
        spec_of((E, width)), spec_of((E,), jnp.int32),
    )
    assert "tpu_custom_call" in text


def test_edge_softmax_compiles_for_v5e(spec_of):
    from repro.kernels.edge_softmax import edge_softmax

    text = _compiled_text(lambda lg, ids: edge_softmax(lg, ids, N),
                          spec_of((E, 4)), spec_of((E,), jnp.int32))
    assert "tpu_custom_call" in text


def _fused_operands(gamma: str, precision: str, spec_of) -> dict:
    """Operand shapes of one fused layer, as ``gnn/models.py`` passes
    them at the paper's widths."""
    f = HIDDEN[gamma]
    w_dtype = jnp.int8 if precision == "int8" else jnp.float32
    ops = dict(msrc=spec_of((N, f)), x_res=spec_of((N, f)))
    if gamma == "gcn":
        ops["nop"] = spec_of((N, 1))
        return ops
    k_in, k_out = {"gin": (f, 2 * f), "pna": (12 * f, f),
                   "dgn": (3 * f, f)}[gamma]
    ops.update(w1=spec_of((k_in, k_out), w_dtype), b1=spec_of((k_out,)))
    if precision == "int8":
        ops["w1_scale"] = spec_of((k_out,))
    if gamma == "gin":
        ops.update(eop=spec_of((E, f)), w2=spec_of((2 * f, f)),
                   b2=spec_of((f,)))
    elif gamma == "pna":
        ops["nop"] = spec_of((N, 3))
    else:  # dgn
        ops.update(nop=spec_of((N, 1)), ew=spec_of((E, 1)))
    return ops


_SPECS = {
    "gcn": dict(phi="copy", ops=("sum",)),
    "gin": dict(phi="add_relu", ops=("sum",)),
    "pna": dict(phi="copy", ops=("sum", "sqsum", "max", "min")),
    "dgn": dict(phi="copy", ops=("sum", "wsum")),
}


@pytest.mark.parametrize("precision", ["fp32", "int8"])
@pytest.mark.parametrize("gamma", ["gcn", "gin", "pna", "dgn"])
def test_fused_mp_compiles_for_v5e(spec_of, gamma, precision):
    from repro.core.message_passing import MPSpec
    from repro.kernels.fused_mp import fused_mp

    spec = MPSpec(gamma=gamma, precision=precision, **_SPECS[gamma])
    operands = _fused_operands(gamma, precision, spec_of)
    names = sorted(operands)

    def layer(ids, src, deg, mask, *vals):
        return fused_mp(spec, ids, src, deg, mask,
                        **dict(zip(names, vals)))

    text = _compiled_text(
        layer, spec_of((E,), jnp.int32), spec_of((E,), jnp.int32),
        spec_of((N,)), spec_of((N,)), *(operands[k] for k in names),
    )
    assert "tpu_custom_call" in text


# ------------------------------------------------------- whole forwards


def _served_forward(monkeypatch, model, sharding, mesh=None,
                    precision="fp32", fused=False, rows=N, edges=E):
    """Compile the whole jitted program ``Executor`` builds for ``model``
    at a packed rung (the top one unless ``rows``/``edges`` say), the
    flush's one wire buffer and the parameters placed by ``sharding``.
    ``kernels.ops`` picks the Pallas path from the backend, which is the
    CPU here, so the rehearsal steers it to the chip's choice."""
    from repro.configs.gengnn_models import get_gnn_config
    from repro.core.batching import BucketBudget, pack_prepared
    from repro.data.pipeline import MOLHIV, MoleculeStream
    from repro.gnn import init
    from repro.kernels import ops
    from repro.serve.executor import Executor

    cfg = get_gnn_config(model)
    ex = Executor(mesh=mesh)
    tenant = ex.register(model, cfg, init(jax.random.PRNGKey(0), cfg),
                         precision=precision, fused=fused)
    graphs = [g[:4] for g in MoleculeStream(MOLHIV, seed=0).take(8)]
    eigvecs = ([np.zeros(g[2].shape[0], np.float32) for g in graphs]
               if cfg.model == "dgn" else None)
    prep, _ = pack_prepared(graphs, BucketBudget(n_pad=rows, e_pad=edges, g_pad=8),
                            eigvecs=eigvecs)
    program = ex._program(tenant, prep.bucket_key, prep.num_graphs)

    def shaped(a):
        a = jnp.asarray(a)
        return jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=sharding,
                                    weak_type=a.weak_type)

    args = jax.tree.map(shaped, (tenant.params, prep))
    monkeypatch.setattr(ops, "_on_tpu", lambda: True)
    with ex._mesh_scope():
        compiled = program.fn.lower(*args).compile()
    out = compiled.out_info
    assert tuple(out.shape) == (8, cfg.out_dim)
    assert np.dtype(out.dtype) == np.float32
    return compiled.as_text()


def test_gat_served_forward_compiles_for_v5e(one_chip, no_persistent_cache,
                                             monkeypatch):
    assert "tpu_custom_call" in _served_forward(monkeypatch, "gat", one_chip)


@pytest.mark.parametrize("model,precision,fused", [
    ("gcn", "fp32", False), ("gin", "fp32", False), ("gin_vn", "fp32", False),
    ("pna", "fp32", False), ("dgn", "fp32", False), ("gin", "int8", False),
    ("gcn", "fp32", True), ("gin", "int8", True), ("pna", "fp32", True),
    ("dgn", "fp32", True),
])
def test_served_forward_compiles_for_v5e(one_chip, no_persistent_cache,
                                         monkeypatch, model, precision, fused):
    text = _served_forward(monkeypatch, model, one_chip,
                           precision=precision, fused=fused)
    assert "tpu_custom_call" in text


def test_gps_served_forward_compiles_for_v5e(one_chip, no_persistent_cache,
                                             monkeypatch):
    """GraphGPS at its molpcba widths: the node MLPs, and the segment-id
    attention kernel under its stable name, one per layer."""
    text = _served_forward(monkeypatch, "gps", one_chip)
    assert "tpu_custom_call" in text
    assert text.count("gps_attention") >= 5


# Device ops (and async copies, which the trace lists twice) one GPS flush
# may run.  The profiler keeps a bounded number of device events: a 45 s
# traced window of GPS screening holds ~10,000 flushes, and at ~1,030
# events per flush (the 202 weight arrays each copied to on-chip memory)
# the trace lost its first seconds.  The serving tree brings it to ~320.
# The program that takes the flush as one int32 buffer and slices the
# leaves out of it counts 308 (323 when it took the leaves one by one).
GPS_OPS_PER_FLUSH = 340


def test_gps_served_forward_stays_within_its_device_op_budget(
        one_chip, no_persistent_cache, monkeypatch):
    """At a rung of the screening cell (256 rows), counted in the compiled
    program's entry computation."""
    import re

    text = _served_forward(monkeypatch, "gps", one_chip, rows=256, edges=768)
    entry = text[text.index("ENTRY"):]
    entry = entry[:entry.index("\n}\n")]
    kinds = re.findall(r" = .*? ([a-z][\w-]*)\(", entry)
    ops = [k for k in kinds
           if k not in ("parameter", "bitcast", "get-tuple-element", "constant")]
    starts = [k for k in ops if k in ("copy-start", "slice-start")]
    assert len(ops) + len(starts) <= GPS_OPS_PER_FLUSH


@pytest.mark.parametrize("model,fused", [
    ("gat", False), ("pna", False), ("pna", True), ("gin", True),
])
def test_sharded_served_forward_compiles_for_v5e_2x2(topo, no_persistent_cache,
                                                    monkeypatch, model, fused):
    """The ``--gnn-mesh 4`` program over the described 2x2 mesh: every
    kernel runs per shard (the compiler cannot partition one), and the
    devices exchange partial results."""
    from jax.sharding import NamedSharding, PartitionSpec

    from repro.runtime import compat

    mesh = compat.mesh_from_devices(np.array(topo.devices), ("data",))
    text = _served_forward(monkeypatch, model,
                           NamedSharding(mesh, PartitionSpec()), mesh=mesh,
                           fused=fused)
    assert "tpu_custom_call" in text
    assert "all-reduce" in text or "all-gather" in text
