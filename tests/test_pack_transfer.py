"""The packed flush is built on the host and crosses to the device once,
as one buffer.

``core.batching.pack_prepared`` assembles the padded graph, the packed
eigenvector, the layout plan and the warm signature from numpy arrays,
writes every leaf into one int32 buffer (``core.wire``), then makes one
``jax.device_put`` of that one array.  These tests hold it to that:

  * no implicit host-to-device transfer happens while packing (under
    ``jax.transfer_guard_host_to_device("disallow")``), exactly one
    ``jax.device_put`` of exactly one array is made per call, and
    ``host_layout`` only ever sees numpy arrays;
  * the graph, eigenvector and plan read back from the buffer are the
    ones a construction through the device gives (``batch_graphs``, then
    the plan read back from its device graph; ``Executor.prepare_packed``
    of a device graph): every leaf bitwise equal, with equal shapes,
    dtypes, weak types and warm signature, and the plan equal to the
    on-device sort's;
  * the buffer round-trips every bit pattern, and the served program,
    which splits it, gives the bits of ``forward_program`` called on the
    leaves one by one;
  * served through the scheduler after ``prewarm_ladders``, no program is
    lowered again, and all six models serve the same bits from either
    construction.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import graph as G
from repro.core import layout as LY
from repro.core import wire as W
from repro.core.batching import (
    BucketBudget, pack_eigvecs, pack_graphs, pack_layout, pack_prepared,
)
from repro.core.graph import batch_graphs
from repro.data.pipeline import MOLHIV, MoleculeStream
from repro.gnn import init
from repro.gnn.models import GNNConfig, forward_program, paper_config
from repro.serve import executor as X
from repro.serve.executor import DEFAULT_BUCKETS
from repro.serve.gnn_engine import GNNEngine
from repro.serve.scheduler import StreamScheduler

KEY = jax.random.PRNGKey(0)
# every rung of the default ladder: each base bucket at StreamScheduler's
# default capacity of 4 (rung multiples 1, 2, 3, 4)
RUNGS = [
    BucketBudget(n_pad=k * nb, e_pad=k * eb, g_pad=2 * k)
    for nb, eb in DEFAULT_BUCKETS
    for k in (1, 2, 3, 4)
]


def _molecules(rng, budget, count):
    """``count`` seeded molecules that together fit ``budget``, sizes drawn
    anew per call so the padding left over varies (padding fuzz)."""
    n_cap = max(1, budget.n_pad // count)
    e_cap = max(1, budget.e_pad // count)
    out = []
    for _ in range(count):
        n = int(rng.integers(1, n_cap + 1))
        e = int(rng.integers(1, e_cap + 1))
        out.append((
            rng.integers(0, n, e).astype(np.int32),
            rng.integers(0, n, e).astype(np.int32),
            rng.normal(size=(n, 9)).astype(np.float32),
            rng.normal(size=(e, 3)).astype(np.float32),
        ))
    return out


def _eigvecs(rng, graphs):
    return [rng.normal(size=(g[2].shape[0],)).astype(np.float32) for g in graphs]


def _device_graph_leaves(graphs, budget, vecs, share_layout=True):
    """The construction with a device round trip: ``(graph, eigvec,
    layout)`` with the batch put on the device leaf by leaf, the
    eigenvector through ``jnp.asarray``, the plan read back from the
    device graph and put there again."""
    g = batch_graphs([tuple(x[:4]) for x in graphs],
                     n_pad=budget.n_pad, e_pad=budget.e_pad)
    _, meta = pack_graphs(graphs, budget)
    eig = None if vecs is None else jnp.asarray(pack_eigvecs(vecs, meta),
                                                jnp.float32)
    layout = (jax.tree.map(jnp.asarray, LY.host_layout(g))
              if share_layout else None)
    return g, eig, layout


def _leaf_facts(tree):
    leaves, treedef = jax.tree.flatten(tree)
    return treedef, [(x.shape, str(x.dtype), jax.typeof(x).weak_type)
                     for x in leaves]


def _assert_same_batch(got, want, budget, msg):
    """``got``, a device ``PreparedBatch``, carries ``want``'s
    ``(graph, eigvec, layout)`` leaves: the views read back from its one
    buffer are bitwise equal, with equal shapes, dtypes, weak types and
    absent parts, and its signature is theirs."""
    assert got.bucket_key == ("packed", budget.n_pad, budget.e_pad,
                              budget.g_pad), msg
    assert got.num_graphs == budget.g_pad, msg
    assert got.signature == X.trace_signature(*want), msg
    (buf,) = jax.tree.leaves(got)
    assert isinstance(buf, jax.Array) and buf.dtype == jnp.int32, msg
    views = got.unwire()
    assert _leaf_facts(views) == _leaf_facts(want), msg
    for a, b in zip(jax.tree.leaves(views), jax.tree.leaves(want)):
        assert isinstance(a, jax.Array), msg
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b), err_msg=msg)


# ------------------------------------------------------------ one crossing


@pytest.mark.parametrize("with_eig", [False, True], ids=["plain", "eigvec"])
@pytest.mark.parametrize("fill", ["one", "several", "full"])
def test_pack_prepared_crosses_to_the_device_once(fill, with_eig, rng,
                                                  monkeypatch):
    budget = BucketBudget(n_pad=128, e_pad=384, g_pad=8)
    count = {"one": 1, "several": 3, "full": budget.g_pad}[fill]
    graphs = _molecules(rng, budget, count)
    vecs = _eigvecs(rng, graphs) if with_eig else None

    puts, layout_inputs = [], []
    real_put, real_layout = jax.device_put, LY.host_layout

    def counting_put(*args, **kwargs):
        puts.append(args)
        return real_put(*args, **kwargs)

    def recording_layout(graph):
        layout_inputs.append(jax.tree.leaves(graph))
        return real_layout(graph)

    monkeypatch.setattr(jax, "device_put", counting_put)
    monkeypatch.setattr(LY, "host_layout", recording_layout)
    with jax.transfer_guard_host_to_device("disallow"):
        prep, meta = pack_prepared(graphs, budget, eigvecs=vecs)

    assert len(puts) == 1
    (sent,) = jax.tree.leaves(puts[0])
    assert type(sent) is np.ndarray and sent.dtype == np.int32
    assert len(layout_inputs) == 1
    assert all(type(x) is np.ndarray for x in layout_inputs[0])
    assert meta.num_graphs == count
    assert (prep.eigvec is not None) == with_eig
    (buf,) = jax.tree.leaves(prep)
    assert isinstance(buf, jax.Array) and buf.shape == sent.shape


@pytest.mark.parametrize("fill", ["one", "several", "full"])
def test_dgn_flush_eigvec_and_pack_cross_to_the_device_once(fill, rng,
                                                            monkeypatch):
    """A DGN flush's eigenvectors stay on the host from the solve to the
    flush's one put, on an LRU miss and on a hit alike: the eigvec stage
    runs under a guard against every transfer, explicit ones included
    (``jnp.asarray`` is explicit, and on the CPU backend reading a device
    array back is no transfer at all, so ``"disallow"`` alone would let a
    round trip through); the pack then makes exactly one
    ``jax.device_put``, and the packed leaf holds the solve's bits."""
    from repro.data.pipeline import laplacian_eigvec

    budget = BucketBudget(n_pad=128, e_pad=384, g_pad=8)
    count = {"one": 1, "several": 3, "full": budget.g_pad}[fill]
    graphs = _molecules(rng, budget, count)
    sched = StreamScheduler(X.Executor(buckets=DEFAULT_BUCKETS),
                            with_eigvec=True)
    want = [laplacian_eigvec(s, r, nf.shape[0], nf.shape[0])
            for s, r, nf, _ in graphs]

    puts = []
    real_put = jax.device_put

    def counting_put(*args, **kwargs):
        puts.append(args)
        return real_put(*args, **kwargs)

    monkeypatch.setattr(jax, "device_put", counting_put)
    for lookup in ("miss", "hit"):
        puts.clear()
        with jax.transfer_guard("disallow_explicit"):
            vecs = sched._eigvecs(graphs)
        with jax.transfer_guard("disallow"):
            prep, meta = pack_prepared(graphs, budget, eigvecs=vecs)
        assert len(puts) == 1, lookup
        assert len(jax.tree.leaves(puts[0])) == 1, lookup
        assert all(type(v) is np.ndarray for v in vecs), lookup
        np.testing.assert_array_equal(np.asarray(prep.eigvec),
                                      pack_eigvecs(want, meta), err_msg=lookup)


# ------------------------------------------------------- nothing else moved


@pytest.mark.parametrize("model", ["gin", "dgn"])
@pytest.mark.parametrize("rung", RUNGS, ids=lambda b: f"{b.n_pad}x{b.e_pad}x{b.g_pad}")
def test_pack_prepared_equals_device_graph_construction(model, rung, rng):
    for count in sorted({1, max(1, rung.g_pad // 2), rung.g_pad}):
        graphs = _molecules(rng, rung, count)
        vecs = _eigvecs(rng, graphs) if model == "dgn" else None
        got, _ = pack_prepared(graphs, rung, eigvecs=vecs)
        want = _device_graph_leaves(graphs, rung, vecs)
        msg = f"{model} {rung} graphs={count}"
        _assert_same_batch(got, want, rung, msg)
        device_plan = LY.build_layout(want[0])
        for a, b in zip(jax.tree.leaves(got.layout),
                        jax.tree.leaves(device_plan)):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b),
                                          err_msg=msg + " (device sort)")


def test_pack_prepared_without_layout_equals_device_graph_construction(rng):
    budget = BucketBudget(n_pad=64, e_pad=192, g_pad=4)
    graphs = _molecules(rng, budget, 3)
    got, _ = pack_prepared(graphs, budget, with_layout=False)
    assert got.layout is None
    want = _device_graph_leaves(graphs, budget, None, share_layout=False)
    _assert_same_batch(got, want, budget, "no plan")


# --------------------------------------------------- served, no new program


def _reduced_config(name):
    model, vn = ("gin", True) if name == "gin_vn" else (name, False)
    kw = dict(num_layers=2, virtual_node=vn)
    kw.update(dict(heads=2, head_features=8) if model == "gat" else dict(hidden=16))
    return paper_config(model, **kw)


@pytest.mark.parametrize("name", ["gcn", "gin", "gin_vn", "gat", "pna", "dgn"])
def test_served_bits_and_programs_unchanged(name, rng):
    cfg = _reduced_config(name)
    eng = GNNEngine(cfg, init(KEY, cfg), buckets=((32, 96),))
    sched = StreamScheduler(eng, capacity=2, max_wait_s=0.001,
                            with_eigvec="auto")
    budget = BucketBudget(n_pad=32, e_pad=96, g_pad=2)
    graphs = _molecules(rng, budget, 2) + _molecules(rng, budget, 2)
    sched.prewarm_ladders(graphs)
    ex = eng.executor
    lowered = ex.lowered_count
    assert lowered > 0

    rep = sched.run(graphs)
    assert len(rep.outputs) == len(graphs)
    assert ex.lowered_count == lowered, "a flush lowered a new program"

    vecs = sched._eigvecs(graphs[:2]) if cfg.model == "dgn" else None
    new, _ = pack_prepared(graphs[:2], budget, eigvecs=vecs,
                           with_layout=eng.share_layout)
    _, meta = pack_graphs(graphs[:2], budget)
    old = ex.prepare_packed(
        batch_graphs(graphs[:2], n_pad=budget.n_pad, e_pad=budget.e_pad),
        budget, eigvec=None if vecs is None else pack_eigvecs(vecs, meta),
        model=eng.name)
    _assert_same_batch(new, old.unwire(), budget, name)
    np.testing.assert_array_equal(np.asarray(new.wire), np.asarray(old.wire))
    out_new, _ = ex.run(new, model=eng.name)
    out_old, _ = ex.run(old, model=eng.name)
    np.testing.assert_array_equal(out_new, out_old, err_msg=name)
    assert ex.lowered_count == lowered, "the two constructions differ in program"


# ------------------------------------------------------------ the wire


def _odd_floats(shape, rng):
    """float32 values with every awkward bit pattern: NaNs with payloads
    (quiet and signalling, either sign), -0.0, infinities, subnormals."""
    bits = np.array([0x7FC00001, 0xFFC12345, 0x7F800001, 0xFFBFFFFF,
                     0x80000000, 0x7F800000, 0xFF800000, 0x00000001],
                    np.uint32).view(np.float32)
    x = rng.normal(size=shape).astype(np.float32).reshape(-1)
    x[: min(x.size, bits.size)] = bits[: x.size]
    return x.reshape(shape)


def _assert_bitwise(got, want, msg):
    """Same tree (absent parts included), shapes, dtypes and bytes."""
    assert jax.tree.structure(got) == jax.tree.structure(want), msg
    for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        a, b = np.asarray(a), np.asarray(b)
        assert (a.shape, a.dtype) == (b.shape, b.dtype), msg
        assert a.tobytes() == b.tobytes(), msg


@pytest.mark.parametrize("parts", ["graph", "eigvec", "plan", "eigvec+plan",
                                   "plan+static", "float16"])
def test_wire_round_trips_every_bit(parts, rng):
    """``unwire(wire(x)) == x`` bitwise, eagerly and inside a jitted
    program: float32 NaN payloads and -0.0, bool masks, the 0-d
    ``n_graph``, absent eigenvector, plan and model statics."""
    budget = BucketBudget(n_pad=64, e_pad=192, g_pad=4)
    graphs = _molecules(rng, budget, 3)
    g, meta = pack_graphs(graphs, budget)
    dtype = np.float16 if parts == "float16" else np.float32
    g = G.Graph(**{**vars(g),
                   "node_feat": _odd_floats(g.node_feat.shape, rng).astype(dtype),
                   "edge_feat": _odd_floats(g.edge_feat.shape, rng)})
    eig = (_odd_floats((budget.n_pad,), rng) if "eigvec" in parts else None)
    layout = pack_layout(g) if "plan" in parts else None
    if parts == "plan+static":
        layout = LY.GraphLayout(**{**vars(layout),
                                   "gcn_inv_sqrt": _odd_floats((64,), rng)})
    want = (g, eig, layout)

    buf, manifest = W.wire(*want)
    assert type(buf) is np.ndarray and buf.dtype == np.int32
    assert buf.size == W.wire_words(manifest)
    assert all(seg[0] % W.ALIGN == 0 for _, seg in manifest if seg)
    segments = dict(manifest)
    assert (segments["eigvec"] is None) == (eig is None)
    assert (segments["layout.perm"] is None) == (layout is None)
    assert (segments["layout.gcn_inv_sqrt"] is None) == (parts != "plan+static")
    assert segments["layout.pna_scalers"] is None
    assert segments["graph.n_graph"][1] == () == g.n_graph.shape
    assert manifest == W.manifest_of(*want)

    _assert_bitwise(W.unwire(buf, manifest), want, f"{parts} eager")
    traced = jax.jit(lambda b: W.unwire(b, manifest))(buf)
    _assert_bitwise(traced, want, f"{parts} jitted")


def _small_config(model):
    if model == "gps":
        return GNNConfig(model="gps", num_layers=2, hidden=32, heads=2,
                         head_features=16, out_dim=5, pe_steps=16, pe_dim=8,
                         ffn_hidden=64)
    return paper_config(model, num_layers=2, hidden=16)


@pytest.mark.parametrize("model", ["gin", "dgn", "gps"])
def test_served_program_equals_a_leaf_wise_call(model):
    """The served program splits the flush's one buffer inside itself; its
    outputs are bitwise those of ``forward_program`` called on the same
    flush's leaves one by one (the form before the buffer)."""
    from repro.data.pipeline import laplacian_eigvec

    cfg = _small_config(model)
    ex = X.Executor(buckets=DEFAULT_BUCKETS)
    tenant = ex.register(model, cfg, init(KEY, cfg))
    graphs = [g[:4] for g in MoleculeStream(MOLHIV, seed=3).take(5)]
    budget = BucketBudget(n_pad=256, e_pad=768, g_pad=8)
    vecs = ([laplacian_eigvec(s, r, nf.shape[0], nf.shape[0])
             for s, r, nf, _ in graphs] if model == "dgn" else None)

    prep, meta = pack_prepared(graphs, budget, eigvecs=vecs)
    served, _ = ex.run(prep, model=model)

    packed, _ = pack_graphs(graphs, budget)
    eig = None if vecs is None else pack_eigvecs(vecs, meta)
    leaves = jax.device_put((packed, eig, pack_layout(packed)))
    program = jax.jit(forward_program(cfg, num_graphs=budget.g_pad))
    leaf_wise = np.asarray(program(tenant.params, *leaves))
    assert served.shape == (budget.g_pad, cfg.out_dim)
    np.testing.assert_array_equal(served, leaf_wise, err_msg=model)
