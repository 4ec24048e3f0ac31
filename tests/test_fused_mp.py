"""Fused (phi, A, gamma) megakernel: parity, fallbacks, serving statics.

Three layers of guarantees:

  * **kernel vs oracle** — the Pallas megakernel (interpret mode on CPU)
    matches ``kernels/ref.fused_mp_ref`` for every gamma x precision over
    ragged shapes, empty edge blocks, and isolated nodes.  PNA gets a
    documented tolerance: its std derives from ``sqsum/c - mean^2``, and
    XLA may contract the multiply-subtract into an FMA (exact ``mean^2``
    against the *rounded* ``sqsum``), leaving ~1 ulp of variance that
    ``sqrt`` at zero amplifies to ~ value * sqrt(eps) — benign, backend-
    dependent, and orders below the model's quantization noise.
  * **fused vs unfused model forward** — ``models.apply(..., fused=True)``
    is *bitwise* identical to the unfused closure path in fp32 for all six
    models (the CPU fused path is the same jnp arithmetic in one jit
    scope), matches unfused int8 within quantization-noise bounds for
    int8-dynamic, and falls back to bitwise-identical unfused execution
    for the parameterizations that can't lower (GAT, int8-static, fixed).
  * **serving statics** — ``fused`` rides ``program_key`` exactly like
    ``share_layout``: distinct programs, zero recompiles after warm, no
    new bucket/warm keys.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import layout as LY
from repro.core import message_passing as mp
from repro.core.graph import batch_graphs
from repro.gnn import init
from repro.gnn.models import apply, paper_config
from repro.kernels import fused_mp as FK
from repro.kernels import ops as kops
from repro.kernels import ref as KR
from repro.quant import qconfig as qc

KEY = jax.random.PRNGKey(0)
MODELS = [("gcn", False), ("gin", False), ("gin", True), ("gat", False),
          ("pna", False), ("dgn", False)]
PADDINGS = [(48, 120), (80, 160), (50, 300)]

# std tolerance: FMA contraction of `sqsum/c - mean^2` (see module doc)
PNA_TOL = 5e-3
# int8 kernel/oracle use the same exact-emulation accumulate; only the
# f32 requant tail can diverge by rounding
INT8_TOL = 2e-5


def _bitwise(a, b, msg):
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b), err_msg=msg)


def _random_batch(rng, n_pad, e_pad, n_graphs=3):
    gs = []
    for _ in range(n_graphs):
        n = int(rng.integers(5, 14))
        e = int(rng.integers(n, 2 * n))
        gs.append((
            rng.integers(0, n, e).astype(np.int32),
            rng.integers(0, n, e).astype(np.int32),
            rng.normal(size=(n, 9)).astype(np.float32),
            rng.normal(size=(e, 3)).astype(np.float32),
        ))
    return batch_graphs(gs, n_pad=n_pad, e_pad=e_pad)


def _quant_cols(w):
    """Per-channel symmetric int8 weights, the fused operand form."""
    s = jnp.max(jnp.abs(w), axis=0) / 127.0
    return jnp.clip(jnp.round(w / s), -127, 127).astype(jnp.int8), s


def _spec_operands(rng, gamma, precision, n, e_pad, f=12):
    """(MPSpec, operand dict) exercising every operand slot of ``gamma``."""
    msrc = jnp.asarray(rng.normal(size=(n, f)), jnp.float32)
    x_res = jnp.asarray(rng.normal(size=(n, f)), jnp.float32)
    b1 = jnp.asarray(rng.normal(size=(f,)), jnp.float32)
    if gamma == "gcn":
        spec = mp.MPSpec("copy", ("sum",), "gcn", precision)
        return spec, dict(
            msrc=msrc, x_res=x_res,
            nop=jnp.asarray(rng.normal(size=(n, 1)), jnp.float32),
        )
    if gamma == "gin":
        w1 = jnp.asarray(rng.normal(size=(f, f)) * 0.3, jnp.float32)
        kw = dict(
            msrc=msrc, x_res=x_res,
            eop=jnp.asarray(rng.normal(size=(e_pad, f)), jnp.float32),
            b1=b1,
            w2=jnp.asarray(rng.normal(size=(f, f)) * 0.3, jnp.float32),
            b2=jnp.asarray(rng.normal(size=(f,)), jnp.float32),
        )
    elif gamma == "pna":
        w1 = jnp.asarray(rng.normal(size=(12 * f, f)) * 0.2, jnp.float32)
        kw = dict(
            msrc=msrc, x_res=x_res, b1=b1,
            nop=jnp.asarray(np.abs(rng.normal(size=(n, 3))) + 0.5,
                            jnp.float32),
        )
    else:  # dgn
        w1 = jnp.asarray(rng.normal(size=(3 * f, f)) * 0.2, jnp.float32)
        kw = dict(
            msrc=msrc, x_res=x_res, b1=b1,
            nop=jnp.asarray(np.abs(rng.normal(size=(n, 1))) + 0.1,
                            jnp.float32),
            ew=jnp.asarray(rng.normal(size=(e_pad, 1)), jnp.float32),
        )
    phi = "add_relu" if gamma == "gin" else "copy"
    ops = {"gin": ("sum",), "pna": ("sum", "sqsum", "max", "min"),
           "dgn": ("sum", "wsum")}[gamma]
    if precision == "int8":
        kw["w1"], kw["w1_scale"] = _quant_cols(w1)
    else:
        kw["w1"] = w1
    return mp.MPSpec(phi, ops, gamma, precision), kw


# --------------------------------------------------------------- the spec


def test_mpspec_validation():
    mp.MPSpec("copy", ("sum", "max"), "pna", "int8")  # fine
    with pytest.raises(ValueError):
        mp.MPSpec(phi="exp")
    with pytest.raises(ValueError):
        mp.MPSpec(ops=("mean",))  # derived in gamma, not an accumulator
    with pytest.raises(ValueError):
        mp.MPSpec(ops=())
    with pytest.raises(ValueError):
        mp.MPSpec(gamma="gat")  # the documented opt-out is not a gamma
    with pytest.raises(ValueError):
        mp.MPSpec(precision="int4")


def test_mp_layer_spec_requires_layout(rng):
    g = _random_batch(rng, 48, 120)
    spec, kw = _spec_operands(rng, "gcn", "fp32", 48, 120)
    with pytest.raises(ValueError, match="requires a GraphLayout"):
        mp.mp_layer(g, kw["msrc"], spec=spec, operands=kw)


def test_compiled_dispatch_over_vmem_budget_raises(monkeypatch):
    """On the chip, a resident footprint over the VMEM budget is an
    error naming the bytes — never a quiet switch to the reference."""
    monkeypatch.setattr(kops, "_on_tpu", lambda: True)
    rows = kops._FUSED_VMEM_BUDGET // (100 * 4) + 1
    msrc = np.zeros((rows, 100), np.float32)
    n, e = rows, 8
    spec = mp.MPSpec(phi="copy", ops=("sum",), gamma="gcn")
    with pytest.raises(ValueError, match=f"{msrc.size * 4} resident bytes"):
        kops.fused_mp(spec, np.zeros(e, np.int32), np.zeros(e, np.int32),
                      np.zeros(n, np.float32), np.ones(n, bool), msrc, msrc,
                      nop=np.zeros((n, 1), np.float32))


def test_int8_row_eps_constants_pinned():
    """The kernel re-implements qconfig's dynamic recipe; the epsilon in
    `rs = max(rowmax|x|, eps) / 127` must stay one constant in all three
    homes or fused/unfused int8 silently diverge on near-zero rows."""
    assert KR._ROW_EPS == qc._EPS
    assert FK._ROW_EPS == qc._EPS


# ----------------------------------------------------- kernel vs oracle


@pytest.mark.parametrize("gamma", ["gcn", "gin", "pna", "dgn"])
@pytest.mark.parametrize("precision", ["fp32", "int8"])
def test_kernel_matches_oracle(gamma, precision, rng):
    """Interpret-mode Pallas vs the jnp oracle, small blocks so the grid
    exercises multi-block accumulation, ragged tails, and node blocks
    with no overlapping edges."""
    tol = PNA_TOL if gamma == "pna" else (
        INT8_TOL if precision == "int8" else 1e-5
    )
    for n_pad, e_pad, n_graphs in [(50, 121, 3), (33, 70, 2)]:
        g = _random_batch(rng, n_pad, e_pad, n_graphs=n_graphs)
        lay = LY.build_layout(g)
        spec, kw = _spec_operands(rng, gamma, precision, n_pad, e_pad)
        a = kops.fused_mp(spec, lay.ids_sorted, lay.src_sorted,
                          lay.in_degree, g.node_mask, mode="reference", **kw)
        b = kops.fused_mp(spec, lay.ids_sorted, lay.src_sorted,
                          lay.in_degree, g.node_mask, mode="kernel",
                          block_e=32, block_n=16, **kw)
        d = float(np.abs(np.asarray(a) - np.asarray(b)).max())
        assert d <= tol, (gamma, precision, (n_pad, e_pad), d)


def test_kernel_sparse_and_isolated(rng):
    """One tiny graph in huge padding: most edge blocks are pure padding
    (overlap early-out), most node rows are empty segments, and real
    isolated nodes get zero (not the +/-inf fill) from max/min."""
    g = batch_graphs(
        [(np.asarray([1], np.int32), np.asarray([0], np.int32),
          rng.normal(size=(5, 9)).astype(np.float32),
          rng.normal(size=(1, 3)).astype(np.float32))],
        n_pad=33, e_pad=70,
    )
    lay = LY.build_layout(g)
    spec, kw = _spec_operands(rng, "pna", "fp32", 33, 70)
    a = np.asarray(kops.fused_mp(spec, lay.ids_sorted, lay.src_sorted,
                                 lay.in_degree, g.node_mask,
                                 mode="reference", **kw))
    b = np.asarray(kops.fused_mp(spec, lay.ids_sorted, lay.src_sorted,
                                 lay.in_degree, g.node_mask, mode="kernel",
                                 block_e=32, block_n=16, **kw))
    assert np.isfinite(a).all() and np.isfinite(b).all()
    assert np.abs(a - b).max() <= PNA_TOL
    # padded node rows are masked to exactly zero on both paths
    assert (a[5:] == 0).all() and (b[5:] == 0).all()


# ------------------------------------------- fused vs unfused model paths


@pytest.mark.parametrize("model,vn", MODELS)
def test_fused_apply_bitwise_equals_unfused_fp32(model, vn, rng):
    """The CPU fused path is the same jnp arithmetic fused into one jit
    scope — fp32 must be *bitwise* identical, across padding fuzz (the
    packed-flush shapes included)."""
    cfg = paper_config(model, virtual_node=vn)
    params = init(KEY, cfg)
    for n_pad, e_pad in PADDINGS:
        g = _random_batch(rng, n_pad, e_pad)
        eig = jnp.asarray(rng.normal(size=(n_pad,)), jnp.float32)
        lay = LY.for_model(None, g, model, avg_degree=cfg.avg_degree,
                           eigvec=eig)
        un = apply(params, g, cfg, eigvec=eig, layout=lay)
        fu = apply(params, g, cfg, eigvec=eig, layout=lay, fused=True)
        _bitwise(fu, un, f"{model} vn={vn} pad=({n_pad},{e_pad})")


@pytest.mark.parametrize("model,vn", MODELS)
def test_fused_int8_within_quantization_noise(model, vn, rng):
    """int8-dynamic: the fused lowering re-quantizes at the same boundary
    with the same recipe; GIN's auxiliary linears run weight-only
    dequantized, so fused != unfused bit-for-bit there — the bound is that
    fused int8 stays as close to fp32 as unfused int8 is (same error
    class, no compounding)."""
    from repro.quant import apply as QA

    cfg = paper_config(model, virtual_node=vn)
    params = init(KEY, cfg)
    qparams, _ = QA.quantize_model(params, cfg, (),
                                   QA.precision_qconfig("int8"))
    g = _random_batch(rng, 80, 160)
    eig = jnp.asarray(rng.normal(size=(80,)), jnp.float32)
    lay = LY.for_model(None, g, model, avg_degree=cfg.avg_degree, eigvec=eig)
    fp32 = np.asarray(apply(params, g, cfg, eigvec=eig, layout=lay))
    un = np.asarray(apply(qparams, g, cfg, eigvec=eig, layout=lay))
    fu = np.asarray(apply(qparams, g, cfg, eigvec=eig, layout=lay,
                          fused=True))
    mae_un = np.abs(un - fp32).mean()
    mae_fu = np.abs(fu - fp32).mean()
    # factor 5: GIN trades its auxiliaries' activation quantization for
    # weight-only dequant — a different rounding profile of the same
    # order, not compounding (both MAEs stay ~1e-3 on an O(4) logit span)
    assert mae_fu <= 5.0 * mae_un + 1e-4, (model, vn, mae_fu, mae_un)


@pytest.mark.parametrize("precision", ["int8-static", "fixed"])
def test_unlowerable_precisions_fall_back_bitwise(precision, rng):
    """int8-static / ap_fixed params return None from the operand probes,
    so fused=True must execute the identical unfused computation."""
    from repro.quant import apply as QA

    cfg = paper_config("gin")
    params = init(KEY, cfg)
    calib = []
    for _ in range(3):
        n = int(rng.integers(6, 12))
        e = int(rng.integers(n, 2 * n))
        calib.append((rng.integers(0, n, e).astype(np.int32),
                      rng.integers(0, n, e).astype(np.int32),
                      rng.normal(size=(n, 9)).astype(np.float32),
                      rng.normal(size=(e, 3)).astype(np.float32)))
    qparams, _ = QA.quantize_model(params, cfg, calib,
                                   QA.precision_qconfig(precision))
    g = _random_batch(rng, 48, 120)
    lay = LY.build_layout(g)
    un = apply(qparams, g, cfg, layout=lay)
    fu = apply(qparams, g, cfg, layout=lay, fused=True)
    _bitwise(fu, un, precision)


def test_fused_forward_stays_zero_sort(rng):
    """Fusion must not reintroduce sorts: with a supplied plan the fused
    jaxpr contains zero sort ops (one when built in-forward), matching
    the unfused layout invariant."""
    from benchmarks.bench_layout import count_jaxpr_sorts

    g = _random_batch(rng, 48, 120)
    for model, vn in MODELS:
        cfg = paper_config(model, virtual_node=vn)
        params = init(KEY, cfg)
        eig = jnp.asarray(rng.normal(size=(48,)), jnp.float32)
        lay = LY.for_model(None, g, model, avg_degree=cfg.avg_degree,
                           eigvec=eig)
        pre = count_jaxpr_sorts(jax.make_jaxpr(
            lambda p, gg, e, l: apply(p, gg, cfg, eigvec=e, layout=l,
                                      fused=True)
        )(params, g, eig, lay).jaxpr)
        inf = count_jaxpr_sorts(jax.make_jaxpr(
            lambda p, gg, e: apply(p, gg, cfg, eigvec=e, fused=True)
        )(params, g, eig).jaxpr)
        assert pre == 0, (model, vn, pre)
        assert inf == 1, (model, vn, inf)


# ------------------------------------------------------- serving statics


def _reduced_config(model="gin"):
    return paper_config(model, num_layers=2, hidden=16)


def _raw_graphs(rng, k=4):
    out = []
    for _ in range(k):
        n = int(rng.integers(5, 14))
        e = int(rng.integers(n, 2 * n))
        out.append((rng.integers(0, n, e).astype(np.int32),
                    rng.integers(0, n, e).astype(np.int32),
                    rng.normal(size=(n, 9)).astype(np.float32),
                    rng.normal(size=(e, 3)).astype(np.float32)))
    return out


def test_fused_is_a_program_key_static(rng):
    """fused tenants compile their own programs (no silent sharing with
    unfused same-arch tenants) but share with equal-fused tenants."""
    from repro.serve.executor import Executor

    cfg = _reduced_config()
    params = init(KEY, cfg)
    ex = Executor(buckets=((16, 32),))
    a = ex.register("plain", cfg, params)
    b = ex.register("fused", cfg, params, fused=True)
    c = ex.register("fused2", cfg, params, fused=True)
    assert a.program_key != b.program_key
    assert b.program_key == c.program_key
    g = _raw_graphs(rng, 1)[0]
    pa = ex.prepare_stream(g)
    ex.run(pa, model="plain")
    ex.run(pa, model="fused")
    assert len(ex._compiled) == 2  # one program per distinct key


def test_fused_engine_zero_recompiles_after_warm(rng):
    """Same bucket signatures as unfused: after the first graph warms a
    bucket, further fused traffic through it never compiles again."""
    from repro.serve.gnn_engine import GNNEngine

    cfg = _reduced_config()
    params = init(KEY, cfg)
    eng = GNNEngine(cfg, params, buckets=((16, 32),), fused=True)
    assert eng.fused
    graphs = _raw_graphs(rng)
    eng.infer_stream(graphs[:1])
    warm = eng.compile_seconds
    assert warm > 0.0
    outs, lats, compile_s = eng.infer_stream(graphs)
    assert compile_s == 0.0
    assert eng.compile_seconds == warm
    assert len(outs) == len(graphs)


def test_fused_engine_matches_unfused_engine_bitwise(rng):
    """End-to-end through the serving stack: fp32 fused serving returns
    bit-identical outputs to unfused serving."""
    from repro.serve.gnn_engine import GNNEngine

    cfg = _reduced_config("pna")
    params = init(KEY, cfg)
    graphs = _raw_graphs(rng)
    plain = GNNEngine(cfg, params, buckets=((16, 32),))
    fused = GNNEngine(cfg, params, buckets=((16, 32),), fused=True,
                      name="fused")
    outs_a, _, _ = plain.infer_stream(graphs)
    outs_b, _, _ = fused.infer_stream(graphs)
    for i, (a, b) in enumerate(zip(outs_a, outs_b)):
        _bitwise(a, b, f"stream graph {i}")
