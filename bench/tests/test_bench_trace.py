"""The trace reduction (bench/devtrace.py) on a small recorded chip trace and
on hand-made events, and the operation and byte counts of one GIN and one
DGN flush against hand counts."""
import importlib.util
import json
import sys
from pathlib import Path

import numpy as np
import pytest

BENCH = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(BENCH))

import devtrace  # noqa: E402

RECORDED = BENCH / "tests" / "data" / "trace_gin_screen.json"


def _config(name):
    spec = importlib.util.spec_from_file_location(
        f"bench_config_{name}", BENCH / "configs" / f"{name}.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod, json.loads((BENCH / "configs" / f"{name}.json").read_text())


@pytest.fixture(scope="module")
def recorded():
    trace = json.loads(RECORDED.read_text())
    trace["devices"] = {int(k): v for k, v in trace["devices"].items()}
    return trace


def _mask(events, lo, hi):
    """Busy nanoseconds of [lo, hi), one array cell per nanosecond."""
    busy = np.zeros(hi - lo, bool)
    for _, s, e in events:
        busy[max(s, lo) - lo:max(min(e, hi) - lo, 0)] = True
    return busy


# ------------------------------------------------------ hand-made events


def test_busy_union_counts_overlaps_once_and_clips_to_the_window():
    ops = [["a", 0, 10], ["b", 5, 15], ["c", 20, 30], ["d", 25, 26], ["e", 40, 60]]
    assert devtrace.busy_ns(ops, 0, 100) == 15 + 10 + 20
    assert devtrace.busy_ns(ops, 8, 50) == 7 + 10 + 10
    assert devtrace.busy_ns(ops, 60, 70) == 0


def test_top_ops_sums_per_name_inside_the_window():
    ops = [["a", 0, 10], ["b", 5, 15], ["a", 20, 30], ["c", 95, 120]]
    assert devtrace.top_ops(ops, 0, 100, k=2) == [["a", 20e-9], ["b", 10e-9]]
    assert devtrace.top_ops(ops, 0, 100)[-1] == ["c", 5e-9]


def test_idle_gaps_are_named_by_the_host_span_covering_most_of_them():
    ops = [["a", 10, 20], ["b", 50, 60]]
    host = [["bench.window", 0, 100], ["bench.wait", 0, 12],
            ["bench.serve", 12, 55], ["bench.generate", 55, 100]]
    gaps = devtrace.idle_gaps(ops, host, 0, 100)
    assert gaps == [["bench.generate", 40e-9], ["bench.serve", 30e-9],
                    ["bench.wait", 10e-9]]


def test_op_labels_name_the_program_and_the_result_type():
    name = ("%fusion.12 = f32[128,100]{1,0:T(8,128)S(1)} fusion(f32[128,100] %x), "
            "kind=kCustom, calls=%fused_computation.31")
    assert devtrace.op_label(name, "jit_run(123)") == "jit_run:fusion.12 f32[128,100]"
    ops = [[name, 5, 6], ["%copy = s32[4]{0} copy(s32[4] %y)", 50, 51]]
    progs = [["jit_run(123)", 0, 10]]
    assert [o[0] for o in devtrace.labelled(ops, progs)] == [
        "jit_run:fusion.12 f32[128,100]", "copy s32[4]"]


# --------------------------------------------------- the recorded trace


def test_recorded_busy_union_matches_a_per_nanosecond_mask(recorded):
    lo, hi = devtrace.window(recorded)
    ops = recorded["devices"][0]["ops"]
    busy = devtrace.busy_ns(ops, lo, hi)
    assert busy == int(_mask(ops, lo, hi).sum())
    # the window is host-bound: the device ran ops a small share of it
    idle = 1 - busy / (hi - lo)
    assert 0.9 < idle < 1.0
    assert busy <= sum(e - s for _, s, e in ops)


def test_recorded_ops_are_attributed_to_their_programs(recorded):
    lo, hi = devtrace.window(recorded)
    dev = recorded["devices"][0]
    named = devtrace.labelled(dev["ops"], dev["programs"])
    assert len(named) == len(dev["ops"])
    runs = devtrace.programs_in(dev["programs"], lo, hi, "jit_run")
    assert len(runs) == 6  # the six flushes the trace was trimmed to
    inside = [o for o in named
              if any(s <= o[1] < e for s, e in runs)]
    assert inside and all(o[0].startswith("jit_run:") for o in inside)
    top = devtrace.top_ops(named, lo, hi)
    totals = {}
    for name, s, e in named:
        totals[name] = totals.get(name, 0) + min(e, hi) - max(s, lo)
    assert len(top) == 10
    for name, seconds in top:
        assert seconds == pytest.approx(totals[name] / 1e9, rel=1e-12)
    assert [t[1] for t in top] == sorted((t[1] for t in top), reverse=True)


def test_recorded_idle_gaps_fall_inside_the_harness_spans(recorded):
    lo, hi = devtrace.window(recorded)
    gaps = devtrace.idle_gaps(recorded["devices"][0]["ops"], recorded["host"], lo, hi)
    assert {g[0] for g in gaps} <= {"bench.serve", "bench.generate"}
    every = devtrace.idle_gaps(recorded["devices"][0]["ops"], recorded["host"],
                               lo, hi, k=10 ** 6)
    busy = devtrace.busy_ns(recorded["devices"][0]["ops"], lo, hi)
    assert sum(g[1] for g in every) == pytest.approx((hi - lo - busy) / 1e9, rel=1e-9)
    assert [g[1] for g in gaps] == sorted((g[1] for g in every), reverse=True)[:10]


# ----------------------------------------------- operation and byte counts


def test_gin_flush_counts_match_a_hand_count():
    model, cfg = _config("gin")
    n, e, g = 128, 384, 8
    # encoder 128x9 @ 9x100 (+bias); per layer: edge embedding 384x3 @ 3x100
    # (+bias), x_src + e and relu, the sum over in-edges, (1 + eps) x + agg,
    # 128x100 @ 100x200 (+bias, relu), 128x200 @ 200x100 (+bias); mean pool;
    # head 100 -> 1 (+bias) per slot
    enc = 2 * 128 * 9 * 100 + 128 * 100
    layer = (2 * 384 * 3 * 100 + 384 * 100 + 2 * 384 * 100 + 384 * 100
             + 2 * 128 * 100 + 2 * 128 * 100 * 200 + 2 * 128 * 200
             + 2 * 128 * 200 * 100 + 128 * 100)
    pool = 128 * 100 + 8 * 100
    head = 8 * (2 * 100 + 1)
    assert model.forward_flops(n, e, g, cfg) == enc + 5 * layer + pool + head
    params = (9 * 100 + 100) + 5 * (3 * 100 + 100 + 1 + 100 * 200 + 200
                                    + 200 * 100 + 100) + (100 + 1)
    assert model.param_count(cfg) == params == 204_606
    graph = 128 * 9 * 4 + 384 * (3 * 4 + 2 * 4)
    assert model.forward_bytes(n, e, g, cfg) == 4 * params + graph + 8 * 4


def test_dgn_flush_counts_match_a_hand_count():
    model, cfg = _config("dgn")
    n, e, g = 128, 384, 8
    # encoder; directional weights once (4 per edge, 2 per node); per layer:
    # mean (a sum over in-edges and a division), the weighted sum (a
    # multiply and an add per edge), |wx - x * sum w| (3 per node feature),
    # 128x300 @ 300x100 with bias, relu and skip; mean pool; head
    # 100 -> 50 -> 25 -> 1 with biases per slot
    enc = 2 * 128 * 9 * 100 + 128 * 100
    weights = 4 * 384 + 2 * 128
    layer = (384 * 100 + 128 * 100 + 2 * 384 * 100 + 3 * 128 * 100
             + 2 * 128 * 300 * 100 + 3 * 128 * 100)
    pool = 128 * 100 + 8 * 100
    head = 8 * ((2 * 100 * 50 + 50) + (2 * 50 * 25 + 25) + (2 * 25 + 1))
    assert model.forward_flops(n, e, g, cfg) == enc + weights + 4 * layer + pool + head
    params = (9 * 100 + 100) + 4 * (300 * 100 + 100) + (100 * 50 + 50
                                                        + 50 * 25 + 25 + 25 + 1)
    assert model.param_count(cfg) == params
    graph = 128 * (9 * 4 + 4) + 384 * (3 * 4 + 2 * 4)
    assert model.forward_bytes(n, e, g, cfg) == 4 * params + graph + 8 * 4


@pytest.mark.parametrize("name", ["gin", "dgn"])
def test_param_count_matches_the_weights_made_from_the_seed(name):
    import jax

    model, cfg = _config(name)
    params = jax.eval_shape(lambda k: model.init_params(k, cfg), jax.random.key(0))
    assert sum(int(np.prod(v.shape)) for v in jax.tree.leaves(params)) \
        == model.param_count(cfg)
