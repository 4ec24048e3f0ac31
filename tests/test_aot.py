"""The persistent AOT compile cache (serve/aot.py) + XLA flag table.

* **Round-trip parity** — an executable serialized to disk and
  deserialized by a fresh Executor must produce *bitwise* the outputs of
  the fresh compile, across models x precision x fused, with every load
  a hit and zero fresh lowerings in the second executor.
* **Fingerprint invalidation** — a cache entry from a different flag
  set, jax version, or device topology reports ``stale`` (distinct from
  ``miss``), recompiles, and overwrites in place.
* **Corruption** — truncated/garbage/colliding entries degrade to a
  plain miss (never an exception on the serving path) and are healed by
  the write-back.
* **Restart** — a subprocess given only the cache directory and the
  saved params serves bitwise-identical outputs with ``lowered_count ==
  0``: not one ``jax.jit`` trace in the whole process (the kill-the-
  warm-up contract).
"""
import json
import os
import pickle
import subprocess
import sys
import textwrap
from pathlib import Path

import jax
import numpy as np
import pytest

from repro.gnn import init
from repro.gnn.models import paper_config
from repro.serve.aot import (AOTCache, XlaFlagConfig, default_flags_path,
                             environment_fingerprint, flags_hash, model_label)
from repro.serve.executor import Executor
from repro.serve.gnn_engine import GNNEngine
from repro.serve.scheduler import StreamScheduler

KEY = jax.random.PRNGKey(0)


def _reduced_config(model, vn=False, **kw):
    base = dict(num_layers=2, virtual_node=vn)
    if model == "gat":
        base.update(heads=2, head_features=8)
    else:
        base.update(hidden=16)
    base.update(kw)
    return paper_config(model, **base)


def _raw_graphs(rng, k=3, feat=9, edge=3):
    out = []
    for _ in range(k):
        n = int(rng.integers(5, 14))
        e = int(rng.integers(n, 2 * n))
        out.append((
            rng.integers(0, n, e).astype(np.int32),
            rng.integers(0, n, e).astype(np.int32),
            rng.normal(size=(n, feat)).astype(np.float32),
            rng.normal(size=(e, edge)).astype(np.float32),
        ))
    return out


def _serve(cache_dir, cfg, params, graphs, precision="fp32", fused=False,
           xla_flags=None):
    """(outputs, engine) — one fresh engine over ``cache_dir`` serving
    ``graphs`` through the stream path."""
    eng = GNNEngine(cfg, params, buckets=((16, 32),), precision=precision,
                    fused=fused, aot_cache=AOTCache(str(cache_dir)),
                    xla_flags=xla_flags)
    outs, _, _ = eng.infer_stream(graphs)
    return np.concatenate(outs), eng


# ------------------------------------------------------------ round trip


@pytest.mark.parametrize("model,precision,fused", [
    ("gcn", "fp32", False),
    ("gin", "fp32", True),
    ("gin", "int8", False),
    ("gat", "fp32", False),
])
def test_aot_round_trip_is_bitwise_and_trace_free(model, precision, fused,
                                                  rng, tmp_path):
    cfg = _reduced_config(model)
    params = init(KEY, cfg)
    graphs = _raw_graphs(rng)
    out_fresh, eng1 = _serve(tmp_path, cfg, params, graphs,
                             precision=precision, fused=fused)
    ex1 = eng1.executor
    assert ex1.lowered_count > 0
    assert ex1.aot_stats()["miss"] == ex1.lowered_count
    assert ex1.aot_stats()["hit"] == 0

    out_disk, eng2 = _serve(tmp_path, cfg, params, graphs,
                            precision=precision, fused=fused)
    ex2 = eng2.executor
    assert ex2.lowered_count == 0, "warm restart must not trace once"
    assert ex2.aot_stats() == {"hit": ex1.lowered_count, "miss": 0,
                               "stale": 0}
    np.testing.assert_array_equal(
        out_fresh, out_disk,
        err_msg=f"{model}/{precision}/fused={fused}: cache-hit outputs "
                f"differ from the fresh compile",
    )


def test_compile_warm_split_accounts_both_halves(rng, tmp_path):
    """Fresh run pays compile+warm; the disk-hit run still pays warm
    (one untimed execution) but compile collapses to the deserialize."""
    cfg = _reduced_config("gcn")
    params = init(KEY, cfg)
    graphs = _raw_graphs(rng)
    _, eng1 = _serve(tmp_path, cfg, params, graphs)
    assert eng1.compile_seconds > 0 and eng1.warm_seconds > 0
    _, eng2 = _serve(tmp_path, cfg, params, graphs)
    assert eng2.warm_seconds > 0, "first-run warm is paid even on a hit"
    assert eng2.compile_seconds < eng1.compile_seconds, (
        "disk load must be cheaper than the fresh compile it replaces"
    )


# ---------------------------------------------------------- invalidation


def test_stale_fingerprint_is_not_a_miss_and_heals(tmp_path):
    cache = AOTCache(str(tmp_path))
    fn = jax.jit(lambda x: x * 2.0 + 1.0)
    compiled = fn.lower(np.ones((4,), np.float32)).compile()
    key = ("prog", ("stream", 16, 32), 1, ("sig",))
    fp = environment_fingerprint()
    assert cache.store(key, fp, compiled)

    # same key, moved flag hash -> stale (the autotuner-retune case)
    moved = dict(fp, flags=flags_hash({"xla_whatever": 1}))
    assert cache.load(key, moved) is None
    assert cache.stats["stale"] == 1 and cache.stats["miss"] == 0

    # jax version / topology drift -> stale too
    for field, value in [("jax", "9.9.9"), ("num_devices", 1024),
                         ("backend", "tpu")]:
        assert cache.load(key, dict(fp, **{field: value})) is None
    assert cache.stats["stale"] == 4

    # overwrite under the new fingerprint heals it in place
    assert cache.store(key, moved, compiled)
    assert cache.load(key, moved) is not None
    assert cache.stats["hit"] == 1
    assert len(cache.entries()) == 1


def test_executor_recompiles_and_overwrites_stale_entries(rng, tmp_path):
    """End to end: retuned flags must invalidate exactly the cached
    programs whose flags changed — served outputs stay available
    throughout (numerics-neutral flags)."""
    cfg = _reduced_config("gcn")
    params = init(KEY, cfg)
    graphs = _raw_graphs(rng)
    out1, eng1 = _serve(tmp_path, cfg, params, graphs)
    # "retune": a different (valid) flag set -> every entry stale
    flags = XlaFlagConfig(default={"xla_embed_ir_in_executable": True})
    out2, eng2 = _serve(tmp_path, cfg, params, graphs, xla_flags=flags)
    stats = eng2.executor.aot_stats()
    assert stats["stale"] > 0 and stats["hit"] == 0
    assert eng2.executor.lowered_count == stats["stale"]
    np.testing.assert_array_equal(out1, out2)
    # third run under the retuned flags: all hits again
    _, eng3 = _serve(tmp_path, cfg, params, graphs, xla_flags=flags)
    assert eng3.executor.lowered_count == 0
    assert eng3.executor.aot_stats()["stale"] == 0


# ------------------------------------------------------------ corruption


def test_corrupt_entries_degrade_to_miss_and_heal(tmp_path):
    cache = AOTCache(str(tmp_path))
    fn = jax.jit(lambda x: x - 3.0)
    compiled = fn.lower(np.ones((2,), np.float32)).compile()
    key = ("p", ("stream", 16, 32), 1, ("s",))
    fp = environment_fingerprint()
    assert cache.store(key, fp, compiled)
    path = Path(cache.entry_path(key))

    path.write_bytes(b"\x00garbage")  # not a pickle
    assert cache.load(key, fp) is None and cache.stats["miss"] == 1

    path.write_bytes(pickle.dumps({"schema": "wrong/v0"}))
    assert cache.load(key, fp) is None and cache.stats["miss"] == 2

    # right schema, wrong logical key (hash collision / tamper)
    path.write_bytes(pickle.dumps({
        "schema": "repro-aot/v1", "key": repr(("other",)), "fingerprint": fp,
        "payload": b"", "in_tree": None, "out_tree": None,
    }))
    assert cache.load(key, fp) is None and cache.stats["miss"] == 3

    path.write_bytes(path.read_bytes()[:10])  # truncated
    assert cache.load(key, fp) is None and cache.stats["miss"] == 4

    assert cache.store(key, fp, compiled)  # heal
    exe = cache.load(key, fp)
    assert exe is not None
    np.testing.assert_array_equal(
        np.asarray(exe(np.ones((2,), np.float32))), -2.0 * np.ones(2)
    )


def test_executor_serves_through_a_poisoned_cache(rng, tmp_path):
    """A corrupt entry on the serving path is a fresh compile plus an
    overwrite — never an exception, and the next process hits."""
    cfg = _reduced_config("gcn")
    params = init(KEY, cfg)
    graphs = _raw_graphs(rng)
    out1, eng1 = _serve(tmp_path, cfg, params, graphs)
    for f in Path(tmp_path).glob("*.aotx"):
        f.write_bytes(b"poison")
    out2, eng2 = _serve(tmp_path, cfg, params, graphs)
    assert eng2.executor.aot_stats()["miss"] == eng2.executor.lowered_count > 0
    np.testing.assert_array_equal(out1, out2)
    _, eng3 = _serve(tmp_path, cfg, params, graphs)
    assert eng3.executor.lowered_count == 0


# -------------------------------------------------------- the flag table


def test_flag_config_merge_order_and_io(tmp_path):
    flags = XlaFlagConfig(
        default={"a": 1, "b": 1},
        models={"gin": {"default": {"b": 2, "c": 2},
                        "buckets": {"packed|64|192|4": {"c": 3}}}},
    )
    assert flags.resolve("gcn", ("stream", 16, 32)) == {"a": 1, "b": 1}
    assert flags.resolve("gin", ("stream", 16, 32)) == {"a": 1, "b": 2,
                                                        "c": 2}
    assert flags.resolve("gin", ("packed", 64, 192, 4)) == {"a": 1, "b": 2,
                                                            "c": 3}
    path = tmp_path / "flags.json"
    flags.save(str(path), provenance={"tool": "test"})
    loaded = XlaFlagConfig.load(str(path))
    assert loaded.default == flags.default and loaded.models == flags.models
    with pytest.raises(FileNotFoundError):
        XlaFlagConfig.load(str(tmp_path / "absent.json"))
    (tmp_path / "bad.json").write_text(json.dumps({"schema": "nope"}))
    with pytest.raises(ValueError, match="repro-xla-flags/v1"):
        XlaFlagConfig.load(str(tmp_path / "bad.json"))


def test_checked_in_flag_table_loads_and_is_validated():
    """The committed configs/xla_flags.json parses, carries no CPU-only
    (``xla_cpu_*``) flag, and every flag in it is accepted by this
    backend (the autotuner's try-compile contract).  The table is empty
    until it is re-derived from measurements on the chip."""
    assert os.path.exists(default_flags_path())
    table = XlaFlagConfig.load()
    probe = jax.jit(lambda x: x + 1.0).lower(np.ones((2,), np.float32))
    flag_sets = [table.default]
    for spec in table.models.values():
        flag_sets += [spec.get("default", {})] + \
            list(spec.get("buckets", {}).values())
    for flags in flag_sets:
        assert not [k for k in flags if k.startswith("xla_cpu_")], flags
        if flags:
            probe.compile(compiler_options=dict(flags))  # must not raise


def test_rejected_flag_set_falls_back_and_fingerprints_honestly(rng,
                                                                tmp_path):
    """A flag set XLA rejects is an error at compile time: nothing is
    served on default options in its place and nothing is written to
    the cache under either fingerprint."""
    cfg = _reduced_config("gcn")
    params = init(KEY, cfg)
    graphs = _raw_graphs(rng)
    bad = XlaFlagConfig(default={"xla_no_such_option_exists": True})
    with pytest.raises(Exception, match="xla_no_such_option_exists"):
        _serve(tmp_path, cfg, params, graphs, xla_flags=bad)
    assert AOTCache(str(tmp_path)).entries() == []


def test_model_label_distinguishes_virtual_node():
    assert model_label(_reduced_config("gin")) == "gin"
    assert model_label(_reduced_config("gin", vn=True)) == "gin_vn"
    assert flags_hash(None) == flags_hash({})
    assert flags_hash({"a": 1}) != flags_hash({"a": 2})


# -------------------------------------------------------- restart process


def test_restarted_process_serves_with_zero_traces(rng, tmp_path):
    """The whole point: process A populates the cache through the
    scheduler's ladder prewarm; process B (given only the cache dir and
    the saved params) serves bitwise-identical outputs with
    ``lowered_count == 0`` and every load a hit."""
    cfg = _reduced_config("gin")
    params = init(KEY, cfg)
    graphs = _raw_graphs(rng, k=4)
    cache_dir = tmp_path / "aot"
    eng = GNNEngine(cfg, params, buckets=((16, 32),),
                    aot_cache=AOTCache(str(cache_dir)))
    sched = StreamScheduler(eng, capacity=2, max_wait_s=0.001)
    sched.prewarm_ladders(graphs)
    rep = sched.run(graphs)
    assert eng.executor.lowered_count > 0

    blob = tmp_path / "state.pkl"
    with open(blob, "wb") as f:
        pickle.dump({
            "params": jax.tree_util.tree_map(np.asarray, params),
            "graphs": graphs,
            "outputs": [np.asarray(o) for o in rep.outputs],
        }, f)

    child = textwrap.dedent(f"""
        import pickle, sys
        import numpy as np
        from repro.gnn.models import paper_config
        from repro.serve.aot import AOTCache
        from repro.serve.gnn_engine import GNNEngine
        from repro.serve.scheduler import StreamScheduler

        state = pickle.load(open({str(blob)!r}, "rb"))
        cfg = paper_config("gin", num_layers=2, hidden=16)
        eng = GNNEngine(cfg, state["params"], buckets=((16, 32),),
                        aot_cache=AOTCache({str(cache_dir)!r}))
        sched = StreamScheduler(eng, capacity=2, max_wait_s=0.001)
        sched.prewarm_ladders(state["graphs"])
        rep = sched.run(state["graphs"])
        stats = eng.executor.aot_stats()
        assert eng.executor.lowered_count == 0, (
            "restarted process traced", eng.executor.lowered_count)
        assert stats["miss"] == 0 and stats["stale"] == 0, stats
        assert stats["hit"] > 0, stats
        for mine, theirs in zip(rep.outputs, state["outputs"]):
            np.testing.assert_array_equal(np.asarray(mine), theirs)
        print("RESTART_OK hits=%d" % stats["hit"])
    """)
    env = dict(os.environ, PYTHONPATH=str(
        Path(__file__).resolve().parent.parent / "src"))
    r = subprocess.run([sys.executable, "-c", child], capture_output=True,
                       text=True, env=env)
    assert r.returncode == 0, r.stdout + r.stderr
    assert "RESTART_OK" in r.stdout


# ------------------------------------------------- the one-buffer program


def test_a_leaf_wise_signature_misses_a_wire_entry(rng, tmp_path):
    """An entry for a program that took the flush leaf by leaf (the form
    before the one buffer), stored under that form's signature, is never
    loaded for the one-buffer program: the wire signature keys it apart,
    so the executor compiles fresh and serves the right bits."""
    from repro.gnn.models import forward_program

    cfg = _reduced_config("gcn")
    params = init(KEY, cfg)
    graphs = _raw_graphs(rng, k=1)
    want, _ = _serve(tmp_path / "fresh", cfg, params, graphs)

    cache = AOTCache(str(tmp_path / "shared"))
    ex = Executor(buckets=((16, 32),), aot_cache=cache)
    tenant = ex.register("m", cfg, params)
    p = ex.prepare_stream(graphs[0])
    graph, eigvec, layout = p.unwire()
    leaf_wise = (("eig", eigvec is not None), ("lay", layout is not None)) + \
        tuple((tuple(v.shape), str(v.dtype))
              for v in jax.tree.leaves((graph, eigvec, layout)))
    assert p.signature != leaf_wise
    fp = ex._fingerprint(ex._compiler_options(tenant, p.bucket_key))
    old = jax.jit(forward_program(cfg, num_graphs=1)).lower(
        tenant.params, graph, eigvec, layout).compile()
    old_key = (repr(tenant.program_key), p.bucket_key, p.num_graphs,
               (tenant.params_sig,) + leaf_wise)
    assert cache.store(old_key, fp, old)

    out, _ = ex.run(p, model="m")
    assert cache.stats == {"hit": 0, "miss": 1, "stale": 0}
    assert ex.lowered_count == 1
    np.testing.assert_array_equal(out[:1], want)
    assert len(cache.entries()) == 2
    assert cache.load(old_key, fp) is not None  # the old entry is intact
