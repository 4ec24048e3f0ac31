"""CPU rehearsal of bench/run.py: every traffic mix runs for about a second
at a tiny pool and prints the result line a benchmark run ends with; a CPU run
writes no device metric; without an explicit CPU request, or without the
program beside it, the harness prints no result and exits non-zero."""
import json
import os
import shutil
import subprocess
import sys
from pathlib import Path

import pytest

BENCH = Path(__file__).resolve().parents[1]
ROOT = BENCH.parent
KEYS = ["correct", "attempted", "failed", "metrics", "device"]


def _bench_json():
    return json.loads((ROOT / "BENCHMARK.json").read_text())


def _run(args, cwd=ROOT, root=ROOT):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env["PYTHONPATH"] = os.pathsep.join([str(ROOT / "src"), env.get("PYTHONPATH", "")])
    return subprocess.run([sys.executable, str(root / "bench" / "run.py"), *args],
                          cwd=cwd, env=env, capture_output=True, text=True,
                          timeout=600)


def _cell_for(traffic):
    return next(w["name"] for w in _bench_json()["workloads"] if w["traffic"] == traffic)


@pytest.mark.parametrize("trace", [0, 1])
@pytest.mark.parametrize("traffic", ["screen", "stream", "solo"])
def test_each_traffic_mix_rehearses_on_the_cpu(traffic, trace, tmp_path):
    cell = _cell_for(traffic)
    proc = _run(["--workload", cell, "--seed", "2147483659", "--seconds", "1",
                 "--trace", str(trace), "--cpu", "--pool", "300"], cwd=tmp_path)
    assert proc.returncode == 0, proc.stderr[-3000:]
    out = json.loads(proc.stdout.strip().splitlines()[-1])
    assert list(out)[:5] == KEYS and list(out)[-1] == "checks"
    assert out["correct"] is True and out["failed"] == 0 and out["attempted"] > 0
    assert out["device"]["platform"] == "cpu"
    # a CPU run names no device number: no trace window, no breakdown, and
    # only the metrics that count work
    assert "busy_s" not in out["device"] and "window_s" not in out["device"]
    assert "breakdown" not in out
    bench = _bench_json()
    listed = bench["per_layer"] if trace else bench["end_to_end"]
    counts = {m["name"] for m in listed if m["source"] == "program_counter"
              and cell in m.get("workloads", [cell])}
    assert set(out["metrics"]) == counts
    for name, c in out["checks"].items():
        assert c["value"] <= c["limit"], name


def test_no_result_without_a_chip_or_an_explicit_cpu_request(tmp_path):
    proc = _run(["--workload", "gin.screen", "--seed", "1", "--seconds", "1",
                 "--trace", "0"], cwd=tmp_path)
    assert proc.returncode != 0
    assert proc.stdout.strip() == ""


def test_no_result_in_a_checkout_without_the_program(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path / "BENCHMARK.json")
    shutil.copytree(BENCH, tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH="")
    proc = subprocess.run(
        [sys.executable, str(tmp_path / "bench" / "run.py"), "--workload", "gin.screen",
         "--seed", "1", "--seconds", "1", "--trace", "0", "--cpu"],
        cwd=tmp_path, env=env, capture_output=True, text=True, timeout=600)
    assert proc.returncode != 0
    assert proc.stdout.strip() == ""


def test_the_compile_cache_stays_inside_the_checkout(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path / "BENCHMARK.json")
    shutil.copytree(BENCH, tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    (tmp_path / "src").symlink_to(ROOT / "src")
    elsewhere = tmp_path / "elsewhere"
    env = dict(os.environ, JAX_PLATFORMS="cpu", JAX_COMPILATION_CACHE_DIR=str(elsewhere))
    env["PYTHONPATH"] = str(ROOT / "src")
    proc = subprocess.run(
        [sys.executable, str(tmp_path / "bench" / "run.py"), "--workload", "gin.solo",
         "--seed", "5", "--seconds", "1", "--trace", "0", "--cpu", "--pool", "300"],
        cwd=tmp_path, env=env, capture_output=True, text=True, timeout=600)
    assert proc.returncode == 0, proc.stderr[-3000:]
    assert any((tmp_path / ".jax_cache").iterdir())
    assert not elsewhere.exists()


def test_every_metric_has_a_reader_and_every_cell_its_files():
    sys.path.insert(0, str(BENCH))
    import run as R

    bench = _bench_json()
    for m in bench["end_to_end"] + bench["per_layer"]:
        assert callable(R.metric_reader(m["name"]))
    for w in bench["workloads"]:
        assert (BENCH / "traffic" / f"{w['traffic']}.json").exists()
        assert (BENCH / "configs" / f"{w['config']}.json").exists()
        assert (BENCH / "configs" / f"{w['config']}.py").exists()
