"""A configuration, a traffic mix and a per-layer metric dropped in as
new files, with new entries in ``BENCHMARK.json``, run as a cell with no
edit to any file already there; and a run without a TPU measures
nothing."""

import json
import os
import shutil
import subprocess
import sys

from bench import harness, registry
from bench.tests.rehearse import rehearse

NEW_METRIC = '''"""Points the walk's spans saw, per chunk (a test metric)."""


def read(r):
    return r.counters.get("sweep.points", 0) / max(r.chunks, 1) or None
'''


def test_new_config_traffic_and_metric_are_found(tmp_path, monkeypatch):
    bench = tmp_path / "bench"
    shutil.copytree(registry.BENCH_DIR, bench,
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    before = {p: p.read_bytes() for p in bench.rglob("*") if p.is_file()}

    cfg = json.loads((bench / "configs" / "paper_cnn_27k.json").read_text())
    cfg.update(name="two_resnets", models=cfg["models"][3:5])
    (bench / "configs" / "two_resnets.json").write_text(json.dumps(cfg))
    tr = json.loads((bench / "traffic" / "walk_full.json").read_text())
    tr.update(budget={"area_mm2": 2.0})
    (bench / "traffic" / "walk_pruned.json").write_text(json.dumps(tr))
    (bench / "metrics" / "points_per_chunk.py").write_text(NEW_METRIC)

    spec = registry.load_benchmark(harness.ROOT)
    spec["configs"].append(dict(name="two_resnets", source="test",
                                file="bench/configs/two_resnets.json",
                                reduced=[], why="test"))
    spec["workloads"].append(dict(name="pruned", config="two_resnets",
                                  traffic="walk_pruned", chips=1, why="t"))
    spec["end_to_end"][0]["workloads"].append("pruned")
    spec["per_layer"].append(dict(
        name="points_per_chunk", unit="points", better="higher",
        source="program_counter", layer="chunk decode",
        moves="points_per_s", workloads=["pruned"]))
    monkeypatch.setattr(registry, "BENCH_DIR", bench)
    monkeypatch.setattr(registry, "load_benchmark", lambda root: spec)

    res, lines = rehearse(monkeypatch, "pruned", seconds=0.5, trace=True)
    assert res["correct"], lines
    assert res["metrics"]["points_per_chunk"]["value"] > 0
    after = {p: p.read_bytes() for p in before}
    assert after == before


def test_no_tpu_no_result(tmp_path):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, "-m", "bench.run", "--workload", "paper_sweep",
         "--seed", "3000000001", "--seconds", "1", "--trace", "0"],
        cwd=harness.ROOT, env=env, capture_output=True, text=True,
        timeout=300)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "no TPU" in p.stderr


def test_needs_the_program(tmp_path):
    """A checkout holding only BENCHMARK.json and the benchmark's files
    exits non-zero and prints no result."""
    shutil.copytree(registry.BENCH_DIR, tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(harness.ROOT / "BENCHMARK.json", tmp_path)
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    p = subprocess.run(
        [sys.executable, "-m", "bench.run", "--workload", "paper_sweep",
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=tmp_path, env=env, capture_output=True, text=True, timeout=300)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
