"""Finds a cell's pieces by the names ``BENCHMARK.json`` gives.

A configuration is ``configs/<name>.json``, a traffic mix
``traffic/<name>.json``, a per-layer metric's reader
``metrics/<name>.py`` and a traffic driver ``drivers/<driver>.py``: a
later cell or metric is new files plus new entries, with no edit to a
file that is already here."""

from __future__ import annotations

import importlib
import importlib.util
import json
from pathlib import Path

BENCH_DIR = Path(__file__).resolve().parent


def load_benchmark(root: Path) -> dict:
    with open(root / "BENCHMARK.json") as f:
        return json.load(f)


def workload(spec: dict, name: str) -> dict:
    for w in spec["workloads"]:
        if w["name"] == name:
            return w
    raise KeyError(f"no workload {name!r} in BENCHMARK.json; known: "
                   f"{[w['name'] for w in spec['workloads']]}")


def _json(kind: str, name: str, base: Path | None) -> dict:
    path = (base or BENCH_DIR) / kind / f"{name}.json"
    if not path.is_file():
        raise FileNotFoundError(f"no {kind[:-1]} file {path}")
    with open(path) as f:
        return json.load(f)


def config(name: str, base: Path | None = None) -> dict:
    return _json("configs", name, base)


def traffic(name: str, base: Path | None = None) -> dict:
    return _json("traffic", name, base)


def driver(name: str):
    """The module that generates a traffic mix's load (``drivers/``)."""
    return importlib.import_module(f"bench.drivers.{name}")


def metric_reader(name: str, base: Path | None = None):
    """``read(readings) -> float | None`` of a per-layer metric, from
    ``metrics/<name>.py`` (names may hold dots, so load by path)."""
    path = (base or BENCH_DIR) / "metrics" / f"{name}.py"
    if not path.is_file():
        raise FileNotFoundError(f"no reader for per-layer metric {name!r} "
                                f"at {path}")
    spec = importlib.util.spec_from_file_location(
        "bench_metric_" + name.replace(".", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def cell_metrics(spec: dict, cell: str, kind: str) -> list[dict]:
    """The ``end_to_end`` or ``per_layer`` metrics a cell reports: those
    listing it under ``workloads``; a metric without the key goes to
    every cell that reports the end-to-end metric it moves."""
    e2e = [m for m in spec["end_to_end"]
           if "workloads" not in m or cell in m["workloads"]]
    if kind == "end_to_end":
        return e2e
    names = {m["name"] for m in e2e}
    return [m for m in spec["per_layer"]
            if cell in m.get("workloads", ())
            or ("workloads" not in m and m["moves"] in names)]
