"""Runs a cell end to end on the CPU at a tiny size: the configuration's
grid cut to a few values per axis, small chunks, short windows.  The
cells are those of ``BENCHMARK.json`` and those of
``pending_cells.json``.  The chip check of ``bench.run`` is bypassed
here, and only here, by calling ``harness.run_cell`` directly."""

from __future__ import annotations

import copy
import json
import time
from pathlib import Path

from bench import registry

TINY_SPACE_VALUES = 2      # values kept per grid axis (pe_type keeps all)


_config, _traffic = registry.config, registry.traffic


def tiny_config(name: str) -> dict:
    c = copy.deepcopy(_config(name))
    c["space"] = {k: (v if k == "pe_type" else v[:TINY_SPACE_VALUES])
                  for k, v in c["space"].items()}
    c["chunk_size"] = 64
    return c


def tiny_traffic(name: str) -> dict:
    t = copy.deepcopy(_traffic(name))
    if "points_per_walk" in t:
        # the whole tiny space, or 16 chunks of a one-bucket subsample
        t["points_per_walk"] = 1024 if t["points_per_walk"] < 243_000 \
            else t["points_per_walk"]
    if "max_evals" in t:
        t["max_evals"] = 256
        t["driver_args"] = dict(t.get("driver_args", {}), population=64)
    if "rate_per_s" in t:
        t["rate_per_s"] = 20.0
    return t


def full_spec() -> dict:
    """``BENCHMARK.json`` plus the entries of the cells whose files are
    here but which are not yet proven on the chip
    (``pending_cells.json``)."""
    from bench import harness
    spec = registry.load_benchmark(harness.ROOT)
    pend = json.loads((Path(__file__).parent / "pending_cells.json")
                      .read_text())
    spec["workloads"] += pend["workloads"]
    spec["end_to_end"] += pend["end_to_end"]
    spec["per_layer"] += pend["per_layer"]
    for cell, names in pend["also_report"].items():
        for m in spec["end_to_end"]:
            if m["name"] in names:
                m["workloads"].append(cell)
    return spec


def rehearse(monkeypatch, cell: str, seconds: float = 1.0, trace=False,
             seed: int = 3_000_000_001, devices=None):
    import jax
    from bench import harness
    monkeypatch.setattr(registry, "config", tiny_config)
    monkeypatch.setattr(registry, "traffic", tiny_traffic)
    spec = full_spec()
    chips = registry.workload(spec, cell)["chips"]
    devs = devices if devices is not None else jax.devices()[:chips]
    return harness.run_cell(spec, cell, seed, seconds, trace, devs,
                            time.perf_counter(), compile_cache=False)
