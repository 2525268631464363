"""Every cell end to end on the CPU at a tiny size, its control, and the
faults the comparison has to catch.

The chip check of ``bench.run`` is bypassed here, and only here
(``rehearse`` calls ``harness.run_cell`` directly); sizes are cut by
``rehearse.tiny_config`` / ``tiny_traffic``.  The four-chip cell runs on
four virtual CPU devices (``conftest.py``)."""

import numpy as np
import pytest

from bench import harness, registry
from bench.tests.rehearse import (full_spec, rehearse, tiny_config,
                                  tiny_traffic)

CELLS = [w["name"] for w in full_spec()["workloads"]]


@pytest.mark.parametrize("cell", CELLS)
def test_cell_runs_correct(monkeypatch, cell):
    res, lines = rehearse(monkeypatch, cell, seconds=1.0)
    assert res["correct"], lines
    assert res["attempted"] >= 1 and res["failed"] == 0
    want = {m["name"] for m in registry.cell_metrics(full_spec(), cell,
                                                     "end_to_end")}
    assert set(res["metrics"]) == want
    assert all(m["value"] > 0 for m in res["metrics"].values())
    assert list(res)[-1] == "checks"


def test_traced_run_reports_per_layer_metrics(monkeypatch):
    res, lines = rehearse(monkeypatch, "paper_sweep", seconds=1.0, trace=True)
    assert res["correct"], lines
    assert {"decode_ms_per_chunk.sweep", "finish_ms_per_chunk.sweep",
            "archive_ms_per_chunk.sweep"} <= set(res["metrics"])
    # no device plane on the CPU: the device metrics stay silent
    assert "device_idle_share.sweep" not in res["metrics"]
    assert res["device"]["window_s"] > 0
    assert "breakdown" in res


def _cell(name, monkeypatch):
    import jax
    monkeypatch.setattr(registry, "config", tiny_config)
    monkeypatch.setattr(registry, "traffic", tiny_traffic)
    w = registry.workload(full_spec(), name)
    traffic = registry.traffic(w["traffic"])
    drv = registry.driver(traffic["driver"])
    cell = harness.Cell(name, registry.config(w["config"]), traffic,
                        11, jax.devices()[:w["chips"]])
    drv.setup(cell)
    return cell, drv


@pytest.mark.parametrize("cell_name", ["paper_sweep", "llm_search",
                                       "paper_query_open"])
def test_control_fails(monkeypatch, cell_name):
    """The reference in bfloat16 (device stages) and float32 (host
    columns), put in the program's place, reads far above the limits."""
    cell, drv = _cell(cell_name, monkeypatch)
    win = drv.window(cell, 1.0, None)
    assert drv.check(cell, win).correct()
    ctl = drv.control(cell, win)
    assert not ctl.correct(), ctl.values


def test_fault_answer_altered(monkeypatch):
    """Every point's throughput objective is off by 1e-3 where the
    objectives are produced."""
    from repro.core import coexplore
    real = coexplore._joint_objectives

    def altered(res, lane_acc):
        obj = real(res, lane_acc)
        obj[:, 1] *= 1.001
        return obj
    monkeypatch.setattr(coexplore, "_joint_objectives", altered)
    res, lines = rehearse(monkeypatch, "paper_sweep", seconds=0.5)
    assert not res["correct"]
    assert res["checks"]["obj_rel_err"]["value"] > \
        res["checks"]["obj_rel_err"]["limit"]


def test_fault_half_the_points_left_out(monkeypatch):
    """Each chunk folds only its first half into the front."""
    from repro.core import coexplore
    real = coexplore.fold_budget_chunk

    def half(archive, obj, idx, *a, aux=(), **kw):
        h = len(idx) // 2
        return real(archive, obj[:h], idx[:h], *a,
                    aux=tuple(x[:h] for x in aux), **kw)
    monkeypatch.setattr(coexplore, "fold_budget_chunk", half)
    res, lines = rehearse(monkeypatch, "paper_sweep", seconds=0.5)
    assert not res["correct"], lines


def test_fault_exchange_between_chips_left_out(monkeypatch):
    """The sharded walk keeps its first shard's front instead of merging
    every shard's."""
    from repro.core import shard
    monkeypatch.setattr(shard, "merge_archives",
                        lambda archives, d: archives[0])
    res, lines = rehearse(monkeypatch, "paper_wide_4chip", seconds=0.5)
    assert not res["correct"], lines
    assert res["checks"]["front_miss"]["value"] > \
        res["checks"]["front_miss"]["limit"]
