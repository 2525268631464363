"""The ``mla_walk`` cell's configuration, ``dsv3_mla_decode_mapped``:
DeepSeek-V3 decode steps with absorbed multi-head latent attention on
the mapped grid.  The program's layer tables against the reference
family's, row for row at the published widths; a walk over a seeded
subsample of the full (uncut) joint space against the reference within
``limits.json``; and the control rejected at a test size."""

import numpy as np
import pytest

from bench import check as compare, harness, registry
from bench.reference import joint
from bench.reference.costmodel import LAYER_FIELDS
from bench.reference.families import build
from bench.tests.test_cells import _cell

CONFIG = "dsv3_mla_decode_mapped"


def test_widths_are_the_published_config():
    """Each model's ``widths`` are the configuration file's copy of the
    published config.json, key for key."""
    cfg = registry.config(CONFIG)
    for m in cfg["models"]:
        for k, v in m["args"]["widths"].items():
            assert cfg[k] == v, k


def test_tables_match_reference_row_for_row():
    """The program's decode tables (``llm_decode``) and the reference
    family's (``mla_decode``) at the published widths: every row and
    field equal, the program's float32 fields to the reference's values
    rounded to float32."""
    from repro.core import workloads
    cfg = registry.config(CONFIG)
    for m in cfg["models"]:
        wl = getattr(workloads, m["program"]["fn"])(**m["program"]["args"])
        ref = build(m["family"], m["args"])
        assert wl.name == ref["name"]
        assert len(wl.layer_names) == len(ref["acc_class"]) == 16
        for f in LAYER_FIELDS:
            np.testing.assert_array_equal(
                np.asarray(getattr(wl.layers, f)),
                np.asarray(ref["layers"][f], np.float32), err_msg=f)
        np.testing.assert_array_equal(
            np.asarray(wl.layers.acc_class), np.asarray(ref["acc_class"],
                                                        np.float32))


@pytest.mark.parametrize("seed", [3_000_000_019])
def test_full_space_subsample_matches_reference(monkeypatch, seed):
    """A walk over 8,192 seeded points of the whole 9,720,000-point space
    (the 131,072-position rows included): every point's objectives, the
    front and the per-(model, PE type) bests within ``limits.json``."""
    from repro.core import coexplore
    cfg = registry.config(CONFIG)
    models = harness.program_models(cfg)
    space = {k: tuple(v) for k, v in cfg["space"].items()}
    seen = []
    real = coexplore.fold_budget_chunk

    def spy(archive, obj, idx, *a, **kw):
        seen.append((np.array(idx), np.array(obj)))
        return real(archive, obj, idx, *a, **kw)
    monkeypatch.setattr(coexplore, "fold_budget_chunk", spy)
    front = coexplore.coexplore_front(
        models, space=space, chunk_size=int(cfg["chunk_size"]),
        layer_buckets=tuple(cfg["layer_buckets"]), max_points=8192,
        seed=seed)
    idx = joint.subsample(joint.space_size(space) * len(models), 8192, seed)
    ref = joint.evaluate([joint.Model(m) for m in cfg["models"]], space, idx)

    got_idx = np.concatenate([i for i, _ in seen])
    got_obj = np.concatenate([o for _, o in seen])
    order = np.argsort(got_idx)
    np.testing.assert_array_equal(got_idx[order], idx)
    assert len(np.unique(idx // joint.space_size(space))) == 3
    numbers = compare.Numbers()
    numbers.add("obj_rel_err", joint.rel_err(got_obj[order],
                                             ref["objectives"]))
    numbers.merge(compare.front_numbers(ref, None, front.archive.indices,
                                        front.archive.objectives, idx))
    cell = harness.Cell("mla_walk", cfg, {}, seed, [])
    numbers.merge(compare.best_numbers(
        ref, cell.best_by_index(front.per_model_best)))
    assert numbers.correct(), numbers.lines()


def test_control_fails(monkeypatch):
    """The reference in bfloat16 (device stages) and float32 (host
    columns), put in the program's place, reads far above the limits."""
    cell, drv = _cell("mla_walk", monkeypatch)
    win = drv.window(cell, 1.0, None)
    assert drv.check(cell, win).correct()
    ctl = drv.control(cell, win)
    assert not ctl.correct(), ctl.values
