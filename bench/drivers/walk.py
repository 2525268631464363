"""Walks run back to back: ``coexplore_front`` over the whole joint
space or a seeded uniform subsample of it, one pass after another.

Traffic keys: ``points_per_walk`` (the subsample; the whole joint space
when it is that large), ``budget`` (optional deployment bounds, a
``Budget``'s fields; the program prunes as it does by default).  The
last pass of the window is compared with the reference.  On more than
one chip the walk is sharded over them.

Set-up runs one pass, which compiles every stage at the chunk shape.  A
mix keeps to passes whose chunk lengths repeat: the whole space, or a
subsample over one layer bucket (a multiple of the chunk size).  A
subsample over several buckets ends each bucket's group on a chunk of
a random length, and the program compiles the decode of each new
length (about 1 s on a v5e) inside the window.

``points_per_s`` is every point of every pass over the time from the
window's start to the end of the first pass that ends after
``seconds``.
"""

from __future__ import annotations

import time

import numpy as np

from bench import check as compare
from bench.harness import Window, sub_seed
from bench.reference import joint

WARM = 0xFFFF


def _kwargs(cell) -> dict:
    from repro.core import Budget, joint_space_size
    t = cell.traffic
    n = joint_space_size(cell.space, len(cell.models))
    kw = dict(space=cell.space, chunk_size=cell.chunk_size,
              layer_buckets=cell.layer_buckets,
              max_points=None if t["points_per_walk"] >= n
              else int(t["points_per_walk"]))
    if t.get("budget"):
        kw.update(budget=Budget(**t["budget"]))
    if len(cell.devices) > 1:
        kw.update(shards=len(cell.devices), devices=cell.devices)
    return kw


def setup(cell) -> None:
    from repro.core import coexplore_front
    cell.state["kw"] = _kwargs(cell)
    coexplore_front(cell.models, seed=sub_seed(cell.seed, WARM),
                    **cell.state["kw"])
    cell.marks["warm_pass"] = time.perf_counter()


def window(cell, seconds: float, tracer) -> Window:
    from repro.core import coexplore_front
    kw = cell.state["kw"]
    walks = []
    t0 = time.perf_counter()
    while True:
        s = sub_seed(cell.seed, len(walks))
        walks.append((s, coexplore_front(cell.models, seed=s,
                                         telemetry=tracer, **kw)))
        elapsed = time.perf_counter() - t0
        if elapsed >= seconds:
            break
    points = sum(f.points_evaluated for _, f in walks)
    return Window(metrics=dict(points_per_s=points / elapsed),
                  attempted=len(walks), failed=0, items=walks)


def release(cell, win: Window) -> None:
    cell.state.clear()


def _points(cell, s: int) -> np.ndarray:
    n = joint.space_size(cell.space) * len(cell.models)
    return joint.subsample(n, int(cell.traffic["points_per_walk"]), s)


def check(cell, win: Window) -> compare.Numbers:
    out = compare.Numbers()
    budget = cell.traffic.get("budget")
    models = cell.reference_models()
    s, front = win.items[-1]
    idx = _points(cell, s)
    ref = joint.evaluate(models, cell.space, idx)
    out.add("count_gap", abs(front.points_evaluated - len(idx)))
    out.merge(compare.front_numbers(ref, budget, front.archive.indices,
                                  front.archive.objectives, idx))
    if not budget:
        out.merge(compare.best_numbers(
            ref, cell.best_by_index(front.per_model_best)))
    return out


def control(cell, win: Window) -> compare.Numbers:
    out = compare.Numbers()
    budget = cell.traffic.get("budget")
    models = cell.reference_models()
    idx = _points(cell, win.items[-1][0])
    ref = joint.evaluate(models, cell.space, idx)
    ctl, cidx, cobj = compare.control_front(models, cell.space, idx, budget)
    out.merge(compare.front_numbers(ref, budget, cidx, cobj, idx))
    if not budget:
        out.merge(compare.best_numbers(ref, joint.per_model_best(ctl)))
    return out
