"""Budgeted searches run back to back: ``search_front`` with a search
driver, one search after another, each from its own seed.

Traffic keys: ``driver_args`` (the ``EvolutionaryDriver``'s constructor
arguments), ``max_evals`` (full evaluations per search),
``check_searches`` (searches compared with the reference).  The driver
is the program's own, subclassed only to keep what its ``observe`` hook
is handed: the indices and objectives of every point the search
evaluated.

``points_per_s`` is every full evaluation of every search over the time
from the window's start to the end of the first search that ends after
``seconds``.
"""

from __future__ import annotations

import time

import numpy as np

from bench import check as compare
from bench.harness import Window, sub_seed
from bench.reference import joint

WARM = 0xFFFF


def _driver(cell):
    from repro.core.search import EvolutionaryDriver

    class Recording(EvolutionaryDriver):
        def reset(self, ctx):
            super().reset(ctx)
            self.seen = []

        def observe(self, idx, obj, feasible):
            self.seen.append((np.array(idx, np.int64),
                              np.array(obj, np.float64)))
            super().observe(idx, obj, feasible)

    return Recording(**cell.traffic.get("driver_args", {}))


def _search(cell, seed: int, tracer):
    from repro.core.search import search_front
    drv = _driver(cell)
    front = search_front(cell.models, space=cell.space, driver=drv,
                         max_evals=int(cell.traffic["max_evals"]), seed=seed,
                         chunk_size=cell.chunk_size,
                         layer_buckets=cell.layer_buckets, telemetry=tracer)
    return seed, drv.seen, front


def setup(cell) -> None:
    _search(cell, sub_seed(cell.seed, WARM), None)
    cell.marks["warm_search"] = time.perf_counter()


def window(cell, seconds: float, tracer) -> Window:
    searches = []
    t0 = time.perf_counter()
    while True:
        searches.append(_search(cell, sub_seed(cell.seed, len(searches)),
                                tracer))
        elapsed = time.perf_counter() - t0
        if elapsed >= seconds:
            break
    evals = sum(f.points_evaluated for _, _, f in searches)
    return Window(metrics=dict(points_per_s=evals / elapsed),
                  attempted=len(searches), failed=0, items=searches)


def release(cell, win: Window) -> None:
    cell.state.clear()


def _checked(cell, win: Window):
    n = min(int(cell.traffic.get("check_searches", 1)), len(win.items))
    rng = np.random.default_rng(sub_seed(cell.seed, 0xC0FFEE))
    return [win.items[i] for i in
            sorted(rng.choice(len(win.items), size=n, replace=False))]


def _observed(seen):
    idx = np.concatenate([i for i, _ in seen])
    obj = np.concatenate([o for _, o in seen])
    order = np.argsort(idx, kind="stable")
    return idx[order], obj[order]


def check(cell, win: Window) -> compare.Numbers:
    out = compare.Numbers()
    want = int(cell.traffic["max_evals"])
    for _, seen, front in _checked(cell, win):
        idx, obj = _observed(seen)
        uniq = np.unique(idx)
        out.add("count_gap", (len(idx) - len(uniq)) + abs(len(idx) - want)
                + abs(front.points_evaluated - want))
        ref = joint.evaluate(cell.reference_models(), cell.space, uniq)
        rows = np.searchsorted(uniq, idx)
        out.add("obj_rel_err", joint.rel_err(obj, ref["objectives"][rows]))
        out.merge(_front(compare.front_numbers(
            ref, None, front.archive.indices, front.archive.objectives,
            uniq)))
        out.merge(compare.best_numbers(
            ref, cell.best_by_index(front.per_model_best)))
    return out


def _front(n: compare.Numbers) -> compare.Numbers:
    """A search's front numbers without ``front_excess``: over the
    points one search evaluates, the control's front is dominated by no
    reference point either (it reads 0 on every seed), so that number
    has no upper reading here and is not compared."""
    n.values.pop("front_excess", None)
    return n


def control(cell, win: Window) -> compare.Numbers:
    out = compare.Numbers()
    models = cell.reference_models()
    for _, seen, _ in _checked(cell, win):
        idx = np.unique(_observed(seen)[0])
        ref = joint.evaluate(models, cell.space, idx)
        ctl, cidx, cobj = compare.control_front(models, cell.space, idx, None)
        out.add("obj_rel_err", joint.rel_err(ctl["objectives"],
                                             ref["objectives"]))
        out.merge(_front(compare.front_numbers(ref, None, cidx, cobj, idx)))
        out.merge(compare.best_numbers(ref, joint.per_model_best(ctl)))
    return out
