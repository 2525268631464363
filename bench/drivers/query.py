"""Budget queries against one ``FrontServer``, offered open loop.

The schedule is fixed before the window opens.  Its arrival times and
its multiset of budgets are drawn from the mix's own ``mix_seed``, so
every run offers the same ``round(rate_per_s * seconds)`` queries at the
same times; the run's seed only deals the budgets to the arrival slots
in another order.  Arrival times are uniform over the window.  A share
``repeat_share`` of the budgets reuses one of ``repeat_budgets`` with
Zipf (``zipf_s``) popularity; the rest are fresh envelopes drawn from
``fresh`` (a uniform range per bound; a bound given as ``{"p", "range"}``
is present with probability ``p``).  The loop submits each query once it
is due, between ``step()`` calls of the server, and times it from its
due time to the step after which it is answered.  Queries still open
when the window closes are waited for up to ``drain_s``; what is never
answered is ``unanswered``, a rejected query is ``failed``.

``check_queries`` answered queries, drawn from the seed with the one
with the largest front among them, are compared with the reference's
front of the same budget over the whole joint space.
"""

from __future__ import annotations

import time

import numpy as np

from bench import check as compare
from bench.harness import Window, sub_seed
from bench.reference import joint


def _server(cell, tracer):
    from repro.serve.frontserver import FrontServer
    t = cell.traffic
    return FrontServer(cell.models, cell.space, chunk_size=cell.chunk_size,
                       layer_buckets=cell.layer_buckets,
                       cache_size=int(t["cache_size"]),
                       max_queue=int(t["max_queue"]), telemetry=tracer)


def _budget(spec):
    from repro.core import Budget
    return None if spec is None else Budget(**spec)


def schedule(traffic: dict, seconds: float, seed: int):
    """(due time in seconds from the window's start, budget spec), in due
    order."""
    rng = np.random.default_rng(int(traffic.get("mix_seed", 0)))
    n = int(round(float(traffic["rate_per_s"]) * seconds))
    due = rng.uniform(0.0, seconds, n)
    reps = traffic["repeat_budgets"]
    n_rep = int(round(float(traffic["repeat_share"]) * n))
    w = 1.0 / np.arange(1, len(reps) + 1) ** float(traffic["zipf_s"])
    picks = rng.choice(len(reps), size=n_rep, p=w / w.sum())
    budgets = [reps[i] for i in picks]
    for _ in range(n - n_rep):
        b = {}
        for field, spec in traffic["fresh"].items():
            if isinstance(spec, dict):
                if rng.random() < float(spec["p"]):
                    b[field] = float(rng.uniform(*spec["range"]))
            else:
                b[field] = float(rng.uniform(*spec))
        budgets.append(b)
    order = np.random.default_rng(seed).permutation(n)
    return list(zip(np.sort(due).tolist(), [budgets[i] for i in order]))


def setup(cell) -> None:
    _server(cell, None).query(None)
    cell.marks["warm_walk"] = time.perf_counter()


def window(cell, seconds: float, tracer) -> Window:
    from repro.serve.frontserver import DONE, EXPIRED, REJECTED
    t = cell.traffic
    server = _server(cell, tracer)
    todo = schedule(t, seconds, sub_seed(cell.seed, 1))
    open_q, answered, lags = [], [], []
    failed = 0
    nxt = 0
    t0 = time.perf_counter()
    close = t0 + seconds
    drain_until = close + float(t.get("drain_s", 60.0))
    while True:
        now = time.perf_counter()
        while nxt < len(todo) and t0 + todo[nxt][0] <= now:
            due, spec = todo[nxt]
            nxt += 1
            q = server.submit(_budget(spec))
            lags.append(time.perf_counter() - t0 - due)
            open_q.append((due, spec, q))
        busy = server.step()
        now = time.perf_counter()
        still = []
        for due, spec, q in open_q:
            if q.state == DONE:
                answered.append((due, spec, now - t0 - due, q.response))
            elif q.state in (REJECTED, EXPIRED):
                failed += 1
            else:
                still.append((due, spec, q))
        open_q = still
        if nxt == len(todo) and not open_q:
            break
        if now > drain_until:
            break
        if not busy and nxt < len(todo):
            time.sleep(max(0.0, t0 + todo[nxt][0] - time.perf_counter()))
    lat = np.asarray([a[2] for a in answered]) * 1e3
    cell.state["server"] = server
    return Window(metrics=dict(query_p95_ms=float(np.percentile(lat, 95)),
                               query_p50_ms=float(np.percentile(lat, 50))),
                  attempted=len(todo), failed=failed + len(open_q),
                  items=answered, lags_s=lags, unanswered=len(open_q))


def release(cell, win: Window) -> None:
    cell.state.clear()


def _checked(cell, win: Window):
    """Distinct budgets among the answered queries, ``check_queries`` of
    them drawn from the seed, the largest answered front among them."""
    by_key = {}
    for due, spec, _, resp in win.items:
        key = repr(sorted((spec or {}).items()))
        by_key.setdefault(key, (spec, resp))
    items = list(by_key.values())
    n = min(int(cell.traffic.get("check_queries", 8)), len(items))
    largest = max(range(len(items)), key=lambda i: len(items[i][1].archive))
    rng = np.random.default_rng(sub_seed(cell.seed, 0xC0FFEE))
    pick = rng.choice(len(items), size=n, replace=False).tolist()
    if largest not in pick:
        pick[-1] = largest
    return [items[i] for i in sorted(pick)]


def _grid(cell):
    n = joint.space_size(cell.space) * len(cell.models)
    return np.arange(n, dtype=np.int64)


def check(cell, win: Window) -> compare.Numbers:
    out = compare.Numbers()
    idx = _grid(cell)
    ref = joint.evaluate(cell.reference_models(), cell.space, idx)
    for spec, resp in _checked(cell, win):
        out.merge(compare.front_numbers(ref, spec or None,
                                      resp.archive.indices,
                                      resp.archive.objectives, idx))
    return out


def control(cell, win: Window) -> compare.Numbers:
    out = compare.Numbers()
    idx = _grid(cell)
    models = cell.reference_models()
    ref = joint.evaluate(models, cell.space, idx)
    ctl = joint.evaluate(models, cell.space, idx, *compare.CONTROL_DTYPES)
    for spec, _ in _checked(cell, win):
        keep = np.flatnonzero(joint.violation(ctl, spec) <= 0) if spec \
            else idx
        f = keep[joint.pareto_front(ctl["objectives"][keep])]
        out.merge(compare.front_numbers(ref, spec or None, idx[f],
                                      ctl["objectives"][f], idx))
    return out
