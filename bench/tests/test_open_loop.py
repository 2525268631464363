"""The open-loop load generator: a fixed schedule per seed, and each
query timed from its due time, not from when it was submitted."""

import time

import numpy as np
import pytest

from bench import registry
from bench.drivers import query
from bench.harness import Cell


def test_schedule_same_arrivals_and_budgets_in_another_order():
    t = registry.traffic("query_open")
    a = query.schedule(t, 30.0, 1)
    b = query.schedule(t, 30.0, 2)
    n = round(t["rate_per_s"] * 30.0)
    assert len(a) == len(b) == n
    for s in (a, b):
        due = [d for d, _ in s]
        assert due == sorted(due) and 0.0 <= due[0] and due[-1] < 30.0
        reps = sum(1 for _, spec in s if spec in t["repeat_budgets"])
        assert reps >= round(t["repeat_share"] * n)
        for _, spec in s:
            if spec is not None and spec not in t["repeat_budgets"]:
                lo, hi = t["fresh"]["area_mm2"]
                assert lo <= spec["area_mm2"] <= hi
    assert [d for d, _ in a] == [d for d, _ in b]
    key = lambda s: sorted(repr(sorted((x or {}).items())) for _, x in s)
    assert key(a) == key(b)
    assert a != b
    assert query.schedule(t, 30.0, 1) == a


class _SlowServer:
    """Answers each query two steps after it is submitted; every step
    takes ``step_s``, so a query due while a step runs is submitted
    late."""

    def __init__(self, step_s):
        self.step_s, self.open = step_s, []

    def submit(self, budget):
        from repro.serve.frontserver import QUEUED

        class Q:
            state, response, age = QUEUED, None, 0
        q = Q()
        self.open.append(q)
        return q

    def step(self):
        from repro.serve.frontserver import DONE
        time.sleep(self.step_s)
        for q in self.open:
            q.age += 1
            if q.age >= 2:
                q.state = DONE
        self.open = [q for q in self.open if q.state != DONE]
        return bool(self.open)


def test_latency_counts_from_due_time(monkeypatch):
    step_s = 0.05
    traffic = dict(registry.traffic("query_open"), rate_per_s=40.0)
    cell = Cell.__new__(Cell)
    cell.traffic, cell.seed, cell.state = traffic, 7, {}
    monkeypatch.setattr(query, "_server", lambda c, tr: _SlowServer(step_s))
    win = query.window(cell, 1.0, None)
    assert win.attempted == 40 and win.unanswered == 0 and win.failed == 0
    lat = np.asarray([a[2] for a in win.items])
    lags = np.asarray(win.lags_s)
    # from due: at least the two steps after submission plus the lateness
    # of the submission itself
    assert lat.min() >= 2 * step_s
    assert lags.max() > 0.5 * step_s
    assert np.percentile(lat, 95) * 1e3 == pytest.approx(
        win.metrics["query_p95_ms"])
    assert np.mean(lat) >= 2 * step_s + np.mean(lags) - 1e-3
