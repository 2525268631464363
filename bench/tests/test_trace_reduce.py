"""The trace reduction on a synthesized trace: busy union, device time
per executable, the busiest operations, idle gaps by host span; the
idle-gap labelling against the per-gap search it replaced, and its cost
at three times a traced paper_sweep window's counts."""

import time

import numpy as np
import pytest

from bench.tracing import Event, Trace, _label_gaps, reduce

MS = 1_000_000


def _trace():
    # window [0, 100) ms.  Device 0 runs two executables: _ppa_stage
    # [10, 20) holding op a [10, 15) and op b [14, 20) (overlapping), and
    # _network_sums [40, 70) holding op c [40, 70).  Device 1 runs one
    # _network_sums [0, 10) with op c.  Host spans: decode [0, 12),
    # archive [20, 40), device_wait [70, 100) holding a short
    # nested archive [80, 90).
    mods = {0: [Event("jit__ppa_stage(7)", 10 * MS, 20 * MS),
                Event("jit__network_sums(3)", 40 * MS, 70 * MS)],
            1: [Event("jit__network_sums(3)", 0, 10 * MS)]}
    ops = {0: [Event("a", 10 * MS, 15 * MS), Event("b", 14 * MS, 20 * MS),
               Event("c", 40 * MS, 70 * MS)],
           1: [Event("c", 0, 10 * MS)]}
    host = [Event("sweep.decode", 0, 12 * MS),
            Event("sweep.archive", 20 * MS, 40 * MS),
            Event("sweep.device_wait", 70 * MS, 100 * MS),
            Event("sweep.archive", 80 * MS, 90 * MS)]
    return Trace(ops=ops, modules=mods, host=host, window=(0, 100 * MS))


def test_busy_union_per_device():
    r = reduce(_trace(), ("_ppa_stage", "_network_sums"))
    assert r["window_s"] == pytest.approx(0.1)
    # device 0: [10, 20) as one interval (a and b overlap) + [40, 70)
    assert r["busy_s"][0] == pytest.approx(0.040)
    assert r["busy_s"][1] == pytest.approx(0.010)


def test_time_per_executable_sums_devices():
    r = reduce(_trace(), ("_ppa_stage", "_network_sums", "_lane_layers"))
    assert r["exec_s"]["_ppa_stage"] == pytest.approx(0.010)
    assert r["exec_s"]["_network_sums"] == pytest.approx(0.040)
    assert r["exec_s"]["_lane_layers"] == 0.0


def test_ops_named_by_their_executable():
    ops = dict(reduce(_trace(), ())["device_ops"])
    assert ops["jit__network_sums(3)/c"] == pytest.approx(0.040)
    assert ops["jit__ppa_stage(7)/a"] == pytest.approx(0.005)
    assert ops["jit__ppa_stage(7)/b"] == pytest.approx(0.006)


def test_idle_gaps_go_to_the_host_span_covering_them():
    gaps = dict(reduce(_trace(), ())["idle_gaps"])
    # device-0 gaps: [0, 10) under decode, [20, 40) under archive,
    # [70, 100) under device_wait (the nested archive covers only a third)
    assert gaps == pytest.approx({"sweep.decode": 0.010,
                                  "sweep.archive": 0.020,
                                  "sweep.device_wait": 0.030})


def test_events_outside_the_window_are_cut():
    t = _trace()._replace(window=(15 * MS, 50 * MS))
    r = reduce(t, ("_ppa_stage",))
    assert r["busy_s"][0] == pytest.approx(0.015)   # [15, 20) + [40, 50)
    assert r["exec_s"]["_ppa_stage"] == pytest.approx(0.005)
    assert sum(v for _, v in r["idle_gaps"]) == pytest.approx(0.020)


# -- idle-gap labelling ------------------------------------------------------

def _label_gaps_per_gap(host: list, gaps: np.ndarray) -> dict:
    """The labelling as it was before the sweep, kept verbatim as the
    oracle: for every gap, the cover of every span and one sort."""
    names = [e.name for e in host]
    starts = np.asarray([e.start_ns for e in host], np.int64)
    ends = np.asarray([e.end_ns for e in host], np.int64)
    out: dict[str, float] = {}
    for g0, g1 in gaps:
        label = "no host span"
        if len(host):
            cover = np.minimum(ends, g1) - np.maximum(starts, g0)
            if cover.max() > 0:
                k = np.lexsort((ends - starts, -cover))[0]
                label = names[k]
        out[label] = out.get(label, 0.0) + (g1 - g0) / 1e9
    return out


def _nested(rng, lo, hi, depth, prefix, out):
    """Spans nested inside [lo, hi): a run of siblings with gaps between
    them (some touching), each holding children down to ``depth``."""
    t = lo
    while t < hi - 1:
        s = min(hi - 1, t + int(rng.integers(0, 3)))
        e = min(hi, s + int(rng.integers(1, max(2, (hi - lo) // 2))))
        out.append(Event(f"{prefix}.d{depth}.{int(rng.integers(3))}", s, e))
        if depth > 1 and e - s > 2:
            _nested(rng, s, e, depth - 1, prefix, out)
        t = e


def _random_case(seed):
    """A few hundred spans and gaps on a coarse clock, so that equal
    covers, equal durations and touching edges are common: two threads of
    nested spans, loose spans overlapping them, instants (spans of no
    length), duplicates listed under other names, and spans ending
    exactly at a gap's start or starting exactly at its end.  Gaps are
    sorted and disjoint as ``reduce`` builds them; some lie past every
    span."""
    rng = np.random.default_rng(seed)
    host = []
    for thread in ("t0", "t1"):
        _nested(rng, 0, 1000, 3, thread, host)
    for _ in range(40):
        s = int(rng.integers(0, 1000))
        host.append(Event(f"loose.{int(rng.integers(3))}", s,
                          s + int(rng.integers(1, 60))))
    for t in rng.integers(0, 1200, 20).tolist():
        host.append(Event("instant", t, t))
    for k in rng.choice(len(host), 20, replace=False):
        host.append(host[k]._replace(name=f"dup.{k}"))
    cuts = np.sort(rng.choice(1200, 400, replace=False))
    gaps = cuts.reshape(-1, 2).astype(np.int64)
    for g0, g1 in gaps[rng.choice(len(gaps), 30, replace=False)]:
        host.append(Event("edge.before", int(g0) - 5, int(g0)))
        host.append(Event("edge.after", int(g1), int(g1) + 5))
    order = rng.permutation(len(host))
    return [host[k] for k in order], gaps


def _walk_trace(chunks, seed):
    """A walk's trace as the program nests it, per chunk of 22.67 ms: ten
    spans (decode holding the upload, dispatch, the wait holding the
    fetch, objectives, bests, the archive holding its prefilter, the
    PE-code copy) and 157 idle gaps between device operations, with a
    walk set-up span every 60 chunks."""
    rng = np.random.default_rng(seed)
    period = 22_670_000
    template = [("sweep.decode", 0.0, 0.2), ("copy.upload", 0.05, 0.18),
                ("sweep.dispatch", 0.2, 0.37),
                ("sweep.device_wait", 0.37, 0.58), ("copy.fetch", 0.4, 0.55),
                ("sweep.objectives", 0.58, 0.6), ("sweep.best", 0.6, 0.62),
                ("sweep.archive", 0.62, 0.98),
                ("archive.prefilter", 0.63, 0.9), ("copy.codes", 0.98, 0.995)]
    base = np.arange(chunks, dtype=np.int64) * period
    jitter = rng.integers(0, period // 200, (chunks, len(template), 2))
    host = []
    for j, (name, a, b) in enumerate(template):
        s = base + int(a * period) + jitter[:, j, 0]
        e = base + int(b * period) - jitter[:, j, 1]
        host += [Event(name, int(x), int(y)) for x, y in zip(s, e)]
    host += [Event("sweep.walk_setup", int(b) - period // 4, int(b))
             for b in base[::60]]
    cuts = np.sort(rng.integers(0, period, (chunks, 314)), axis=1)
    gaps = (cuts + base[:, None]).reshape(-1, 2)
    return host, gaps[gaps[:, 1] > gaps[:, 0]]


def _tie_case(reverse):
    # equal cover and equal duration (identical spans, and two shifted
    # ones); then equal cover with different durations
    host = [Event("same.a", 0, 10), Event("same.b", 0, 10),
            Event("shift.a", 20, 30), Event("shift.b", 22, 32),
            Event("long", 40, 60), Event("short", 42, 50)]
    if reverse:
        host = host[::-1]
    return host, np.asarray([[2, 5], [23, 27], [44, 48]], np.int64)


def _unsorted_case():
    host, gaps = _random_case(11)
    return host, gaps[np.random.default_rng(1).permutation(len(gaps))]


def _overlapping_case():
    # gaps that overlap, nest, or have no length, in no order
    gaps = np.random.default_rng(2).integers(-50, 1250, (300, 2))
    return _random_case(12)[0], np.sort(gaps, axis=1)


EMPTY = np.zeros((0, 2), np.int64)

CASES = {
    **{f"random{s}": (lambda s=s: _random_case(s)) for s in range(8)},
    "random_gaps_unsorted": _unsorted_case,
    "random_gaps_overlapping": _overlapping_case,
    "walk_nested": lambda: _walk_trace(12, 3),
    "ties": lambda: _tie_case(False),
    "ties_reversed": lambda: _tie_case(True),
    "no_host": lambda: ([], _random_case(13)[1]),
    "no_gaps": lambda: (_random_case(14)[0], EMPTY),
    "nothing": lambda: ([], EMPTY),
}


@pytest.mark.parametrize("case", list(CASES))
def test_label_gaps_matches_the_per_gap_search(case):
    host, gaps = CASES[case]()
    want = _label_gaps_per_gap(host, gaps)
    got = _label_gaps(host, gaps)
    assert got == want
    assert list(got.items()) == list(want.items())   # same order of labels


def test_label_gaps_breaks_ties_by_duration_then_position():
    assert _label_gaps(*_tie_case(False)) == {
        "same.a": 3e-9, "shift.a": 4e-9, "short": 4e-9}
    assert _label_gaps(*_tie_case(True)) == {
        "short": 4e-9, "shift.b": 4e-9, "same.b": 3e-9}


def test_label_gaps_at_three_times_a_traced_walk():
    # paper_sweep's traced 40 s window: 277,321 gaps and 17,748 host
    # events; three times that here.  The per-gap search took ~2 ms a
    # gap at the real size, over half an hour at this one.
    host, gaps = _walk_trace(5300, 0)
    assert len(gaps) > 800_000 and len(host) > 53_000
    t0 = time.perf_counter()
    out = _label_gaps(host, gaps)
    assert time.perf_counter() - t0 < 30
    assert sum(out.values()) == pytest.approx(
        (gaps[:, 1] - gaps[:, 0]).sum() / 1e9)
    assert {"sweep.archive", "archive.prefilter", "copy.upload"} <= set(out)
