"""The trace reduction on a synthesized trace: busy union, device time
per executable, the busiest operations, idle gaps by host span."""

import pytest

from bench.tracing import Event, Trace, reduce

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
