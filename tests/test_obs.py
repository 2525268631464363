"""Sweep telemetry (repro.obs): tracer/registry/exporter units plus the
instrumentation contract — fronts bit-identical with telemetry on or off
(all three walks, sharded and unsharded, both cost-model backends),
near-zero disabled cost, one Chrome-trace lane per shard, checkpoint and
serving events, and the registry-derived benchmark helpers."""

import copy
import glob
import json
import math
import os
import sys
import threading
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

try:
    from hypothesis import given, settings, strategies as st
except ImportError:  # CI images without hypothesis: deterministic fallback
    from _hypothesis_fallback import given, settings, st

import repro.obs.tracer as tracer_mod
from repro.checkpoint import manager
from repro.core import (Budget, ParetoArchive, coexplore_front,
                        dispatch_chunk, enumerate_space,
                        evaluate_space_streaming, finish_chunk,
                        fit_ppa_models, model_entry, pareto_front_streaming,
                        resnet_cifar, space_points, space_size,
                        transformer_gemm)
from repro.core.coexplore import plan_joint_walk
from repro.core.dse import _dominated_by
from repro.core.search import EvolutionaryDriver, search_front
from repro.obs import (MAX_SAMPLES, TOP_LEVEL_COUNTER, Histogram,
                       MetricsRegistry, NULL_TRACER, NullTracer, Tracer,
                       as_tracer, build_sweep_report, chrome_trace,
                       load_sweep_report, rss_mb, timed_iter, trace_lanes,
                       write_chrome_trace, write_sweep_report)

TINY_SPACE = dict(
    pe_rows=(8, 12), pe_cols=(8, 14), gbuf_kb=(54.0,), spad_ifmap=(12,),
    spad_filter=(112, 224), spad_psum=(16,),
    pe_type=tuple(range(5)), bandwidth_gbps=(25.6,),
)
CHUNK = 16
METRICS = ("perf_per_area", "neg_energy_j")
BUDGET = Budget(area_mm2=60.0, power_mw=1e5)


@pytest.fixture(scope="module")
def workload():
    return resnet_cifar(20)


@pytest.fixture(scope="module")
def tiny_models():
    return (model_entry(resnet_cifar(20)),
            model_entry(transformer_gemm(seq=128, d_model=128, n_layers=2,
                                         n_heads=4, d_ff=256, vocab=1024)))


@pytest.fixture(scope="module")
def ppa_models():
    return fit_ppa_models(enumerate_space(max_points=500, seed=1),
                          degrees=(1, 2), k=4)


def _assert_archives_equal(a, b):
    np.testing.assert_array_equal(np.sort(a.indices), np.sort(b.indices))
    oa, ob = np.argsort(a.indices), np.argsort(b.indices)
    np.testing.assert_array_equal(np.asarray(a.objectives)[oa],
                                  np.asarray(b.objectives)[ob])


# ---------------------------------------------------------------------------
# metric primitives
# ---------------------------------------------------------------------------

class TestPrimitives:

    def test_histogram_exact_stats_and_quantiles(self):
        h = Histogram()
        for v in range(1000):
            h.observe(float(v))
        assert h.count == 1000
        assert h.total == sum(range(1000))
        assert (h.min, h.max, h.last) == (0.0, 999.0, 999.0)
        assert abs(h.quantile(0.5) - 499.5) < 5
        assert h.quantile(0.99) > h.quantile(0.90) > h.quantile(0.50)
        s = h.summary()
        assert s["count"] == 1000 and "p50" in s and "p99" in s
        assert Histogram().summary() == dict(count=0)

    def test_histogram_decimation_keeps_exact_aggregates(self):
        h = Histogram()
        n = MAX_SAMPLES * 2 + 17
        for v in range(n):
            h.observe(v)
        assert h.count == n                    # exact despite decimation
        assert h.total == sum(range(n))
        assert (h.min, h.max) == (0, n - 1)
        assert len(h._values) < MAX_SAMPLES    # buffer stays bounded
        assert abs(h.quantile(0.5) / (n / 2) - 1) < 0.05

    def test_gauge_growth_marks(self):
        reg = MetricsRegistry()
        g = reg.gauge("rss_mb")
        for v in (100, 120, 110):
            g.set(v)
        mark = len(g.series)
        for v in (110, 140, 150):
            g.set(v)
        assert g.growth() == 50
        assert g.growth(since_sample=mark) == 40   # phase slice only
        assert g.growth(since_sample=len(g.series)) == 0.0
        assert (g.first, g.last, g.min, g.max) == (100, 150, 100, 150)

    def test_counter_value_and_series(self):
        reg = MetricsRegistry()
        c = reg.counter("pts")
        for _ in range(10):
            c.inc(16)
        assert c.value == 160
        assert sum(n for _, n in c.series) == 160
        ts = [t for t, _ in c.series]
        assert ts == sorted(ts)

    def test_registry_thread_safety(self):
        reg = MetricsRegistry()

        def hammer():
            for _ in range(5000):
                reg.counter("c").inc()
                reg.histogram("h").observe(1.0)
                reg.gauge("g").set(1.0)

        threads = [threading.Thread(target=hammer) for _ in range(8)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        assert reg.counter("c").value == 40000
        assert reg.histogram("h").count == 40000
        d = reg.as_dict()
        assert set(d) == {"counters", "gauges", "histograms"}


# ---------------------------------------------------------------------------
# tracer
# ---------------------------------------------------------------------------

class TestTracer:

    def test_null_tracer_contract(self):
        assert as_tracer(None) is NULL_TRACER
        assert not NULL_TRACER.enabled
        tr = Tracer(record_events=False)
        assert as_tracer(tr) is tr
        assert isinstance(as_tracer(NULL_TRACER), NullTracer)
        with pytest.raises(TypeError):
            as_tracer(object())
        # every method is a no-op that doesn't blow up
        with NULL_TRACER.span("x", track="shard0", foo=1):
            pass
        NULL_TRACER.instant("i", level="warning")
        NULL_TRACER.complete("c", 0, 10)
        NULL_TRACER.counter("c")
        NULL_TRACER.gauge("g", 1.0)
        NULL_TRACER.observe("h", 1.0)
        NULL_TRACER.sample_rss()
        NULL_TRACER.close()

    def test_span_feeds_histogram_and_events(self, tmp_path):
        path = str(tmp_path / "events.jsonl")
        with Tracer(jsonl_path=path) as tr:
            with tr.span("decode", cat="sweep", track="main"):
                pass
            tr.instant("compile", bucket="L22", level="warning")
            tr.complete("chunk", 100, 300, cat="pipeline", track="shard0",
                        chunk=7)
            tr.gauge("pipeline.in_flight", 3)
            tr.counter("sweep.points", 16)
            tr.observe("compile.L22", 1.5)
        reg = tr.registry
        assert reg.histograms["sweep.decode"].count == 1
        assert reg.histograms["pipeline.chunk"].count == 1
        assert reg.histograms["pipeline.chunk"].last == pytest.approx(2e-7)
        assert reg.counters["sweep.points"].value == 16
        assert reg.gauges["pipeline.in_flight"].last == 3
        phases = [(e.ph, e.name) for e in tr.events]
        assert ("X", "decode") in phases and ("X", "chunk") in phases
        assert ("i", "compile") in phases and ("C", "pipeline.in_flight") \
            in phases
        inst = next(e for e in tr.events if e.ph == "i")
        assert inst.args["level"] == "warning"
        with open(path) as f:
            lines = [json.loads(ln) for ln in f]
        assert len(lines) >= 4 and all("ph" in ln and "ts_ns" in ln
                                       for ln in lines)
        tr.close()  # idempotent

    def test_event_cap_counts_drops(self, monkeypatch):
        monkeypatch.setattr(tracer_mod, "MAX_EVENTS", 5)
        tr = Tracer(rss_interval_s=0)
        for i in range(9):
            tr.instant(f"e{i}")
        assert len(tr.events) == 5
        assert tr.dropped_events == 4

    def test_timed_iter(self):
        items = list(range(7))
        assert list(timed_iter(iter(items), NULL_TRACER)) == items
        tr = Tracer(record_events=False)
        assert list(timed_iter(iter(items), tr, name="decode")) == items
        assert tr.registry.histograms["sweep.decode"].count >= len(items)

    def test_rss_gauge_samples_current_rss(self):
        assert rss_mb() > 10.0
        tr = Tracer(record_events=False, rss_interval_s=0.0)
        tr.sample_rss(force=True)
        g = tr.registry.gauges["rss_mb"]
        assert g.count >= 2 and g.last > 10.0     # __init__ seeds one
        assert g.growth() >= 0.0

    def test_disabled_tracer_near_zero_cost(self):
        # the "~1% overhead when disabled" bound, made deterministic: a
        # chunk makes O(10) telemetry calls and takes >= ~1 ms to
        # evaluate, so <= 1 us per disabled call keeps overhead < 1%.
        n = 50_000
        t0 = time.perf_counter()
        for _ in range(n):
            with NULL_TRACER.span("x"):
                pass
            NULL_TRACER.counter("c", 16)
            NULL_TRACER.observe("h", 1.0)
        per_call = (time.perf_counter() - t0) / (3 * n)
        assert per_call < 5e-6


# ---------------------------------------------------------------------------
# exporters + report
# ---------------------------------------------------------------------------

class TestExportAndReport:

    def _tracer_with_shards(self):
        tr = Tracer(rss_interval_s=0)
        for s in (0, 1):
            with tr.span("dispatch", track=f"shard{s}"):
                pass
        with tr.span("archive"):
            pass
        tr.gauge("pipeline.in_flight", 2)
        return tr

    def test_chrome_trace_one_lane_per_shard(self, tmp_path):
        tr = self._tracer_with_shards()
        trace = chrome_trace(tr)
        assert trace["displayTimeUnit"] == "ms"
        evs = trace["traceEvents"]
        assert all(e["pid"] == 0 for e in evs)
        lanes = trace_lanes(trace)
        assert {"main", "shard0", "shard1"} <= set(lanes)
        assert len(set(lanes.values())) == len(lanes)  # distinct tids
        # main sorts first, shards in numeric order
        assert lanes["main"] < lanes["shard0"] < lanes["shard1"]
        for e in evs:
            if e["ph"] == "X":
                assert e["dur"] >= 0 and e["ts"] >= 0
        out = tmp_path / "trace.json"
        write_chrome_trace(str(out), tr)
        assert trace_lanes(json.loads(out.read_text())) == lanes

    def test_sweep_report_attribution_exact(self, tmp_path):
        tr = Tracer(rss_interval_s=0)
        t0 = tr.now_ns()
        tr.complete("decode", t0, t0 + int(2e8))          # 0.2 s
        tr.complete("dispatch", t0, t0 + int(3e8))        # 0.3 s
        tr.complete("chunk", t0, t0 + int(9e8), cat="pipeline")  # ignored
        tr.counter("sweep.points", 100)
        tr.counter("sweep.compiles", 2)
        tr.observe("compile.L22", 1.5)
        rep = build_sweep_report(tr, wall_s=1.0)
        assert rep.points == 100 and rep.pts_per_s == pytest.approx(100.0)
        assert rep.attribution["decode"]["share"] == pytest.approx(0.2)
        assert rep.attribution["dispatch"]["share"] == pytest.approx(0.3)
        assert "chunk" not in rep.attribution   # pipeline cat excluded
        assert rep.coverage == pytest.approx(0.5)
        assert rep.n_compiles == 2
        assert rep.compiles["L22"]["count"] == 1
        text = rep.render()
        assert "decode" in text and "total accounted" in text
        out = tmp_path / "sweep_report.json"
        write_sweep_report(str(out), rep)
        back = load_sweep_report(str(out))
        assert back.points == rep.points
        assert back.attribution["decode"]["seconds"] == \
            pytest.approx(rep.attribution["decode"]["seconds"])


# ---------------------------------------------------------------------------
# the walks: bit-identical fronts with telemetry on, real trace content
# ---------------------------------------------------------------------------

class TestWalksBitIdentical:

    @pytest.mark.parametrize("shards", (None, 2))
    @pytest.mark.parametrize("backend", ("oracle", "surrogate"))
    def test_pareto_front_streaming(self, workload, ppa_models, shards,
                                    backend):
        kw = dict(chunk_size=CHUNK, metrics=METRICS)
        if backend == "surrogate":
            kw["surrogate"] = ppa_models
        if shards:
            kw["shards"] = shards
        ref, _ = pareto_front_streaming(workload, TINY_SPACE, **kw)
        with Tracer(rss_interval_s=0) as tr:
            got, _ = pareto_front_streaming(workload, TINY_SPACE,
                                            telemetry=tr, **kw)
        _assert_archives_equal(ref, got)
        reg = tr.registry
        assert reg.counters["sweep.points"].value == 40  # |TINY_SPACE|
        assert reg.histograms["sweep.dispatch"].count >= 1
        assert reg.histograms["sweep.archive"].count >= 1

    @pytest.mark.parametrize("prune", (False, True))
    def test_pruned_budget_walk(self, workload, prune):
        kw = dict(chunk_size=CHUNK, metrics=METRICS, budget=BUDGET,
                  prune=prune)
        ref, _ = pareto_front_streaming(workload, TINY_SPACE, **kw)
        with Tracer(rss_interval_s=0) as tr:
            got, _ = pareto_front_streaming(workload, TINY_SPACE,
                                            telemetry=tr, **kw)
        _assert_archives_equal(ref, got)
        if prune:
            assert tr.registry.histograms["sweep.prune_stage1"].count >= 1
            assert tr.registry.counters["prune.flushes"].value >= 1

    @pytest.mark.parametrize("shards", (None, 3))
    def test_evaluate_space_streaming(self, workload, shards):
        def collect(**kw):
            rows = {}
            for res, idx in evaluate_space_streaming(
                    workload, TINY_SPACE, chunk_size=CHUNK, **kw):
                for j, i in enumerate(np.asarray(idx)):
                    rows[int(i)] = (float(res.latency_s[j]),
                                    float(res.energy_j[j]))
            return rows
        kw = dict(shards=shards) if shards else {}
        ref = collect(**kw)
        with Tracer(rss_interval_s=0) as tr:
            got = collect(telemetry=tr, **kw)
        assert ref == got
        assert tr.registry.counters["sweep.points"].value == 40

    @pytest.mark.parametrize("shards", (None, 2))
    @pytest.mark.parametrize("backend", ("oracle", "surrogate"))
    def test_coexplore_front(self, tiny_models, ppa_models, shards, backend):
        kw = dict(chunk_size=CHUNK, max_points=150, seed=3)
        if backend == "surrogate":
            kw["surrogate"] = ppa_models
        if shards:
            kw["shards"] = shards
        ref = coexplore_front(tiny_models, TINY_SPACE, **kw)
        with Tracer(rss_interval_s=0) as tr:
            got = coexplore_front(tiny_models, TINY_SPACE, telemetry=tr,
                                  **kw)
        _assert_archives_equal(ref.archive, got.archive)
        assert got.points_evaluated == ref.points_evaluated
        assert tr.registry.counters["sweep.points"].value == \
            ref.points_evaluated

    def test_coexplore_budget_kill_counters(self, tiny_models):
        # mid-range area bound: TINY_SPACE spans ~0.38-3.4 mm^2, so some
        # lanes die at the config-only stage and feed the kill counters
        kw = dict(chunk_size=CHUNK, budget=Budget(area_mm2=0.6), prune=True)
        ref = coexplore_front(tiny_models, TINY_SPACE, **kw)
        with Tracer(rss_interval_s=0) as tr:
            got = coexplore_front(tiny_models, TINY_SPACE, telemetry=tr,
                                  **kw)
        _assert_archives_equal(ref.archive, got.archive)
        # stage-1 + stage-2 kill counters add up to evaluated - feasible
        expected = ref.budget_stats.evaluated - ref.budget_stats.feasible
        assert expected > 0
        assert tr.registry.counters["budget.killed"].value == expected
        per_cons = {k: c.value for k, c in tr.registry.counters.items()
                    if k.startswith("budget.kill.")}
        # independent per-constraint counts cover every killed lane
        assert per_cons and sum(per_cons.values()) >= expected

    def test_sharded_trace_has_one_lane_per_shard(self, workload):
        with Tracer() as tr:
            pareto_front_streaming(workload, TINY_SPACE, chunk_size=CHUNK,
                                   metrics=METRICS, shards=2, telemetry=tr)
        lanes = trace_lanes(chrome_trace(tr))
        assert {"shard0", "shard1"} <= set(lanes)
        reg = tr.registry
        assert reg.histograms["pipeline.chunk"].count >= 1
        assert reg.gauges["pipeline.in_flight"].max >= 1
        rep = build_sweep_report(tr)
        assert rep.points == 40
        # host phases are sequential, so attribution never exceeds wall
        assert 0.0 < rep.coverage <= 1.05

    def test_compile_events_charged_to_layer_bucket(self, workload):
        # the jit cache is process-wide, so an earlier test may already
        # have compiled this shape — clear it to force a fresh trace
        jax.clear_caches()
        with Tracer(rss_interval_s=0) as tr:
            pareto_front_streaming(workload, TINY_SPACE, chunk_size=13,
                                   metrics=METRICS, telemetry=tr)
        reg = tr.registry
        assert reg.counters["sweep.compiles"].value >= 1
        buckets = [k for k in reg.histograms if k.startswith("compile.L")]
        assert buckets and reg.histograms[buckets[0]].count >= 1
        assert any(e.name == "compile" for e in tr.events)


# ---------------------------------------------------------------------------
# checkpoint + serving instrumentation
# ---------------------------------------------------------------------------

class TestCheckpointTelemetry:

    def test_save_load_durations_sizes_and_gc_warning(self, tmp_path):
        state = {"front": np.arange(32).reshape(4, 8), "cursor": 7}
        with Tracer(rss_interval_s=0) as tr:
            for step in (1, 2, 3):
                manager.save_state(str(tmp_path), step, state, keep=2,
                                   telemetry=tr)
            step, got = manager.load_state(str(tmp_path), telemetry=tr)
        assert step == 3 and got["cursor"] == 7
        reg = tr.registry
        assert reg.histograms["checkpoint.save"].count == 3
        assert reg.histograms["checkpoint.load"].count == 1
        assert reg.histograms["checkpoint.bytes"].count == 4
        assert reg.histograms["checkpoint.bytes"].min > 0
        warns = [e for e in tr.events if e.name == "gc_removed"]
        assert len(warns) == 1                      # keep=2 removed step 1
        assert warns[0].args["level"] == "warning"
        assert warns[0].args["step"] == 1


class TestServeTelemetry:

    def test_engine_metrics(self):
        from repro.configs import reduced
        from repro.models import family_module
        from repro.serve import ServeEngine
        cfg = reduced("smollm-135m")
        mod = family_module(cfg)
        params = mod.init_params(cfg, jax.random.PRNGKey(0))
        with Tracer(rss_interval_s=0) as tr:
            eng = ServeEngine(cfg, mod, params, batch_slots=2, max_len=64,
                              telemetry=tr)
            reqs = [eng.submit(np.arange(4) % cfg.vocab, max_new=3)
                    for _ in range(4)]
            eng.run()
        assert all(r.done and len(r.out) == 3 for r in reqs)
        reg = tr.registry
        assert reg.counters["serve.requests"].value == 4
        assert reg.counters["serve.tokens"].value == 12
        assert reg.histograms["serve.queue_s"].count == 4
        assert reg.histograms["serve.request_s"].count == 4
        assert reg.histograms["serve.prefill"].count >= 1
        assert reg.histograms["serve.decode"].count >= 1
        occ = reg.gauges["serve.slot_occupancy"]
        assert 0.0 <= occ.min and occ.max <= 1.0


# ---------------------------------------------------------------------------
# benchmark helpers derive from the registry
# ---------------------------------------------------------------------------

class TestBenchCommon:

    def test_time_call_stats_and_emit_spread(self):
        from benchmarks.common import REGISTRY, Timing, emit, time_call
        t = time_call(lambda: np.ones(8), iters=5, name="obs_unit")
        assert isinstance(t, Timing) and isinstance(t, float)
        assert t.min_us <= float(t) <= t.max_us
        assert t.iters == 5
        assert REGISTRY.histogram("bench.obs_unit").count == 5
        row = emit("obs_unit_row", t, "k=1")
        assert row.startswith("obs_unit_row,")
        assert "min_us=" in row and "iters=5" in row
        assert REGISTRY.gauge("row.obs_unit_row").last == float(t)

    def test_sweep_timer_and_rss_marks(self):
        from benchmarks.common import (REGISTRY, rss_growth_mark,
                                       rss_growth_mb, sweep_timer)
        before = REGISTRY.histogram("bench.obs_sweep").count
        mark = rss_growth_mark()
        with sweep_timer("obs_sweep") as t:
            time.sleep(0.01)
        assert t.seconds >= 0.01
        assert REGISTRY.histogram("bench.obs_sweep").count == before + 1
        assert rss_growth_mb(mark) >= 0.0


# ---------------------------------------------------------------------------
# span nesting, top-level accounting, profiler annotations
# ---------------------------------------------------------------------------

def _parents(tr) -> dict:
    return {f"{e.cat}.{e.name}": e.parent for e in tr.events if e.ph == "X"}


class TestNesting:

    def test_nested_spans_name_parent_and_count_top_level(self, tmp_path):
        path = str(tmp_path / "events.jsonl")
        with Tracer(jsonl_path=path, rss_interval_s=0) as tr:
            with tr.span("walk_setup"):
                with tr.span("decode"):
                    with tr.span("upload", cat="copy"):
                        pass
                with tr.span("prefilter", cat="archive"):
                    pass
            with tr.span("archive"):
                pass
        want = {"sweep.walk_setup": None, "sweep.decode": "sweep.walk_setup",
                "copy.upload": "sweep.decode",
                "archive.prefilter": "sweep.walk_setup",
                "sweep.archive": None}
        assert _parents(tr) == want
        h = tr.registry.histograms
        top = tr.registry.counters[TOP_LEVEL_COUNTER]
        assert top.count == 2                    # only the parentless two
        assert top.value == pytest.approx(
            h["sweep.walk_setup"].total + h["sweep.archive"].total)
        assert top.value < sum(x.total for x in h.values())
        with open(path) as f:
            lines = [json.loads(ln) for ln in f]
        assert {f"{d['cat']}.{d['name']}": d.get("parent")
                for d in lines if d["ph"] == "X"} == want
        chrome = {f"{e['cat']}.{e['name']}": e.get("args", {}).get("parent")
                  for e in chrome_trace(tr)["traceEvents"] if e["ph"] == "X"}
        assert chrome == want
        assert tr._open_spans() == []

    def test_spans_on_two_threads_nest_separately(self):
        tr = Tracer(rss_interval_s=0)
        both_open = threading.Barrier(2, timeout=10)

        def work(k):
            with tr.span(f"outer{k}"):
                both_open.wait()      # both outer spans open at once
                with tr.span(f"inner{k}"):
                    both_open.wait()

        threads = [threading.Thread(target=work, args=(k,)) for k in (0, 1)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=10)
        assert not any(t.is_alive() for t in threads)
        assert _parents(tr) == {"sweep.outer0": None, "sweep.outer1": None,
                                "sweep.inner0": "sweep.outer0",
                                "sweep.inner1": "sweep.outer1"}
        h = tr.registry.histograms
        top = tr.registry.counters[TOP_LEVEL_COUNTER]
        assert top.count == 2
        assert top.value == pytest.approx(h["sweep.outer0"].total
                                          + h["sweep.outer1"].total)

    def test_top_level_counter_loses_no_update_across_threads(self):
        tr = Tracer(record_events=False, rss_interval_s=0)
        n_threads, per_thread = 2 * (os.cpu_count() or 2) + 2, 500
        old = sys.getswitchinterval()
        sys.setswitchinterval(1e-6)
        try:
            def work():
                for _ in range(per_thread):
                    with tr.span("top"):
                        with tr.span("nested"):
                            pass
            threads = [threading.Thread(target=work)
                       for _ in range(n_threads)]
            for t in threads:
                t.start()
            for t in threads:
                t.join(timeout=60)
        finally:
            sys.setswitchinterval(old)
        assert not any(t.is_alive() for t in threads)
        top = tr.registry.counters[TOP_LEVEL_COUNTER]
        assert top.count == n_threads * per_thread
        assert top.value == pytest.approx(
            tr.registry.histograms["sweep.top"].total)

    def test_span_closed_out_of_order_leaves_the_stack_clean(self):
        tr = Tracer(rss_interval_s=0)
        a, b = tr.span("a"), tr.span("b")
        a.__enter__()
        b.__enter__()
        a.__exit__(None, None, None)
        b.__exit__(None, None, None)
        assert tr._open_spans() == []
        assert _parents(tr) == {"sweep.a": None, "sweep.b": "sweep.a"}
        with tr.span("c"):
            pass
        assert _parents(tr)["sweep.c"] is None

    def test_report_coverage_reads_top_level_seconds(self):
        tr = Tracer(rss_interval_s=0)
        with tr.span("decode"):
            with tr.span("upload", cat="copy"):
                time.sleep(0.002)
        with tr.span("propose", cat="search"):
            time.sleep(0.002)
        rep = build_sweep_report(tr, wall_s=1.0)
        top = tr.registry.counters[TOP_LEVEL_COUNTER].value
        h = tr.registry.histograms
        assert rep.coverage == pytest.approx(top)
        # the search span counts, the nested copy does not count twice
        assert top == pytest.approx(h["sweep.decode"].total
                                    + h["search.propose"].total)

    def test_annotate_puts_spans_in_the_profiler_trace(self, tmp_path):
        annotated = Tracer(annotate=True, rss_interval_s=0)
        plain = Tracer(rss_interval_s=0)
        with jax.profiler.trace(str(tmp_path)):
            with annotated.span("decode"):
                with annotated.span("upload", cat="copy"):
                    jnp.ones(8).block_until_ready()
            with plain.span("unannotated"):
                pass
        files = glob.glob(str(tmp_path / "**" / "*.xplane.pb"),
                          recursive=True)
        assert files
        data = jax.profiler.ProfileData.from_file(files[-1])
        names = {e.name for plane in data.planes for line in plane.lines
                 for e in line.events}
        assert {"sweep.decode", "copy.upload"} <= names
        assert "sweep.unannotated" not in names
        assert _parents(annotated) == {"sweep.decode": None,
                                       "copy.upload": "sweep.decode"}


# ---------------------------------------------------------------------------
# the chunk path's spans and counters, per chunk and per walk or search
# ---------------------------------------------------------------------------

class _Recording(EvolutionaryDriver):
    """Keeps every evaluated (indices, objectives) the search observes."""

    def reset(self, ctx):
        super().reset(ctx)
        self.seen = []

    def observe(self, idx, obj, feasible):
        self.seen.append((np.array(idx), np.array(obj)))
        super().observe(idx, obj, feasible)


def _search(models, telemetry=None, seed=1):
    drv = _Recording(population=32)
    front = search_front(models, space=TINY_SPACE, driver=drv, max_evals=96,
                         seed=seed, chunk_size=CHUNK, telemetry=telemetry)
    return front, drv.seen


PER_CHUNK = ("copy.upload", "copy.fetch", "sweep.dispatch",
             "sweep.objectives", "sweep.archive", "archive.prefilter",
             "sweep.best")


def _model_depths(models):
    """Each model's bucket depth, the layer rows a point of it costs."""
    plan = plan_joint_walk(models, TINY_SPACE)
    return np.array([int(np.shape(plan.stacked[b].layers.H)[-1])
                     for b in plan.bucket_of])


class TestRowsCounter:
    """``sweep.rows``: each evaluated point's bucket depth, summed."""

    @pytest.mark.parametrize("shards", (None, 2))
    def test_walk(self, tiny_models, shards):
        tr = Tracer(rss_interval_s=0)
        front = coexplore_front(tiny_models, TINY_SPACE, chunk_size=CHUNK,
                                shards=shards, telemetry=tr)
        n = front.points_evaluated
        assert n == space_size(TINY_SPACE) * len(tiny_models)
        mids = np.arange(n) // space_size(TINY_SPACE)
        c = tr.registry.counters
        assert c["sweep.points"].value == n
        assert c["sweep.rows"].value == _model_depths(tiny_models)[mids].sum()

    def test_search(self, tiny_models):
        tr = Tracer(rss_interval_s=0)
        _, seen = _search(tiny_models, tr)
        mids = np.concatenate([i for i, _ in seen]) // space_size(TINY_SPACE)
        assert tr.registry.counters["sweep.rows"].value \
            == _model_depths(tiny_models)[mids].sum()

    @pytest.mark.parametrize("shards", (None, 2))
    def test_plain_workload_walk(self, workload, shards):
        from repro.core.workloads import layer_bucket, workload_layers
        with Tracer(rss_interval_s=0) as tr:
            pareto_front_streaming(workload, TINY_SPACE, chunk_size=CHUNK,
                                   metrics=METRICS, shards=shards,
                                   telemetry=tr)
        c = tr.registry.counters
        assert c["sweep.rows"].value == c["sweep.points"].value \
            * layer_bucket(workload_layers(workload))


class TestChunkPathSpans:

    def test_walk_records_every_span_per_chunk(self, tiny_models):
        kw = dict(chunk_size=CHUNK, max_points=150, seed=3)
        tr = Tracer(rss_interval_s=0)
        t0 = time.perf_counter()
        fronts = [coexplore_front(tiny_models, TINY_SPACE, telemetry=tr,
                                  **kw) for _ in range(2)]
        wall = time.perf_counter() - t0
        h, c = tr.registry.histograms, tr.registry.counters
        chunks = h["sweep.device_wait"].count
        plan = plan_joint_walk(tiny_models, TINY_SPACE, **kw)
        assert chunks == 2 * sum(1 for _ in plan.chunks())
        for name in PER_CHUNK:
            assert h[name].count == chunks, name
        assert h["sweep.decode"].count == chunks + 2  # + each StopIteration
        assert h["sweep.walk_setup"].count == 2       # one per walk
        surv = c["archive.survivors"]
        assert surv.count == chunks
        assert 0 < surv.value <= 2 * fronts[0].points_evaluated
        assert _parents(tr) == {
            "sweep.walk_setup": None, "sweep.decode": None,
            "sweep.dispatch": None, "copy.upload": "sweep.dispatch",
            "sweep.device_wait": None, "copy.fetch": "sweep.device_wait",
            "sweep.objectives": None, "sweep.archive": None,
            "archive.prefilter": "sweep.archive", "sweep.best": None}
        top = c[TOP_LEVEL_COUNTER].value
        assert 0.5 * wall < top <= wall

    def test_search_records_every_span_per_chunk(self, tiny_models):
        tr = Tracer(rss_interval_s=0)
        front, _ = _search(tiny_models, tr)
        h, c = tr.registry.histograms, tr.registry.counters
        chunks = h["sweep.device_wait"].count
        assert chunks >= 2
        for name in PER_CHUNK + ("search.decode", "search.observe"):
            assert h[name].count == chunks, name
        assert h["search.setup"].count == 1
        assert h["search.partition"].count == c["search.generations"].value
        assert c["archive.survivors"].count == chunks
        parents = _parents(tr)
        assert parents["copy.upload"] == "sweep.dispatch"
        assert "copy.codes" not in parents   # the PE codes stay on the host
        assert parents["copy.fetch"] == "sweep.device_wait"
        assert parents["archive.prefilter"] == "sweep.archive"
        for name in ("search.setup", "search.propose", "search.partition",
                     "search.decode", "search.observe", "sweep.objectives",
                     "sweep.best"):
            assert parents[name] is None, name
        # the counters that repeated other numbers are gone
        for name in ("search.screened", "search.proposed", "search.evals"):
            assert name not in c

    def test_pruned_walk_keeps_no_buffered_gauge(self, tiny_models):
        tr = Tracer(rss_interval_s=0)
        coexplore_front(tiny_models, TINY_SPACE, chunk_size=CHUNK,
                        budget=Budget(area_mm2=0.6), prune=True,
                        telemetry=tr)
        assert tr.registry.counters["prune.flushes"].value >= 1
        assert "prune.buffered" not in tr.registry.gauges
        assert tr.registry.histograms["sweep.walk_setup"].count == 1


# ---------------------------------------------------------------------------
# the new telemetry= paths leave every value bit-identical
# ---------------------------------------------------------------------------

def _assert_configs_equal(a, b):
    for f in a._fields:
        x, y = np.asarray(getattr(a, f)), np.asarray(getattr(b, f))
        assert x.dtype == y.dtype, f
        np.testing.assert_array_equal(x, y, err_msg=f)


class TestTelemetryPathsBitIdentical:

    @given(seed=st.integers(0, 2**31 - 1), n=st.integers(1, 40),
           annotate=st.booleans())
    @settings(max_examples=10, deadline=None)
    def test_space_points(self, seed, n, annotate):
        idx = np.random.default_rng(seed).integers(
            0, space_size(TINY_SPACE), size=n)
        tr = Tracer(rss_interval_s=0, annotate=annotate)
        _assert_configs_equal(space_points(idx, TINY_SPACE),
                              space_points(idx, TINY_SPACE, telemetry=tr))
        assert tr.registry.histograms["copy.upload"].count == 1

    @pytest.mark.parametrize("annotate", (False, True))
    def test_finish_chunk(self, workload, annotate):
        cfg = space_points(np.arange(CHUNK - 3), TINY_SPACE)
        pending = dispatch_chunk(cfg, workload, pad_to=CHUNK)
        tr = Tracer(rss_interval_s=0, annotate=annotate)
        plain, traced = finish_chunk(pending), finish_chunk(pending,
                                                            telemetry=tr)
        for f in plain._fields:
            x, y = getattr(plain, f), getattr(traced, f)
            assert x.dtype == y.dtype and x.shape == (CHUNK - 3,), f
            np.testing.assert_array_equal(x, y, err_msg=f)
        assert tr.registry.histograms["copy.fetch"].count == 1

    @given(seed=st.integers(0, 2**31 - 1), chunks=st.integers(1, 6))
    @settings(max_examples=15, deadline=None)
    def test_archive_update(self, seed, chunks):
        rng = np.random.default_rng(seed)
        plain, traced = ParetoArchive(3), ParetoArchive(3)
        tr = Tracer(rss_interval_s=0)
        survivors = 0
        for k in range(chunks):
            # small integer grid: ties and duplicates on the front
            obj = rng.integers(0, 6, size=(int(rng.integers(0, 40)), 3)
                               ).astype(np.float64)
            idx = np.arange(k * 40, k * 40 + len(obj))
            survivors += int((~_dominated_by(obj, plain.objectives)).sum()) \
                if len(plain) else len(obj)
            plain.update(obj, idx)
            traced.update(obj, idx, telemetry=tr)
            np.testing.assert_array_equal(plain.indices, traced.indices)
            np.testing.assert_array_equal(plain.objectives,
                                          traced.objectives)
        c = tr.registry.counters["archive.survivors"]
        assert (c.count, c.value) == (chunks, survivors)
        assert tr.registry.histograms["archive.prefilter"].count == chunks

    @pytest.mark.parametrize("annotate", (False, True))
    def test_search_front(self, tiny_models, annotate):
        ref, ref_seen = _search(tiny_models)
        got, got_seen = _search(tiny_models,
                                Tracer(rss_interval_s=0, annotate=annotate))
        _assert_archives_equal(ref.archive, got.archive)
        assert got.per_model_best == ref.per_model_best
        assert got.points_evaluated == ref.points_evaluated
        assert len(got_seen) == len(ref_seen)
        for (ri, ro), (gi, go) in zip(ref_seen, got_seen):
            np.testing.assert_array_equal(ri, gi)
            np.testing.assert_array_equal(ro, go)

    def test_coexplore_front_annotated(self, tiny_models):
        kw = dict(chunk_size=CHUNK, max_points=150, seed=3)
        ref = coexplore_front(tiny_models, TINY_SPACE, **kw)
        got = coexplore_front(tiny_models, TINY_SPACE,
                              telemetry=Tracer(rss_interval_s=0,
                                               annotate=True), **kw)
        _assert_archives_equal(ref.archive, got.archive)
        assert got.per_model_best == ref.per_model_best


# ---------------------------------------------------------------------------
# the benchmark's per-layer readers of these spans and counters
# ---------------------------------------------------------------------------

NEW_METRICS = ("unspanned_ms_per_chunk.sweep", "copy_ms_per_chunk.sweep",
               "archive_prefilter_ms_per_chunk.sweep",
               "archive_survivors_per_chunk.sweep",
               "bookkeeping_ms_per_chunk.sweep",
               "decode_ms_per_chunk.search", "setup_ms_per_walk")


def _readings(tracer, window_s):
    from bench.harness import Readings, Window
    reduced = dict(window_s=window_s, busy_s={}, exec_s={}, device_ops=[],
                   idle_gaps=[])
    return Readings(tracer, reduced,
                    Window(metrics={}, attempted=1, failed=0))


@pytest.fixture(scope="module")
def traced_cells(tiny_models):
    """Readings of a traced walk window and a traced search window, as
    the benchmark's paper_sweep and llm_search cells take them."""
    out = {}
    for cell, run in (
            ("paper_sweep", lambda tr: coexplore_front(
                tiny_models, TINY_SPACE, chunk_size=CHUNK, telemetry=tr)),
            ("llm_search", lambda tr: _search(tiny_models, tr))):
        tr = Tracer(record_events=False, rss_interval_s=0)
        t0 = time.perf_counter()
        run(tr)
        out[cell] = _readings(tr, time.perf_counter() - t0)
    return out


class TestBenchReaders:

    @pytest.mark.parametrize("name", NEW_METRICS)
    def test_reader_reads_a_traced_window(self, traced_cells, name):
        from bench import registry
        spec = registry.load_benchmark(registry.BENCH_DIR.parent)
        entry = next(m for m in spec["per_layer"] if m["name"] == name)
        read = registry.metric_reader(name)
        for cell in ("paper_sweep", "llm_search"):
            value = read(traced_cells[cell])
            if cell in entry["workloads"]:
                assert value is not None and math.isfinite(value), cell
                assert value >= 0.0, cell
        # a program without these spans and counters reads nothing
        assert read(_readings(Tracer(record_events=False,
                                     rss_interval_s=0), 1.0)) is None

    def test_network_ns_per_row(self, traced_cells):
        """Device seconds of the network stage over ``sweep.rows``; no
        device plane (the CPU) or no counter (a program without it)
        reads nothing."""
        from bench import registry
        read = registry.metric_reader("network_ns_per_row.sweep")
        for cell in ("paper_sweep", "llm_search"):
            r = copy.copy(traced_cells[cell])
            assert read(r) is None
            r.trace = dict(r.trace, exec_s={"_lane_layers": 2e-4,
                                            "_network_sums": 1e-3,
                                            "_ppa_stage": 5.0})
            assert read(r) == pytest.approx(
                1.2e-3 / r.counters["sweep.rows"] * 1e9)
            no_rows = dict(r.counters)
            del no_rows["sweep.rows"]
            r.counters = no_rows
            assert read(r) is None

    def test_readers_add_up_against_the_registry(self, traced_cells):
        from bench import registry
        r = traced_cells["llm_search"]
        read = {n: registry.metric_reader(n)(r) for n in NEW_METRICS}
        per_chunk = lambda *ns: sum(r.span_s(n) for n in ns) \
            / r.chunks * 1e3  # noqa: E731
        assert read["copy_ms_per_chunk.sweep"] == pytest.approx(
            per_chunk("copy.upload", "copy.fetch"))
        assert read["archive_survivors_per_chunk.sweep"] == pytest.approx(
            r.counters["archive.survivors"] / r.chunks)
        # copies of the search sit inside its dispatch and finish spans
        assert read["copy_ms_per_chunk.sweep"] <= per_chunk(
            "sweep.dispatch", "sweep.device_wait")
        assert read["unspanned_ms_per_chunk.sweep"] == pytest.approx(
            (r.trace["window_s"] - r.counters[TOP_LEVEL_COUNTER])
            / r.chunks * 1e3)
