"""Cost-model backend layer: the oracle/surrogate batched PPA stage, the
registry, compile accounting (no per-config dispatch), and the two-stage
config-only constraint pre-pruning — bit-identity of pruned walks with
the single-stage masking path on all three walks, for both backends."""

import jax.numpy as jnp
import numpy as np
import pytest

try:
    from hypothesis import given, settings, strategies as st
except ImportError:  # CI images without hypothesis: deterministic fallback
    from _hypothesis_fallback import given, settings, st

from repro.core import (Budget, BudgetStats, CostModel, DseResult,
                        OracleCostModel, SurrogateCostModel, TwoStagePruner,
                        as_cost_model, coexplore_front, cost_model,
                        default_model_set, enumerate_space, evaluate_chunk,
                        evaluate_space_streaming, fit_ppa_models, layer_bucket,
                        make_config, model_entry, pareto_front_streaming,
                        ppa_trace_count, register_cost_model, resnet_cifar,
                        reset_trace_count, stack_configs, synthesize,
                        trace_count, transformer_gemm, workload_layers)
from repro.core.costmodel import COST_MODELS

# 2*2*1*1*2*1*5*1 = 40 accelerator points keeps every walk here cheap.
TINY_SPACE = dict(
    pe_rows=(8, 12), pe_cols=(8, 14), gbuf_kb=(54.0,), spad_ifmap=(12,),
    spad_filter=(112, 224), spad_psum=(16,),
    pe_type=tuple(range(5)), bandwidth_gbps=(25.6,),
)
CHUNK = 16
METRICS = ("perf_per_area", "neg_energy_j")


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
    """Polynomial surrogate fitted on a sample covering every PE type."""
    return fit_ppa_models(enumerate_space(max_points=500, seed=1),
                          degrees=(1, 2), k=4)


def _assert_front_equal(a_idx, a_obj, b_idx, b_obj):
    np.testing.assert_array_equal(np.sort(a_idx), np.sort(b_idx))
    order_a, order_b = np.argsort(a_idx), np.argsort(b_idx)
    np.testing.assert_array_equal(np.asarray(a_obj)[order_a],
                                  np.asarray(b_obj)[order_b])


class TestBackendProtocol:
    def test_oracle_ppa_matches_synthesize(self):
        """The oracle backend's batched triple is the synthesis oracle's
        nominal-activity (power, clock, area), lane for lane."""
        cfg = enumerate_space(TINY_SPACE)
        backend = OracleCostModel()
        power, clock, area = backend.ppa_fn(backend.ppa_params, cfg)
        truth = synthesize(cfg)
        np.testing.assert_array_equal(np.asarray(power),
                                      np.asarray(truth.power_mw))
        np.testing.assert_array_equal(np.asarray(clock),
                                      np.asarray(truth.clock_ghz))
        np.testing.assert_array_equal(np.asarray(area),
                                      np.asarray(truth.area_mm2))

    def test_surrogate_ppa_matches_predict(self, ppa_models):
        """The backend's batch stage and PPAModels.predict are the same
        computation (predict routes through the same pure function;
        eager-vs-jit only differs in ulps)."""
        cfg = enumerate_space(TINY_SPACE)
        backend = SurrogateCostModel(ppa_models)
        power, clock, area = backend.ppa_fn(backend.ppa_params, cfg)
        pred = ppa_models.predict(cfg)
        np.testing.assert_allclose(np.asarray(power),
                                   np.asarray(pred.power_mw), rtol=1e-5)
        np.testing.assert_allclose(np.asarray(clock),
                                   np.asarray(pred.clock_ghz), rtol=1e-5)
        np.testing.assert_allclose(np.asarray(area),
                                   np.asarray(pred.area_mm2), rtol=1e-5)

    def test_surrogate_predict_matches_per_type_polynomials(self,
                                                            ppa_models):
        """The lane-gathered batch evaluation equals evaluating each PE
        type's fitted polynomial on its own lanes (the historical
        per-type-subset semantics)."""
        from repro.core.arch import PE_TYPE_NAMES
        from repro.core.ppa import TARGETS, config_features
        cfg = enumerate_space(TINY_SPACE)
        x = config_features(cfg)
        pt = np.asarray(cfg.pe_type).astype(int)
        pred = ppa_models.predict(cfg)
        got = dict(power_mw=np.asarray(pred.power_mw, np.float64),
                   clock_ghz=np.asarray(pred.clock_ghz, np.float64),
                   area_mm2=np.asarray(pred.area_mm2, np.float64))
        for code, name in enumerate(PE_TYPE_NAMES):
            sel = pt == code
            if not sel.any():
                continue
            for t in TARGETS:
                ref = np.asarray(ppa_models.models[name][t].predict(x[sel]),
                                 np.float64)
                np.testing.assert_allclose(got[t][sel], ref, rtol=1e-5)

    def test_unfitted_pe_type_surfaces_through_evaluate_chunk(self,
                                                              workload):
        """The PR 4 unfitted-type ValueError must fire from inside the
        evaluator path, naming the missing types, before any evaluation."""
        int16_only = enumerate_space(dict(TINY_SPACE, pe_type=(1,)))
        models = fit_ppa_models(int16_only, degrees=(1,), k=3)
        mixed = stack_configs([make_config(pe_type="int16"),
                               make_config(pe_type="lightpe1")])
        with pytest.raises(ValueError, match="lightpe1"):
            evaluate_chunk(mixed, workload, surrogate=models, pad_to=4)
        # and through the streaming walk's two-stage pruner as well
        with pytest.raises(ValueError, match="lightpe1"):
            list(evaluate_space_streaming(
                workload, TINY_SPACE, surrogate=models, chunk_size=CHUNK,
                budget=Budget(area_mm2=1e6)))

    def test_evaluate_chunk_same_result_any_spec_form(self, workload,
                                                      ppa_models):
        """PPAModels, SurrogateCostModel and a pre-resolved backend are
        the same backend — bit-identical columns."""
        cfg = enumerate_space(TINY_SPACE)
        a = evaluate_chunk(cfg, workload, surrogate=ppa_models)
        b = evaluate_chunk(cfg, workload,
                           surrogate=SurrogateCostModel(ppa_models))
        c = evaluate_chunk(cfg, workload,
                           surrogate=as_cost_model(ppa_models))
        for f in DseResult._fields:
            np.testing.assert_array_equal(getattr(a, f), getattr(b, f))
            np.testing.assert_array_equal(getattr(a, f), getattr(c, f))


class TestRegistry:
    def test_builtin_backends_registered(self):
        assert set(COST_MODELS) >= {"oracle", "surrogate"}
        assert isinstance(cost_model("oracle"), OracleCostModel)

    def test_surrogate_needs_models(self, ppa_models):
        with pytest.raises(ValueError, match="fit_ppa_models"):
            cost_model("surrogate")
        backend = cost_model("surrogate", models=ppa_models)
        assert isinstance(backend, SurrogateCostModel)

    def test_unknown_and_duplicate_names_rejected(self):
        with pytest.raises(ValueError, match="unknown cost model"):
            cost_model("no-such-backend")
        with pytest.raises(ValueError, match="already registered"):
            register_cost_model("oracle", OracleCostModel)

    def test_custom_backend_registration(self):
        name = "test-oracle-alias"
        try:
            register_cost_model(name, OracleCostModel)
            assert isinstance(cost_model(name), OracleCostModel)
        finally:
            COST_MODELS.pop(name, None)

    def test_as_cost_model_resolution(self, ppa_models):
        assert isinstance(as_cost_model(None), OracleCostModel)
        backend = as_cost_model(ppa_models)
        assert isinstance(backend, SurrogateCostModel)
        assert as_cost_model(ppa_models) is backend     # cached on instance
        assert as_cost_model(backend) is backend
        assert isinstance(as_cost_model("oracle"), OracleCostModel)
        with pytest.raises(TypeError):
            as_cost_model(3.14)


class TestCompileAccounting:
    def test_surrogate_no_longer_compiles_per_config(self, workload,
                                                     ppa_models):
        """The surrogate PPA stage is ONE compilation per chunk shape —
        streaming many chunks (mixed PE-type composition each) must not
        trace again, and a SECOND fit with the same selected degrees
        reuses the very same executable (parameters are pytree args)."""
        list(evaluate_space_streaming(workload, TINY_SPACE,
                                      surrogate=ppa_models,
                                      chunk_size=CHUNK))  # warm the shape
        reset_trace_count()
        list(evaluate_space_streaming(workload, TINY_SPACE,
                                      surrogate=ppa_models,
                                      chunk_size=CHUNK))
        assert ppa_trace_count() == 0
        assert trace_count() == 0
        refit = fit_ppa_models(enumerate_space(max_points=500, seed=9),
                               degrees=(1, 2), k=4)
        if all(refit.models[n][t].degree == ppa_models.models[n][t].degree
               for n in refit.models for t in refit.models[n]):
            list(evaluate_space_streaming(workload, TINY_SPACE,
                                          surrogate=refit,
                                          chunk_size=CHUNK))
            assert ppa_trace_count() == 0       # same structure, same exe

    def test_joint_sweep_compiles_once_per_bucket_surrogate(self,
                                                            tiny_models,
                                                            ppa_models):
        """Acceptance criterion: a surrogate joint sweep costs exactly one
        dataflow compilation per layer bucket and one PPA-stage
        compilation per chunk shape — never one per config or model."""
        buckets = {layer_bucket(workload_layers(m.workload))
                   for m in tiny_models}
        coexplore_front(tiny_models, TINY_SPACE, chunk_size=CHUNK,
                        surrogate=ppa_models)   # warm
        reset_trace_count()
        coexplore_front(tiny_models, TINY_SPACE, chunk_size=CHUNK,
                        surrogate=ppa_models)
        assert trace_count() == 0 and ppa_trace_count() == 0
        from repro.core.dse import _network_sums, _ppa_stage
        _network_sums.clear_cache()
        _ppa_stage.clear_cache()
        reset_trace_count()
        front = coexplore_front(tiny_models, TINY_SPACE, chunk_size=CHUNK,
                                surrogate=ppa_models)
        assert trace_count() == len(buckets) == len(front.buckets)
        assert ppa_trace_count() == 1

    def test_new_model_costs_lanes_not_a_compile(self):
        """Growing the model axis with the ImageNet-scale 224-resolution
        ResNet keeps the default zoo at the {16, 32, 64} bucket set: the
        10-model joint sweep still compiles exactly once per bucket (the
        new member adds lanes to the bucket-32 stack), never once per
        model or per layer count."""
        models = default_model_set()
        names = [m.name for m in models]
        assert "resnet20-cifar10-r224" in names
        buckets = {layer_bucket(workload_layers(m.workload)) for m in models}
        assert buckets == {16, 32, 64}
        from repro.core.dse import _network_sums, _ppa_stage
        _network_sums.clear_cache()
        _ppa_stage.clear_cache()
        reset_trace_count()
        front = coexplore_front(models, TINY_SPACE, chunk_size=CHUNK,
                                max_points=300, seed=3)
        by_depth = dict(front.buckets)
        assert "resnet20-cifar10-r224" in by_depth[32]
        # n_compiles stays at the bucket count, not the model count
        assert trace_count() == len(front.buckets) == len(buckets)


class TestTwoStagePruning:
    @given(q_area=st.floats(0.0, 1.0), q_power=st.floats(0.0, 1.0),
           use_surrogate=st.booleans())
    @settings(max_examples=8, deadline=None)
    def test_pruned_walk_matches_single_stage_plain_dse(
            self, workload, ppa_models, q_area, q_power, use_surrogate):
        """Two-stage pruning == PR 4 post-evaluation masking on the plain
        DSE walk, bit-for-bit (indices AND objectives), for budgets across
        the feasibility spectrum and both backends.  The area bound is
        config-stage (pruned before the dataflow fold), the power bound is
        workload-stage (applied to the survivors)."""
        surrogate = ppa_models if use_surrogate else None
        ref = np.concatenate([np.asarray(r.area_mm2) for r, _ in
                              evaluate_space_streaming(
                                  workload, TINY_SPACE, chunk_size=CHUNK,
                                  surrogate=surrogate)])
        power = np.concatenate([np.asarray(r.power_mw) for r, _ in
                                evaluate_space_streaming(
                                    workload, TINY_SPACE, chunk_size=CHUNK,
                                    surrogate=surrogate)])
        budget = Budget(area_mm2=float(np.quantile(ref, q_area)),
                        power_mw=float(np.quantile(power, q_power)))
        stats = {True: BudgetStats(), False: BudgetStats()}
        fronts = {}
        for prune in (True, False):
            fronts[prune], _ = pareto_front_streaming(
                workload, TINY_SPACE, metrics=METRICS, chunk_size=CHUNK,
                surrogate=surrogate, budget=budget,
                budget_stats=stats[prune], prune=prune)
        _assert_front_equal(fronts[True].indices, fronts[True].objectives,
                            fronts[False].indices, fronts[False].objectives)
        for p in (True, False):
            assert stats[p].evaluated == len(ref)
        assert stats[True].feasible == stats[False].feasible
        # area kills are counted identically in both modes (full chunks)
        area_key = [k for k in stats[False].kills if "area" in k]
        for k in area_key:
            assert stats[True].kills[k] == stats[False].kills[k]
        assert stats[True].pruned == sum(stats[True].kills[k]
                                         for k in area_key)
        assert stats[False].pruned == 0

    @given(q_area=st.floats(0.0, 1.0), q_acc=st.floats(0.0, 1.0),
           mix=st.booleans(), use_surrogate=st.booleans())
    @settings(max_examples=8, deadline=None)
    def test_pruned_walk_matches_single_stage_joint(
            self, tiny_models, ppa_models, q_area, q_acc, mix,
            use_surrogate):
        """Two-stage pruning == single-stage masking on BOTH joint walks
        (mixed one-compile and per-model oracle), both backends: same
        front bits, same aggregates, same evaluated/feasible/kill
        accounting (area and accuracy are both config-stage here)."""
        surrogate = ppa_models if use_surrogate else None
        free = coexplore_front(tiny_models, TINY_SPACE, chunk_size=CHUNK,
                               surrogate=surrogate, mix_models=mix)
        area = np.asarray([0.4, 0.7, 1.1, 2.0, 3.5])  # spectrum anchors
        budget = Budget(area_mm2=float(np.quantile(area, q_area)),
                        min_accuracy=float(np.quantile(
                            np.asarray([0.3, 0.4, 0.9]), q_acc)))
        pruned = coexplore_front(tiny_models, TINY_SPACE, chunk_size=CHUNK,
                                 surrogate=surrogate, mix_models=mix,
                                 budget=budget)
        masked = coexplore_front(tiny_models, TINY_SPACE, chunk_size=CHUNK,
                                 surrogate=surrogate, mix_models=mix,
                                 budget=budget, prune=False)
        _assert_front_equal(pruned.archive.indices,
                            pruned.archive.objectives,
                            masked.archive.indices,
                            masked.archive.objectives)
        assert pruned.per_model_best == masked.per_model_best
        assert pruned.points_evaluated == masked.points_evaluated \
            == free.points_evaluated
        assert pruned.budget_stats.evaluated == masked.budget_stats.evaluated
        assert pruned.budget_stats.feasible == masked.budget_stats.feasible
        assert pruned.budget_stats.kills == masked.budget_stats.kills
        assert pruned.budget_stats.pruned \
            == pruned.budget_stats.evaluated - pruned.budget_stats.feasible

    def test_surrogate_mixed_front_equals_oracle_walk_front(self,
                                                            tiny_models,
                                                            ppa_models):
        """Satellite: the surrogate backend under the joint MIXED walk is
        bit-identical to the per-model oracle walk through the shared walk
        code (fronts, objectives, aggregates) — with and without a pruned
        budget."""
        for budget in (None, Budget(area_mm2=1.5, min_accuracy=0.35)):
            mixed = coexplore_front(tiny_models, TINY_SPACE,
                                    chunk_size=CHUNK, surrogate=ppa_models,
                                    budget=budget)
            grouped = coexplore_front(tiny_models, TINY_SPACE,
                                      chunk_size=CHUNK, surrogate=ppa_models,
                                      mix_models=False, budget=budget)
            _assert_front_equal(mixed.archive.indices,
                                mixed.archive.objectives,
                                grouped.archive.indices,
                                grouped.archive.objectives)
            assert mixed.per_model_best == grouped.per_model_best
            if budget is not None:
                assert mixed.budget_stats == grouped.budget_stats

    def test_pruner_requires_config_stage_bound(self, ppa_models):
        with pytest.raises(ValueError, match="config-stage"):
            TwoStagePruner(Budget(power_mw=100.0), CHUNK)

    def test_min_accuracy_on_plain_walk_raises_cleanly(self, workload):
        """min_accuracy is config-stage, so it engages the pruner even on
        the accuracy-less plain DSE walk — which must surface the PR 4
        needs-joint-walk ValueError, not an AttributeError from the
        stage-1 PPA view."""
        with pytest.raises(ValueError, match="co-exploration"):
            list(evaluate_space_streaming(
                workload, TINY_SPACE, chunk_size=CHUNK,
                budget=Budget(min_accuracy=0.9)))

    def test_predict_shares_the_evaluator_ppa_executable(self, workload,
                                                         ppa_models):
        """PPAModels.predict and the DSE evaluator run the surrogate
        stage through ONE jit entry point: predicting at the chunk shape
        first leaves the streaming sweep nothing to compile (and predict
        traffic shows up in ppa_trace_count)."""
        from repro.core import space_points
        cfg = space_points(np.arange(CHUNK), TINY_SPACE)
        reset_trace_count()
        ppa_models.predict(cfg)
        assert ppa_trace_count() <= 1       # 0 if the shape is warm
        before = ppa_trace_count()
        list(evaluate_space_streaming(workload, TINY_SPACE,
                                      surrogate=ppa_models,
                                      chunk_size=CHUNK))
        assert ppa_trace_count() == before  # sweep reused predict's exe

    def test_empty_feasible_set_never_runs_stage_two(self, workload):
        """A budget nothing satisfies prunes every lane at stage 1 — the
        dataflow evaluator is never invoked."""
        from repro.core.dse import _network_sums
        _network_sums.clear_cache()
        stats = BudgetStats()
        reset_trace_count()
        archive, cfgs = pareto_front_streaming(
            workload, TINY_SPACE, metrics=METRICS, chunk_size=CHUNK,
            budget=Budget(area_mm2=1e-6), budget_stats=stats)
        assert len(archive) == 0
        assert trace_count() == 0               # no dataflow compilation
        assert stats.pruned == stats.evaluated
        assert stats.feasible == 0

    def test_workload_stage_kills_counted_over_survivors(self, workload):
        """Two-stage workload-stage kill counts cover only config-feasible
        lanes (documented semantics): with an area bound plus an
        impossible latency bound, latency kills == area survivors."""
        ref = np.concatenate([np.asarray(r.area_mm2) for r, _ in
                              evaluate_space_streaming(
                                  workload, TINY_SPACE, chunk_size=CHUNK)])
        bound = float(np.median(ref))
        stats = BudgetStats()
        archive, _ = pareto_front_streaming(
            workload, TINY_SPACE, metrics=METRICS, chunk_size=CHUNK,
            budget=Budget(area_mm2=bound, latency_s=1e-12),
            budget_stats=stats)
        assert len(archive) == 0
        survivors = int((ref <= bound).sum())
        assert stats.kills[f"area_mm2<={bound:g}"] == len(ref) - survivors
        assert stats.kills["latency_s<=1e-12"] == survivors
        assert stats.pruned == len(ref) - survivors
        assert stats.feasible == 0
