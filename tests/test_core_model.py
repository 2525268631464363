"""QADAM core: dataflow cost model, synthesis oracle, PPA fit, DSE/Pareto."""

import jax.numpy as jnp
import numpy as np
import pytest
try:
    from hypothesis import given, settings, strategies as st
except ImportError:  # CI images without hypothesis: deterministic fallback
    from _hypothesis_fallback import given, settings, st

from repro.core import (PAPER_WORKLOADS, enumerate_space, evaluate_space,
                        fit_ppa_models, make_config, normalized_report,
                        pareto_mask, r2, mape, spread, synthesize)
from repro.core.arch import PE_TYPE_NAMES, stack_configs
from repro.core.dataflow import layer_cost, network_cost
from repro.core.ppa import config_features
from repro.core.workloads import LayerSpec, gemm, vgg16


def _layer(**kw):
    d = dict(H=34, W=34, C=16, K=32, R=3, S=3, stride=1, batch=1, count=1)
    d.update(kw)
    return LayerSpec(**{k: jnp.asarray(v, jnp.float32) for k, v in d.items()})


class TestDataflow:
    def test_macs(self):
        ly = _layer()
        # E = F = 32; MACs = K*C*R*S*E*F
        assert float(ly.macs()) == 32 * 16 * 9 * 32 * 32

    def test_cycles_lower_bound(self):
        """Compute cycles >= MACs / total PEs (can't beat full utilization)."""
        ly = _layer()
        cfg = make_config()
        c = layer_cost(ly, cfg, jnp.asarray(1.0))
        assert float(c.cycles_compute) >= float(ly.macs()) / \
            float(cfg.pe_rows * cfg.pe_cols) - 1
        assert 0 < float(c.utilization) <= 1

    def test_dram_compulsory_traffic(self):
        """DRAM bits >= one read of ifmap+filters and one write of ofmap."""
        ly = _layer()
        cfg = make_config(pe_type="int16")
        c = layer_cost(ly, cfg, jnp.asarray(1.0))
        a_bits = w_bits = 16
        compulsory = (34 * 34 * 16 * a_bits + 32 * 16 * 9 * w_bits
                      + 32 * 32 * 32 * a_bits)
        assert float(c.dram_bits) >= compulsory

    def test_bandwidth_monotone(self):
        ly = _layer(C=64, K=128)
        lo = layer_cost(ly, make_config(bandwidth_gbps=4.0), jnp.asarray(1.0))
        hi = layer_cost(ly, make_config(bandwidth_gbps=64.0), jnp.asarray(1.0))
        assert float(hi.cycles) <= float(lo.cycles)

    def test_lower_precision_less_energy_and_traffic(self):
        ly = _layer(C=64, K=64)
        costs = {pe: layer_cost(ly, make_config(pe_type=pe), jnp.asarray(1.0))
                 for pe in ("fp32", "int16", "lightpe1")}
        assert float(costs["fp32"].energy_pj) > \
            float(costs["int16"].energy_pj) > \
            float(costs["lightpe1"].energy_pj)
        assert float(costs["fp32"].dram_bits) > \
            float(costs["int16"].dram_bits) > \
            float(costs["lightpe1"].dram_bits)

    @given(k=st.integers(4, 256), c=st.integers(1, 128),
           hw=st.integers(4, 64))
    @settings(max_examples=25, deadline=None)
    def test_costs_positive_finite(self, k, c, hw):
        ly = _layer(H=hw + 2, W=hw + 2, C=c, K=k)
        cost = layer_cost(ly, make_config(), jnp.asarray(1.0))
        for leaf in cost:
            v = float(leaf)
            assert np.isfinite(v) and v >= 0

    def test_network_sums_layers(self):
        wl = vgg16("cifar10")
        cfg = make_config()
        total = network_cost(wl.layers, cfg, jnp.asarray(1.0))
        assert float(total.macs) == pytest.approx(
            float(wl.layers.macs().sum()), rel=1e-5)


class TestSynth:
    def test_deterministic(self):
        cfg = make_config()
        a, b = synthesize(cfg), synthesize(cfg)
        assert float(a.area_mm2) == float(b.area_mm2)

    def test_bigger_array_more_area_power(self):
        small = synthesize(make_config(pe_rows=8, pe_cols=8))
        big = synthesize(make_config(pe_rows=32, pe_cols=32))
        assert float(big.area_mm2) > float(small.area_mm2)
        assert float(big.power_mw) > float(small.power_mw)

    def test_pe_type_ordering(self):
        """fp32 > int16 > lightpe2 > lightpe1 on PE-dominated area/power."""
        res = {pe: synthesize(make_config(pe_type=pe, pe_rows=24, pe_cols=28))
               for pe in ("fp32", "int16", "lightpe2", "lightpe1")}
        areas = [float(res[p].area_mm2)
                 for p in ("fp32", "int16", "lightpe2", "lightpe1")]
        assert areas == sorted(areas, reverse=True)
        clocks = [float(res[p].clock_ghz)
                  for p in ("fp32", "int16", "lightpe2", "lightpe1")]
        assert clocks == sorted(clocks)

    def test_noise_is_the_same_compiled_or_not(self):
        """The noise hash is integer arithmetic: fusing it under jit
        cannot change a bit of it; the factor stays within +-3% and moves
        by no more than an ulp."""
        import jax
        from repro.core.synth import _hash_units, _noise
        space = enumerate_space(max_points=2000, seed=0)
        for salt in (1, 2, 3):
            for eager, fused in zip(
                    _hash_units(space, salt),
                    jax.jit(_hash_units, static_argnums=1)(space, salt)):
                np.testing.assert_array_equal(eager, fused)
            eager = np.asarray(_noise(space, salt))
            fused = np.asarray(jax.jit(_noise, static_argnums=1)(space, salt))
            np.testing.assert_allclose(fused, eager, rtol=2.4e-7, atol=0)
            assert np.all(np.abs(eager - 1.0) <= 0.03)
            assert np.std(eager) > 0.01

    def test_log2_within_an_ulp(self):
        from repro.core.synth import _log2
        x = np.concatenate([np.arange(2, 5000, dtype=np.float32),
                            np.geomspace(2, 2 ** 30, 5000, dtype=np.float32)])
        got = np.asarray(_log2(jnp.asarray(x)), np.float64)
        want = np.log2(x.astype(np.float64))
        ulp = np.spacing(want.astype(np.float32)).astype(np.float64)
        assert np.max(np.abs(got - want) / ulp) <= 1.0


class TestPPAFit:
    def test_fit_quality(self):
        """The paper's Fig. 3: polynomial PPA models agree closely."""
        space = enumerate_space(max_points=600, seed=1)
        models = fit_ppa_models(space, degrees=(1, 2), k=4)
        truth = synthesize(space)
        pred = models.predict(space)
        for t in ("power_mw", "clock_ghz", "area_mm2"):
            assert r2(getattr(truth, t), getattr(pred, t)) > 0.97, t
            assert mape(getattr(truth, t), getattr(pred, t)) < 0.08, t


class TestPPAHardening:
    def test_predict_missing_pe_type_raises(self):
        """Lanes of an unfitted PE type used to silently predict zero
        power/clock/area (1e6 ns crit path, +inf perf/area downstream);
        the surrogate must name the missing types loudly instead."""
        int16_only = enumerate_space(dict(
            pe_rows=(8, 12, 16), pe_cols=(8, 14), gbuf_kb=(54.0, 108.0),
            spad_ifmap=(12, 24), spad_filter=(112, 224), spad_psum=(16, 24),
            pe_type=(1,), bandwidth_gbps=(12.8, 25.6)))
        models = fit_ppa_models(int16_only, degrees=(1,), k=3)
        mixed = stack_configs([make_config(pe_type="int16"),
                               make_config(pe_type="lightpe1"),
                               make_config(pe_type="fp32")])
        with pytest.raises(ValueError) as e:
            models.predict(mixed)
        assert "lightpe1" in str(e.value) and "fp32" in str(e.value)
        assert "int16" not in str(e.value).split("fitted:")[0]
        # fitted types still predict fine
        res = models.predict(stack_configs([make_config(pe_type="int16")]))
        assert np.isfinite(np.asarray(res.clock_ghz)).all()
        assert (np.asarray(res.area_mm2) > 0).all()

    @pytest.mark.parametrize("code", [-1, 99])
    def test_predict_out_of_range_code_raises(self, code):
        """A negative code would alias a real PE type through Python
        indexing (its lanes silently keeping zero predictions); an
        oversized one would IndexError — both must fail as ValueError."""
        space = enumerate_space(max_points=200, seed=5)
        models = fit_ppa_models(space, degrees=(1,), k=3)
        bad = stack_configs([make_config(pe_type=code)])
        with pytest.raises(ValueError, match="not a known PE type"):
            models.predict(bad)

    def test_surrogate_leakage_matches_oracle_density(self):
        """The surrogate derives leakage from predicted area with the SAME
        named constant the synthesis oracle uses (no drifting duplicate)."""
        from repro.core.synth import LEAKAGE_MW_PER_MM2
        space = enumerate_space(max_points=300, seed=7)
        models = fit_ppa_models(space, degrees=(1,), k=3)
        pred = models.predict(space)
        np.testing.assert_allclose(
            np.asarray(pred.leakage_mw),
            LEAKAGE_MW_PER_MM2 * np.asarray(pred.area_mm2), rtol=1e-6)
        truth = synthesize(space)
        np.testing.assert_allclose(
            np.asarray(truth.leakage_mw),
            LEAKAGE_MW_PER_MM2 * np.asarray(truth.area_mm2), rtol=1e-6)

    def test_kfold_clamps_k_to_sample_count(self):
        """k > n used to split into empty folds whose MSE is a mean over
        an empty slice (NaN + RuntimeWarning), silently breaking degree
        selection; the fold count is clamped instead."""
        from repro.core.ppa import kfold_mse, select_and_fit
        x = config_features(enumerate_space(max_points=3, seed=2))
        y = jnp.asarray([1.0, 2.0, 3.0])
        import warnings
        with warnings.catch_warnings():
            warnings.simplefilter("error", RuntimeWarning)
            mse = kfold_mse(x, y, degree=1, k=5)
        assert np.isfinite(mse)
        # degree selection over the tiny sample stays NaN-free too
        model = select_and_fit(x, y, degrees=(1, 2), k=5)
        assert model.degree in (1, 2)

    def test_kfold_needs_two_samples(self):
        from repro.core.ppa import kfold_mse
        x = config_features(enumerate_space(max_points=1, seed=2))
        with pytest.raises(ValueError, match=">= 2"):
            kfold_mse(x, jnp.asarray([1.0]), degree=1)


class TestPareto:
    def test_pareto_mask_correct(self, rng):
        pts = jnp.asarray(rng.normal(size=(200, 2)))
        mask = np.asarray(pareto_mask(pts))
        pts = np.asarray(pts)
        for i in range(len(pts)):
            dominated = bool(np.any(np.all(pts >= pts[i], axis=1)
                                    & np.any(pts > pts[i], axis=1)))
            assert mask[i] == (not dominated)

    def test_front_nonempty_and_contains_max(self, rng):
        pts = jnp.asarray(rng.normal(size=(64, 3)))
        mask = np.asarray(pareto_mask(pts))
        assert mask.any()
        assert mask[int(np.argmax(np.asarray(pts)[:, 0]))]


class TestDSE:
    @pytest.fixture(scope="class")
    def space_result(self):
        space = enumerate_space(max_points=1200, seed=0)
        wl = PAPER_WORKLOADS["resnet20-cifar10"]()
        return space, evaluate_space(space, wl)

    def test_paper_fig2_spread(self, space_result):
        """Fig. 2: perf/area and energy vary widely (>5x / and decades)."""
        _, res = space_result
        sp = spread(res)
        assert sp["perf_per_area_spread"] > 5.0
        assert sp["energy_spread"] > 5.0

    def test_paper_fig4_lightpe_dominance(self, space_result):
        """LightPEs beat the best INT16 config on both axes (paper's main
        claim); exact ratios are reported in benchmarks/fig4_dse.py."""
        space, res = space_result
        rep = normalized_report(res, space)
        assert rep["lightpe1"]["norm_perf_per_area"] > 2.0
        assert rep["lightpe2"]["norm_perf_per_area"] > 1.5
        assert rep["lightpe1"]["norm_energy"] < 0.5
        assert rep["lightpe2"]["norm_energy"] < 0.6
        # INT16 dominates FP32
        assert rep["fp32"]["norm_perf_per_area"] < 1.0
        assert rep["fp32"]["norm_energy"] > rep["int16"]["norm_energy"]

    def test_surrogate_agrees_with_oracle(self, space_result):
        space, res = space_result
        models = fit_ppa_models(enumerate_space(max_points=500, seed=3),
                                degrees=(2,), k=3)
        res_pred = evaluate_space(
            space, PAPER_WORKLOADS["resnet20-cifar10"](), surrogate=models)
        # DSE conclusions stable under the surrogate (Fig. 3's purpose)
        rep_o = normalized_report(res, space)
        rep_p = normalized_report(res_pred, space)
        for pe in ("lightpe1", "lightpe2"):
            assert rep_p[pe]["norm_perf_per_area"] == pytest.approx(
                rep_o[pe]["norm_perf_per_area"], rel=0.25)
