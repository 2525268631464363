"""Phase-aware layer IR + LLM serving workload families.

Covers the PR-9 contracts: the first_dense/dense_d_ff extraction fix
(regression vs ``repro.configs.deepseek_moe_16b``), closed-form MACs
identities for decode-vs-prefill and MoE top-k gating across every
``repro.configs`` arch, memory-bound decode attention at long context,
per-layer-class accuracy sensitivity (opt-in, exact legacy path when
off), the IR-aware workload signature, Parquet front export, and the
bit-identity of serving-model joint sweeps across walks, shards,
backends, pruning and the frontserver.
"""

import csv
import os

import jax
import numpy as np
import pytest

try:
    from hypothesis import given, settings, strategies as st
except ImportError:  # CI images without hypothesis: deterministic fallback
    from _hypothesis_fallback import given, settings, st

from repro.configs import ARCH_IDS, get, reduced
from repro.core import (ACC_CLASS_SENS, AccuracySurrogate, Budget,
                        accuracy_matrix, coexplore_front, default_model_set,
                        enumerate_space, export_front_csv,
                        export_front_parquet, fit_ppa_models, layer_bucket,
                        lightpe_claim, llm_decode, llm_moe, make_config,
                        model_entry, resnet_cifar, touched_experts,
                        transformer_workload, workload_layers, workload_macs,
                        workloads_signature)
from repro.core.arch import AcceleratorConfig
from repro.core.dataflow import layer_cost, network_cost
from repro.core.dse import reset_trace_count, trace_count
from repro.core.workloads import (ACC_CLASSES, ACC_DEFAULT, KIND_ATTN_KV,
                                  KIND_CONV, KIND_GEMM, LAYER_KINDS,
                                  LayerSpec, acc_class_mix, gemm, pad_workload)
from repro.serve import FrontServer

TINY_SPACE = dict(
    pe_rows=(8, 12), pe_cols=(8, 14), gbuf_kb=(54.0,), spad_ifmap=(12,),
    spad_filter=(112, 224), spad_psum=(16,),
    pe_type=tuple(range(5)), bandwidth_gbps=(25.6,),
)
CHUNK = 16
SEQ = 16


@pytest.fixture(scope="module")
def serving_models():
    """A reduced-size serving model axis: decode + MoE on the phase-aware
    IR, plus a legacy CNN lane (mixed chunks must stay exact)."""
    return (
        model_entry(llm_decode(reduced("qwen3-32b"), context=256),
                    acc_classes=True),
        model_entry(llm_moe(reduced("deepseek-moe-16b"), seq=64,
                            mode="decode"), acc_classes=True),
        model_entry(resnet_cifar(20)),
    )


@pytest.fixture(scope="module")
def ppa_models():
    return fit_ppa_models(enumerate_space(max_points=500, seed=1),
                          degrees=(1, 2), k=4)


def _assert_front_identical(a, b):
    """Indices, objectives AND row order — the bit-identity contract."""
    np.testing.assert_array_equal(a.archive.indices, b.archive.indices)
    np.testing.assert_array_equal(a.archive.objectives, b.archive.objectives)


def _row(wl, tag):
    i = wl.layer_names.index(tag)
    return LayerSpec(*[np.asarray(getattr(wl.layers, f))[i]
                       for f in LayerSpec._fields])


# ---------------------------------------------------------------------------
# Satellite 1: first_dense / dense_d_ff extraction fix
# ---------------------------------------------------------------------------

class TestFirstDenseFix:
    def test_deepseek_dense_first_layer_extracted_as_dense(self):
        """DeepSeekMoE-16B: layer 0 is a DENSE FFN at dense_d_ff width;
        the remaining 27 layers are routed experts.  The pre-fix code read
        a nonexistent ``dense_layers`` attribute and emitted all 28 layers
        as expert layers."""
        cfg = get("deepseek-moe-16b")
        assert cfg.first_dense == 1 and cfg.dense_d_ff > 0  # fixture sanity
        wl = transformer_workload(cfg, seq=SEQ, batch=1, mode="prefill")
        ffn = _row(wl, "ffn_in")
        moe = _row(wl, "moe_in")
        assert float(ffn.count) == float(cfg.first_dense)
        assert float(ffn.K) == 2.0 * cfg.dense_d_ff   # gate+up at dense width
        assert float(moe.count) == float(cfg.n_layers - cfg.first_dense)
        assert float(moe.K) == 2.0 * cfg.moe_d_ff
        # shared (always-on) experts ride along as resident rows
        sh = _row(wl, "moe_shared_in")
        assert float(sh.count) == float(
            (cfg.n_layers - cfg.first_dense) * cfg.moe_shared)

    def test_non_moe_config_unaffected(self):
        cfg = reduced("qwen3-32b")
        wl = transformer_workload(cfg, seq=SEQ, batch=1, mode="prefill")
        assert "moe_in" not in wl.layer_names
        assert float(_row(wl, "ffn_in").count) == float(cfg.n_layers)


# ---------------------------------------------------------------------------
# Satellite 3: closed-form MACs identities across the configs registry
# ---------------------------------------------------------------------------

class TestMacsIdentities:
    @pytest.mark.parametrize("arch", sorted(ARCH_IDS))
    def test_prefill_is_seq_times_decode(self, arch):
        """Every extracted row's M dimension is linear in the token count
        and nothing else differs between phases, so prefill at seq tokens
        does exactly seq times the decode-step MACs (same context)."""
        cfg = reduced(arch)
        pre = workload_macs(transformer_workload(cfg, seq=SEQ, batch=1,
                                                 mode="prefill"))
        dec = workload_macs(transformer_workload(cfg, seq=SEQ, batch=1,
                                                 mode="decode"))
        assert pre == pytest.approx(SEQ * dec, rel=1e-6)

    @pytest.mark.parametrize("arch", ["deepseek-moe-16b",
                                      "phi3.5-moe-42b-a6.6b"])
    def test_moe_active_macs_linear_in_topk(self, arch):
        """Active (gated) MACs scale linearly in top-k: the layer shape
        carries the ACTIVE compute, so m(k) = const + slope*k exactly."""
        cfg = reduced(arch)
        m = {k: workload_macs(llm_moe(cfg, topk=k, seq=SEQ, mode="decode"))
             for k in (1, 2, 4)}
        assert m[2] > m[1]
        assert m[4] - m[2] == pytest.approx(2.0 * (m[2] - m[1]), rel=1e-6)

    def test_decode_touches_exactly_topk_experts(self):
        assert touched_experts(64, 6, 1) == pytest.approx(6.0)
        assert touched_experts(8, 2, 1) == pytest.approx(2.0)
        # many routed tokens saturate toward the full expert set
        assert touched_experts(64, 6, 100_000) == pytest.approx(64.0)
        # monotone in routed tokens
        ts = [touched_experts(64, 6, n) for n in (1, 4, 64, 4096)]
        assert all(a <= b for a, b in zip(ts, ts[1:]))

    def test_llm_moe_rejects_dense_configs(self):
        with pytest.raises(ValueError):
            llm_moe("qwen3-32b")


# ---------------------------------------------------------------------------
# Acceptance: decode attention is memory-bound at long context
# ---------------------------------------------------------------------------

class TestDecodeMemoryBound:
    @pytest.mark.parametrize("arch,context", [
        ("qwen3-32b", 1024), ("qwen3-32b", 8192),
        ("deepseek-moe-16b", 4096),
    ])
    def test_streamed_kv_layers_memory_bound(self, arch, context):
        """The attn_kv rows stream the KV cache with no reuse: at serving
        context lengths their DRAM time dwarfs their matrix-vector
        compute (cycles_memory > cycles_compute) — the arithmetic-
        intensity cliff the decode family exists to model."""
        wl = llm_decode(arch, context=context)
        pl = jax.vmap(layer_cost, in_axes=(0, None, None))(
            wl.layers, make_config(), np.float32(1.0))
        kinds = np.asarray(wl.layers.kind)
        assert (kinds == float(KIND_ATTN_KV)).sum() == 2  # qk + av
        for i, name in enumerate(wl.layer_names):
            if kinds[i] == float(KIND_ATTN_KV):
                assert float(pl.cycles_memory[i]) \
                    > float(pl.cycles_compute[i]), name

    def test_stream_words_grow_linearly_with_context(self):
        """The streamed KV operand is exactly context x head_dim words per
        batch element — linear in context (total DRAM adds replay terms on
        top, so the invariant lives on the stream field itself)."""
        def stream(context):
            wl = llm_decode("qwen3-32b", context=context)
            sel = np.asarray(wl.layers.kind) == float(KIND_ATTN_KV)
            return np.asarray(wl.layers.stream_words)[sel]
        np.testing.assert_allclose(stream(8192), 4.0 * stream(2048),
                                   rtol=1e-6)

    def test_prefill_attention_stays_resident(self):
        wl = transformer_workload(reduced("qwen3-32b"), seq=SEQ, batch=1,
                                  mode="prefill")
        assert not np.any(np.asarray(wl.layers.kind) == float(KIND_ATTN_KV))


# ---------------------------------------------------------------------------
# Tentpole: neutral IR fields reproduce the legacy cost model bit-exactly
# ---------------------------------------------------------------------------

class TestNeutralIRBitIdentity:
    def test_defaulted_fields_are_neutral(self):
        wl = resnet_cifar(20)
        # conv rows stay conv; the fc head is tagged gemm — both are
        # resident-weight kinds on the identical legacy cost path
        assert np.all(np.isin(np.asarray(wl.layers.kind),
                              [float(KIND_CONV), float(KIND_GEMM)]))
        assert np.all(np.asarray(wl.layers.stream_words) == 0.0)
        assert np.all(np.asarray(wl.layers.active_frac) == 1.0)
        assert np.all(np.asarray(wl.layers.acc_class) == float(ACC_DEFAULT))

    def test_gemm_kind_costs_identically_to_conv_kind(self):
        """conv and gemm are both resident-weight kinds: re-tagging must
        not move a single bit of the cost."""
        a = LayerSpec(**{k: np.float32(v) for k, v in
                         gemm(32, 64, 128, kind=KIND_CONV).items()})
        b = LayerSpec(**{k: np.float32(v) for k, v in
                         gemm(32, 64, 128, kind=KIND_GEMM).items()})
        cfg = make_config()
        ca = layer_cost(a, cfg, np.float32(1.0))
        cb = layer_cost(b, cfg, np.float32(1.0))
        for f, va, vb in zip(ca._fields, ca, cb):
            np.testing.assert_array_equal(np.asarray(va), np.asarray(vb),
                                          err_msg=f)

    def test_padding_contract_holds_for_serving_workloads(self):
        """count=0 padding rows still contribute exact 0.0 under the IR:
        a padded serving workload reduces to the unpadded oracle's bits."""
        cfg = make_config()
        for wl in (llm_decode(reduced("qwen3-32b"), context=128),
                   llm_moe(reduced("deepseek-moe-16b"), seq=32)):
            base = network_cost(wl.layers, cfg, np.float32(1.0))
            padded = network_cost(
                pad_workload(wl, workload_layers(wl) + 5).layers,
                cfg, np.float32(1.0))
            for f, va, vb in zip(base._fields, base, padded):
                np.testing.assert_array_equal(np.asarray(va), np.asarray(vb),
                                              err_msg=f)


# ---------------------------------------------------------------------------
# Tentpole: serving sweeps bit-identical across walks/shards/backends/pruning
# ---------------------------------------------------------------------------

class TestServingSweepBitIdentity:
    def test_default_zoo_includes_serving_members_same_buckets(self):
        models = default_model_set()
        names = [m.name for m in models]
        assert any("decode" in n for n in names)
        assert any("-moe-" in n for n in names)
        assert {layer_bucket(workload_layers(m.workload))
                for m in models} == {16, 32, 64}

    def test_compile_count_is_bucket_count(self, serving_models):
        from repro.core.dse import _network_sums, _ppa_stage
        _network_sums.clear_cache()
        _ppa_stage.clear_cache()
        reset_trace_count()
        front = coexplore_front(serving_models, TINY_SPACE, chunk_size=CHUNK)
        assert trace_count() == len(front.buckets)

    @given(shards=st.sampled_from([2, 8]), prune=st.booleans(),
           use_surrogate=st.booleans())
    @settings(max_examples=8, deadline=None)
    def test_sharded_pruned_backends(self, serving_models, ppa_models,
                                     shards, prune, use_surrogate):
        """The acceptance matrix: {sharded, unsharded} x {oracle,
        surrogate} x {pruned, unpruned} all yield the identical front for
        the serving model axis."""
        budget = Budget(area_mm2=2.0)
        sur = ppa_models if use_surrogate else None
        ref = coexplore_front(serving_models, TINY_SPACE, chunk_size=CHUNK,
                              surrogate=sur, budget=budget, prune=False)
        got = coexplore_front(serving_models, TINY_SPACE, chunk_size=CHUNK,
                              surrogate=sur, budget=budget, prune=prune,
                              shards=shards)
        _assert_front_identical(got, ref)
        assert got.budget_stats.feasible == ref.budget_stats.feasible

    def test_per_model_walk_matches_mixed(self, serving_models):
        mixed = coexplore_front(serving_models, TINY_SPACE, chunk_size=CHUNK)
        per = coexplore_front(serving_models, TINY_SPACE, chunk_size=CHUNK,
                              mix_models=False)
        _assert_front_identical(per, mixed)

    def test_claim_reported_per_serving_family(self, serving_models):
        """Decode and MoE members sweep end-to-end and the LightPE claim
        is evaluated (determinately) for each serving family member."""
        front = coexplore_front(serving_models, TINY_SPACE, chunk_size=CHUNK)
        claim = lightpe_claim(front)
        for m in serving_models:
            verdict = claim["per_model"][m.name]
            assert verdict["ok"] is not None
            assert "lightpe1" in verdict and "lightpe2" in verdict

    def test_frontserver_serves_serving_models(self, serving_models):
        """The serving axis through the frontserver: bit-identical to the
        standalone sweep, and the signature carries the workloads digest
        (IR-aware cache keys)."""
        srv = FrontServer(serving_models, TINY_SPACE, chunk_size=CHUNK)
        assert srv.signature["workloads"] \
            == workloads_signature(serving_models)
        budget = Budget(area_mm2=2.0)
        resp = srv.query(budget)
        ref = coexplore_front(serving_models, TINY_SPACE, chunk_size=CHUNK,
                              budget=budget, prune=False)
        _assert_front_identical(resp, ref)
        # warm repeat: served from cache, still identical
        resp2 = srv.query(budget)
        assert resp2.served_from.startswith("cache")
        _assert_front_identical(resp2, ref)


# ---------------------------------------------------------------------------
# Tentpole: per-layer-class accuracy sensitivity (opt-in, exact when off)
# ---------------------------------------------------------------------------

class TestLayerClassAccuracy:
    def test_default_class_sensitivity_is_exactly_one(self):
        assert ACC_CLASS_SENS["default"] == 1.0

    def test_none_and_all_default_mix_are_exact_legacy(self):
        acc = AccuracySurrogate()
        all_default = tuple(1.0 if i == 0 else 0.0
                            for i in range(len(ACC_CLASSES)))
        for pe in ("int16", "lightpe1"):
            base = acc.delta_pp(pe, macs=1e9)
            assert acc.delta_pp(pe, macs=1e9, class_mix=None) == base
            assert acc.delta_pp(pe, macs=1e9, class_mix=all_default) == base
        assert acc.class_multiplier(None) == 1.0
        assert acc.class_multiplier(all_default) == 1.0

    def test_attn_heavy_mix_amplifies_ffn_heavy_shrinks(self):
        acc = AccuracySurrogate()
        attn_mix = (0.0, 1.0, 0.0, 0.0)
        ffn_mix = (0.0, 0.0, 1.0, 0.0)
        assert acc.class_multiplier(attn_mix) > 1.0
        assert acc.class_multiplier(ffn_mix) < 1.0
        base = abs(acc.delta_pp("lightpe1", macs=1e9))
        assert abs(acc.delta_pp("lightpe1", macs=1e9,
                                class_mix=attn_mix)) > base

    def test_acc_class_mix_sums_to_one_and_tags_serving(self):
        dec = llm_decode(reduced("qwen3-32b"), context=128)
        mix = acc_class_mix(dec)
        assert sum(mix) == pytest.approx(1.0)
        assert mix[ACC_CLASSES.index("attn")] > 0.0
        cnn_mix = acc_class_mix(resnet_cifar(20))
        assert cnn_mix == tuple(1.0 if i == 0 else 0.0
                                for i in range(len(ACC_CLASSES)))

    def test_accuracy_matrix_untagged_rows_unchanged(self, serving_models):
        tagged = accuracy_matrix(serving_models)
        untagged = accuracy_matrix([m._replace(acc_mix=None)
                                    for m in serving_models])
        # CNN lane (no classes): bit-equal either way
        np.testing.assert_array_equal(tagged[2], untagged[2])
        # serving lanes: the class mix moves the predicted deltas
        assert np.abs(tagged[:2] - untagged[:2]).max() > 0.0

    def test_unknown_class_sens_key_rejected(self):
        with pytest.raises(KeyError):
            AccuracySurrogate(class_sens={"bogus": 2.0})

    def test_bad_mix_length_rejected(self):
        with pytest.raises(ValueError):
            AccuracySurrogate().class_multiplier((1.0, 0.0))


# ---------------------------------------------------------------------------
# IR-aware signatures
# ---------------------------------------------------------------------------

class TestWorkloadsSignature:
    def test_stable_and_ir_sensitive(self):
        cfg = reduced("qwen3-32b")
        a = (model_entry(llm_decode(cfg, context=128), acc_classes=True),)
        b = (model_entry(llm_decode(cfg, context=128), acc_classes=True),)
        # same extraction -> same digest; the name alone is NOT the key
        assert workloads_signature(a) == workloads_signature(b)
        c = (model_entry(llm_decode(cfg, context=256, name=a[0].name),
                         acc_classes=True),)
        assert workloads_signature(a) != workloads_signature(c)

    def test_topk_regating_changes_digest(self):
        cfg = reduced("deepseek-moe-16b")
        nm = "fixed-name"
        a = (model_entry(llm_moe(cfg, topk=1, seq=32, name=nm)),)
        b = (model_entry(llm_moe(cfg, topk=2, seq=32, name=nm)),)
        assert workloads_signature(a) != workloads_signature(b)


# ---------------------------------------------------------------------------
# Satellite 2: Parquet front export
# ---------------------------------------------------------------------------

class TestParquetExport:
    def test_round_trip_matches_csv(self, serving_models, tmp_path):
        pa = pytest.importorskip("pyarrow")
        pq = pytest.importorskip("pyarrow.parquet")
        front = coexplore_front(serving_models, TINY_SPACE, chunk_size=CHUNK)
        csv_path = os.path.join(tmp_path, "front.csv")
        pq_path = os.path.join(tmp_path, "front.parquet")
        export_front_csv(csv_path, front.archive, front.metrics,
                         space=TINY_SPACE, models=front.models)
        export_front_parquet(pq_path, front.archive, front.metrics,
                             space=TINY_SPACE, models=front.models)
        table = pq.read_table(pq_path)
        with open(csv_path, newline="") as f:
            rows = list(csv.DictReader(f))
        assert table.num_rows == len(rows) == len(front.archive.indices)
        cols = table.to_pydict()
        assert list(cols) == list(rows[0])  # same columns, same order
        for i, row in enumerate(rows):
            assert cols["index"][i] == int(row["index"])
            assert cols["model"][i] == row["model"]
            assert cols["pe_type_name"][i] == row["pe_type_name"]
            for m in front.metrics:
                # CSV stores repr(float) -> exact round-trip comparison
                assert cols[m][i] == float(row[m])
            for k in AcceleratorConfig._fields:
                assert float(cols[k][i]) == float(row[k])

    def test_atomic_no_partial_file_on_missing_dep(self, serving_models,
                                                   tmp_path, monkeypatch):
        """Without pyarrow the exporter raises a RuntimeError up front and
        never leaves a partial file behind."""
        import builtins
        real_import = builtins.__import__

        def no_pyarrow(name, *a, **k):
            if name.startswith("pyarrow"):
                raise ImportError(name)
            return real_import(name, *a, **k)
        monkeypatch.setattr(builtins, "__import__", no_pyarrow)
        front = coexplore_front(serving_models, TINY_SPACE, chunk_size=CHUNK)
        path = os.path.join(tmp_path, "front.parquet")
        with pytest.raises(RuntimeError, match="pyarrow"):
            export_front_parquet(path, front.archive, front.metrics,
                                 space=TINY_SPACE, models=front.models)
        assert not os.path.exists(path)
        assert not os.path.exists(path + ".tmp")


class TestIRRegistry:
    def test_kind_and_class_registries(self):
        assert LAYER_KINDS == ("conv", "gemm", "attn_kv", "moe_expert")
        assert ACC_CLASSES == ("default", "attn", "ffn", "expert")
        assert set(ACC_CLASS_SENS) == set(ACC_CLASSES)
