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
        """Every row both phases extract has its M dimension linear in the
        token count and nothing else differs between phases, so prefill
        at seq tokens does exactly seq times the decode-step MACs over
        those rows (same context).  Every config extracts the same rows
        in both phases, except latent attention's core rows (absorbed in
        decode, decompressed in prefill; ``TestLatentAttentionRows``)."""
        cfg = reduced(arch)
        pre = transformer_workload(cfg, seq=SEQ, batch=1, mode="prefill")
        dec = transformer_workload(cfg, seq=SEQ, batch=1, mode="decode")
        if not cfg.kv_lora_rank:
            assert pre.layer_names == dec.layer_names
        shared = [n for n in pre.layer_names if n in dec.layer_names]

        def macs(wl):
            m = np.asarray(wl.layers.macs(), np.float64)
            return sum(m[wl.layer_names.index(n)] for n in shared)
        assert macs(pre) == pytest.approx(SEQ * macs(dec), rel=1e-6)

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
# DeepSeek-V3's multi-head latent attention: absorbed decode rows
# ---------------------------------------------------------------------------

# The three decode steps of the dsv3_mla_decode_mapped benchmark
# configuration: 524,288 cached positions per step each.
MLA_STEPS = [(16384, 32), (65536, 8), (131072, 4)]

# Sha256 prefixes of the decode tables the llm_decode_mapped benchmark
# configuration prices and of prefill tables of the same models, taken
# before latent attention was added: no other config's table moves.
TABLE_DIGESTS = {
    "qwen3-32b-decode": "6688dd7c849eed00",
    "deepseek-moe-16b-decode": "cae8ead88b88d43a",
    "phi35-moe-decode": "a62492f0b1da22a6",
    "qwen3-32b-train": "aab61f3f90762768",
    "deepseek-moe-16b-train": "fa4a30a9324d8b80",
    "phi3.5-moe-42b-a6.6b-train": "cc8244882ae09ed7",
}


def _table_digest(wl):
    import hashlib
    h = hashlib.sha256(wl.name.encode())
    for n in wl.layer_names:
        h.update(n.encode())
    for f in LayerSpec._fields:
        h.update(np.asarray(getattr(wl.layers, f), np.float32).tobytes())
    return h.hexdigest()[:16]


class TestLatentAttentionRows:
    @staticmethod
    def _rows(wl):
        cols = ("W", "C", "K", "count", "batch", "kind", "stream_words",
                "acc_class")
        return {n: tuple(float(np.asarray(getattr(wl.layers, f))[i])
                         for f in cols)
                for i, n in enumerate(wl.layer_names)}

    @pytest.mark.parametrize("context,batch", MLA_STEPS)
    def test_decode_rows_closed_form(self, context, batch):
        """The 16 rows of one decode step, (M, Kd, N, count) each, from
        the published widths: the absorbed attention rows, then the
        dense, expert, shared-expert, router and head rows."""
        cfg = get("deepseek-v3")
        wl = llm_decode("deepseek-v3", context=context, batch=batch)
        assert wl.name == f"deepseek-v3-decode-c{context}-b{batch}"
        L, hq, d = 61, 128, 7168
        ql, r, nope, p, dv = 1536, 512, 128, 64, 128
        touched = touched_experts(256, 8, batch)
        gemm_, attn_kv, gated = float(KIND_GEMM), float(KIND_ATTN_KV), 3.0
        A, F, X, D = 1.0, 2.0, 3.0, 0.0        # accuracy classes
        want = {
            "wq_a": (1, d, ql, L, gemm_, 0, A),
            "wq_b": (1, ql, hq * (nope + p), L, gemm_, 0, A),
            "wkv_a": (1, d, r + p, L, gemm_, 0, A),
            "q_absorb": (1, nope, r, L * hq, gemm_, 0, A),
            "qk_latent": (hq, r + p, context, L, attn_kv,
                          context * (r + p), A),
            "av_latent": (hq, context, r, L, attn_kv, context * r, A),
            "v_absorb": (1, r, dv, L * hq, gemm_, 0, A),
            "wo": (1, hq * dv, d, L, gemm_, 0, A),
            "ffn_in": (1, d, 2 * 18432, 3, gemm_, 0, F),
            "ffn_out": (1, 18432, d, 3, gemm_, 0, F),
            "moe_in": (8, d, 2 * 2048, 58, gated, 0, X),
            "moe_out": (8, 2048, d, 58, gated, 0, X),
            "moe_shared_in": (1, d, 2 * 2048, 58, gemm_, 0, X),
            "moe_shared_out": (1, 2048, d, 58, gemm_, 0, X),
            "router": (1, d, 256, 58, gemm_, 0, F),
            "lm_head": (1, d, 129280, 1, gemm_, 0, D),
        }
        assert wl.layer_names == tuple(want)
        got = self._rows(wl)
        for name, (m, kd, n, count, kind, stream, acc) in want.items():
            assert got[name] == (m, kd, n, count, batch, kind, stream,
                                 acc), name
        frac = np.asarray(wl.layers.active_frac)
        gated_rows = np.asarray(wl.layers.kind) == gated
        np.testing.assert_array_equal(
            frac[gated_rows], np.float32(1.0 / touched))
        assert np.all(frac[~gated_rows] == 1.0)
        assert cfg.n_layers == L and cfg.first_dense == 3

    def test_stream_words_independent_of_heads(self):
        """The latent is one stream shared by the heads: M follows the
        head count while the streamed words do not."""
        cfg = get("deepseek-v3")
        rows = {h: self._rows(llm_decode(cfg.replace(n_heads=h),
                                         context=16384, batch=32))
                for h in (32, 128)}
        for name in ("qk_latent", "av_latent"):
            assert rows[32][name][0] == 32 and rows[128][name][0] == 128
            assert rows[32][name][6] == rows[128][name][6]
        assert rows[128]["qk_latent"][6] == 16384 * 576
        assert rows[128]["av_latent"][6] == 16384 * 512

    @pytest.mark.parametrize("context,batch", MLA_STEPS)
    def test_macs_equal_absorbed_einsums(self, context, batch):
        """Each attention row's MACs are the multiply-adds of the einsums
        the zoo's absorbed decode (``repro.models.mla``) computes for one
        token per sequence: the product of every index's size, times the
        layer count."""
        L = 61
        size = dict(b=batch, s=1, d=7168, q=1536, e=128 * 192, k=576,
                    h=128, n=128, r=512, p=64, t=context, v=128,
                    z=128 * 128)
        einsums = {
            "wq_a": ["bsd,dq->bsq"],
            "wq_b": ["bsq,qe->bse"],
            "wkv_a": ["bsd,dk->bsk"],
            "q_absorb": ["bshn,rhn->bshr"],
            "qk_latent": ["bshr,btr->bhst", "bshp,btp->bhst"],
            "av_latent": ["bhst,btr->bshr"],
            "v_absorb": ["bshr,rhv->bshv"],
            "wo": ["bsz,zd->bsd"],
        }
        wl = llm_decode("deepseek-v3", context=context, batch=batch)
        macs = np.asarray(wl.layers.macs(), np.float64)
        for name, specs in einsums.items():
            want = L * sum(
                float(np.prod([size[c] for c in set(e.replace(",", "")
                                                    .split("->")[0])]))
                for e in specs)
            assert macs[wl.layer_names.index(name)] \
                == pytest.approx(want, rel=1e-6), name

    def test_prefill_rows_are_naive(self):
        """Prefill decompresses the latent (``kv_b``) and attends per
        head on the resident path; nothing is streamed."""
        wl = transformer_workload(get("deepseek-v3"), seq=4096, batch=1,
                                  mode="prefill")
        rows = self._rows(wl)
        assert wl.layer_names[:7] == ("wq_a", "wq_b", "wkv_a", "kv_b",
                                      "qk", "av", "wo")
        assert rows["kv_b"][:4] == (4096, 512, 128 * 256, 61)
        assert rows["qk"][:4] == (4096, 192, 4096, 61 * 128)
        assert rows["av"][:4] == (4096, 4096, 128, 61 * 128)
        assert not np.any(np.asarray(wl.layers.kind) == float(KIND_ATTN_KV))

    def test_latent_attention_needs_a_query_low_rank_path(self):
        cfg = get("deepseek-v3").replace(q_lora_rank=0)
        with pytest.raises(ValueError, match="q_lora_rank"):
            llm_decode(cfg, context=1024)

    def test_other_tables_unchanged(self):
        """The tables of every other config are bit for bit those from
        before latent attention."""
        tables = {
            "qwen3-32b-decode": llm_decode("qwen3-32b", context=8192),
            "deepseek-moe-16b-decode": llm_decode("deepseek-moe-16b",
                                                  context=4096),
            "phi35-moe-decode": llm_moe("phi3.5-moe-42b-a6.6b", seq=512,
                                        mode="decode"),
        }
        for a in ("qwen3-32b", "deepseek-moe-16b", "phi3.5-moe-42b-a6.6b"):
            tables[a + "-train"] = transformer_workload(get(a), seq=2048,
                                                        batch=4, mode="train")
        assert {k: _table_digest(w) for k, w in tables.items()} \
            == TABLE_DIGESTS

    def test_long_context_rows_cost_exactly(self):
        """At 131,072 cached positions operands of the cost model's
        floor and ceil quotients pass 2**24, past ``floor_div``'s promise
        (the value row's C x H x W x bits reaches 2**29); the float32
        cost model still matches the same formulas in float64 on the
        mapped grid's corners."""
        from repro.core.arch import AcceleratorConfig
        wl = llm_decode("deepseek-v3", context=131072, batch=4)
        grid = dict(pe_rows=(8., 32.), pe_cols=(8., 32.),
                    gbuf_kb=(54., 432.), spad_ifmap=(12., 24.),
                    spad_filter=(112., 448.), spad_psum=(16., 32.),
                    pe_type=(0., 2., 4.), bandwidth_gbps=(12.8, 51.2),
                    mapping=(0., 1., 47., 119.))
        pts = np.stack(np.meshgrid(*grid.values(), indexing="ij"),
                       -1).reshape(-1, len(grid))
        cfg = AcceleratorConfig(**{
            k: pts[:, i].astype(np.int32 if k == "pe_type" else np.float32)
            for i, k in enumerate(grid)})
        clock = np.float32(1.0)
        f32 = jax.vmap(lambda c: jax.vmap(
            layer_cost, in_axes=(0, None, None))(wl.layers, c, clock))(cfg)
        with jax.enable_x64(True):
            lay64 = LayerSpec(*[np.asarray(x, np.float64)
                                for x in wl.layers])
            cfg64 = cfg._replace(**{
                k: np.asarray(v, np.float64)
                for k, v in cfg._asdict().items() if k != "pe_type"})
            f64 = jax.vmap(lambda c: jax.vmap(
                layer_cost, in_axes=(0, None, None))(lay64, c,
                                                     np.float64(1.0)))(cfg64)
            f64 = {k: np.asarray(v) for k, v in f64._asdict().items()}
        for k in ("cycles", "dram_bits", "gbuf_bits", "energy_pj",
                  "utilization"):
            got = np.asarray(getattr(f32, k), np.float64)
            np.testing.assert_allclose(got, f64[k], rtol=1e-5, err_msg=k)


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
