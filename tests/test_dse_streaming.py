"""Streaming DSE engine: mixed-radix enumeration, chunked evaluation,
tiled/sorted Pareto masks vs the dense oracle, non-dominated archive."""

import itertools

import jax.numpy as jnp
import numpy as np
import pytest

try:
    from hypothesis import given, settings, strategies as st
except ImportError:  # CI images without hypothesis: deterministic fallback
    from _hypothesis_fallback import given, settings, st

from repro.core import (PAPER_WORKLOADS, ParetoArchive, enumerate_space,
                        evaluate_space, evaluate_space_streaming,
                        iter_space_chunks, normalized_report,
                        pareto_front_streaming, pareto_mask,
                        pareto_mask_2d, pareto_mask_dense, pareto_mask_tiled,
                        report_pe_types, space_points, space_size)
from repro.core.arch import DEFAULT_SPACE, AcceleratorConfig, PE_TYPE_CODES

# A small space (2*2*2*1*2*1*5*1 = 80 points) keeps evaluation cheap.
SMALL_SPACE = dict(
    pe_rows=(8, 12), pe_cols=(8, 14), gbuf_kb=(54.0, 108.0),
    spad_ifmap=(12,), spad_filter=(112, 224), spad_psum=(16,),
    pe_type=tuple(range(5)), bandwidth_gbps=(25.6,),
)


def _config_matrix(cfg: AcceleratorConfig) -> np.ndarray:
    return np.stack([np.asarray(getattr(cfg, f), np.float64)
                     for f in AcceleratorConfig._fields], axis=-1)


class TestMixedRadixEnumeration:
    def test_matches_itertools_product(self):
        # absent fields (e.g. the mapping digit) default to a radix-1 axis
        axes = [SMALL_SPACE.get(k, (0.0,)) for k in AcceleratorConfig._fields]
        # configs store float32 — the reference must round the same way
        ref = np.array(list(itertools.product(*axes)),
                       np.float32).astype(np.float64)
        got = _config_matrix(enumerate_space(SMALL_SPACE))
        np.testing.assert_array_equal(got, ref)
        assert space_size(SMALL_SPACE) == len(ref)

    def test_default_space_size(self):
        assert space_size() == 27000

    def test_space_points_decodes_subsets(self):
        full = _config_matrix(enumerate_space(SMALL_SPACE))
        idx = np.array([0, 7, 13, 79, 42])
        got = _config_matrix(space_points(idx, SMALL_SPACE))
        np.testing.assert_array_equal(got, full[idx])

    @given(chunk=st.integers(1, 30))
    @settings(max_examples=10, deadline=None)
    def test_chunks_concat_to_full_space(self, chunk):
        full = _config_matrix(enumerate_space(SMALL_SPACE))
        parts, idxs = [], []
        for cfg, idx in iter_space_chunks(SMALL_SPACE, chunk_size=chunk):
            assert len(idx) <= chunk
            parts.append(_config_matrix(cfg))
            idxs.append(idx)
        np.testing.assert_array_equal(np.concatenate(parts), full)
        np.testing.assert_array_equal(np.concatenate(idxs), np.arange(80))

    def test_subsample_matches_enumerate_space(self):
        sub = _config_matrix(enumerate_space(SMALL_SPACE, max_points=17,
                                             seed=3))
        parts = [_config_matrix(c) for c, _ in iter_space_chunks(
            SMALL_SPACE, chunk_size=5, max_points=17, seed=3)]
        np.testing.assert_array_equal(np.concatenate(parts), sub)


class TestChunkedEvaluation:
    @pytest.fixture(scope="class")
    def workload(self):
        return PAPER_WORKLOADS["resnet20-cifar10"]()

    @pytest.fixture(scope="class")
    def one_shot(self, workload):
        space = enumerate_space(SMALL_SPACE)
        return space, evaluate_space(space, workload)

    @pytest.mark.parametrize("chunk", [7, 16, 80, 100])
    def test_chunked_equals_one_shot(self, one_shot, workload, chunk):
        """Includes non-divisible final chunks (80 % 7, 80 % 16 == 0,
        chunk == N, chunk > N)."""
        space, ref = one_shot
        got = evaluate_space(space, workload, chunk_size=chunk)
        for a, b in zip(ref, got):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=1e-6)

    def test_evaluate_chunk_accepts_unbatched_config(self, workload):
        from repro.core import evaluate_chunk, make_config
        res = evaluate_chunk(make_config(), workload, pad_to=8)
        assert np.shape(res.latency_s) == (1,)
        assert np.isfinite(np.asarray(res.latency_s)).all()

    def test_evaluate_chunk_empty_with_pad_to(self, workload):
        """An N=0 chunk with pad_to set must return the canonical empty
        result (matching evaluate_space), not crash padding f[-1:] of an
        empty array."""
        from repro.core import RESULT_DTYPES, evaluate_chunk
        empty = space_points(np.empty(0, np.int64), SMALL_SPACE)
        res = evaluate_chunk(empty, workload, pad_to=8)
        for f in res._fields:
            col = np.asarray(getattr(res, f))
            assert col.shape == (0,) and col.dtype == RESULT_DTYPES[f], f

    def test_streaming_equals_one_shot(self, one_shot, workload):
        _, ref = one_shot
        chunks = list(evaluate_space_streaming(workload, SMALL_SPACE,
                                               chunk_size=13))
        for f, field in enumerate(ref._fields):
            got = np.concatenate([np.asarray(res[f]) for res, _ in chunks])
            np.testing.assert_allclose(np.asarray(ref[f]), got, rtol=1e-6)
        idx = np.concatenate([i for _, i in chunks])
        np.testing.assert_array_equal(idx, np.arange(80))


def _random_objectives(rng, n, d, dupes=True):
    pts = rng.normal(size=(n, d))
    # quantize to force ties / duplicates — the hard cases for exactness
    if dupes:
        pts = np.round(pts, 1)
        pts[rng.integers(0, n, n // 4)] = pts[rng.integers(0, n, n // 4)]
    return pts


class TestParetoMaskEquivalence:
    @given(seed=st.integers(0, 100), n=st.integers(1, 150),
           d=st.integers(2, 4), block=st.integers(1, 64))
    @settings(max_examples=25, deadline=None)
    def test_tiled_equals_dense(self, seed, n, d, block):
        pts = _random_objectives(np.random.default_rng(seed), n, d)
        dense = np.asarray(pareto_mask_dense(jnp.asarray(pts)))
        tiled = np.asarray(pareto_mask_tiled(jnp.asarray(pts),
                                             block_size=block))
        np.testing.assert_array_equal(dense, tiled)

    @given(seed=st.integers(0, 100), n=st.integers(1, 200))
    @settings(max_examples=25, deadline=None)
    def test_sorted_equals_dense(self, seed, n):
        pts = _random_objectives(np.random.default_rng(seed), n, 2)
        dense = np.asarray(pareto_mask_dense(jnp.asarray(pts)))
        np.testing.assert_array_equal(dense, pareto_mask_2d(pts))

    def test_duplicates_of_front_point_all_kept(self):
        pts = np.array([[1.0, 1.0], [1.0, 1.0], [0.0, 0.0]])
        for method in ("dense", "tiled", "sorted"):
            mask = np.asarray(pareto_mask(jnp.asarray(pts), method=method))
            np.testing.assert_array_equal(mask, [True, True, False])

    def test_dispatcher_methods_agree(self):
        pts = _random_objectives(np.random.default_rng(7), 300, 3)
        auto = np.asarray(pareto_mask(jnp.asarray(pts)))
        dense = np.asarray(pareto_mask(jnp.asarray(pts), method="dense"))
        np.testing.assert_array_equal(auto, dense)

    def test_empty_and_singleton(self):
        assert np.asarray(pareto_mask(jnp.zeros((0, 2)))).shape == (0,)
        for method in ("dense", "tiled", "sorted"):
            assert np.asarray(pareto_mask(jnp.zeros((1, 2)),
                                          method=method)).all()


class TestParetoArchive:
    @given(seed=st.integers(0, 100), n=st.integers(1, 200),
           chunk=st.integers(1, 64))
    @settings(max_examples=25, deadline=None)
    def test_streamed_front_equals_dense(self, seed, n, chunk):
        pts = _random_objectives(np.random.default_rng(seed), n, 2)
        dense = set(np.flatnonzero(
            np.asarray(pareto_mask_dense(jnp.asarray(pts)))).tolist())
        archive = ParetoArchive(2)
        for lo in range(0, n, chunk):
            archive.update(pts[lo:lo + chunk],
                           np.arange(lo, min(lo + chunk, n)))
        assert set(archive.indices.tolist()) == dense
        np.testing.assert_array_equal(archive.objectives,
                                      pts[archive.indices])

    def test_order_invariance(self):
        pts = _random_objectives(np.random.default_rng(1), 120, 3)
        a1, a2 = ParetoArchive(3), ParetoArchive(3)
        a1.update(pts, np.arange(120))
        perm = np.random.default_rng(2).permutation(120)
        for lo in range(0, 120, 37):
            sel = perm[lo:lo + 37]
            a2.update(pts[sel], sel)
        assert set(a1.indices.tolist()) == set(a2.indices.tolist())

    def test_rejects_wrong_width(self):
        archive = ParetoArchive(2)
        with pytest.raises(ValueError):
            archive.update(np.zeros((4, 3)))

    @pytest.mark.parametrize("bad_val", [np.nan, np.inf, -np.inf])
    def test_rejects_non_finite_rows(self, bad_val):
        """+inf corrupts the front exactly like NaN (an all-+inf-beating
        row can never be dominated), so the guard covers all non-finite
        values — and rejection must leave the archive untouched."""
        archive = ParetoArchive(2)
        archive.update(np.array([[1.0, 1.0]]))
        before = (archive.objectives.copy(), archive.indices.copy())
        with pytest.raises(ValueError, match="non-finite"):
            archive.update(np.array([[2.0, 2.0], [bad_val, 0.0]]))
        np.testing.assert_array_equal(archive.objectives, before[0])
        np.testing.assert_array_equal(archive.indices, before[1])
        archive.update(np.array([[2.0, 2.0]]))   # clean updates still work
        assert len(archive) == 1

    def test_preserves_float64_precision(self):
        """Chunk self-reduction must not round through float32: these two
        points differ only past float32 precision and neither dominates."""
        archive = ParetoArchive(2)
        archive.update(np.array([[1.0 + 1e-12, 0.0], [1.0, 1.0]]))
        assert set(archive.indices.tolist()) == {0, 1}


class TestStreamingFront:
    def test_end_to_end_matches_dense(self):
        wl = PAPER_WORKLOADS["resnet20-cifar10"]()
        space = enumerate_space(SMALL_SPACE)
        res = evaluate_space(space, wl)
        obj = np.stack([np.asarray(res.perf_per_area, np.float64),
                        -np.asarray(res.energy_j, np.float64)], -1)
        dense = set(np.flatnonzero(
            np.asarray(pareto_mask_dense(jnp.asarray(obj)))).tolist())
        archive, front_cfg = pareto_front_streaming(
            wl, SMALL_SPACE, chunk_size=13)
        assert set(archive.indices.tolist()) == dense
        got = _config_matrix(front_cfg)
        ref = _config_matrix(space)[archive.indices]
        np.testing.assert_array_equal(got, ref)


class TestNormalizedReportFallback:
    def test_no_int16_falls_back_to_global_best(self):
        wl = PAPER_WORKLOADS["resnet20-cifar10"]()
        space_no16 = dict(SMALL_SPACE, pe_type=tuple(
            c for name, c in PE_TYPE_CODES.items() if name != "int16"))
        space = enumerate_space(space_no16)
        res = evaluate_space(space, wl)
        rep = normalized_report(res, space)
        assert rep["_reference"]["fallback"] is True
        assert "int16" not in report_pe_types(rep)
        # normalized to the global best perf/area -> max norm is exactly 1
        norms = [r["norm_perf_per_area"]
                 for r in report_pe_types(rep).values()]
        assert max(norms) == pytest.approx(1.0)
        assert all(np.isfinite(v) and v > 0 for v in norms)

    def test_with_int16_no_fallback(self):
        wl = PAPER_WORKLOADS["resnet20-cifar10"]()
        space = enumerate_space(SMALL_SPACE)
        res = evaluate_space(space, wl)
        rep = normalized_report(res, space)
        assert rep["_reference"] == dict(pe_type="int16",
                                         index=rep["_reference"]["index"],
                                         fallback=False, note=None)
        assert rep["int16"]["norm_perf_per_area"] == pytest.approx(1.0)


class TestReportPeTypes:
    def test_drops_metadata_keeps_pe_entries(self):
        rep = {"_reference": {"pe_type": "int16"}, "_future_meta": 1,
               "fp32": {"norm_perf_per_area": 0.13},
               "lightpe1": {"norm_perf_per_area": 3.2}}
        assert report_pe_types(rep) == {
            "fp32": {"norm_perf_per_area": 0.13},
            "lightpe1": {"norm_perf_per_area": 3.2}}

    def test_empty_report(self):
        assert report_pe_types({"_reference": {}}) == {}

    def test_round_trip_with_normalized_report(self):
        wl = PAPER_WORKLOADS["resnet20-cifar10"]()
        space = enumerate_space(SMALL_SPACE)
        rep = normalized_report(evaluate_space(space, wl), space)
        pes = report_pe_types(rep)
        # every entry is a real PE-type name with the per-type fields
        assert set(pes) <= set(PE_TYPE_CODES)
        assert all(not k.startswith("_") for k in pes)
        for r in pes.values():
            assert {"best_perf_per_area", "norm_perf_per_area",
                    "best_energy_j", "norm_energy",
                    "energy_at_best_ppa"} <= set(r)


# ---------------------------------------------------------------------------
# The host domination primitive against the (N, F, D) broadcast it replaced.
# These oracles are the earlier production formulas, kept verbatim.

def _oracle_dominated_by(points: np.ndarray, front: np.ndarray) -> np.ndarray:
    if len(front) == 0 or len(points) == 0:
        return np.zeros(len(points), bool)
    ge = np.all(front[None, :, :] >= points[:, None, :], axis=-1)
    gt = np.any(front[None, :, :] > points[:, None, :], axis=-1)
    return np.any(ge & gt, axis=1)


def _oracle_self_nondominated(pts: np.ndarray) -> np.ndarray:
    ge = np.all(pts[None, :, :] >= pts[:, None, :], axis=-1)
    gt = np.any(pts[None, :, :] > pts[:, None, :], axis=-1)
    return ~np.any(ge & gt, axis=1)


def _oracle_chunk_dominators(obj: np.ndarray, block: int = 512):
    obj = np.asarray(obj, np.float64)
    front = np.flatnonzero(ParetoArchive._chunk_front_mask(obj))
    f = obj[front]
    dom = np.empty((len(front), len(obj)), bool)
    for lo in range(0, len(front), block):
        blk = f[lo:lo + block, None, :]
        dom[lo:lo + block] = (np.all(blk >= obj[None, :, :], axis=-1)
                              & np.any(blk > obj[None, :, :], axis=-1))
    return front, dom


def _oracle_matrix(points: np.ndarray, front: np.ndarray) -> np.ndarray:
    ge = np.all(front[None, :, :] >= points[:, None, :], axis=-1)
    gt = np.any(front[None, :, :] > points[:, None, :], axis=-1)
    return ge & gt


def _grid_rows(rng, n, d):
    """Integer-grid rows from a tiny alphabet: full of ties and duplicates."""
    return rng.integers(0, 3, size=(n, d)).astype(np.float64)


# (case, rows N, front F, row kind, elements per block of front rows)
_DOMINANCE_CASES = [
    ("random", 97, 61, "random", 1 << 20),
    ("grid", 130, 140, "grid", 1 << 20),
    ("grid_small_block", 130, 140, "grid", 130 * 16),
    ("no_rows", 0, 40, "grid", 1 << 20),
    ("no_front", 40, 0, "grid", 1 << 20),
    ("one_row", 1, 50, "grid", 1 << 20),
    ("one_front_row", 50, 1, "grid", 1 << 20),
    ("front_wider_than_block", 70, 1100, "random", 70 * 64),
    ("row_wider_than_block", 2100, 9, "grid", 1024),
    ("points_are_front", 150, None, "grid", 150 * 32),
    ("points_are_front_random", 150, None, "random", 1 << 20),
]


class TestDominancePrimitive:
    """``dse._dominance`` and everything built on it must return exactly
    the booleans of the (N, F, D) broadcast it replaced."""

    @pytest.mark.parametrize("d", [2, 3, 4])
    @pytest.mark.parametrize("case,n,f,kind,block", _DOMINANCE_CASES,
                             ids=[c[0] for c in _DOMINANCE_CASES])
    def test_matches_broadcast_oracle(self, case, n, f, kind, block, d):
        from repro.core.dse import (_dominance, _dominated_by,
                                    _self_nondominated)
        rng = np.random.default_rng([n, f or 0, d, len(case)])
        rows = _grid_rows if kind == "grid" else (
            lambda r, m, k: r.random((m, k)))
        points = rows(rng, n, d)
        front = points if f is None else rows(rng, f, d)
        if kind == "grid" and len(points) and len(front):
            points[:len(points) // 3] = front[
                rng.integers(0, len(front), len(points) // 3)]
        want = _oracle_matrix(points, front)
        got = _dominance(points, front, block=block)
        assert got.shape == (len(points), len(front)) and got.dtype == bool
        assert (got == want).all()
        assert (_dominated_by(points, front)
                == _oracle_dominated_by(points, front)).all()
        if f is None:
            assert (_self_nondominated(points)
                    == _oracle_self_nondominated(points)).all()

    @pytest.mark.parametrize("d", [2, 3, 4])
    def test_archive_stream_equals_oracle_archive(self, d, monkeypatch):
        from repro.core import dse
        rng = np.random.default_rng(1000 + d)
        chunks = []
        for c in range(12):
            n = int(rng.choice([1, 7, 300, 700]))
            obj = (_grid_rows(rng, n, d) if c % 2
                   else np.round(rng.normal(size=(n, d)), 2))
            chunks.append(obj)
        new = ParetoArchive(d)
        for c, obj in enumerate(chunks):
            new.update(obj, np.arange(c * 1000, c * 1000 + len(obj)))
        monkeypatch.setattr(dse, "_dominated_by", _oracle_dominated_by)
        monkeypatch.setattr(dse, "_self_nondominated",
                            _oracle_self_nondominated)
        old = ParetoArchive(d)
        for c, obj in enumerate(chunks):
            old.update(obj, np.arange(c * 1000, c * 1000 + len(obj)))
        assert len(new) > 1
        assert new.objectives.tobytes() == old.objectives.tobytes()
        assert new.indices.tobytes() == old.indices.tobytes()
        s_new, s_old = new.state_dict(), old.state_dict()
        assert s_new.keys() == s_old.keys()
        for key in s_new:
            assert (np.asarray(s_new[key]).tobytes()
                    == np.asarray(s_old[key]).tobytes())

    @pytest.mark.parametrize("seed", [0, 1, 2])
    @pytest.mark.parametrize("n", [1, 90, 700])
    def test_chunk_dominators_equal_oracle(self, seed, n):
        from repro.core import chunk_dominators
        rng = np.random.default_rng([seed, n])
        obj = _grid_rows(rng, n, 3) if seed != 1 else rng.random((n, 3))
        front, dom = chunk_dominators(obj)
        want_front, want_dom = _oracle_chunk_dominators(obj)
        assert front.tobytes() == want_front.tobytes()
        assert dom.shape == want_dom.shape and (dom == want_dom).all()
