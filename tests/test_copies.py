"""Host-device copies of the chunk path: the decoded config goes up packed
in one transfer, the PE codes stay on the host, and the nine results
come back in one read.

Each new leg is held to the per-array path it replaced, kept here as the
oracle: the same bits field by field, the same ``DseResult`` columns, the
same fronts and bests in every walk mode and in search.  A walk repeated
with new trailing-chunk lengths compiles nothing new."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import (Budget, DEFAULT_SPACE, MAPPED_SPACE, WIDE_SPACE,
                        coexplore_front, dispatch_chunk, finish_chunk,
                        iter_space_chunks, model_entry,
                        pareto_front_streaming, resnet_cifar, space_points,
                        space_size, stack_workloads, transformer_gemm)
from repro.core import arch, dse
from repro.core.arch import (AcceleratorConfig, CONFIG_DTYPES, as_config,
                             space_columns)
from repro.core.coexplore import _pe_codes, plan_joint_walk
from repro.core.search import EvolutionaryDriver, search_front

TINY_SPACE = dict(
    pe_rows=(8, 12), pe_cols=(8, 14), gbuf_kb=(54.0,), spad_ifmap=(12,),
    spad_filter=(112, 224), spad_psum=(16,),
    pe_type=tuple(range(5)), bandwidth_gbps=(12.8, 25.6, 51.2, 102.4),
    mapping=(0.0, 7.0, 119.0),
)
CHUNK = 16


@pytest.fixture(scope="module")
def tiny_models():
    """Two layer buckets: a 20-layer CNN and a 2-layer transformer."""
    return (model_entry(resnet_cifar(20)),
            model_entry(transformer_gemm(seq=128, d_model=128, n_layers=2,
                                         n_heads=4, d_ff=256, vocab=1024)))


# -- the per-array path this change replaced (the oracle) -------------------

def _old_columns(indices, space):
    """The float64 decode the old upload converted field by field."""
    axes = arch._space_axes(space)
    radices = np.array([len(a) for a in axes], np.int64)
    strides = np.concatenate([np.cumprod(radices[::-1])[::-1][1:], [1]])
    idx = np.asarray(indices, np.int64)
    return {k: axes[i][(idx // strides[i]) % radices[i]]
            for i, k in enumerate(AcceleratorConfig._fields)}


def _old_upload(cols: dict) -> AcceleratorConfig:
    """One ``jnp.asarray(col, dtype)`` per field."""
    return AcceleratorConfig(
        pe_rows=jnp.asarray(cols["pe_rows"], jnp.float32),
        pe_cols=jnp.asarray(cols["pe_cols"], jnp.float32),
        gbuf_kb=jnp.asarray(cols["gbuf_kb"], jnp.float32),
        spad_ifmap=jnp.asarray(cols["spad_ifmap"], jnp.float32),
        spad_filter=jnp.asarray(cols["spad_filter"], jnp.float32),
        spad_psum=jnp.asarray(cols["spad_psum"], jnp.float32),
        pe_type=jnp.asarray(cols["pe_type"], jnp.int32),
        bandwidth_gbps=jnp.asarray(cols["bandwidth_gbps"], jnp.float32),
        mapping=jnp.asarray(cols["mapping"], jnp.float32),
    )


def _old_fetch(cost, clock_ghz, area_mm2, leak_mw) -> tuple:
    """The nine results read to float64 one after another."""
    f64 = lambda x: np.asarray(x, np.float64)  # noqa: E731
    return (f64(cost.cycles), f64(cost.utilization), f64(cost.macs),
            f64(cost.energy_mac_pj), f64(cost.energy_mem_pj),
            f64(cost.energy_dram_pj), f64(clock_ghz), f64(area_mm2),
            f64(leak_mw))


def _old_stage_inputs(cfg, *lanes, telemetry=None):
    """The stages' inputs as the old path gave them: nine device fields
    (no packing), each per-lane array copied on its own."""
    cfg = AcceleratorConfig(*[jnp.asarray(f) for f in jax.device_get(cfg)])
    return (cfg, *[None if x is None else jnp.asarray(x) for x in lanes])


def _use_old_path(monkeypatch):
    """Run the engine with the per-array copies this change replaced."""
    monkeypatch.setattr(dse, "_upload", _old_stage_inputs)
    monkeypatch.setattr(dse, "_fetch", _old_fetch)


# -- helpers ----------------------------------------------------------------

def _every_value(space) -> np.ndarray:
    """Flat indices that hold every value of every axis at least once."""
    radices = arch.space_radices(space)
    strides = np.concatenate([np.cumprod(radices[::-1])[::-1][1:], [1]])
    return np.concatenate([np.arange(r) * s
                           for r, s in zip(radices, strides)])


def _sample(space, seed=0, n=500) -> np.ndarray:
    rng = np.random.default_rng(seed)
    return np.concatenate([_every_value(space),
                           rng.integers(0, space_size(space), n)])


def _assert_bits_equal(got, want):
    for f in AcceleratorConfig._fields:
        x, y = np.asarray(getattr(got, f)), np.asarray(getattr(want, f))
        assert x.dtype == y.dtype, f
        np.testing.assert_array_equal(x.view(np.uint32), y.view(np.uint32),
                                      err_msg=f)


def _assert_results_equal(got, want):
    for f in dse.DseResult._fields:
        x, y = getattr(got, f), getattr(want, f)
        assert x.dtype == y.dtype and x.shape == y.shape, f
        np.testing.assert_array_equal(x.view(np.uint64), y.view(np.uint64),
                                      err_msg=f)


def _assert_fronts_equal(a, b):
    oa, ob = np.argsort(a.archive.indices), np.argsort(b.archive.indices)
    np.testing.assert_array_equal(a.archive.indices[oa], b.archive.indices[ob])
    np.testing.assert_array_equal(
        np.asarray(a.archive.objectives)[oa].view(np.uint64),
        np.asarray(b.archive.objectives)[ob].view(np.uint64))
    assert a.per_model_best == b.per_model_best
    assert a.points_evaluated == b.points_evaluated


SPACES = {"default": DEFAULT_SPACE, "wide": WIDE_SPACE,
          "mapped": MAPPED_SPACE}


class TestUpload:
    """The config reaches the device with the old per-field bits."""

    def test_samples_hold_the_values_asked_for(self):
        bw = {float(v) for v in space_columns(_sample(WIDE_SPACE),
                                              WIDE_SPACE).bandwidth_gbps}
        assert {12.8, 25.6, 51.2, 102.4} <= {round(v, 1) for v in bw}
        codes = space_columns(_sample(MAPPED_SPACE), MAPPED_SPACE).mapping
        assert set(codes.tolist()) == set(range(arch.MAPPING_CHOICES))

    @pytest.mark.parametrize("name", sorted(SPACES))
    def test_host_columns_in_device_dtypes(self, name):
        cols = space_columns(_sample(SPACES[name]), SPACES[name])
        for f in AcceleratorConfig._fields:
            assert getattr(cols, f).dtype == CONFIG_DTYPES[f], f

    @pytest.mark.parametrize("name", sorted(SPACES))
    def test_space_points(self, name):
        space = SPACES[name]
        idx = _sample(space, seed=1)
        _assert_bits_equal(space_points(idx, space),
                           _old_upload(_old_columns(idx, space)))

    @pytest.mark.parametrize("name", sorted(SPACES))
    def test_packed_upload_unpacks_to_the_old_fields(self, name):
        space = SPACES[name]
        idx = _sample(space, seed=2)
        packed, = dse._upload(space_columns(idx, space))
        assert packed.knobs.dtype == jnp.float32
        assert packed.pe_type.dtype == jnp.int32
        _assert_bits_equal(jax.jit(as_config)(packed),
                           _old_upload(_old_columns(idx, space)))

    @pytest.mark.parametrize("name", sorted(SPACES))
    def test_trailing_partial_chunk(self, name):
        """The last chunk of a subsampled walk, padded on the host and
        packed, against the old path: per-field upload, then pad."""
        space = SPACES[name]
        *_, (cols, idx) = iter_space_chunks(space, chunk_size=64,
                                            max_points=1000, seed=3)
        assert 0 < len(idx) < 64
        packed, = dse._upload(dse._pad_config(cols, 64 - len(idx)))
        want = dse._pad_config(_old_upload(_old_columns(idx, space)),
                               64 - len(idx))
        _assert_bits_equal(jax.jit(as_config)(packed), want)

    @pytest.mark.parametrize("name", sorted(SPACES))
    def test_host_pe_codes_equal_the_device_read_back(self, name):
        space = SPACES[name]
        idx = _sample(space, seed=4)
        codes = _pe_codes(space_columns(idx, space))
        assert codes.dtype == np.int64
        np.testing.assert_array_equal(
            codes, np.asarray(space_points(idx, space).pe_type)
            .astype(np.int64))


class TestFetch:
    """``finish_chunk``'s one read gives the old per-array columns."""

    @pytest.fixture(scope="class")
    def stacked(self, tiny_models):
        return stack_workloads([m.workload for m in tiny_models], pad_to=32)

    @pytest.mark.parametrize("n", (CHUNK, CHUNK - 5, 0))
    def test_plain_workload(self, tiny_models, n):
        cfg = space_columns(np.arange(n) * 7, TINY_SPACE)
        pending = dispatch_chunk(cfg, tiny_models[0].workload, pad_to=CHUNK)
        self._check(pending)

    @pytest.mark.parametrize("n", (CHUNK, CHUNK - 5, 0))
    def test_stacked_mixed_chunk(self, stacked, n):
        cfg = space_columns(np.arange(n) * 5, TINY_SPACE)
        ids = np.arange(n) % 2
        pending = dispatch_chunk(cfg, stacked, pad_to=CHUNK, model_ids=ids)
        self._check(pending)

    @staticmethod
    def _check(pending):
        got = finish_chunk(pending)
        if pending.n == 0:
            want = dse._empty_result()
        else:
            res = dse._finish(*_old_fetch(pending.cost, pending.clock,
                                          pending.area, pending.leak))
            want = dse.DseResult(*[
                np.asarray(col[:pending.n], dse.RESULT_DTYPES[f])
                for f, col in zip(dse.DseResult._fields, res)])
        _assert_results_equal(got, want)


WALKS = {
    "mixed": {},
    "per_model": dict(mix_models=False),
    "sharded": dict(shards=2),
    "pruned": dict(budget=Budget(area_mm2=0.6)),
}


class TestFrontsMatchTheOldPath:
    """Every front, best and evaluated count equals the per-array path's,
    bit for bit."""

    @pytest.mark.parametrize("mode", sorted(WALKS))
    def test_joint_walk(self, tiny_models, mode, monkeypatch):
        kw = dict(chunk_size=CHUNK, max_points=300, seed=5, **WALKS[mode])
        new = coexplore_front(tiny_models, TINY_SPACE, **kw)
        _use_old_path(monkeypatch)
        _assert_fronts_equal(new, coexplore_front(tiny_models, TINY_SPACE,
                                                  **kw))

    def test_search(self, tiny_models, monkeypatch):
        def run():
            return search_front(tiny_models, space=TINY_SPACE,
                                driver=EvolutionaryDriver(population=32),
                                max_evals=96, seed=6, chunk_size=CHUNK)
        new = run()
        _use_old_path(monkeypatch)
        _assert_fronts_equal(new, run())

    @pytest.mark.parametrize("shards", (None, 2))
    def test_plain_walk(self, tiny_models, shards, monkeypatch):
        def run():
            return pareto_front_streaming(
                tiny_models[0].workload, TINY_SPACE, chunk_size=CHUNK,
                max_points=70, seed=7, shards=shards,
                metrics=("perf_per_area", "neg_energy_j"))
        new, _ = run()
        _use_old_path(monkeypatch)
        old, _ = run()
        np.testing.assert_array_equal(np.sort(new.indices),
                                      np.sort(old.indices))
        np.testing.assert_array_equal(
            np.asarray(new.objectives)[np.argsort(new.indices)],
            np.asarray(old.objectives)[np.argsort(old.indices)])


class TestNoCompileOnALaterPass:

    def test_new_trailing_lengths_compile_nothing(self, tiny_models):
        """A subsampled walk over two layer buckets ends each bucket on a
        chunk of a seed-dependent length; a second walk with a new seed
        (new lengths) compiles no XLA program."""
        kw = dict(chunk_size=CHUNK, max_points=150)

        def trailing(seed):
            plan = plan_joint_walk(tiny_models, TINY_SPACE, seed=seed, **kw)
            return sorted(len(c[-1]) for c in plan.chunks()
                          if len(c[-1]) < CHUNK)

        assert trailing(8) != trailing(9)
        jax.clear_caches()            # the first walk compiles every stage
        compiles = []
        jax.monitoring.register_event_duration_secs_listener(
            lambda event, duration, **_: compiles.append(event)
            if "backend_compile" in event else None)
        coexplore_front(tiny_models, TINY_SPACE, seed=8, **kw)
        first = len(compiles)
        assert first > 0              # the listener sees this walk compile
        coexplore_front(tiny_models, TINY_SPACE, seed=9, **kw)
        assert len(compiles) == first
