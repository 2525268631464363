"""Compile the engine's jitted stages and the Pallas kernels for a
described TPU v5e, at the shapes the main path runs.

Nothing runs: the TPU compiler refuses here what it would refuse on the
chip (unaligned tiles, too much VMEM, layouts Mosaic cannot lower).  The
topology is described inside a fixture, never while a module is
imported, and the persistent compilation cache is off around the
compiles.
"""

import functools
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core import dse, ppa
from repro.core.arch import KNOB_FIELDS, PackedConfig
from repro.core.synth import oracle_ppa
from repro.core.workloads import LayerSpec
from repro.kernels.fake_quant.fake_quant import fake_quant
from repro.kernels.flash_attention.flash_attention import flash_attention
from repro.kernels.quant_matmul.quant_matmul import quant_matmul

CHUNK = dse.DEFAULT_CHUNK_SIZE
DEPTH = 64            # deepest layer bucket of default_model_set()
STACK = 2             # models in that bucket


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - any failure means "cannot"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _spec(sharding, shape, dtype=jnp.float32):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _compile(fn, *args):
    """Lower and compile a fresh jit of ``fn`` (the module's own jit
    caches stay untouched); returns the compiled text."""
    return jax.jit(fn).lower(*args).compile().as_text()


def _config(sharding):
    """A chunk's config as the engine's stages receive it (packed)."""
    return PackedConfig(_spec(sharding, (len(KNOB_FIELDS), CHUNK)),
                        _spec(sharding, (CHUNK,), jnp.int32))


def test_ppa_stage(one_chip):
    fn = functools.partial(dse._ppa_stage.__wrapped__, oracle_ppa, {})
    assert _compile(fn, _config(one_chip))


def test_lane_gather(one_chip):
    stack = LayerSpec(*[_spec(one_chip, (STACK, DEPTH))
                        for _ in LayerSpec._fields])
    ids = _spec(one_chip, (CHUNK,), jnp.int32)
    assert _compile(dse._lane_layers.__wrapped__, stack, ids)


def test_network_sums_64_layer_bucket(one_chip):
    lanes = LayerSpec(*[_spec(one_chip, (CHUNK, DEPTH))
                        for _ in LayerSpec._fields])
    clock = _spec(one_chip, (CHUNK,))
    assert _compile(dse._network_sums.__wrapped__, _config(one_chip), clock,
                    lanes)


def test_result_block(one_chip):
    """The nine result columns stacked for the finish's one read."""
    cols = [_spec(one_chip, (CHUNK,)) for _ in range(9)]
    assert _compile(dse._stack_results.__wrapped__, *cols)


def test_surrogate_fit_degree_3(one_chip):
    """The ridge fit of the surrogate (QR on the device) at the sample
    size ``chip_smoke`` fits: 600 designs, the degree-3 basis."""
    n, f = 600, len(ppa.FEATURE_FIELDS)
    fn = functools.partial(ppa._fit_arrays.__wrapped__, degree=3,
                           log_target=True)
    assert _compile(fn, _spec(one_chip, (n, f)), _spec(one_chip, (n,)),
                    _spec(one_chip, (n,)), _spec(one_chip, (f,)),
                    _spec(one_chip, (f,)), _spec(one_chip, ()))


def test_quant_matmul_int4(one_chip):
    m, k, n = 128, 4096, 4096
    text = _compile(functools.partial(quant_matmul, mode="int4"),
                    _spec(one_chip, (m, k)),
                    _spec(one_chip, (k // 2, n), jnp.uint8),
                    _spec(one_chip, (n,)))
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("mode", ["affine", "pow2"])
def test_fake_quant(one_chip, mode):
    text = _compile(functools.partial(fake_quant, mode=mode),
                    _spec(one_chip, (4096, 4096)), _spec(one_chip, (4096,)))
    assert "tpu_custom_call" in text


def test_flash_attention(one_chip):
    q, k, v = (_spec(one_chip, (2048, 128)) for _ in range(3))
    text = _compile(flash_attention, q, k, v)
    assert "tpu_custom_call" in text
