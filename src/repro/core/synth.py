"""Synthesis oracle — the stand-in for Synopsys DC + FreePDK45.

The paper obtains ground-truth power / area / timing by synthesizing each
RTL design point.  No EDA tools exist offline, so this module plays the
role of the synthesis flow: a gate-level-informed analytical model built
from the 45 nm constants in ``pe.py`` / ``energy.py``, plus the second-
order effects a synthesis run exhibits (wiring overhead growing with array
size, clock degradation from broadcast fan-out and SRAM decoder depth,
leakage proportional to area) and a small deterministic pseudo-noise term
(~3%) standing in for synthesis variability.  The polynomial PPA models in
``ppa.py`` are fit against THIS oracle exactly as the paper fits against
DC output — the fit-quality experiment (Fig. 3) is the reproduction
target, not the absolute pJ numbers (DESIGN.md §3).

Everything is pure jnp and array-first: every formula below is
elementwise over the config leaves, so a batched ``AcceleratorConfig``
with (N,)-shaped fields evaluates all N design points in one fused
computation — no vmap needed, no per-config dispatch.  ``oracle_ppa`` is
the cost-model-backend entry point (``repro.core.costmodel``): the pure
``(params, cfg) -> (power, clock, area)`` stage the DSE evaluator jits.
"""

from __future__ import annotations

import math
from typing import NamedTuple

import jax.numpy as jnp

from repro.core import energy as E
from repro.core import pe as PE
from repro.core.arch import AcceleratorConfig


class SynthResult(NamedTuple):
    area_mm2: jnp.ndarray
    crit_path_ns: jnp.ndarray
    clock_ghz: jnp.ndarray
    power_mw: jnp.ndarray          # at nominal (70%) MAC activity
    leakage_mw: jnp.ndarray


_NOISE_AMP = 0.03

# 45 nm leakage power density: mW of static power per mm^2 of synthesized
# area.  THE shared constant — the PPA surrogate's SynthResult derives its
# leakage from predicted area with this same value, so the surrogate and
# oracle DSE paths can only diverge through the fitted power/clock/area
# polynomials, never through a drifting leakage model.
LEAKAGE_MW_PER_MM2 = 3.5


_SQRT_HALF = math.sqrt(0.5)


def _log2(x):
    """``log2`` of positive float32 ``x`` from multiplies and adds.

    The TPU's own ``log2`` is good to ~1e-5 relative, not to an ulp.  This
    one agrees across backends to a few ulp: ``x = m * 2**e`` exactly
    (``frexp``), ``m`` moved into [sqrt(1/2), sqrt(2)), and ``log2(m) =
    2 atanh(s) / ln 2`` with ``s = (m - 1) / (m + 1)``, ``|s| < 0.172``,
    whose odd series is below float32's ulp past ``s**9``."""
    m, e = jnp.frexp(x)                                  # m in [0.5, 1)
    low = m < _SQRT_HALF
    m = jnp.where(low, 2.0 * m, m)
    e = jnp.where(low, e - 1, e).astype(jnp.float32)
    s = (m - 1.0) / (m + 1.0)
    s2 = s * s
    atanh2 = s * (2.0 + s2 * (2.0 / 3.0 + s2 * (2.0 / 5.0 + s2 * (
        2.0 / 7.0 + s2 * (2.0 / 9.0)))))
    return e + atanh2 * (1.0 / math.log(2.0))


# The hardware fields the noise hashes, each read in 1/64 steps.
_HASH_FIELDS = ("pe_rows", "pe_cols", "gbuf_kb", "spad_ifmap", "spad_filter",
                "spad_psum", "pe_type", "bandwidth_gbps")


def _fmix32(h):
    """MurmurHash3's 32-bit finalizer: every input bit moves every output
    bit."""
    h = h ^ (h >> 16)
    h = h * jnp.uint32(0x85EBCA6B)
    h = h ^ (h >> 13)
    h = h * jnp.uint32(0xC2B2AE35)
    return h ^ (h >> 16)


def _unit(h):
    """The top 24 bits of ``h`` as a float32 in [0, 1), exactly."""
    return (h >> 8).astype(jnp.float32) * (1.0 / (1 << 24))


def _hash_units(cfg: AcceleratorConfig, salt: int):
    """Two uniforms in [0, 1) per design point from an integer hash of
    its fields (read exactly, in 1/64 steps): the same bits on every
    backend and under every fusion."""
    h = jnp.full(jnp.shape(cfg.pe_rows), salt, jnp.uint32)
    for f in _HASH_FIELDS:
        q = jnp.round(jnp.asarray(getattr(cfg, f), jnp.float32) * 64.0)
        h = _fmix32(h ^ q.astype(jnp.int32).astype(jnp.uint32))
    return _unit(h), _unit(_fmix32(h ^ jnp.uint32(0x9E3779B9)))


def _noise(cfg: AcceleratorConfig, salt: int):
    """Deterministic ~3% 'synthesis variability' from a config hash.

    ``sin``/``cos`` see arguments within [0, 2*pi) only.  A float hash
    would not do: ``sin`` of an argument near 1e3 turns its last bit
    into ~1e-5 of the factor, and backends round such sums differently."""
    u, v = _hash_units(cfg, salt)
    return 1.0 + _NOISE_AMP * (jnp.sin(2.0 * math.pi * u)
                               * jnp.cos(2.0 * math.pi * v))


def synthesize(cfg: AcceleratorConfig) -> SynthResult:
    n_pes = cfg.pe_rows * cfg.pe_cols

    # ---- area -----------------------------------------------------------
    pe_area = PE.pe_area_um2(cfg.pe_type, cfg.spad_ifmap, cfg.spad_filter,
                             cfg.spad_psum)
    wiring = 1.0 + 0.015 * _log2(jnp.maximum(n_pes, 2.0))  # global routing
    area_um2 = (n_pes * pe_area * wiring
                + E.gbuf_area_um2(cfg.gbuf_kb)
                + n_pes * E.NOC_AREA_PER_PE_UM2
                + E.IO_AREA_UM2)
    area_mm2 = area_um2 * 1e-6 * _noise(cfg, 1)

    # ---- timing ----------------------------------------------------------
    # MAC critical path + broadcast fan-out across columns + gbuf decoders.
    crit = (PE.mac_delay_ns(cfg.pe_type)
            * (1.0 + 0.02 * _log2(jnp.maximum(n_pes, 2.0)))
            + 0.035 * _log2(jnp.maximum(cfg.gbuf_kb, 2.0)))
    crit = crit * _noise(cfg, 2)
    clock_ghz = 1.0 / crit

    # ---- power at nominal activity ----------------------------------------
    activity = 0.70
    a_b = PE.act_bits(cfg.pe_type)
    w_b = PE.weight_bits(cfg.pe_type)
    p_b = PE.psum_bits(cfg.pe_type)
    # per-cycle per-PE: one MAC + RF traffic (act + w reads; psum RMW hits
    # the spad ~once per c*S~12 MACs — register accumulation, cf. dataflow)
    pe_pj_per_cycle = (PE.mac_energy_pj(cfg.pe_type)
                       + E.rf_access_energy(a_b, cfg.spad_ifmap * a_b)
                       + E.rf_access_energy(w_b, cfg.spad_filter * w_b)
                       + (2.0 / 12.0) * E.rf_access_energy(
                           p_b, cfg.spad_psum * p_b)
                       + PE.PE_CTRL_ENERGY_PJ)
    # gbuf serves ~one ifmap word per column + one filter word per row / cycle
    gbuf_pj_per_cycle = (cfg.pe_cols * a_b + cfg.pe_rows * w_b) \
        * E.gbuf_energy_per_bit(cfg.gbuf_kb)
    dyn_mw = activity * clock_ghz * (n_pes * pe_pj_per_cycle
                                     + gbuf_pj_per_cycle)  # pJ * GHz = mW
    leak_mw = LEAKAGE_MW_PER_MM2 * area_mm2
    power_mw = (dyn_mw + leak_mw) * _noise(cfg, 3)
    return SynthResult(area_mm2=area_mm2, crit_path_ns=crit,
                       clock_ghz=clock_ghz, power_mw=power_mw,
                       leakage_mw=leak_mw)


def oracle_ppa(params, cfg: AcceleratorConfig):
    """Batched PPA stage of the analytical oracle backend.

    The ``CostModel.ppa_fn`` contract (see ``repro.core.costmodel``): a
    pure jit-safe ``(params, config_chunk) -> (power_mw, clock_ghz,
    area_mm2)`` function.  The oracle is parameter-free (``params`` is an
    empty pytree, present only so every backend shares one signature) and
    simply exposes the synthesis model's nominal-activity triple — one
    fused elementwise computation for the whole (N,)-lane chunk.
    """
    del params  # the analytical oracle has no fitted state
    s = synthesize(cfg)
    return s.power_mw, s.clock_ghz, s.area_mm2
