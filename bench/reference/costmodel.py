"""Plain reference of QADAM's analytical cost model, in numpy.

Written from the model's description (an Eyeriss-style row-stationary
array priced with 45 nm constants, a synthesis oracle for clock and area
with a hashed ~3% variability term, and a per-layer dataflow walk folded
over the network), not from the program's code: nothing here imports
the program.  Every formula is elementwise over design points and
layers, with no padding, no buckets, no chunking and no jit.

``dtype`` selects the arithmetic: float64 is the reference; a lower one
(bfloat16 from ``ml_dtypes``) computes the same formulas less exactly,
which is the control that the comparison must reject.  The host-side
metric columns (``finish``) take ``finish_dtype``.
"""

from __future__ import annotations

import math

import numpy as np

# PE types, in the code order the design spaces use.
PE_TYPES = ("fp32", "int16", "lightpe1", "lightpe2", "int8")

# 45 nm datapath constants per PE type (Horowitz ISSCC'14, LightNN,
# Eyeriss), the values the QADAM reproduction states.
ACT_BITS = (32.0, 16.0, 8.0, 8.0, 8.0)
WEIGHT_BITS = (32.0, 16.0, 4.0, 8.0, 8.0)
PSUM_BITS = (32.0, 32.0, 20.0, 20.0, 24.0)
MAC_ENERGY_PJ = (4.6, 0.9, 0.104, 0.208, 0.28)
MAC_AREA_UM2 = (11884.0, 1067.0, 200.0, 260.0, 382.0)
MAC_DELAY_NS = (2.50, 1.25, 0.70, 0.72, 0.95)
PE_CTRL_AREA_UM2 = 500.0
PE_CTRL_ENERGY_PJ = 0.05
SPAD_AREA_PER_BIT_UM2 = 0.50

# Memory hierarchy (pJ per bit, um^2), Eyeriss level ratios.
NOC_E_PER_BIT_PJ = 2.0 / 16.0
GBUF_E_PER_BIT_PJ = 5.0 / 16.0
DRAM_E_PER_BIT_PJ = 200.0 / 16.0
GBUF_REF_KB = 108.0
RF_C0_PJ = 0.20
RF_C1_PJ_PER_BIT = 0.65 / 16.0
RF_REF_CAP_BITS = 4096.0
GBUF_AREA_PER_BIT_UM2 = 0.22
GBUF_PERIPHERY_UM2 = 45000.0
NOC_AREA_PER_PE_UM2 = 120.0
IO_AREA_UM2 = 150000.0
LEAKAGE_MW_PER_MM2 = 3.5
NOISE_AMP = 0.03

# Layer kinds: how a layer's second operand resides.
KIND_CONV, KIND_GEMM, KIND_ATTN_KV, KIND_MOE_EXPERT = 0, 1, 2, 3

CONFIG_FIELDS = ("pe_rows", "pe_cols", "gbuf_kb", "spad_ifmap", "spad_filter",
                 "spad_psum", "pe_type", "bandwidth_gbps", "mapping")
# The fields the variability hash reads, each in 1/64 steps.
HASH_FIELDS = CONFIG_FIELDS[:8]
LAYER_FIELDS = ("H", "W", "C", "K", "R", "S", "stride", "batch", "count",
                "kind", "stream_words", "active_frac")


# -- the synthesis oracle -----------------------------------------------------

def _fmix32(h: np.ndarray) -> np.ndarray:
    """MurmurHash3's 32-bit finalizer on uint32."""
    h = h ^ (h >> np.uint32(16))
    h = h * np.uint32(0x85EBCA6B)
    h = h ^ (h >> np.uint32(13))
    h = h * np.uint32(0xC2B2AE35)
    return h ^ (h >> np.uint32(16))


def _noise(cfg: dict, salt: int, dt) -> np.ndarray:
    """1 + 3% * sin(2 pi u) cos(2 pi v), u and v the top 24 bits of an
    integer hash of the design point's fields."""
    n = len(cfg["pe_rows"])
    with np.errstate(over="ignore"):
        h = np.full(n, salt, np.uint32)
        for f in HASH_FIELDS:
            q = np.rint(np.asarray(cfg[f], np.float64) * 64.0).astype(np.int64)
            h = _fmix32(h ^ q.astype(np.uint32))
        h2 = _fmix32(h ^ np.uint32(0x9E3779B9))
    u = ((h >> np.uint32(8)).astype(np.float64) / float(1 << 24)).astype(dt)
    v = ((h2 >> np.uint32(8)).astype(np.float64) / float(1 << 24)).astype(dt)
    two_pi = dt(2.0 * math.pi)
    return dt(1.0) + dt(NOISE_AMP) * (np.sin(two_pi * u) * np.cos(two_pi * v))


def _table(values, pe_type: np.ndarray, dt) -> np.ndarray:
    return np.asarray(values, dt)[pe_type]


def _rf_access(bits, cap_bits, dt):
    scale = np.sqrt(np.maximum(cap_bits, dt(64.0)) / dt(RF_REF_CAP_BITS))
    return (dt(RF_C0_PJ) + bits * dt(RF_C1_PJ_PER_BIT)) * scale


def ppa(cfg: dict, dt=np.float64) -> dict:
    """Clock (GHz), chip area (mm^2) and leakage (mW) of each design point."""
    c = {f: np.asarray(cfg[f], np.float64).astype(dt) for f in CONFIG_FIELDS}
    t = np.asarray(cfg["pe_type"]).astype(np.int64)
    n_pes = c["pe_rows"] * c["pe_cols"]
    spad_bits = (c["spad_ifmap"] * _table(ACT_BITS, t, dt)
                 + c["spad_filter"] * _table(WEIGHT_BITS, t, dt)
                 + c["spad_psum"] * _table(PSUM_BITS, t, dt))
    pe_area = (_table(MAC_AREA_UM2, t, dt) + spad_bits * dt(SPAD_AREA_PER_BIT_UM2)
               + dt(PE_CTRL_AREA_UM2))
    log_pes = np.log2(np.maximum(n_pes, dt(2.0)))
    wiring = dt(1.0) + dt(0.015) * log_pes
    gbuf_area = c["gbuf_kb"] * dt(1024.0 * 8.0) * dt(GBUF_AREA_PER_BIT_UM2) \
        + dt(GBUF_PERIPHERY_UM2)
    area_um2 = (n_pes * pe_area * wiring + gbuf_area
                + n_pes * dt(NOC_AREA_PER_PE_UM2) + dt(IO_AREA_UM2))
    area_mm2 = area_um2 * dt(1e-6) * _noise(cfg, 1, dt)
    crit = (_table(MAC_DELAY_NS, t, dt) * (dt(1.0) + dt(0.02) * log_pes)
            + dt(0.035) * np.log2(np.maximum(c["gbuf_kb"], dt(2.0))))
    crit = crit * _noise(cfg, 2, dt)
    return dict(clock_ghz=dt(1.0) / crit, area_mm2=area_mm2,
                leak_mw=dt(LEAKAGE_MW_PER_MM2) * area_mm2)


# -- the row-stationary dataflow ------------------------------------------------

def _mapping_knobs(mapping: np.ndarray, dt):
    """Schedule code -> (legacy, filter share of the gbuf, columns-first
    replication, channel-tile divisor, filter-tile divisor); mixed radix
    3 x 2 x 4 x 5, code 0 the legacy schedule."""
    m = np.rint(np.asarray(mapping, np.float64)).astype(np.int64)
    split = m % 3
    fil_frac = np.where(split == 1, 0.75, np.where(split == 2, 0.25, 0.5))
    cols_first = (m // 3) % 2 == 1
    c_div = (2 ** ((m // 6) % 4)).astype(np.float64)
    q_code = (m // 24) % 5
    q_div = np.where(q_code == 4, 6.0, q_code + 1.0)
    return m == 0, fil_frac.astype(dt), cols_first, c_div.astype(dt), \
        q_div.astype(dt)


def layer_costs(cfg: dict, clock_ghz: np.ndarray, layers: dict,
                dt=np.float64) -> dict:
    """Per-(design point, layer) cost terms, shape (N, L), of one model.

    ``cfg`` columns are (N,), ``layers`` columns (L,).  The quotients the
    model floors or ceils are of whole numbers, taken exactly."""
    col = lambda a: np.asarray(a, np.float64).astype(dt)[:, None]  # noqa: E731
    row = lambda a: np.asarray(a, np.float64).astype(dt)[None, :]  # noqa: E731
    one = dt(1.0)
    t = np.asarray(cfg["pe_type"]).astype(np.int64)
    a_bits = _table(ACT_BITS, t, dt)[:, None]
    w_bits = _table(WEIGHT_BITS, t, dt)[:, None]
    p_bits = _table(PSUM_BITS, t, dt)[:, None]
    H, W, C, K = (row(layers[k]) for k in ("H", "W", "C", "K"))
    R, S, stride = row(layers["R"]), row(layers["S"]), row(layers["stride"])
    batch, count = row(layers["batch"]), row(layers["count"])
    kind = np.asarray(layers["kind"], np.float64)[None, :]
    streamed = kind == KIND_ATTN_KV
    gated = kind == KIND_MOE_EXPERT
    active_frac = np.maximum(row(layers["active_frac"]), dt(1e-9))
    stream_words = row(layers["stream_words"])
    Pr, Pc = col(cfg["pe_rows"]), col(cfg["pe_cols"])
    si, sf, sp = col(cfg["spad_ifmap"]), col(cfg["spad_filter"]), \
        col(cfg["spad_psum"])
    gbuf_kb, bw = col(cfg["gbuf_kb"]), col(cfg["bandwidth_gbps"])
    clock = np.asarray(clock_ghz).astype(dt)[:, None]
    legacy, fil_frac, cols_first, c_div, q_div = _mapping_knobs(
        cfg["mapping"], dt)
    legacy, cols_first = legacy[:, None], cols_first[:, None]
    fil_frac, c_div, q_div = fil_frac[:, None], c_div[:, None], q_div[:, None]

    floor = lambda a, b: np.floor(a / b)                    # noqa: E731
    ceil = lambda a, b: np.ceil(a / np.maximum(b, one))     # noqa: E731
    clip = lambda x, lo, hi: np.minimum(np.maximum(x, lo), hi)  # noqa: E731

    Eh = floor(H - R, stride) + one
    F = floor(W - S, stride) + one
    macs = batch * K * C * R * S * Eh * F * count
    op2_bits = np.where(streamed, a_bits, w_bits)

    c_fit = clip(floor(si, S * np.where(legacy, one, c_div)), one, C)
    q_cap = floor(sf, c_fit * S)
    q_fit = clip(np.minimum(np.where(legacy, q_cap, floor(q_cap, q_div)), sp),
                 one, K)

    rows_used = np.minimum(R, Pr)
    cols_used = np.minimum(Eh, Pc)
    fold_r = ceil(R, Pr)
    fold_e = ceil(Eh, Pc)
    groups = ceil(K, q_fit) * ceil(C, c_fit) * batch
    repl_r_cap = floor(Pr, np.maximum(rows_used, one))
    repl_c_cap = floor(Pc, np.maximum(cols_used, one))
    r_first = clip(repl_r_cap, one, groups)
    c_rest = clip(repl_c_cap, one, np.maximum(groups / r_first, one))
    c_first = clip(repl_c_cap, one, groups)
    r_rest = clip(repl_r_cap, one, np.maximum(groups / c_first, one))
    by_cols = ~legacy & cols_first
    repl_r = np.where(by_cols, r_rest, r_first)
    repl_c = np.where(by_cols, c_first, c_rest)
    util = clip((rows_used * repl_r / (fold_r * Pr))
                * (cols_used * repl_c / (fold_e * Pc)), dt(1e-3), one)
    active_pes = util * Pr * Pc
    cycles_compute = macs / active_pes

    if_words = batch * C * H * W
    fil_words = K * C * R * S
    of_words = batch * K * Eh * F
    gbuf_bits_cap = gbuf_kb * dt(1024.0 * 8.0)
    fil_share = np.where(legacy, dt(0.5), fil_frac)
    if_share = np.where(legacy, dt(0.5), one - fil_frac)
    k_fit = clip(floor(fil_share * gbuf_bits_cap,
                       np.maximum(C * R * S * w_bits, one)), one, K)
    replay_if = ceil(K, k_fit)
    n_if_fit = clip(floor(if_share * gbuf_bits_cap,
                          np.maximum(C * H * W * a_bits, one)), one, batch)
    replay_fil = ceil(batch, n_if_fit)
    fil_dram_bits = np.where(
        streamed, stream_words * a_bits * batch,
        np.where(gated, fil_words * w_bits / active_frac,
                 fil_words * w_bits * replay_fil))
    dram_bits = (if_words * a_bits * replay_if + fil_dram_bits
                 + of_words * a_bits) * count

    if_gbuf = if_words * ceil(K, q_fit * repl_r)
    fil_gbuf = np.where(streamed, stream_words * batch,
                        np.where(gated, fil_words * fold_e * batch / active_frac,
                                 fil_words * fold_e * batch))
    psum_spill = dt(2.0) * of_words * np.maximum(ceil(C, c_fit) - one,
                                                 dt(0.0))
    gbuf_bits = (if_gbuf * a_bits + fil_gbuf * op2_bits + psum_spill * p_bits
                 + of_words * a_bits) * count
    noc_bits = (if_gbuf * a_bits + fil_gbuf * op2_bits
                + psum_spill * p_bits) * count
    psum_rf = dt(2.0) * macs / np.maximum(S * c_fit, one)

    bytes_per_cycle = bw / np.maximum(clock, dt(1e-6))
    cycles_memory = (dram_bits / dt(8.0)) / np.maximum(bytes_per_cycle,
                                                       dt(1e-6))
    cycles_compute = cycles_compute * np.where(streamed, one, count)
    cycles = np.maximum(cycles_compute, cycles_memory)

    gbuf_e = dt(GBUF_E_PER_BIT_PJ) * np.sqrt(gbuf_kb / dt(GBUF_REF_KB))
    e_mac = macs * _table(MAC_ENERGY_PJ, t, dt)[:, None] \
        + cycles * active_pes * dt(PE_CTRL_ENERGY_PJ)
    e_rf = (macs * _rf_access(a_bits, si * a_bits, dt)
            + macs * _rf_access(op2_bits, sf * op2_bits, dt)
            + psum_rf * _rf_access(p_bits, sp * p_bits, dt))
    e_mem = e_rf + noc_bits * dt(NOC_E_PER_BIT_PJ) + gbuf_bits * gbuf_e
    e_dram = dram_bits * dt(DRAM_E_PER_BIT_PJ)
    return dict(macs=macs, cycles=cycles, utilization=util, e_mac=e_mac,
                e_mem=e_mem, e_dram=e_dram)


def network_sums(cfg: dict, clock_ghz, layers: dict, dt=np.float64) -> dict:
    """Whole-network sums per design point: cycles, MACs, energies, and
    the MAC-weighted utilization."""
    per = layer_costs(cfg, clock_ghz, layers, dt)
    out = {k: per[k].sum(axis=1, dtype=dt) for k in
           ("macs", "cycles", "e_mac", "e_mem", "e_dram")}
    out["utilization"] = (per["utilization"] * per["macs"]).sum(
        axis=1, dtype=dt) / np.maximum(out["macs"], dt(1.0))
    return out


def finish(sums: dict, p: dict, dt=np.float64) -> dict:
    """Network sums + PPA -> the design point's metric columns."""
    f = lambda x: np.asarray(x).astype(dt)  # noqa: E731
    clock, area, leak = f(p["clock_ghz"]), f(p["area_mm2"]), f(p["leak_mw"])
    latency = f(sums["cycles"]) / (clock * dt(1e9))
    e_chip = (f(sums["e_mac"]) + f(sums["e_mem"])) * dt(1e-12) \
        + leak * dt(1e-3) * latency
    lat = np.maximum(latency, dt(1e-12))
    return dict(latency_s=latency, energy_j=e_chip,
                energy_total_j=e_chip + f(sums["e_dram"]) * dt(1e-12),
                area_mm2=area, power_mw=e_chip / lat * dt(1e3),
                clock_ghz=clock, utilization=f(sums["utilization"]),
                macs=f(sums["macs"]))


def evaluate(cfg: dict, layers: dict, dt=np.float64,
             finish_dtype=np.float64) -> dict:
    """Metric columns of N design points of one model."""
    p = ppa(cfg, dt)
    return finish(network_sums(cfg, p["clock_ghz"], layers, dt), p,
                  finish_dtype)
