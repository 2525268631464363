"""Row-stationary (RS) dataflow cost model.

Analytical model of the paper's spatial-array accelerator executing one
conv/GEMM layer under the row-stationary dataflow (Eyeriss, [2] in the
paper). Produces compute cycles, per-level access counts, energy, and
latency. Written as pure jnp scalar math so it can be

    jax.vmap(layer_cost, in_axes=(0, None, None))      # over layers
    jax.vmap(..., in_axes=(None, 0, 0))                # over design points

which is the DSE inner loop.

Mapping summary (per Eyeriss):
  * PE(i, j) computes a 1-D row conv: filter row i x ifmap row -> output
    row j.  Logical array = R rows x E cols, folded / replicated onto the
    physical pe_rows x pe_cols grid.
  * Each PE holds q filters x c channels of one filter row in its filter
    spad (q*c*S words), a c*S ifmap sliding window, and q partial sums.
  * Filter weights are *stationary*; ifmap rows are multicast diagonally;
    psums accumulate vertically.

All counts are smooth monotone functions of the config (ceil-style
quantization kept) so Pareto sweeps and property tests behave sanely.
"""

from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp

from repro.core import energy as E
from repro.core import pe as PE
from repro.core.arch import AcceleratorConfig
from repro.core.workloads import (KIND_ATTN_KV, KIND_MOE_EXPERT, LayerSpec,
                                  ceil_div, floor_div)


class LayerCost(NamedTuple):
    macs: jnp.ndarray
    cycles_compute: jnp.ndarray
    cycles_memory: jnp.ndarray
    cycles: jnp.ndarray            # max(compute, memory) — double buffered
    utilization: jnp.ndarray       # spatial PE utilization in [0, 1]
    dram_bits: jnp.ndarray
    gbuf_bits: jnp.ndarray
    noc_bits: jnp.ndarray
    rf_bits: jnp.ndarray
    energy_pj: jnp.ndarray         # total layer energy (incl. DRAM)
    energy_mac_pj: jnp.ndarray
    energy_mem_pj: jnp.ndarray     # on-chip memory (RF + NoC + gbuf)
    energy_dram_pj: jnp.ndarray    # off-chip DRAM (not visible to synthesis)


def _ceil_div(a, b):
    return ceil_div(a, jnp.maximum(b, 1.0))


def _mapping_knobs(mapping):
    """Decompose ``cfg.mapping`` (a code in [0, arch.MAPPING_CHOICES))
    into the schedule knobs QADAM holds fixed:

      * ``fil_frac``  — gbuf capacity fraction granted to the filter
        replay tile (the legacy model hardcodes an even 0.5/0.5 split);
      * ``cols_first`` — replicate spare PE columns before spare rows
        (the legacy replication order is rows-first);
      * ``c_div`` / ``q_div`` — divisors on the channel / filter per-PE
        tile caps (smaller tiles trade RF pressure for spill traffic).

    Mixed radix 3 x 2 x 4 x 5 = 120 codes; code 0 decodes to the exact
    legacy schedule (0.5 split, rows-first, divisors 1).  Decoded in
    integer arithmetic, so no backend's divide can shift a digit.
    """
    m = jnp.asarray(mapping).astype(jnp.int32)
    split_code = m % 3                                 # 0 -> 0.5 (legacy)
    fil_frac = jnp.where(split_code == 1, 0.75,
                         jnp.where(split_code == 2, 0.25, 0.5))
    cols_first = (m // 3) % 2 == 1
    c_div = jnp.left_shift(1, (m // 6) % 4).astype(jnp.float32)  # 1,2,4,8
    q_code = (m // 24) % 5
    q_div = jnp.where(q_code == 4, 6.0,
                      (q_code + 1).astype(jnp.float32))  # 1, 2, 3, 4, 6
    return m == 0, fil_frac, cols_first, c_div, q_div


def layer_cost(layer: LayerSpec, cfg: AcceleratorConfig,
               clock_ghz: jnp.ndarray) -> LayerCost:
    """Cost of one layer on one design point at a given clock.

    Per-operand second-operand streams (the phase-aware IR; neutral
    fields reproduce the legacy resident-weight arithmetic bit-exactly —
    every altered term is a ``jnp.where`` whose false branch is the
    original expression):

    * resident weights (conv/gemm): stationary, gbuf-replayed — the
      paper's model, unchanged;
    * streamed KV (``attn_kv``): ``stream_words`` activation-width words
      read once per batch element with NO cross-batch reuse or replay
      (the cache is per-sequence state, not a shared filter);
    * gated expert weights (``moe_expert``): the layer shape carries the
      ACTIVE top-k compute while weight DRAM/gbuf traffic is divided by
      ``active_frac`` (= 1/touched experts) — traffic follows touched
      experts, compute follows active MACs.

    ``cfg.mapping`` prices the dataflow/mapping axis (``_mapping_knobs``):
    nonzero codes re-tile the per-PE caps, flip the replication order and
    re-split the gbuf.  Code 0 selects the legacy expressions through
    ``jnp.where`` guards whose false branch is the original arithmetic
    unchanged, so every pre-existing space (whose mapping axis is the
    single value 0.0) prices bit-exactly as before.
    """
    H, W, C, K = layer.H, layer.W, layer.C, layer.K
    R, S, stride, batch = layer.R, layer.S, layer.stride, layer.batch
    count = layer.count
    streamed = layer.kind == float(KIND_ATTN_KV)
    gated = layer.kind == float(KIND_MOE_EXPERT)
    active_frac = jnp.maximum(layer.active_frac, 1e-9)
    Eh = floor_div(H - R, stride) + 1.0
    F = floor_div(W - S, stride) + 1.0
    macs = batch * K * C * R * S * Eh * F * count

    a_bits = PE.act_bits(cfg.pe_type)
    w_bits = PE.weight_bits(cfg.pe_type)
    p_bits = PE.psum_bits(cfg.pe_type)
    # the second operand's storage width: resident/gated weights at
    # weight precision, a streamed KV block at activation precision
    op2_bits = jnp.where(streamed, a_bits, w_bits)

    legacy, fil_frac, cols_first, c_div, q_div = _mapping_knobs(cfg.mapping)

    # ---- per-PE tiling limited by scratchpad capacities ----------------
    # mapped codes cap the channel/filter tiles below capacity (c_div /
    # q_div): less RF residency per PE, more replication groups and spill
    c_fit = jnp.where(
        legacy, jnp.clip(floor_div(cfg.spad_ifmap, S), 1.0, C),
        jnp.clip(floor_div(cfg.spad_ifmap, S * c_div), 1.0, C))
    q_cap = floor_div(cfg.spad_filter, c_fit * S)
    q_fit = jnp.where(
        legacy, jnp.clip(jnp.minimum(q_cap, cfg.spad_psum), 1.0, K),
        jnp.clip(jnp.minimum(floor_div(q_cap, q_div), cfg.spad_psum),
                 1.0, K))

    # ---- spatial mapping: logical R x E grid onto pe_rows x pe_cols ----
    Pr, Pc = cfg.pe_rows, cfg.pe_cols
    rows_used = jnp.minimum(R, Pr)
    cols_used = jnp.minimum(Eh, Pc)
    fold_r = _ceil_div(R, Pr)
    fold_e = _ceil_div(Eh, Pc)
    # replication of independent (filter/channel/batch) groups onto idle
    # PEs; the mapping's loop-order bit picks which array dimension gets
    # first claim on the group supply (legacy: rows first)
    groups = _ceil_div(K, q_fit) * _ceil_div(C, c_fit) * batch
    repl_r_cap = floor_div(Pr, jnp.maximum(rows_used, 1.0))
    repl_c_cap = floor_div(Pc, jnp.maximum(cols_used, 1.0))
    repl_r_first = jnp.clip(repl_r_cap, 1.0, groups)
    repl_c_rest = jnp.clip(repl_c_cap, 1.0,
                           jnp.maximum(groups / repl_r_first, 1.0))
    repl_c_first = jnp.clip(repl_c_cap, 1.0, groups)
    repl_r_rest = jnp.clip(repl_r_cap, 1.0,
                           jnp.maximum(groups / repl_c_first, 1.0))
    use_cols = jnp.logical_and(jnp.logical_not(legacy), cols_first)
    repl_r = jnp.where(use_cols, repl_r_rest, repl_r_first)
    repl_c = jnp.where(use_cols, repl_c_first, repl_c_rest)
    util = (rows_used * repl_r / (fold_r * Pr)) * \
           (cols_used * repl_c / (fold_e * Pc))
    util = jnp.clip(util, 1e-3, 1.0)

    active_pes = util * Pr * Pc
    cycles_compute = macs / active_pes  # 1 MAC-equiv per PE per cycle

    # ---- data volumes (words) ------------------------------------------
    if_words = batch * C * H * W
    fil_words = K * C * R * S
    of_words = batch * K * Eh * F

    # ---- DRAM traffic with gbuf-capacity replay factors -----------------
    gbuf_bits_cap = cfg.gbuf_kb * 1024.0 * 8.0
    # filters that fit in the filter share of the gbuf alongside the
    # ifmap tile (legacy: an even 0.5/0.5 split; mapped codes re-split)
    k_fit_gbuf = jnp.where(
        legacy,
        jnp.clip(floor_div(0.5 * gbuf_bits_cap,
                           jnp.maximum(C * R * S * w_bits, 1.0)), 1.0, K),
        jnp.clip(floor_div(fil_frac * gbuf_bits_cap,
                           jnp.maximum(C * R * S * w_bits, 1.0)), 1.0, K))
    replay_if = _ceil_div(K, k_fit_gbuf)
    # ifmaps (batch granularity) that fit in the remaining share
    n_if_fit = jnp.where(
        legacy,
        jnp.clip(floor_div(0.5 * gbuf_bits_cap,
                           jnp.maximum(C * H * W * a_bits, 1.0)), 1.0, batch),
        jnp.clip(floor_div((1.0 - fil_frac) * gbuf_bits_cap,
                           jnp.maximum(C * H * W * a_bits, 1.0)), 1.0, batch))
    replay_fil = _ceil_div(batch, n_if_fit)
    # second-operand DRAM stream: resident weights replay with gbuf
    # capacity; gated expert weights are read once per TOUCHED expert
    # (/ active_frac); a streamed KV block is read once per batch element
    fil_dram_bits = jnp.where(
        streamed, layer.stream_words * a_bits * batch,
        jnp.where(gated, fil_words * w_bits / active_frac,
                  fil_words * w_bits * replay_fil))
    dram_bits = (if_words * a_bits * replay_if
                 + fil_dram_bits
                 + of_words * a_bits) * count

    # ---- gbuf traffic ----------------------------------------------------
    if_gbuf_reads = if_words * _ceil_div(K, q_fit * repl_r)
    fil_gbuf_reads = jnp.where(
        streamed, layer.stream_words * batch,
        jnp.where(gated, fil_words * fold_e * batch / active_frac,
                  fil_words * fold_e * batch))
    psum_spill = 2.0 * of_words * jnp.maximum(_ceil_div(C, c_fit) - 1.0, 0.0)
    gbuf_bits = (if_gbuf_reads * a_bits + fil_gbuf_reads * op2_bits
                 + psum_spill * p_bits + of_words * a_bits) * count

    # ---- NoC + RF traffic ------------------------------------------------
    noc_bits = (if_gbuf_reads * a_bits + fil_gbuf_reads * op2_bits
                + psum_spill * p_bits) * count
    # Each MAC reads one act + one second-operand word from the spads;
    # partial sums accumulate in the PE's register across the S filter
    # taps AND the c channels resident in the spads, touching the psum
    # spad once per c*S MACs (read-modify-write).
    psum_rf_accesses = 2.0 * macs / jnp.maximum(S * c_fit, 1.0)
    rf_bits = macs * (a_bits + op2_bits) + psum_rf_accesses * p_bits

    # ---- memory-bound cycles ----------------------------------------------
    bytes_per_cycle = cfg.bandwidth_gbps / jnp.maximum(clock_ghz, 1e-6)
    cycles_memory = (dram_bits / 8.0) / jnp.maximum(bytes_per_cycle, 1e-6)
    # resident-weight layers keep the historical per-count serialization
    # factor (each repeat re-stages its weights through the array);
    # streamed-KV layers have no weights to stage, so their repeats run at
    # the array's MAC throughput (macs above already carries count)
    cycles_compute = cycles_compute * jnp.where(streamed, 1.0, count)
    cycles = jnp.maximum(cycles_compute, cycles_memory)

    # ---- energy ------------------------------------------------------------
    e_mac = macs * PE.mac_energy_pj(cfg.pe_type) \
        + cycles * active_pes * PE.PE_CTRL_ENERGY_PJ
    e_rf = (macs * E.rf_access_energy(a_bits, cfg.spad_ifmap * a_bits)
            + macs * E.rf_access_energy(op2_bits, cfg.spad_filter * op2_bits)
            + psum_rf_accesses * E.rf_access_energy(
                p_bits, cfg.spad_psum * p_bits))
    e_mem = (e_rf
             + noc_bits * E.NOC_E_PER_BIT_PJ
             + gbuf_bits * E.gbuf_energy_per_bit(cfg.gbuf_kb))
    e_dram = dram_bits * E.DRAM_E_PER_BIT_PJ
    return LayerCost(
        macs=macs, cycles_compute=cycles_compute, cycles_memory=cycles_memory,
        cycles=cycles, utilization=util, dram_bits=dram_bits,
        gbuf_bits=gbuf_bits, noc_bits=noc_bits, rf_bits=rf_bits,
        energy_pj=e_mac + e_mem + e_dram, energy_mac_pj=e_mac,
        energy_mem_pj=e_mem, energy_dram_pj=e_dram)


def _layer_fold(x: jnp.ndarray) -> jnp.ndarray:
    """Strictly sequential left fold over the LAST (layer) axis.

    ``jnp.sum`` lets XLA reassociate the reduction, and the association it
    picks depends on the layer count — so a workload padded with exact-0.0
    layers would sum to a *different* float32 value than its unpadded
    oracle.  An unrolled left fold always adds layers in stack order:
    trailing zeros land after the valid prefix and ``x + 0.0 == x`` is
    exact.
    """
    acc = x[..., 0]
    for i in range(1, x.shape[-1]):
        acc = acc + x[..., i]
    return acc


def reduce_layer_costs(per_layer: LayerCost, counts: jnp.ndarray,
                       barrier: bool = False) -> LayerCost:
    """Mask padded layers to exact 0.0 and fold the LAST (layer) axis.

    The padding contract: layers with ``count == 0`` contribute exact 0.0
    to every summed field and weight 0 to the MAC-weighted utilization, so
    a padded workload reduces to the same values as its unpadded oracle.

    ``barrier=True`` (the DSE evaluators) additionally pins the per-layer
    values with ``lax.optimization_barrier`` before the fold: without it,
    XLA fuses the per-layer arithmetic into the fold chain and makes
    ulp-level FMA/vectorization choices that depend on the padded length,
    which would leak shape-dependent noise into otherwise-identical
    results.  The barrier has no batching rule, so it is only available
    outside ``vmap`` — ``network_cost`` (which is vmapped per lane by
    legacy callers) skips it; under eager execution the fold is
    bit-stable anyway because there is no cross-op fusion.
    """
    valid = counts > 0.0
    per_layer = jax.tree.map(lambda x: jnp.where(valid, x, 0.0), per_layer)
    if barrier:
        per_layer = jax.lax.optimization_barrier(per_layer)
    summed = jax.tree.map(_layer_fold, per_layer)
    # utilization: MAC-weighted mean, not a sum
    util = _layer_fold(per_layer.utilization * per_layer.macs) / \
        jnp.maximum(_layer_fold(per_layer.macs), 1.0)
    # rebuild the total from the folded components at a fixed association
    # (folding per-layer totals would re-round differently than the sums)
    return summed._replace(
        utilization=util,
        energy_pj=(summed.energy_mac_pj + summed.energy_mem_pj
                   + summed.energy_dram_pj))


def network_cost(layers: LayerSpec, cfg: AcceleratorConfig,
                 clock_ghz: jnp.ndarray) -> LayerCost:
    """Sum layer costs over a stacked LayerSpec (vmapped over layers).

    Layers with ``count == 0`` are padding (``workloads.pad_workload``) and
    are masked out of the reduction entirely — see ``reduce_layer_costs``
    for the exact-padding contract that lets mixed-model chunks share one
    compiled evaluator regardless of each model's true layer count.
    """
    per_layer = jax.vmap(layer_cost, in_axes=(0, None, None))(
        layers, cfg, clock_ghz)
    return reduce_layer_costs(per_layer, layers.count)
