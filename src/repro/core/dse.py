"""Design-space exploration + Pareto analysis (the paper's Sec. IV).

Evaluates every design point of the accelerator space against a DNN
workload with the row-stationary cost model, computing the paper's two
hardware-efficiency metrics:

  * performance per area  (inferences/s per mm^2)
  * energy per inference  (J)

and extracts Pareto fronts.

The engine is *streaming*: the design space is walked in fixed-shape
chunks (mixed-radix decode in ``arch.iter_space_chunks``), every chunk is
evaluated under ONE jit compilation (the trailing partial chunk is padded
up to the chunk shape, so batch size never retraces), and the Pareto
front is maintained incrementally in a non-dominated archive.  Peak
memory is O(chunk_size) for evaluation and O(N * block) for the tiled
mask — never the O(N^2) broadcast of the dense mask, which is kept as
the reference oracle (``pareto_mask_dense``) for tests.

The clock for each design point comes either from the synthesis oracle
("actual", the paper's DC flow) or from the fitted polynomial PPA
surrogate ("predicted") — comparing the two DSE outcomes is exactly the
paper's validation story.
"""

from __future__ import annotations

import time
from functools import partial
from typing import Iterator, NamedTuple, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.arch import (AcceleratorConfig, PE_INT16, PE_TYPE_NAMES,
                             concat_configs, iter_space_chunks, space_points,
                             take_config)
from repro.core.constraints import (Budget, BudgetStats, apply_budget,
                                    mask_result)
from repro.core.costmodel import CostModel, as_cost_model
from repro.core.dataflow import layer_cost, reduce_layer_costs
from repro.core.ppa import PPAModels
from repro.core.synth import LEAKAGE_MW_PER_MM2
from repro.core.workloads import (StackedWorkload, Workload, layer_bucket,
                                  pad_layers, workload_layers)
from repro.obs import as_tracer, timed_iter

# Default number of design points evaluated per jit call in the streaming
# paths. Large enough to amortize dispatch, small enough that a chunk's
# intermediates stay in cache-friendly territory.
DEFAULT_CHUNK_SIZE = 4096

# Host-side dtype of every DseResult column (what evaluate_chunk /
# evaluate_space return).  The derived metric columns are computed ON HOST
# in float64 from the device cost sums — one implementation shared by
# every evaluation path, so identical device sums give bit-identical
# columns regardless of batch shape or model mixing (XLA re-fuses the
# derived arithmetic differently per compiled shape, which would otherwise
# leak ulp-level noise into the Pareto objectives).  macs in particular
# needs float64: it is a count that overflows float32's 24-bit mantissa
# for ImageNet-scale networks.
RESULT_DTYPES = dict.fromkeys((
    "latency_s", "energy_j", "energy_total_j", "area_mm2", "power_mw",
    "clock_ghz", "perf", "perf_per_area", "utilization", "macs"), np.float64)


class DseResult(NamedTuple):
    """Struct-of-arrays over N design points for one workload.

    Columns returned by ``evaluate_chunk`` / ``evaluate_space`` are host
    numpy arrays with the dtypes in ``RESULT_DTYPES``.
    """
    latency_s: jnp.ndarray
    energy_j: jnp.ndarray        # chip energy: MAC + on-chip mem + leakage*T
    energy_total_j: jnp.ndarray  # chip + DRAM (beyond-paper reporting)
    area_mm2: jnp.ndarray
    power_mw: jnp.ndarray
    clock_ghz: jnp.ndarray
    perf: jnp.ndarray            # inferences / s
    perf_per_area: jnp.ndarray   # inferences / s / mm^2
    utilization: jnp.ndarray
    macs: jnp.ndarray


# Number of times the jitted evaluators have been TRACED (== compiled for a
# new shape).  Benchmarks read deltas of this to report n_compiles — the
# compile-amortization story of bucketed one-compile sweeps.
# ``trace_count`` covers the dataflow-stage evaluators (one per layer
# bucket x chunk shape — the expensive compilations); ``ppa_trace_count``
# covers the batched PPA stage (one per backend structure x chunk shape,
# shared by every walk — the counter that proves the surrogate path no
# longer re-dispatches per config subset).
_TRACE_COUNT = 0
_PPA_TRACE_COUNT = 0


def trace_count() -> int:
    """Cumulative dataflow-evaluator trace/compile count for this process."""
    return _TRACE_COUNT


def ppa_trace_count() -> int:
    """Cumulative PPA-stage (cost-model backend) trace/compile count."""
    return _PPA_TRACE_COUNT


def reset_trace_count() -> None:
    """Zero BOTH compile counters (benchmarks bracket sweeps with this)."""
    global _TRACE_COUNT, _PPA_TRACE_COUNT
    _TRACE_COUNT = 0
    _PPA_TRACE_COUNT = 0


def _count_trace() -> None:
    # Python side effect inside a jitted function: runs once per trace.
    global _TRACE_COUNT
    _TRACE_COUNT += 1


def _count_ppa_trace() -> None:
    global _PPA_TRACE_COUNT
    _PPA_TRACE_COUNT += 1


# -- telemetry glue (repro.obs) ---------------------------------------------
# Span/phase vocabulary shared by every instrumented walk: ``decode``
# (mixed-radix chunk decode), ``dispatch`` (jit dispatch of the PPA +
# dataflow stages), ``device_wait`` (blocking transfer in finish_chunk),
# ``archive`` (host front reduction), ``checkpoint``, ``prune_stage1`` /
# ``prune_stage2``.  Compile events piggyback on the trace counters: a
# dispatch that bumps trace_count/ppa_trace_count charges its duration to
# histogram ``compile.L<layers>`` — per-layer-bucket compile attribution.

def _compile_mark() -> int:
    return _TRACE_COUNT + _PPA_TRACE_COUNT


def _workload_bucket(workload) -> str:
    # the padded layer count the evaluator compiles per: a stack's
    # trailing axis, or the bucket a plain workload is padded to
    if isinstance(workload, StackedWorkload):
        return f"L{int(np.shape(workload.layers.H)[-1])}"
    return f"L{layer_bucket(workload_layers(workload))}"


def _note_compiles(tr, mark: int, start_ns: int, workload,
                   track: str | None = None) -> None:
    """Charge a dispatch that traced new executables to the compile
    histograms (call right after the dispatch returns)."""
    if not tr.enabled:
        return
    delta = _TRACE_COUNT + _PPA_TRACE_COUNT - mark
    if not delta:
        return
    bucket = _workload_bucket(workload)
    tr.observe(f"compile.{bucket}",
               (time.perf_counter_ns() - start_ns) / 1e9)
    tr.counter("sweep.compiles", delta)
    tr.instant("compile", bucket=bucket, n_traces=delta, track=track)


def _traced_dispatch(tr, cfg, workload, model, pad_to, model_ids=None,
                     track: str | None = None) -> "PendingChunk":
    """``dispatch_chunk`` under a ``dispatch`` span + compile detection."""
    if not tr.enabled:
        return dispatch_chunk(cfg, workload, model, pad_to=pad_to,
                              model_ids=model_ids)
    mark = _compile_mark()
    t0 = time.perf_counter_ns()
    with tr.span("dispatch", track=track):
        pending = dispatch_chunk(cfg, workload, model, pad_to=pad_to,
                                 model_ids=model_ids)
    _note_compiles(tr, mark, t0, workload, track=track)
    return pending


def _traced_finish(tr, pending: "PendingChunk",
                   track: str | None = None) -> "DseResult":
    """``finish_chunk`` under a ``device_wait`` span (the blocking
    transfer — in the async pipeline this is where stall time shows)."""
    if not tr.enabled:
        return finish_chunk(pending)
    with tr.span("device_wait", track=track):
        return finish_chunk(pending)


@jax.jit
def _lane_layers(stacked_layers, model_ids: jnp.ndarray):
    """Each lane's own layer stack, gathered from the (M, L) pytree.

    Pure data movement, compiled apart from the arithmetic: the evaluator
    below receives the same (lanes, L) operands whether a chunk mixes
    models, repeats one model, or broadcasts a plain workload — so a
    lane's value cannot depend on how its chunk was grouped."""
    return jax.tree.map(lambda x: x[model_ids], stacked_layers)


@partial(jax.jit, static_argnums=(1, 2))
def _broadcast_layers(layers, lanes: int, depth: int):
    """A plain (L,) workload padded to ``depth`` and repeated per lane —
    the one-model case of ``_lane_layers``."""
    return jax.tree.map(lambda x: jnp.broadcast_to(x, (lanes, depth)),
                        pad_layers(layers, depth))


@jax.jit
def _network_sums(cfg: AcceleratorConfig, clock_ghz: jnp.ndarray,
                  lane_layers):
    """Summed network cost per design-point lane (the jitted hot path).

    ``lane_layers`` holds every lane's own layer stack, leaves (lanes,
    L).  Every walk reaches this one executable per (layer bucket, chunk
    shape) — mixed-model, per-model and plain walks alike — so walks that
    visit the same lane compute it with the same compiled code."""
    _count_trace()
    per_layer = jax.vmap(
        lambda lay, c, clk: jax.vmap(layer_cost, in_axes=(0, None, None))(
            lay, c, clk))(lane_layers, cfg, clock_ghz)  # leaves (lanes, L)
    return reduce_layer_costs(per_layer, lane_layers.count, barrier=True)


def _finish(cost, clock_ghz, area_mm2, leak_mw) -> DseResult:
    """Network cost sums -> DSE metric columns, on HOST in float64.

    Deliberately outside jit: the derived arithmetic is a handful of
    elementwise ops per lane, and keeping it in one host implementation
    makes the columns a deterministic function of the device sums — the
    property that lets a mixed-model bucketed sweep reproduce the
    per-model walk bit-for-bit.
    """
    f64 = lambda x: np.asarray(x, np.float64)  # noqa: E731
    cycles, util, macs = f64(cost.cycles), f64(cost.utilization), f64(cost.macs)
    e_mac, e_mem = f64(cost.energy_mac_pj), f64(cost.energy_mem_pj)
    e_dram = f64(cost.energy_dram_pj)
    clock_ghz, area_mm2 = f64(clock_ghz), f64(area_mm2)
    latency_s = cycles / (clock_ghz * 1e9)
    # The paper's energy = synthesized chip power x simulated runtime: the
    # dynamic part is the access-count model (MAC + RF/NoC/gbuf), plus
    # leakage x runtime. DRAM energy is invisible to a DC synthesis flow and
    # is reported separately (energy_total_j).
    e_chip = (e_mac + e_mem) * 1e-12 + f64(leak_mw) * 1e-3 * latency_s
    perf = 1.0 / np.maximum(latency_s, 1e-12)
    return DseResult(
        latency_s=latency_s, energy_j=e_chip,
        energy_total_j=e_chip + e_dram * 1e-12,
        area_mm2=area_mm2,
        power_mw=e_chip / np.maximum(latency_s, 1e-12) * 1e3,
        clock_ghz=clock_ghz, perf=perf,
        perf_per_area=perf / np.maximum(area_mm2, 1e-9),
        utilization=util, macs=macs)


# The PPA stage: ONE shape-keyed executable per (backend function,
# parameter structure, chunk shape), shared by every evaluation path —
# single-stage chunks, two-stage pruning, and both walk modes all read
# clock/area/leakage from the same compiled graph, so no pair of walks
# can diverge through the cost-model side.  The backend function is a
# static module-level callable (``CostModel.ppa_fn``) and the fitted
# state is a pytree ARGUMENT, so e.g. two surrogate fits with the same
# selected degrees reuse one executable.  Leakage is derived here, inside
# the jit, from the shared 45 nm density constant — the one-leakage-model
# contract of PR 4.
@partial(jax.jit, static_argnums=0)
def _ppa_stage(ppa_fn, params, cfg: AcceleratorConfig):
    _count_ppa_trace()
    power_mw, clock_ghz, area_mm2 = ppa_fn(params, cfg)
    return power_mw, clock_ghz, area_mm2, LEAKAGE_MW_PER_MM2 * area_mm2


def _network_stage(cfg: AcceleratorConfig, clock_ghz,
                   workload: Workload | StackedWorkload, model_ids=None):
    """Dispatch the dataflow fold (the compiled per-bucket evaluator).

    A plain workload is padded to its ``layer_bucket`` depth, the depth
    ``stack_workloads`` gives it by default, so it shares the bucket's
    executable with every stacked walk."""
    if model_ids is not None:
        lanes = _lane_layers(workload.layers, model_ids)
    else:
        lanes = _broadcast_layers(workload.layers,
                                  int(np.shape(cfg.pe_rows)[0]),
                                  layer_bucket(workload_layers(workload)))
    return _network_sums(cfg, clock_ghz, lanes)


class PendingChunk(NamedTuple):
    """An in-flight chunk evaluation: device arrays already DISPATCHED
    (JAX async dispatch — the host returns before the computation runs)
    but not yet transferred.  ``finish_chunk`` blocks on the transfer and
    produces the host ``DseResult``.  The double-buffering handle of the
    sharded pipeline: dispatch chunk k+1, then finish chunk k while k+1
    computes."""
    cost: object                 # dataflow LayerCost sums (device arrays)
    clock: object                # device arrays from the PPA stage
    area: object
    leak: object
    n: int                       # real (unpadded) lane count


def _pad_config(cfg: AcceleratorConfig, pad: int) -> AcceleratorConfig:
    """Repeat the last design point ``pad`` times so the chunk shape is
    fixed — padded lanes are sliced off after evaluation.  Host numpy:
    padding happens on every trailing partial chunk and eager device
    concatenates cost more than the whole jit dispatch."""
    return AcceleratorConfig(*[
        np.concatenate([np.asarray(f),
                        np.broadcast_to(np.asarray(f)[-1:],
                                        (pad,) + np.shape(f)[1:])])
        for f in cfg])


def _slice_config(cfg: AcceleratorConfig, lo: int, hi: int) -> AcceleratorConfig:
    return AcceleratorConfig(*[f[lo:hi] for f in cfg])


def _next_pow2(n: int) -> int:
    return 1 << max(n - 1, 0).bit_length()


def evaluate_chunk(cfg: AcceleratorConfig,
                   workload: Workload | StackedWorkload,
                   surrogate: PPAModels | CostModel | str | None = None,
                   pad_to: int | None = None,
                   model_ids=None) -> DseResult:
    """Evaluate one pre-chunked batch at a fixed jit shape (host result).

    With ``pad_to`` set, the batch is padded (repeating its last point) up
    to that fixed shape before the jit call and the padded lanes are
    trimmed from the result — so every chunk of a streaming walk hits the
    same compiled executable.  This is the shared building block of
    ``evaluate_space_streaming`` and the joint co-exploration evaluator.

    ``surrogate`` selects the cost-model backend (``costmodel``):
    ``None`` is the analytical synthesis oracle, a fitted ``PPAModels``
    (or ``CostModel``/registered name) switches the batched PPA stage —
    the backend's host-side ``validate`` runs on the UNPADDED chunk first,
    so e.g. the surrogate's unfitted-PE-type ``ValueError`` surfaces here
    before any compilation happens.

    Passing a ``StackedWorkload`` plus a per-lane ``model_ids`` vector
    (positions into the stack) evaluates a MIXED-model chunk: each lane
    gathers its own layer stack inside the jitted function, so chunks
    crossing model boundaries still share one compilation per (chunk
    shape, stacked depth).  Lane results are bit-identical to evaluating
    each lane under its own plain workload when the stack's depth is
    that workload's ``layer_bucket``: both then run the same compiled
    evaluator on the same per-lane layer rows.
    """
    return finish_chunk(dispatch_chunk(cfg, workload, surrogate,
                                       pad_to=pad_to, model_ids=model_ids))


def dispatch_chunk(cfg: AcceleratorConfig,
                   workload: Workload | StackedWorkload,
                   surrogate: PPAModels | CostModel | str | None = None,
                   pad_to: int | None = None,
                   model_ids=None) -> PendingChunk:
    """The non-blocking half of ``evaluate_chunk``: validate, pad and
    DISPATCH the jitted stages, returning device futures immediately.

    JAX dispatches asynchronously, so control returns while the chunk
    still computes — the caller can dispatch the next chunk (on another
    device) or do host-side archive work before blocking in
    ``finish_chunk``.  ``finish_chunk(dispatch_chunk(...))`` is exactly
    ``evaluate_chunk(...)``; the split exists so the sharded walk can
    double-buffer.
    """
    stacked = isinstance(workload, StackedWorkload)
    if stacked != (model_ids is not None):
        raise ValueError("model_ids must be given with a StackedWorkload "
                         "and only with one")
    model = as_cost_model(surrogate)
    model.validate(cfg)
    if np.ndim(cfg.pe_rows) == 0:  # single unbatched point: lift to (1,)
        cfg = AcceleratorConfig(*[jnp.reshape(f, (1,)) for f in cfg])
    n = int(np.shape(cfg.pe_rows)[0])
    mids = None
    if stacked:
        mids = np.asarray(model_ids, np.int32)
        if mids.shape != (n,):
            raise ValueError(f"model_ids shape {mids.shape} != ({n},)")
        n_models = int(np.shape(workload.layers.H)[0])
        if mids.size and (mids.min() < 0 or mids.max() >= n_models):
            raise ValueError(f"model_ids out of range for {n_models} "
                             f"stacked models")
    if n == 0:
        # nothing to evaluate; _pad_config cannot broadcast f[-1:] of an
        # empty array, so finish_chunk returns the canonical empty columns
        return PendingChunk(None, None, None, None, 0)
    if pad_to is not None and n < pad_to:
        cfg = _pad_config(cfg, pad_to - n)
        if mids is not None:  # padded lanes repeat the last (model, config)
            mids = np.concatenate([mids, np.broadcast_to(mids[-1:],
                                                         (pad_to - n,))])
    power, clock, area, leak = _ppa_stage(model.ppa_fn, model.ppa_params, cfg)
    del power  # nominal-activity power; the result's power column is
    #            derived from chip energy over runtime in _finish
    cost = _network_stage(cfg, clock, workload,
                          None if mids is None else jnp.asarray(mids))
    return PendingChunk(cost, clock, area, leak, n)


def finish_chunk(pending: PendingChunk) -> DseResult:
    """The blocking half of ``evaluate_chunk``: transfer the dispatched
    device arrays and derive the host float64 columns (``_finish`` — the
    same single implementation every path shares, so a pipelined chunk is
    bit-identical to a synchronous one)."""
    if pending.n == 0:
        return _empty_result()
    res = _finish(pending.cost, pending.clock, pending.area, pending.leak)
    return DseResult(*[np.asarray(col[:pending.n], RESULT_DTYPES[f])
                       for f, col in zip(DseResult._fields, res)])


def _empty_result() -> DseResult:
    """Zero-point DseResult with the documented per-column host dtypes."""
    return DseResult(*[np.empty((0,), RESULT_DTYPES[f])
                       for f in DseResult._fields])


def chunk_dominators(obj: np.ndarray, block: int = 512):
    """Shared strict-domination structure of one chunk's objective rows:
    the pair ``(front, dom)`` where ``front`` holds the row indices of
    the chunk's own non-dominated front and ``dom[k, r]`` is True when
    row ``front[k]`` strictly dominates row r (>= in every objective,
    > in at least one — the archive's own relation, so duplicates never
    dominate each other).

    Computed ONCE per evaluated chunk and shared across every coalesced
    budget query reading it: a query with feasibility mask ``m`` drops
    rows dominated by a FEASIBLE front row (``dom[m[front]].any(0)``)
    before its archive fold.  Exact on both sides: front rows are never
    dominated in-chunk, so a feasible front-row dominator always reaches
    the archive and kills the dropped row there anyway; and any row the
    prefilter leaves that a feasible non-front row dominates is still
    removed by the archive's own reduction.  Restricting dominators to
    the front keeps the adjacency |front| x N instead of N x N — Q
    per-query O(N^2) in-chunk reductions become one shared front pass
    plus Q boolean reduces.

    Blocked so the (block, N, D) broadcast temporary stays bounded.
    """
    obj = np.asarray(obj, np.float64)
    front = np.flatnonzero(ParetoArchive._chunk_front_mask(obj))
    f = obj[front]
    dom = np.empty((len(front), len(obj)), bool)
    for lo in range(0, len(front), block):
        blk = f[lo:lo + block, None, :]
        dom[lo:lo + block] = (np.all(blk >= obj[None, :, :], axis=-1)
                              & np.any(blk > obj[None, :, :], axis=-1))
    return front, dom


def fold_budget_chunk(archive, obj, idx, result=None, budget=None,
                      accuracy=None, stats=None, aux=(), dom=None,
                      telemetry=None, track=None):
    """Mask one evaluated chunk by ``budget`` and fold the survivors into
    ``archive`` — the per-sink fold every budget-aware walk shares
    (single-process walks, each shard of a sharded walk, and each
    coalesced frontserver query reading the same evaluated chunk).

    ``obj``/``idx`` are the chunk's objective matrix and global flat
    indices; ``result`` is anything ``Budget.feasibility`` can read — a
    full ``DseResult`` or a replayed ``constraints.BudgetColumns`` view —
    and ``accuracy`` is a joint walk's per-lane accuracy.  ``aux`` is any
    number of extra per-lane arrays masked in lockstep (e.g. model ids /
    PE codes feeding the best-seen aggregates).  A ``None`` budget folds
    the chunk unmasked.

    Feeding Q archives from ONE evaluated chunk via Q calls is
    bit-identical to Q standalone constrained walks: the mask is a
    row-wise function of the same host columns, and each archive consumes
    the same (objectives, indices) sequence it would have seen alone.
    ``dom`` (a shared ``chunk_dominators`` result) additionally drops
    rows a feasible front row of the SAME chunk dominates before the
    archive sees them — an exact prefilter (see ``chunk_dominators``)
    that makes the per-query fold cheap when many queries share one
    chunk.

    Returns the (possibly masked) ``(obj, idx, aux)`` that reached the
    archive.
    """
    tr = as_tracer(telemetry)
    mask = None
    if budget is not None:
        mask, kills = budget.feasibility(result, accuracy=accuracy)
        if stats is not None:
            stats.record(mask, kills)
        if tr.enabled:
            killed = len(mask) - int(np.count_nonzero(mask))
            if killed:
                tr.counter("budget.killed", killed)
            for cname, k in kills.items():
                if k:
                    tr.counter(f"budget.kill.{cname}", k)
        if mask.all():
            mask = None
    if dom is not None:
        front, adj = dom
        keep = ~adj.any(axis=0) if mask is None \
            else mask & ~adj[mask[front]].any(axis=0)
        if not keep.all():
            mask, (obj, idx) = None, (obj[keep], idx[keep])
            aux = tuple(a[keep] for a in aux)
    if mask is not None:
        obj, idx = obj[mask], idx[mask]
        aux = tuple(a[mask] for a in aux)
    with tr.span("archive", track=track):
        archive.update(obj, idx)
    return obj, idx, aux


class _PPAView(NamedTuple):
    """The stage-1 columns a config-stage constraint can read (duck-typed
    into ``Budget.feasibility``; accuracy is passed separately)."""
    area_mm2: np.ndarray


class TwoStagePruner:
    """Config-only constraint pre-pruning for the streaming walks.

    Stage 1 runs the batched PPA stage on every raw chunk (at the fixed
    chunk shape — the same executable the single-stage walk uses),
    applies the budget's CONFIG-stage bounds (chip area; per-lane
    accuracy on joint walks) to the PPA columns, and buffers the
    survivors on host: config fields, clock/area/leakage, global indices,
    the stacked-model ids, and any caller-supplied per-lane ``aux``
    arrays.  Whenever the buffer holds a full chunk of survivors, stage 2
    folds the per-layer dataflow walk over exactly those lanes — again at
    the SAME compiled chunk shape (the trailing partial flush pads by
    repeating its last lane, like every streaming trailing chunk), with
    the buffered stage-1 clock/area/leakage passed through instead of
    recomputed.  Workload-stage bounds are then applied to each flush, so
    yielded chunks contain only fully-feasible lanes.

    Bit-identity contract: both stages reuse the single-stage walk's
    executables and per-lane results are position-independent (the same
    property that makes mixed-model chunks match the per-model walk), so
    a surviving lane's columns are bit-identical to its single-stage
    values — pruning only removes rows, exactly like post-hoc filtering,
    and the downstream ``ParetoArchive`` reduction is order-invariant.
    Under a tight config-only budget the dataflow stage — the expensive
    one — runs only on the feasible fraction of the space.

    Accounting (``BudgetStats``): every raw lane counts as evaluated and
    config-stage kills are counted over all of them (identical to
    post-hoc numbers); stage-1 casualties land in ``stats.pruned``;
    workload-stage kills are counted over the surviving lanes only.
    """

    def __init__(self, budget: Budget, chunk_size: int,
                 model: CostModel | PPAModels | str | None = None,
                 stats: BudgetStats | None = None,
                 telemetry=None, track: str | None = None):
        config_cons = budget.config_constraints()
        if not config_cons:
            raise ValueError("TwoStagePruner needs a budget with at least "
                             "one config-stage bound (area_mm2 / "
                             "min_accuracy) — a purely workload-bounded "
                             "walk has nothing to prune early")
        self.budget = budget
        self.chunk_size = int(chunk_size)
        self.model = as_cost_model(model)
        self.stats = stats
        self._tr = as_tracer(telemetry)
        self._track = track
        self._config_cons = config_cons
        self._workload_cons = budget.workload_constraints()
        if stats is not None:
            # stable kill keys even for a stage that never rejects a lane
            stats.merge_kills({c.name: 0 for c in budget.constraints()})
        self._workload = None           # current stage-2 fold target
        self._model_ids_mode = None     # mixed vs plain, pinned per buffer
        self._frags: list[dict] = []    # buffered survivor fragments
        self._n = 0                     # buffered survivor count

    def __len__(self) -> int:
        """Currently buffered (config-feasible, not yet folded) lanes."""
        return self._n

    def feed(self, cfg: AcceleratorConfig, indices, workload,
             model_ids=None, aux: dict | None = None):
        """Stage-1 one raw chunk; yield any completed stage-2 flushes.

        ``workload`` is the stage-2 fold target for these lanes; feeding
        a DIFFERENT workload object first drains the buffer (survivors of
        different folds can't share a flush).  ``model_ids`` are stacked
        positions for mixed chunks (same contract as ``evaluate_chunk``).
        ``aux`` maps names to per-lane host arrays that ride along with
        the survivors and come back with each flush; ``aux["accuracy"]``
        additionally binds a ``min_accuracy`` config-stage bound.
        """
        if isinstance(workload, StackedWorkload) != (model_ids is not None):
            raise ValueError("model_ids must be given with a StackedWorkload "
                             "and only with one")
        if self._n and workload is not self._workload:
            yield from self._drain()
        self._workload = workload
        self._model_ids_mode = model_ids is not None
        idx = np.asarray(indices, np.int64)
        n = len(idx)
        if n == 0:
            return
        if n > self.chunk_size:
            raise ValueError(f"chunk of {n} lanes exceeds the pruner's "
                             f"compiled chunk shape ({self.chunk_size}) — "
                             f"feed chunks at most chunk_size long")
        with self._tr.span("prune_stage1", track=self._track):
            self.model.validate(cfg)
            cfg_p = _pad_config(cfg, self.chunk_size - n) \
                if n < self.chunk_size else cfg
            _, clock, area, leak = _ppa_stage(self.model.ppa_fn,
                                              self.model.ppa_params, cfg_p)
            clock = np.asarray(clock)[:n]
            area = np.asarray(area)[:n]
            leak = np.asarray(leak)[:n]
            accuracy = None if aux is None else aux.get("accuracy")
            mask, kills = self.budget.feasibility(
                _PPAView(area_mm2=area), accuracy=accuracy,
                constraints=self._config_cons)
        kept = int(np.count_nonzero(mask))
        if self._tr.enabled:
            if kept < n:
                self._tr.counter("budget.killed", n - kept)
            for cname, k in kills.items():
                if k:
                    self._tr.counter(f"budget.kill.{cname}", k)
        if self.stats is not None:
            self.stats.record_evaluated(n, kills)
            self.stats.record_pruned(n - kept)
            if not self._workload_cons:
                self.stats.record_feasible(kept)
        if kept == 0:
            return
        rows = slice(None) if kept == n else np.flatnonzero(mask)
        frag = dict(cfg=take_config(cfg, rows), clock=clock[rows],
                    area=area[rows], leak=leak[rows], idx=idx[rows])
        if model_ids is not None:
            frag["model_ids"] = np.asarray(model_ids, np.int32)[rows]
        frag["aux"] = {} if aux is None else \
            {k: np.asarray(v)[rows] for k, v in aux.items()}
        self._frags.append(frag)
        self._n += kept
        if self._tr.enabled:
            self._tr.gauge("prune.buffered", self._n, track=self._track)
        while self._n >= self.chunk_size:
            out = self._flush(self.chunk_size)
            if out is not None:
                yield out

    def finish(self):
        """Drain the final partial buffer (padded to the chunk shape)."""
        yield from self._drain()

    def state_dict(self) -> dict:
        """The pruner's buffered-survivor state as checkpointable plain
        data.  The stage-2 fold target (``workload``) is NOT serialized —
        it is code-side context the caller re-binds on restore."""
        state = dict(n=int(self._n), mixed=self._model_ids_mode)
        if self._n:
            m = self._merged()
            frag = dict(cfg={f: np.asarray(getattr(m["cfg"], f))
                             for f in AcceleratorConfig._fields},
                        clock=m["clock"], area=m["area"], leak=m["leak"],
                        idx=m["idx"],
                        aux={k: np.asarray(v) for k, v in m["aux"].items()})
            if self._model_ids_mode:
                frag["model_ids"] = m["model_ids"]
            state["frag"] = frag
        return state

    def restore_state(self, state: dict, workload) -> None:
        """Rebuild the survivor buffer from ``state_dict()`` output and
        re-bind the stage-2 fold target.  ``workload`` must be the same
        (bit-identical) workload the checkpointed walk was feeding when
        it saved — the walk drivers record which bucket/model was active
        and pass its workload here."""
        self._n = int(state["n"])
        self._model_ids_mode = state["mixed"]
        self._workload = workload if self._n else None
        self._frags = []
        if self._n:
            f = state["frag"]
            frag = dict(cfg=AcceleratorConfig(
                            **{k: np.asarray(v)
                               for k, v in f["cfg"].items()}),
                        clock=np.asarray(f["clock"]),
                        area=np.asarray(f["area"]),
                        leak=np.asarray(f["leak"]),
                        idx=np.asarray(f["idx"], np.int64),
                        aux={k: np.asarray(v) for k, v in f["aux"].items()})
            if self._model_ids_mode:
                frag["model_ids"] = np.asarray(f["model_ids"], np.int32)
            self._frags = [frag]

    def _drain(self):
        while self._n:
            out = self._flush(min(self._n, self.chunk_size))
            if out is not None:
                yield out

    def _merged(self) -> dict:
        if len(self._frags) > 1:
            cat = lambda key: np.concatenate(  # noqa: E731
                [f[key] for f in self._frags])
            merged = dict(cfg=concat_configs([f["cfg"] for f in self._frags]),
                          clock=cat("clock"), area=cat("area"),
                          leak=cat("leak"), idx=cat("idx"))
            if self._model_ids_mode:
                merged["model_ids"] = cat("model_ids")
            merged["aux"] = {k: np.concatenate([f["aux"][k]
                                                for f in self._frags])
                             for k in self._frags[0]["aux"]}
            self._frags = [merged]
        return self._frags[0]

    def _flush(self, count: int):
        """Fold ``count`` buffered survivors through stage 2; returns the
        feasible ``(result, indices, aux)`` or None if the workload-stage
        bounds killed the whole flush."""
        merged = self._merged()
        head, tail = {}, {}
        for k, v in merged.items():
            if k == "cfg":
                head[k] = take_config(v, slice(0, count))
                tail[k] = take_config(v, slice(count, None))
            elif k == "aux":
                head[k] = {a: w[:count] for a, w in v.items()}
                tail[k] = {a: w[count:] for a, w in v.items()}
            else:
                head[k], tail[k] = v[:count], v[count:]
        self._frags = [tail] if self._n > count else []
        self._n -= count
        if self._tr.enabled:
            self._tr.counter("prune.flushes")
        return self._stage2(head, count)

    def _stage2(self, lanes: dict, n: int):
        pad = self.chunk_size - n
        cfg, clock = lanes["cfg"], lanes["clock"]
        area, leak = lanes["area"], lanes["leak"]
        mids = lanes.get("model_ids")
        if pad:
            rep = lambda v: np.concatenate(  # noqa: E731
                [v, np.broadcast_to(v[-1:], (pad,) + v.shape[1:])])
            cfg = _pad_config(cfg, pad)
            clock, area, leak = rep(clock), rep(area), rep(leak)
            mids = None if mids is None else rep(mids)
        mark = _compile_mark()
        t0 = time.perf_counter_ns()
        with self._tr.span("prune_stage2", track=self._track):
            cost = _network_stage(cfg, jnp.asarray(clock), self._workload,
                                  None if mids is None else jnp.asarray(mids))
            full = _finish(cost, clock, area, leak)
        _note_compiles(self._tr, mark, t0, self._workload, track=self._track)
        res = DseResult(*[np.asarray(col[:n], RESULT_DTYPES[f])
                          for f, col in zip(DseResult._fields, full)])
        idx, aux = lanes["idx"], lanes["aux"]
        if self._workload_cons:
            # workload-stage bounds never read "accuracy" (config-stage)
            mask, kills = self.budget.feasibility(
                res, constraints=self._workload_cons)
            kept = int(np.count_nonzero(mask))
            if self._tr.enabled:
                if kept < n:
                    self._tr.counter("budget.killed", n - kept)
                for cname, k in kills.items():
                    if k:
                        self._tr.counter(f"budget.kill.{cname}", k)
            if self.stats is not None:
                self.stats.merge_kills(kills)
                self.stats.record_feasible(kept)
            if kept == 0:
                return None
            if kept < n:
                res = mask_result(res, mask)
                idx = idx[mask]
                aux = {k: v[mask] for k, v in aux.items()}
        return res, idx, aux


def evaluate_space(cfg: AcceleratorConfig, workload: Workload,
                   surrogate: PPAModels | CostModel | str | None = None,
                   chunk_size: int | None = None) -> DseResult:
    """Evaluate a batched design space on one workload.

    surrogate=None uses the synthesis oracle for clock/area ("actual");
    otherwise the fitted polynomial PPA models ("predicted").

    With ``chunk_size`` set, the batch is processed in fixed-shape chunks
    under a single jit compilation (the final partial chunk is padded to
    the chunk shape), and the result columns are accumulated as host
    numpy arrays — device memory stays O(chunk_size) however large N is.

    A batch that fits in one chunk is padded up to a canonical shape (the
    chunk size if given, else the next power of two), so callers throwing
    many distinct small N at the engine reuse a handful of compiled
    executables instead of retracing per batch shape.
    """
    n = int(np.shape(cfg.pe_rows)[0]) if np.ndim(cfg.pe_rows) else 1
    if n == 0:
        return _empty_result()
    if chunk_size is None or n <= chunk_size:
        # canonical next-pow-2 shape (capped at the chunk size) so many
        # distinct small N share a handful of compiled executables without
        # padding a tiny batch all the way up to a huge chunk
        pad = _next_pow2(n) if chunk_size is None \
            else min(chunk_size, _next_pow2(n))
        return evaluate_chunk(cfg, workload, surrogate, pad_to=pad)
    cols: list[list[np.ndarray]] = [[] for _ in DseResult._fields]
    for lo in range(0, n, chunk_size):
        res = evaluate_chunk(_slice_config(cfg, lo, min(lo + chunk_size, n)),
                             workload, surrogate, pad_to=chunk_size)
        for acc, col in zip(cols, res):
            acc.append(col)
    return DseResult(*[np.concatenate(c) for c in cols])


def evaluate_space_streaming(
        workload: Workload,
        space: dict | None = None,
        surrogate: PPAModels | CostModel | str | None = None,
        chunk_size: int = DEFAULT_CHUNK_SIZE,
        max_points: int | None = None,
        seed: int = 0,
        budget: Budget | None = None,
        budget_stats: BudgetStats | None = None,
        prune: bool = True,
        shards: int | None = None,
        devices=None,
        pipeline_depth: int | None = None,
        telemetry=None,
) -> Iterator[tuple[DseResult, np.ndarray]]:
    """Lazily evaluate the cartesian design space chunk-by-chunk.

    Yields ``(chunk_result, flat_indices)`` with every chunk evaluated at
    the fixed ``chunk_size`` shape (single jit compilation per workload
    layer count); the padded tail of the final chunk is trimmed before it
    is yielded.  Memory never exceeds O(chunk_size).

    With a ``budget`` (``constraints.Budget``) set, each chunk's
    infeasible lanes are dropped on host BEFORE the chunk is yielded —
    the compiled evaluators are untouched and a downstream archive only
    ever sees feasible points (bit-identical to filtering the
    unconstrained walk post hoc).  Fully-infeasible chunks are skipped;
    pass a ``budget_stats`` (``constraints.BudgetStats``) to collect
    evaluated/feasible counts and per-constraint kills.

    When the budget carries CONFIG-stage bounds (chip area) and ``prune``
    is left on, the walk runs TWO-STAGE (``TwoStagePruner``): the batched
    PPA stage prices every raw chunk, config-infeasible lanes die before
    the per-layer dataflow fold, and the survivors are re-packed into
    full chunks for the expensive stage — same feasible lanes, bit-
    identical columns, but the dataflow fold only runs on the feasible
    fraction.  Survivor re-packing means yielded chunk boundaries differ
    from the single-stage walk's (the lane set and order do not).
    ``prune=False`` forces the PR 4 single-stage post-evaluation masking.

    ``shards=`` / ``devices=`` / ``pipeline_depth=`` route the walk
    through the multi-device async pipeline of ``repro.core.shard``
    (same point set, every lane bit-identical); the defaults keep this
    single-process generator.

    ``telemetry=`` (a ``repro.obs.Tracer``; default off) times decode /
    dispatch / device-wait / pruner phases and counts walked points,
    compiles, and budget kills.  Telemetry reads timestamps and host
    scalars only — yielded chunks are bit-identical with it on or off.
    """
    tr = as_tracer(telemetry)
    if shards is not None or devices is not None:
        from repro.core import shard as _shard
        yield from _shard.sharded_space_stream(
            workload, space, surrogate, chunk_size=chunk_size,
            max_points=max_points, seed=seed, budget=budget,
            budget_stats=budget_stats, prune=prune, shards=shards,
            devices=devices,
            pipeline_depth=(_shard.DEFAULT_PIPELINE_DEPTH
                            if pipeline_depth is None else pipeline_depth),
            telemetry=telemetry)
        return
    model = as_cost_model(surrogate)
    if budget is not None and prune and budget.config_constraints():
        pruner = TwoStagePruner(budget, chunk_size, model, budget_stats,
                                telemetry=telemetry)
        for cfg, idx in timed_iter(
                iter_space_chunks(space, chunk_size=chunk_size,
                                  max_points=max_points, seed=seed), tr):
            if tr.enabled:
                tr.counter("sweep.points", len(idx))
            for res, fidx, _aux in pruner.feed(cfg, idx, workload):
                yield res, fidx
        for res, fidx, _aux in pruner.finish():
            yield res, fidx
        return
    for cfg, idx in timed_iter(
            iter_space_chunks(space, chunk_size=chunk_size,
                              max_points=max_points, seed=seed), tr):
        n_raw = len(idx)
        if tr.enabled:
            tr.counter("sweep.points", n_raw)
        pending = _traced_dispatch(tr, cfg, workload, model, chunk_size)
        res = _traced_finish(tr, pending)
        if budget is not None:
            res, idx = apply_budget(res, idx, budget, stats=budget_stats)
            if tr.enabled and len(idx) < n_raw:
                tr.counter("budget.killed", n_raw - len(idx))
            if len(idx) == 0:
                continue
        yield res, idx


# ---------------------------------------------------------------------------
# Pareto analysis
# ---------------------------------------------------------------------------

def pareto_mask_dense(objectives: jnp.ndarray) -> jnp.ndarray:
    """Non-dominated mask, O(N^2) broadcast — the REFERENCE ORACLE.

    objectives: (N, D), all HIGHER-IS-BETTER.  Point i is dominated iff
    some j is >= on every objective and > on at least one.  Allocates the
    full (N, N, D) comparison, so only use for N small enough to afford
    it (tests, tiny fronts); the tiled/sorted paths below are exact and
    bounded-memory.
    """
    a = objectives[:, None, :]   # i
    b = objectives[None, :, :]   # j
    ge = jnp.all(b >= a, axis=-1)
    gt = jnp.any(b > a, axis=-1)
    dominated = jnp.any(ge & gt, axis=1)
    return ~dominated


def pareto_mask_tiled(objectives: jnp.ndarray,
                      block_size: int = 1024) -> jnp.ndarray:
    """Non-dominated mask with O(N * block_size) memory, any D.

    ``lax.fori_loop`` over column blocks of the (implicit) N x N dominance
    matrix: each step compares all N points against one block of
    ``block_size`` candidate dominators and ORs into the dominated
    accumulator.  Padding rows are -inf on every objective so they can
    never dominate a real point — the result is bit-identical to
    ``pareto_mask_dense``.
    """
    obj = jnp.asarray(objectives)
    n, d = obj.shape
    if n == 0:
        return jnp.zeros((0,), bool)
    block_size = min(block_size, n)
    n_blocks = -(-n // block_size)
    padded = jnp.pad(obj, ((0, n_blocks * block_size - n), (0, 0)),
                     constant_values=-jnp.inf)

    def body(k, dominated):
        blk = jax.lax.dynamic_slice(padded, (k * block_size, 0),
                                    (block_size, d))
        ge = jnp.all(blk[None, :, :] >= obj[:, None, :], axis=-1)
        gt = jnp.any(blk[None, :, :] > obj[:, None, :], axis=-1)
        return dominated | jnp.any(ge & gt, axis=1)

    dominated = jax.lax.fori_loop(0, n_blocks, body,
                                  jnp.zeros((n,), bool))
    return ~dominated


def pareto_mask_2d(objectives: np.ndarray) -> np.ndarray:
    """Sort-based O(N log N) non-dominated mask for the 2-objective case.

    Runs on host numpy.  Semantics match ``pareto_mask_dense`` exactly,
    including duplicate handling (equal points never dominate each other):
    sort by x desc then y desc; a point is dominated iff the max y among
    strictly-greater-x points is >= its y, or a same-x point has strictly
    greater y.
    """
    obj = np.asarray(objectives, np.float64)
    n, d = obj.shape
    if d != 2:
        raise ValueError(f"pareto_mask_2d needs 2 objectives, got {d}")
    if n == 0:
        return np.zeros((0,), bool)
    x, y = obj[:, 0], obj[:, 1]
    order = np.lexsort((-y, -x))          # x desc, ties broken y desc
    xs, ys = x[order], y[order]
    new_group = np.r_[True, xs[1:] != xs[:-1]]
    group_id = np.cumsum(new_group) - 1
    group_max = np.maximum.reduceat(ys, np.flatnonzero(new_group))
    prev_max = np.r_[-np.inf, np.maximum.accumulate(group_max)[:-1]]
    dominated = (prev_max[group_id] >= ys) | (group_max[group_id] > ys)
    mask = np.empty(n, bool)
    mask[order] = ~dominated
    return mask


# N above which the dispatcher refuses the O(N^2) dense path.
_DENSE_LIMIT = 4096


def pareto_mask(objectives: jnp.ndarray, method: str = "auto",
                block_size: int = 1024) -> jnp.ndarray:
    """Non-dominated mask. objectives: (N, D), all HIGHER-IS-BETTER.

    method:
      * "auto"   — sort-based O(N log N) when D == 2; dense for small N;
                   tiled O(N * block_size) otherwise.
      * "dense"  — O(N^2) broadcast reference oracle.
      * "tiled"  — lax.fori_loop over column blocks, any D.
      * "sorted" — 2-objective sort-based fast path.

    All methods agree exactly (the dense oracle is the spec).
    """
    obj = jnp.asarray(objectives)
    n, d = obj.shape
    if method == "auto":
        if d == 2:
            method = "sorted"
        elif n <= _DENSE_LIMIT:
            method = "dense"
        else:
            method = "tiled"
    if method == "dense":
        return pareto_mask_dense(obj)
    if method == "tiled":
        return pareto_mask_tiled(obj, block_size=block_size)
    if method == "sorted":
        return jnp.asarray(pareto_mask_2d(np.asarray(obj)))
    raise ValueError(f"unknown pareto_mask method {method!r}")


def _objective_columns(result: DseResult, metrics: Sequence[str]) -> np.ndarray:
    """(N, D) higher-is-better objective matrix from DseResult fields;
    a ``neg_`` prefix flips a lower-is-better metric."""
    cols = []
    for m in metrics:
        if m.startswith("neg_"):
            cols.append(-np.asarray(getattr(result, m[4:]), np.float64))
        else:
            cols.append(np.asarray(getattr(result, m), np.float64))
    return np.stack(cols, axis=-1)


def pareto_front(result: DseResult,
                 metrics: tuple = ("perf_per_area", "neg_energy_j"),
                 method: str = "auto") -> jnp.ndarray:
    return pareto_mask(jnp.asarray(_objective_columns(result, metrics)),
                       method=method)


def _dominated_by(points: np.ndarray, front: np.ndarray) -> np.ndarray:
    """Boolean mask: is ``points[i]`` dominated by some row of ``front``?
    O(len(points) * len(front) * D) — cheap while ``front`` is small."""
    if len(front) == 0 or len(points) == 0:
        return np.zeros(len(points), bool)
    ge = np.all(front[None, :, :] >= points[:, None, :], axis=-1)
    gt = np.any(front[None, :, :] > points[:, None, :], axis=-1)
    return np.any(ge & gt, axis=1)


def _self_nondominated(pts: np.ndarray) -> np.ndarray:
    """Dense pairwise non-dominated mask of ``pts`` against itself,
    O(N^2 * D) — reserve for small N (a block of a chunk)."""
    ge = np.all(pts[None, :, :] >= pts[:, None, :], axis=-1)
    gt = np.any(pts[None, :, :] > pts[:, None, :], axis=-1)
    return ~np.any(ge & gt, axis=1)


class ParetoArchive:
    """Streaming non-dominated archive.

    Feed ``update(objectives, indices)`` chunk-by-chunk; the archive keeps
    exactly the points that would be non-dominated in the concatenation of
    everything seen so far (same semantics as the dense oracle on the full
    matrix — duplicates of a non-dominated point are all retained), with
    rows in ascending index order.  State is O(front size); the full
    objective matrix is never held.
    """

    def __init__(self, num_objectives: int):
        self._obj = np.empty((0, num_objectives), np.float64)
        self._idx = np.empty((0,), np.int64)
        self._seen = 0  # total points fed (default index stream)

    def __len__(self) -> int:
        return len(self._idx)

    @property
    def objectives(self) -> np.ndarray:
        """(A, D) objectives of the current front."""
        return self._obj

    @property
    def indices(self) -> np.ndarray:
        """Global flat indices of the current front's design points."""
        return self._idx

    def state_dict(self) -> dict:
        """The archive's complete state as checkpointable plain data
        (``checkpoint.manager.save_state`` consumes this directly)."""
        return dict(objectives=self._obj.copy(), indices=self._idx.copy(),
                    seen=int(self._seen))

    @classmethod
    def from_state(cls, state: dict) -> "ParetoArchive":
        """Rebuild an archive from ``state_dict()`` output.  The restored
        archive continues bit-identically: front row order is part of the
        state, and ``update`` only ever appends/evicts rows."""
        obj = np.asarray(state["objectives"], np.float64)
        archive = cls(obj.shape[1])
        archive._obj = obj
        archive._idx = np.asarray(state["indices"], np.int64)
        archive._seen = int(state["seen"])
        return archive

    @staticmethod
    def _chunk_front_mask(obj: np.ndarray, block: int = 512) -> np.ndarray:
        """Exact non-dominated mask of one chunk, bounded memory/compute.

        D == 2 uses the sort-based mask.  For D >= 3 the rows are scanned
        in lexicographic-descending order in blocks: any dominator of a
        point is lex-strictly-greater (the first differing objective must
        favor it), so it lands in an earlier block (covered by checking
        the block against the running front — transitivity guarantees an
        *undominated* dominator exists there) or in the same block
        (covered by a dense pass within the block).  Typical cost is
        O(N log N + N * front * D) — the O(N^2) broadcast only ever
        happens for pathological all-nondominated blocks, and then at
        block granularity.
        """
        n, d = obj.shape
        if d == 2:
            return pareto_mask_2d(obj)
        if n <= block:
            return _self_nondominated(obj)
        order = np.lexsort(tuple(-obj[:, k] for k in range(d - 1, -1, -1)))
        s = obj[order]
        keep = np.zeros(n, bool)
        front = np.empty((0, d), np.float64)
        for lo in range(0, n, block):
            blk = s[lo:lo + block]
            alive = np.flatnonzero(~_dominated_by(blk, front))
            alive = alive[_self_nondominated(blk[alive])]
            keep[lo + alive] = True
            front = np.concatenate([front, blk[alive]])
        mask = np.zeros(n, bool)
        mask[order] = keep
        return mask

    def update(self, objectives: np.ndarray,
               indices: np.ndarray | None = None) -> None:
        obj = np.asarray(objectives, np.float64)
        if obj.ndim != 2 or obj.shape[1] != self._obj.shape[1]:
            raise ValueError(f"expected (N, {self._obj.shape[1]}) objectives, "
                             f"got {obj.shape}")
        if not np.isfinite(obj).all():
            # NaN compares False both ways, so a NaN row would neither
            # dominate nor be dominated — it would sit on the front forever.
            # A +inf objective is just as corrupting: that row can never be
            # dominated, so it enthrones itself and evicts every real point
            # (the surrogate's old zero-clock/zero-area lanes did exactly
            # this via perf_per_area = +inf).  Refuse all non-finite loudly.
            bad = np.flatnonzero(~np.isfinite(obj).all(axis=1))
            raise ValueError(
                f"objectives contain non-finite values (NaN/inf) in "
                f"{len(bad)} row(s) (first: {bad[:5].tolist()}) — a NaN row "
                f"can never be dominated and a +inf row dominates "
                f"everything; either corrupts the archive front")
        idx = (np.arange(self._seen, self._seen + len(obj))
               if indices is None else np.asarray(indices, np.int64))
        self._seen += len(obj)
        # drop candidates the current front already dominates (one cheap
        # O(N * front) pass that typically kills ~99% of a chunk), then
        # reduce the survivors to their own front — this pair is what
        # keeps the streaming update off the O(N^2) chunk broadcast;
        # stay in host float64 — routing through jnp would downcast to
        # float32 and drop points that differ only past float32 precision
        if len(self._obj) and len(obj):
            keep = ~_dominated_by(obj, self._obj)
            obj, idx = obj[keep], idx[keep]
        if len(obj) > 1:
            m = self._chunk_front_mask(obj)
            obj, idx = obj[m], idx[m]
        if len(obj) == 0:
            return
        if len(self._obj):
            # candidates already survived the front pre-filter and their
            # own reduction, so the merge only evicts archive points a
            # new candidate dominates
            keep_old = ~_dominated_by(self._obj, obj)
            obj = np.concatenate([self._obj[keep_old], obj])
            idx = np.concatenate([self._idx[keep_old], idx])
        # rows in ascending index order: a front's row order then depends
        # only on its point set, never on the order a walk visited chunks
        order = np.argsort(idx, kind="stable")
        self._obj, self._idx = obj[order], idx[order]


def pareto_front_streaming(
        workload: Workload,
        space: dict | None = None,
        metrics: tuple = ("perf_per_area", "neg_energy_j"),
        surrogate: PPAModels | CostModel | str | None = None,
        chunk_size: int = DEFAULT_CHUNK_SIZE,
        max_points: int | None = None,
        seed: int = 0,
        budget: Budget | None = None,
        budget_stats: BudgetStats | None = None,
        prune: bool = True,
        shards: int | None = None,
        devices=None,
        pipeline_depth: int | None = None,
        checkpoint_dir: str | None = None,
        checkpoint_every: int = 64,
        csv_path: str | None = None,
        max_chunks: int | None = None,
        telemetry=None,
) -> tuple[ParetoArchive, AcceleratorConfig]:
    """Pareto front of an arbitrarily large design space in O(chunk) memory.

    Streams the space through ``evaluate_space_streaming`` and merges every
    chunk into a non-dominated archive.  Returns the archive (objectives +
    global flat indices) and the decoded front configs.

    With ``budget`` set the walk is CONSTRAINT-AWARE: infeasible lanes are
    masked out per chunk before the archive sees them, so the result is
    the Pareto front OF THE FEASIBLE SUBSET (bit-identical, indices and
    objectives, to filtering an unconstrained walk post hoc and reducing
    the survivors).  ``budget_stats`` collects kill telemetry.  Budgets
    with config-stage bounds run two-stage by default (see
    ``evaluate_space_streaming``); ``prune=False`` keeps the single-stage
    post-evaluation masking path.

    GIGA-SCALE knobs (all default-off; any of them routes the walk
    through ``repro.core.shard.sharded_pareto_front``, whose front is
    bit-identical — indices AND objectives — to this single-process
    fold):

    * ``shards`` / ``devices`` / ``pipeline_depth`` — round-robin the
      chunk sequence over per-device archives with async double
      buffering.
    * ``checkpoint_dir`` / ``checkpoint_every`` — atomic walk-state
      snapshots every N chunks; an existing checkpoint in the directory
      RESUMES the walk automatically.
    * ``csv_path`` — stream the decoded front to CSV as it evolves.
    * ``max_chunks`` — truncate after that many chunks (preemption for
      kill/resume tests; returns the partial front after a checkpoint).

    ``telemetry=`` (a ``repro.obs.Tracer``) instruments the walk —
    decode/dispatch/device-wait/archive/checkpoint spans, pts/s counters,
    compile and RSS tracking — without touching any evaluated value: the
    returned front is bit-identical with telemetry on or off.
    """
    if (shards is not None or devices is not None
            or checkpoint_dir is not None or csv_path is not None
            or max_chunks is not None):
        from repro.core import shard as _shard
        return _shard.sharded_pareto_front(
            workload, space, metrics=metrics, surrogate=surrogate,
            chunk_size=chunk_size, max_points=max_points, seed=seed,
            budget=budget, budget_stats=budget_stats, prune=prune,
            shards=shards, devices=devices,
            pipeline_depth=(_shard.DEFAULT_PIPELINE_DEPTH
                            if pipeline_depth is None else pipeline_depth),
            checkpoint_dir=checkpoint_dir,
            checkpoint_every=checkpoint_every, csv_path=csv_path,
            max_chunks=max_chunks, telemetry=telemetry)
    tr = as_tracer(telemetry)
    archive = ParetoArchive(len(metrics))
    for res, idx in evaluate_space_streaming(
            workload, space, surrogate=surrogate, chunk_size=chunk_size,
            max_points=max_points, seed=seed, budget=budget,
            budget_stats=budget_stats, prune=prune, telemetry=telemetry):
        with tr.span("archive"):
            archive.update(_objective_columns(res, metrics), idx)
    return archive, space_points(archive.indices, space)


# ---------------------------------------------------------------------------
# The paper's normalized reporting (Figs. 4-6)
# ---------------------------------------------------------------------------

def best_index(result: DseResult, pe_type: jnp.ndarray, code: int | None,
               metric: str = "perf_per_area", mode: str = "max") -> int:
    """Index of the best design of a given PE type under a metric.

    code=None ranks the whole space.  If no design of the requested PE
    type exists, falls back to the global best (argmax over all -inf would
    otherwise silently return 0).
    """
    vals = np.asarray(getattr(result, metric), np.float64)
    if code is not None:
        sel = np.atleast_1d(np.asarray(pe_type)) == code
        if sel.any():
            vals = np.where(sel, vals, -np.inf if mode == "max" else np.inf)
    return int(np.argmax(vals) if mode == "max" else np.argmin(vals))


def normalized_report(result: DseResult, cfg: AcceleratorConfig) -> dict:
    """Per-PE-type best configs, normalized to the best-perf/area INT16
    design — the exact normalization of the paper's Figs. 4-6.

    If the space contains no INT16 design the global best-perf/area design
    becomes the reference instead, and the ``"_reference"`` entry records
    the fallback.  Consumers should skip keys starting with ``_`` when
    iterating PE types.
    """
    types = np.atleast_1d(np.asarray(cfg.pe_type))
    has_int16 = bool((types == PE_INT16).any())
    ref = best_index(result, cfg.pe_type,
                     PE_INT16 if has_int16 else None, "perf_per_area")
    ref_ppa = float(result.perf_per_area[ref])
    ref_energy = float(result.energy_j[ref])
    report = {"_reference": dict(
        pe_type=PE_TYPE_NAMES[int(types[ref])], index=ref,
        fallback=not has_int16,
        note=None if has_int16 else
        "no INT16 design in space; normalized to global best perf/area")}
    for code, name in enumerate(PE_TYPE_NAMES):
        sel = types == code
        if not sel.any():
            continue
        i_ppa = best_index(result, cfg.pe_type, code, "perf_per_area")
        i_en = best_index(result, cfg.pe_type, code, "energy_j", "min")
        report[name] = dict(
            best_perf_per_area=float(result.perf_per_area[i_ppa]),
            norm_perf_per_area=float(result.perf_per_area[i_ppa]) / ref_ppa,
            best_energy_j=float(result.energy_j[i_en]),
            norm_energy=float(result.energy_j[i_en]) / ref_energy,
            # energy of the best-perf/area config (Fig. 4 plots both axes
            # for the same set of design points)
            energy_at_best_ppa=float(result.energy_j[i_ppa]) / ref_energy,
            index_best_ppa=i_ppa, index_best_energy=i_en,
        )
    return report


def report_pe_types(report: dict) -> dict:
    """The per-PE-type entries of a normalized report (metadata dropped)."""
    return {k: v for k, v in report.items() if not k.startswith("_")}


def spread(result: DseResult) -> dict:
    """Fig. 2: how much perf/area and energy vary across the space."""
    ppa = np.asarray(result.perf_per_area, np.float64)
    en = np.asarray(result.energy_j, np.float64)
    return dict(perf_per_area_spread=float(ppa.max() / max(ppa.min(), 1e-30)),
                energy_spread=float(en.max() / max(en.min(), 1e-30)))
