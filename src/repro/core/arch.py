"""Accelerator configuration space for QADAM.

The paper's accelerator template is an Eyeriss-style spatial array:
a 2-D grid of processing elements (PEs), a shared global buffer, and
per-PE scratchpads for ifmap / filter / psum.  Every knob the paper
sweeps (Sec. III-C) is a field here:

  * number of PEs per row / column,
  * global buffer size,
  * per-PE scratchpad sizes (ifmap, filter, psum),
  * bit precision / PE type (FP32, INT16, LightPE-1, LightPE-2),
  * device (DRAM) bandwidth.

Configs are plain NamedTuples of scalars so the whole cost model can be
``jax.vmap``-ed over thousands of stacked design points — that is what
makes the DSE "rapid" in the JAX port (the paper uses a C++/RTL flow
with a regression surrogate; here the analytical model itself is the
fast path and the polynomial surrogate is reproduced on top of it).
"""

from __future__ import annotations

from typing import Iterable, Iterator, NamedTuple, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro.obs import as_tracer

# PE type codes (index into the constant tables in pe.py).
PE_FP32 = 0
PE_INT16 = 1
PE_LIGHTPE1 = 2  # 8-bit activations, 4-bit (power-of-two) weights, 1 shift
PE_LIGHTPE2 = 3  # 8-bit activations, 8-bit weights, 2 shifts + add
PE_INT8 = 4      # conventional int8 MAC (beyond-paper comparison point)

PE_TYPE_NAMES = ("fp32", "int16", "lightpe1", "lightpe2", "int8")
PE_TYPE_CODES = {name: code for code, name in enumerate(PE_TYPE_NAMES)}


class AcceleratorConfig(NamedTuple):
    """One hardware design point. All fields are scalars (vmap-friendly).

    ``mapping`` is the dataflow/mapping digit QADAM holds fixed (loop
    order / tiling / gbuf split; Klhufek et al. on quantization x mapping
    synergy): a code in ``[0, MAPPING_CHOICES)`` decomposed by
    ``dataflow.layer_cost`` into tiling-cap divisors, the replication
    order and the gbuf ifmap/filter split.  Code 0 is the legacy
    schedule bit-exactly, and it is the TRAILING mixed-radix axis with a
    default single-value ``(0.0,)`` grid — so every pre-existing space
    dict keeps its exact flat indices, strides and ``space_size``.
    """

    pe_rows: jnp.ndarray      # int: PEs per column of the array
    pe_cols: jnp.ndarray      # int: PEs per row of the array
    gbuf_kb: jnp.ndarray      # float: global buffer capacity (KB)
    spad_ifmap: jnp.ndarray   # int: ifmap scratchpad entries (words)
    spad_filter: jnp.ndarray  # int: filter scratchpad entries (words)
    spad_psum: jnp.ndarray    # int: psum scratchpad entries (words)
    pe_type: jnp.ndarray      # int: code into PE_TYPE_NAMES
    bandwidth_gbps: jnp.ndarray  # float: DRAM bandwidth (GB/s)
    mapping: jnp.ndarray = 0.0   # float: dataflow schedule code (0 = legacy)

    @property
    def num_pes(self):
        return self.pe_rows * self.pe_cols


# The float32 fields of a config, in field order: the rows of a
# ``PackedConfig``'s ``knobs``.
KNOB_FIELDS = tuple(f for f in AcceleratorConfig._fields if f != "pe_type")


class PackedConfig(NamedTuple):
    """A batched config as one transfer carries it to the device: two
    buffers in place of nine, since a copy costs per buffer far more than
    per byte.  ``knobs`` stacks the float32 fields (``KNOB_FIELDS``) as
    rows; ``pe_type`` holds the int32 codes.  The jitted stages unpack it
    (``as_config``) as they trace, so unpacking dispatches nothing."""

    knobs: jnp.ndarray    # (len(KNOB_FIELDS), lanes) float32
    pe_type: jnp.ndarray  # (lanes,) int32


def pack_config(cfg: AcceleratorConfig) -> PackedConfig:
    """A batched config packed on the host into a ``PackedConfig``, in the
    device dtypes (the ones a jitted stage would convert host fields to);
    fields still on the device come back in one batched read."""
    cfg = jax.device_get(cfg)
    return PackedConfig(
        knobs=np.stack([np.asarray(getattr(cfg, f), np.float32)
                        for f in KNOB_FIELDS]),
        pe_type=np.asarray(cfg.pe_type, np.int32))


def as_config(cfg: AcceleratorConfig | PackedConfig) -> AcceleratorConfig:
    """The ``AcceleratorConfig`` a jitted stage computes on: a packed
    config's rows, or the config itself.  The rows pass an optimization
    barrier, so XLA fuses the stage's arithmetic as it does for nine
    separate arguments: fused into the row slicing, the PPA stage's clock
    came out different in its last bits."""
    if not isinstance(cfg, PackedConfig):
        return cfg
    return jax.lax.optimization_barrier(AcceleratorConfig(
        pe_type=cfg.pe_type,
        **{f: cfg.knobs[i] for i, f in enumerate(KNOB_FIELDS)}))


def make_config(
    pe_rows: int = 12,
    pe_cols: int = 14,
    gbuf_kb: float = 108.0,
    spad_ifmap: int = 12,
    spad_filter: int = 224,
    spad_psum: int = 24,
    pe_type: str | int = "int16",
    bandwidth_gbps: float = 25.6,
    mapping: float = 0.0,
) -> AcceleratorConfig:
    """Build a single design point (defaults follow Eyeriss-like values)."""
    code = PE_TYPE_CODES[pe_type] if isinstance(pe_type, str) else int(pe_type)
    return AcceleratorConfig(
        pe_rows=jnp.asarray(pe_rows, jnp.float32),
        pe_cols=jnp.asarray(pe_cols, jnp.float32),
        gbuf_kb=jnp.asarray(gbuf_kb, jnp.float32),
        spad_ifmap=jnp.asarray(spad_ifmap, jnp.float32),
        spad_filter=jnp.asarray(spad_filter, jnp.float32),
        spad_psum=jnp.asarray(spad_psum, jnp.float32),
        pe_type=jnp.asarray(code, jnp.int32),
        bandwidth_gbps=jnp.asarray(bandwidth_gbps, jnp.float32),
        mapping=jnp.asarray(mapping, jnp.float32),
    )


def stack_configs(configs: Sequence[AcceleratorConfig]) -> AcceleratorConfig:
    """Stack N design points into one batched AcceleratorConfig (for vmap)."""
    return AcceleratorConfig(*[jnp.stack([getattr(c, f) for c in configs])
                               for f in AcceleratorConfig._fields])


def concat_configs(configs: Sequence[AcceleratorConfig]) -> AcceleratorConfig:
    """Concatenate batched configs along the lane axis, on HOST numpy.

    The survivor-buffer primitive of the two-stage pruned walk: fragments
    of config chunks accumulate on host (field dtypes preserved — float32
    knobs, int32 pe_type) until they fill a full compiled chunk shape.
    """
    return AcceleratorConfig(*[
        np.concatenate([np.asarray(getattr(c, f)) for c in configs])
        for f in AcceleratorConfig._fields])


def take_config(cfg: AcceleratorConfig, rows) -> AcceleratorConfig:
    """Row-select a batched config (boolean mask or index array), HOST
    numpy — dtype-preserving, like ``concat_configs``."""
    return AcceleratorConfig(*[np.asarray(f)[rows] for f in cfg])


# ---------------------------------------------------------------------------
# The paper's design space (Sec. III-C): the grid swept for PPA model fitting
# and for the DSE case studies.
# ---------------------------------------------------------------------------

DEFAULT_SPACE = dict(
    pe_rows=(8, 12, 16, 24, 32),
    pe_cols=(8, 14, 16, 28, 32),
    gbuf_kb=(54.0, 108.0, 216.0, 432.0),
    spad_ifmap=(12, 24),
    spad_filter=(112, 224, 448),
    spad_psum=(16, 24, 32),
    pe_type=tuple(range(len(PE_TYPE_NAMES))),
    bandwidth_gbps=(12.8, 25.6, 51.2),
)

# The giga-scale grid (ROADMAP item 2): QUIDAM-style order-of-magnitude
# densification of the PE-array / gbuf / scratchpad axes the paper's 27k
# grid barely samples.  16*16*12*4*6*6*5*5 = 11,059,200 accelerator
# configs (>= 10M) — only ever walked lazily through the mixed-radix
# chunk iterators; nothing here is materialized.
WIDE_SPACE = dict(
    pe_rows=(4, 6, 8, 10, 12, 14, 16, 20, 24, 28, 32, 36, 40, 48, 56, 64),
    pe_cols=(4, 6, 8, 10, 12, 14, 16, 20, 24, 28, 32, 36, 40, 48, 56, 64),
    gbuf_kb=(27.0, 54.0, 81.0, 108.0, 162.0, 216.0, 324.0, 432.0, 648.0,
             864.0, 1296.0, 1728.0),
    spad_ifmap=(6, 12, 24, 48),
    spad_filter=(56, 112, 168, 224, 336, 448),
    spad_psum=(8, 16, 24, 32, 48, 64),
    pe_type=tuple(range(len(PE_TYPE_NAMES))),
    bandwidth_gbps=(6.4, 12.8, 25.6, 51.2, 102.4),
)

# The dataflow/mapping axis (ROADMAP item 4, first slice): one schedule
# code per design point, decomposed by ``dataflow.layer_cost`` into
# 3 gbuf splits x 2 replication orders x 4 channel-tile divisors x
# 5 filter-tile divisors.  Code 0 is the legacy schedule bit-exactly.
MAPPING_CHOICES = 120

# DEFAULT_SPACE with the mapping axis opened: 27,000 x 120 = 3,240,000
# accelerator points (120x the paper grid) — the space where enumeration
# is dishonest and the budgeted search drivers (``repro.core.search``)
# earn their keep.
MAPPED_SPACE = dict(DEFAULT_SPACE,
                    mapping=tuple(float(i) for i in range(MAPPING_CHOICES)))


def _space_axes(space: dict | None) -> list[np.ndarray]:
    """Per-field value axes in AcceleratorConfig field order.

    A space dict without a ``mapping`` key gets the single-value legacy
    axis ``(0.0,)`` — a trailing radix-1 digit multiplies every stride by
    one, so all pre-existing flat indices, chunk boundaries and
    ``space_size`` values are unchanged.
    """
    space = dict(DEFAULT_SPACE if space is None else space)
    space.setdefault("mapping", (0.0,))
    return [np.asarray(space[k], np.float64)
            for k in AcceleratorConfig._fields]


def space_radices(space: dict | None = None) -> np.ndarray:
    """Per-field axis lengths in ``AcceleratorConfig._fields`` order — the
    mixed-radix digit bases of ``space_points``.  The genome alphabet of
    the evolutionary search driver (``repro.core.search``)."""
    return np.array([len(a) for a in _space_axes(space)], np.int64)


def space_size(space: dict | None = None) -> int:
    """Number of points in the cartesian design space (no materialization)."""
    return int(np.prod([len(a) for a in _space_axes(space)]))


def subsample_indices(n: int, max_points: int | None,
                      seed: int = 0) -> np.ndarray | None:
    """Sorted unique flat indices of a uniform subsample, or ``None`` for
    the full walk.

    THE one RNG stream every walk shares: ``iter_space_chunks``,
    ``enumerate_space`` and both modes of ``iter_joint_space_chunks`` all
    draw their subsample here, so the same ``(n, max_points, seed)``
    always visits the same point set — which is what lets a constrained
    walk account feasibility against exactly the points an unconstrained
    walk of the same arguments evaluates (``constraints.BudgetStats``
    counts lanes of these chunks, pre-mask).
    """
    if max_points is None or n <= max_points:
        return None
    rng = np.random.default_rng(seed)
    return np.sort(rng.choice(n, size=max_points, replace=False))


# The dtypes a decoded config reaches the device in: float32 knobs and
# int32 PE-type codes.
CONFIG_DTYPES = {f: np.int32 if f == "pe_type" else np.float32
                 for f in AcceleratorConfig._fields}


def space_columns(indices: np.ndarray,
                  space: dict | None = None) -> AcceleratorConfig:
    """Decode flat space indices into a batched config of HOST numpy
    columns, already in the device dtypes (``CONFIG_DTYPES``).

    Index order matches ``itertools.product`` over the fields in
    ``AcceleratorConfig._fields`` order (last axis varies fastest), so
    ``space_columns(np.arange(space_size()))`` is the whole grid in
    ``enumerate_space()`` order — but any index subset decodes in O(len)
    without materializing the grid.
    """
    axes = _space_axes(space)
    idx = np.asarray(indices, np.int64)
    radices = np.array([len(a) for a in axes], np.int64)
    # strides[i] = product of radix sizes of the faster-varying axes after i
    strides = np.concatenate([np.cumprod(radices[::-1])[::-1][1:], [1]])
    return AcceleratorConfig(*[
        axes[i].astype(CONFIG_DTYPES[k])[(idx // strides[i]) % radices[i]]
        for i, k in enumerate(AcceleratorConfig._fields)])


def space_points(indices: np.ndarray,
                 space: dict | None = None,
                 telemetry=None) -> AcceleratorConfig:
    """``space_columns`` copied to the device in one batched transfer.

    ``space_points(np.arange(space_size()))`` reproduces the historical
    ``enumerate_space()`` exactly.  ``telemetry=`` times the copy
    (``copy.upload``).
    """
    cols = space_columns(indices, space)
    with as_tracer(telemetry).span("upload", cat="copy"):
        return jax.device_put(cols)


def iter_space_chunks(space: dict | None = None,
                      chunk_size: int = 4096,
                      max_points: int | None = None,
                      seed: int = 0,
                      start_chunk: int = 0) -> Iterator[
                          tuple[AcceleratorConfig, np.ndarray]]:
    """Lazily yield ``(config_chunk, flat_indices)`` pairs over the space.

    Every chunk except possibly the last has exactly ``chunk_size`` points;
    ``flat_indices`` are the global space indices of the chunk's points
    and ``config_chunk`` their host columns (``space_columns``): the
    evaluator copies a chunk to the device once, after padding it to the
    compiled shape.  Memory is O(chunk_size) regardless of the total space
    size.  ``max_points`` subsamples the space uniformly (same RNG stream
    as ``enumerate_space``).

    ``start_chunk`` skips the first N chunks WITHOUT decoding them — the
    resume primitive of checkpointed walks: chunk boundaries are a pure
    function of ``(space, chunk_size, max_points, seed)``, so skipping is
    index arithmetic, not re-evaluation.
    """
    n = space_size(space)
    keep = subsample_indices(n, max_points, seed)
    if keep is not None:
        for lo in range(start_chunk * chunk_size, len(keep), chunk_size):
            idx = keep[lo:lo + chunk_size]
            yield space_columns(idx, space), idx
        return
    for lo in range(start_chunk * chunk_size, n, chunk_size):
        idx = np.arange(lo, min(lo + chunk_size, n), dtype=np.int64)
        yield space_columns(idx, space), idx


def enumerate_space(space: dict | None = None,
                    max_points: int | None = None,
                    seed: int = 0) -> AcceleratorConfig:
    """Enumerate (or subsample) the cartesian design space as a batched config.

    Returns an AcceleratorConfig whose leaves all have leading dim N.
    Built on mixed-radix decode — the grid of index tuples is never
    materialized, only the N selected points.
    """
    n = space_size(space)
    idx = subsample_indices(n, max_points, seed)
    if idx is None:
        idx = np.arange(n, dtype=np.int64)
    return space_points(idx, space)


# ---------------------------------------------------------------------------
# Joint (model x accelerator) space: the co-exploration axis (QUIDAM/QAPPA).
#
# The workload axis is one more mixed-radix digit, the SLOWEST-varying one:
# joint flat index = model_id * space_size(space) + accelerator_index,
# matching ``itertools.product(models, accel_points)``.  Chunked walks mix
# models freely by default (lanes carry a model_id vector and the evaluator
# gathers each lane's layer stack from a bucketed (M, L) pytree — one
# compilation per layer-count bucket); ``group_by_model=True`` keeps the
# historical never-mix walk as the oracle path.
# ---------------------------------------------------------------------------

def joint_space_size(space: dict | None = None, num_models: int = 1) -> int:
    """Number of (model, accelerator-config) points in the joint space."""
    if num_models < 1:
        raise ValueError(f"num_models must be >= 1, got {num_models}")
    return num_models * space_size(space)


def joint_space_points(
        indices: np.ndarray, space: dict | None = None,
        num_models: int = 1) -> tuple[np.ndarray, AcceleratorConfig]:
    """Decode flat joint indices into (model_ids, batched accelerator config).

    Inverse of the joint enumeration order: ``model_id = idx // A`` and the
    accelerator point is ``space_points(idx % A)`` with ``A = space_size``.
    Any index subset decodes in O(len) without materializing the grid.
    """
    a = space_size(space)
    idx = np.asarray(indices, np.int64)
    if idx.size and (idx.min() < 0 or idx.max() >= num_models * a):
        raise ValueError(
            f"joint index out of range for {num_models} models x {a} configs")
    return idx // a, space_points(idx % a, space)


def _validate_model_groups(model_groups, num_models: int) -> tuple:
    groups = tuple(tuple(int(m) for m in g) for g in model_groups)
    flat = [m for g in groups for m in g]
    if any(m < 0 or m >= num_models for m in flat):
        raise ValueError(f"model_groups reference models outside "
                         f"[0, {num_models}): {groups}")
    if len(flat) != len(set(flat)):
        raise ValueError(f"model_groups assign a model twice: {groups}")
    return groups


def iter_joint_space_chunks(
        space: dict | None = None,
        num_models: int = 1,
        chunk_size: int = 4096,
        max_points: int | None = None,
        seed: int = 0,
        group_by_model: bool = False,
        model_groups: Sequence[Sequence[int]] | None = None,
        start_chunk: int = 0,
) -> Iterator[tuple[int | np.ndarray, AcceleratorConfig, np.ndarray]]:
    """Lazily yield ``(model_ids, config_chunk, flat_joint_indices)``,
    each config chunk as host columns (``space_columns``).

    Default (mixed) mode yields dense fixed-shape chunks that freely cross
    model boundaries — ``model_ids`` is an int64 array aligned with the
    chunk lanes.  With layer-count-bucketed workloads every chunk then
    hits the same compiled evaluator, which is what makes M-model joint
    sweeps run at single-model throughput.  ``model_groups`` (disjoint
    tuples of model ids) restricts mixing to within each group — the
    bucketing policy's compilation classes; groups are walked in the
    given order, models not in any group are skipped, and global joint
    indices are preserved.

    ``group_by_model=True`` restores the PR 2 behavior — yields a scalar
    ``model_id`` per chunk and never mixes models (one compilation per
    distinct layer count); kept as the oracle path for equivalence tests.

    ``max_points`` subsamples the JOINT space uniformly with the same RNG
    stream in both modes, so mixed and grouped walks visit the exact same
    point set.  Memory stays O(chunk_size + max_points).

    ``start_chunk`` skips the first N chunks of the walk (counted in
    yield order) without decoding them — whole model/group segments are
    skipped by chunk-count arithmetic, so resume cost is O(max_points)
    index bookkeeping, never re-evaluation.
    """
    a = space_size(space)
    n = joint_space_size(space, num_models)
    keep = subsample_indices(n, max_points, seed)
    skip = int(start_chunk)
    if group_by_model:
        for m in range(num_models):
            if keep is None:
                midx = np.arange(m * a, (m + 1) * a, dtype=np.int64)
            else:
                midx = keep[(keep >= m * a) & (keep < (m + 1) * a)]
            n_chunks = -(-len(midx) // chunk_size)
            if skip >= n_chunks:
                skip -= n_chunks
                continue
            for lo in range(skip * chunk_size, len(midx), chunk_size):
                idx = midx[lo:lo + chunk_size]
                yield m, space_columns(idx - m * a, space), idx
            skip = 0
        return
    if model_groups is None:
        groups = (tuple(range(num_models)),)
    else:
        groups = _validate_model_groups(model_groups, num_models)
    for group in groups:
        g = np.asarray(group, np.int64)
        if keep is None:
            # lazy per-chunk decode of the group's local enumeration:
            # local index l -> (model g[l // a], accel l % a)
            g_n = len(g) * a
            n_chunks = -(-g_n // chunk_size)
            if skip >= n_chunks:
                skip -= n_chunks
                continue
            for lo in range(skip * chunk_size, g_n, chunk_size):
                loc = np.arange(lo, min(lo + chunk_size, g_n), dtype=np.int64)
                mids = g[loc // a]
                yield mids, space_columns(loc % a, space), mids * a + loc % a
            skip = 0
        else:
            gidx = keep[np.isin(keep // a, g)]
            n_chunks = -(-len(gidx) // chunk_size)
            if skip >= n_chunks:
                skip -= n_chunks
                continue
            for lo in range(skip * chunk_size, len(gidx), chunk_size):
                idx = gidx[lo:lo + chunk_size]
                yield idx // a, space_columns(idx % a, space), idx
            skip = 0


def config_rows(cfg: AcceleratorConfig) -> Iterable[dict]:
    """Iterate a batched config as python dicts (for reports/CSV)."""
    n = int(np.asarray(cfg.pe_rows).shape[0]) if np.ndim(cfg.pe_rows) else 1
    arrs = {f: np.atleast_1d(np.asarray(getattr(cfg, f))) for f in cfg._fields}
    for i in range(n):
        row = {f: arrs[f][i].item() for f in cfg._fields}
        row["pe_type_name"] = PE_TYPE_NAMES[int(row["pe_type"])]
        yield row
