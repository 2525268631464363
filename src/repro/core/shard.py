"""Sharded, async-pipelined, checkpointable streaming sweeps.

ROADMAP item 2 ("as fast as the hardware allows"): the streaming walks
of ``dse``/``coexplore`` are single-process folds — one chunk dispatched,
one chunk finished, one archive.  This module turns the SAME walk into a
multi-device pipeline without changing a single evaluated bit:

* **Sharding** — the mixed-radix chunk sequence of
  ``arch.iter_space_chunks`` / ``iter_joint_space_chunks`` is dealt
  round-robin across S shards (chunk c -> shard ``c % S``), each shard
  dispatching onto its own device (``jax.default_device``) and folding
  into its own ``ParetoArchive``.  Chunk boundaries, the
  ``subsample_indices`` point set, and every lane's evaluated columns
  are exactly the single-process walk's — the per-shard fronts reduce
  pairwise (``merge_archives``) to a front that is bit-identical
  (indices AND objectives) to the unsharded one, because the archive
  reduction is exact and per-lane results are position-independent.
  Shards > devices is allowed (devices repeat round-robin); the useful
  parallel setting is ``--xla_force_host_platform_device_count=N`` host
  CPU devices, or real accelerators.

* **Async double buffering** — ``dse.dispatch_chunk`` returns device
  futures (JAX async dispatch), so the driver keeps up to
  ``shards * pipeline_depth`` chunks in flight and only blocks in
  ``dse.finish_chunk`` on the OLDEST one: the host-side front reduction
  of chunk k overlaps the device evaluation of chunks k+1.., which is
  what stops the host archive fold from serializing the walk.  Chunks
  retire strictly in dispatch order, so resume cursors stay dense.  The
  two-stage pruned path stays synchronous per shard (its survivor
  re-packing is itself host-side back-pressure) — shards still run
  independent pruners on independent devices.

* **Durability** — ``SweepCheckpointer`` snapshots the complete walk
  state (per-shard archive fronts, budget stats, pruner survivor
  buffers, and the retire cursor) through the atomic template-free
  ``checkpoint.manager.save_state`` every N retired chunks; resume
  skips the first ``cursor`` chunks by index arithmetic
  (``start_chunk``) and provably reproduces the uninterrupted front.  A
  signature (space/chunking/budget/backend fingerprint) is stored with
  every checkpoint and verified on resume, so a stale directory can
  never silently graft one sweep onto another.  ``export_front_csv``
  streams the decoded front to disk (atomic replace) as it evolves.
"""

from __future__ import annotations

import csv
import os
from collections import deque
from typing import Iterator, Sequence

import jax
import numpy as np

from repro.checkpoint import manager as _ckpt
from repro.core.arch import (AcceleratorConfig, PE_TYPE_NAMES, config_rows,
                             iter_space_chunks, joint_space_points,
                             space_points, space_size)
from repro.core.constraints import Budget, BudgetStats, apply_budget
from repro.core.costmodel import as_cost_model
from repro.core.dse import (DEFAULT_CHUNK_SIZE, ParetoArchive, TwoStagePruner,
                            _count_chunk, _objective_columns,
                            _traced_dispatch, _traced_finish, dispatch_chunk,
                            finish_chunk)
from repro.obs import NULL_TRACER, as_tracer, timed_iter

# In-flight chunks per shard: 2 = classic double buffering (one chunk
# computing on device while the previous one's host fold runs).  Deeper
# pipelines only help when host folds are spiky; memory grows linearly.
DEFAULT_PIPELINE_DEPTH = 2


def resolve_shards(shards: int | None = None,
                   devices: Sequence | None = None) -> tuple[int, tuple]:
    """Normalize the ``shards=`` / ``devices=`` pair of the sweep APIs.

    ``devices`` defaults to every local JAX device; ``shards`` defaults
    to ``len(devices)`` when devices are given explicitly and 1
    otherwise (so ``shards=None, devices=None`` means the single-process
    walk).  More shards than devices round-robins shards onto devices.
    """
    devs = tuple(devices) if devices is not None else tuple(jax.devices())
    if not devs:
        raise ValueError("no devices to shard over")
    n = int(shards) if shards is not None \
        else (len(devs) if devices is not None else 1)
    if n < 1:
        raise ValueError(f"shards must be >= 1, got {n}")
    return n, devs


def shard_device(devices: Sequence, shard: int):
    """The device a shard dispatches on (round-robin past the end)."""
    return devices[shard % len(devices)]


def merge_archives(archives: Sequence[ParetoArchive],
                   num_objectives: int) -> ParetoArchive:
    """Reduce per-shard fronts pairwise into one exact global front.

    Pure (inputs untouched).  The archive reduction is exact and
    order-invariant as a set — a point is on the merged front iff it is
    non-dominated in the union of everything any shard saw — so the
    merged (index, objective) row set is bit-identical to the
    single-archive walk's.  Pairwise tree reduction keeps every merge
    input front-sized.
    """
    level = [a for a in archives]
    if not level:
        return ParetoArchive(num_objectives)
    while len(level) > 1:
        nxt = []
        for i in range(0, len(level) - 1, 2):
            m = ParetoArchive(num_objectives)
            m.update(level[i].objectives, level[i].indices)
            m.update(level[i + 1].objectives, level[i + 1].indices)
            nxt.append(m)
        if len(level) % 2:
            nxt.append(level[-1])
        level = nxt
    if level[0] in archives:      # single shard: still return a copy
        m = ParetoArchive(num_objectives)
        m.update(level[0].objectives, level[0].indices)
        return m
    return level[0]


def merge_budget_stats(stats: Sequence[BudgetStats]) -> BudgetStats:
    """Sum per-shard feasibility telemetry (all fields are additive)."""
    out = BudgetStats()
    for s in stats:
        out.merge(s)
    return out


# ---------------------------------------------------------------------------
# Durability
# ---------------------------------------------------------------------------

class SweepCheckpointer:
    """Atomic every-N-chunks checkpointing of a sharded walk's state.

    Thin policy layer over ``checkpoint.manager.save_state`` /
    ``load_state``: the walk driver owns WHAT the state is (archives,
    stats, pruner buffers, cursor); this class owns WHEN it is written
    (every ``every`` retired chunks + once at the end), the keep-k GC,
    and the resume-safety signature check.
    """

    def __init__(self, ckpt_dir: str, every: int = 64, keep: int = 3,
                 signature: dict | None = None):
        self.dir = ckpt_dir
        self.every = max(1, int(every))
        self.keep = keep
        self.signature = signature or {}

    def load(self, step: int | None = None, telemetry=None) -> dict | None:
        """Latest (or given-step) state, or None for a fresh directory.
        Raises on a signature mismatch — resuming a walk with different
        chunking/space/budget arguments would silently corrupt the front.
        """
        step, state = _ckpt.load_state(self.dir, step, telemetry=telemetry)
        if state is None:
            return None
        if state.get("signature") != self.signature:
            raise ValueError(
                f"checkpoint at {self.dir!r} was written by a different "
                f"sweep: signature {state.get('signature')!r} != expected "
                f"{self.signature!r} — point checkpoint_dir at a fresh "
                f"directory or rerun with the original arguments")
        return state

    def due(self, cursor: int) -> bool:
        return cursor % self.every == 0

    def save(self, cursor: int, state: dict, telemetry=None) -> str:
        return _ckpt.save_state(self.dir, cursor,
                                dict(state, signature=self.signature),
                                keep=self.keep, telemetry=telemetry)


def space_signature(space: dict | None) -> dict:
    """JSON-stable fingerprint of an accelerator space (axis values in
    field order) — part of the checkpoint signature."""
    from repro.core.arch import _space_axes
    return {f: [float(v) for v in axis]
            for f, axis in zip(AcceleratorConfig._fields,
                               _space_axes(space))}


def workloads_signature(models: Sequence) -> str:
    """Content digest of a model axis: every ``LayerSpec`` field of every
    workload (INCLUDING the phase-aware IR fields — kind/stream_words/
    active_frac/acc_class) plus the per-model normalizers and accuracy
    class mix.

    Two model axes with the same names but different layer IR (e.g. a
    decode member re-extracted at a different context length, or an MoE
    member re-gated at a different top-k) hash differently, so checkpoint
    resume and the frontserver cache can never serve a front computed
    from different traffic streams under a stale name match.
    """
    import hashlib

    from repro.core.workloads import LayerSpec

    h = hashlib.sha256()
    for m in models:
        h.update(m.name.encode())
        h.update(np.float64(m.macs).tobytes())
        h.update(np.float64(m.base_acc).tobytes())
        mix = getattr(m, "acc_mix", None)
        h.update(b"-" if mix is None
                 else np.asarray(mix, np.float64).tobytes())
        for f in LayerSpec._fields:
            h.update(np.asarray(getattr(m.workload.layers, f),
                                np.float64).tobytes())
    return h.hexdigest()[:16]


def export_front_csv(path: str, archive: ParetoArchive,
                     metrics: Sequence[str], space: dict | None = None,
                     models: Sequence | None = None) -> str:
    """Write the decoded front to CSV atomically (tmp + ``os.replace``).

    Plain-space fronts get ``index`` + objective columns + the decoded
    config fields; joint fronts (``models`` given — a sequence of
    ``coexplore.ModelEntry``) additionally decode the model name and PE
    type per row.  Called at every checkpoint AND at sweep completion,
    so the file always holds a consistent snapshot of the front as it
    evolves — never a torn write.
    """
    idx = archive.indices
    obj = archive.objectives
    if models is not None:
        mids, cfgs = joint_space_points(idx, space, num_models=len(models))
    else:
        mids, cfgs = None, space_points(idx, space)
    tmp = f"{path}.tmp"
    os.makedirs(os.path.dirname(os.path.abspath(path)), exist_ok=True)
    with open(tmp, "w", newline="") as f:
        w = csv.writer(f)
        head = ["index"]
        if models is not None:
            head += ["model"]
        head += list(metrics) + ["pe_type_name"] \
            + list(AcceleratorConfig._fields)
        w.writerow(head)
        for i, row in enumerate(config_rows(cfgs)):
            out = [int(idx[i])]
            if models is not None:
                out.append(models[int(mids[i])].name)
            out += [repr(float(v)) for v in obj[i]]
            out.append(row["pe_type_name"])
            out += [row[k] for k in AcceleratorConfig._fields]
            w.writerow(out)
    os.replace(tmp, path)
    return path


def _front_columns(archive: ParetoArchive, metrics: Sequence[str],
                   space: dict | None, models: Sequence | None) -> dict:
    """The decoded front as name -> column list (shared by the tabular
    exporters)."""
    idx = archive.indices
    obj = archive.objectives
    if models is not None:
        mids, cfgs = joint_space_points(idx, space, num_models=len(models))
    else:
        mids, cfgs = None, space_points(idx, space)
    cols: dict[str, list] = {"index": [int(i) for i in idx]}
    if models is not None:
        cols["model"] = [models[int(m)].name for m in mids]
    for j, m in enumerate(metrics):
        cols[m] = [float(v) for v in obj[:, j]]
    rows = list(config_rows(cfgs))
    cols["pe_type_name"] = [r["pe_type_name"] for r in rows]
    for k in AcceleratorConfig._fields:
        cols[k] = [r[k] for r in rows]
    return cols


def export_front_parquet(path: str, archive: ParetoArchive,
                         metrics: Sequence[str], space: dict | None = None,
                         models: Sequence | None = None) -> str:
    """Write the decoded front to Parquet atomically — the columnar twin
    of ``export_front_csv`` (same columns, same row order) for fronts big
    enough that downstream analysis wants predicate pushdown instead of
    CSV parsing.

    Optional-dependency-guarded: requires ``pyarrow`` and raises a clear
    ``RuntimeError`` (not an ImportError deep inside a sweep) when the
    environment lacks it.
    """
    try:
        import pyarrow as pa
        import pyarrow.parquet as pq
    except ImportError as e:  # pragma: no cover - env-dependent
        raise RuntimeError(
            "export_front_parquet requires pyarrow (not installed); "
            "use export_front_csv instead") from e
    cols = _front_columns(archive, metrics, space, models)
    table = pa.table({k: pa.array(v) for k, v in cols.items()})
    tmp = f"{path}.tmp"
    os.makedirs(os.path.dirname(os.path.abspath(path)), exist_ok=True)
    pq.write_table(table, tmp)
    os.replace(tmp, path)
    return path


# ---------------------------------------------------------------------------
# The sharded plain-space walk
# ---------------------------------------------------------------------------

def _sharded_space_events(
        workload, space, model, chunk_size, max_points, seed, budget,
        stats, pruners, shards, devices, pipeline_depth, start_chunk,
        max_chunks, tracer=NULL_TRACER) -> Iterator[tuple]:
    """The engine: yields ``("chunk", shard, (result, indices))`` for
    every feasible evaluated chunk/flush and ``("retired", shard, c)``
    when raw chunk ``c`` is fully absorbed (its result folded, or its
    survivors buffered in the shard's pruner).  Retires are strictly in
    walk order — the dense cursor that makes checkpoints resumable.

    Unpruned shards run the async double-buffered pipeline (at most
    ``shards * pipeline_depth`` chunks in flight, finished oldest-first);
    pruned shards feed synchronously.  At a ``max_chunks`` truncation the
    in-flight chunks are drained but pruner buffers are NOT (they belong
    in the checkpoint); at natural exhaustion the pruners drain too.

    With an enabled ``tracer`` every chunk's dispatch->retire residency
    lands as a complete event on its shard's lane (``shard<s>`` — the
    Chrome-trace view where pipeline overlap is visible), the in-flight
    depth becomes a gauge, and dispatch/device-wait/decode time is
    attributed exactly like the single-process walk.
    """
    use_prune = pruners is not None
    cap = max(1, shards * max(1, pipeline_depth))
    inflight: deque = deque()
    traced = tracer.enabled

    def _finish_one():
        c, s, pending, idx, t_disp = inflight.popleft()
        res = _traced_finish(tracer, pending, track=f"shard{s}") \
            if traced else finish_chunk(pending)
        if traced:
            tracer.complete("chunk", t_disp, tracer.now_ns(),
                            cat="pipeline", track=f"shard{s}", chunk=c)
            tracer.gauge("pipeline.in_flight", len(inflight))
        if budget is not None:
            res, idx = apply_budget(res, idx, budget,
                                    stats=None if stats is None
                                    else stats[s])
            if traced and len(idx) < pending.n:
                tracer.counter("budget.killed", pending.n - len(idx))
        return c, s, ((res, idx) if len(idx) else None)

    completed = True
    chunks = timed_iter(
        iter_space_chunks(space, chunk_size=chunk_size,
                          max_points=max_points, seed=seed,
                          start_chunk=start_chunk), tracer)
    for c, (cfg, idx) in enumerate(chunks, start=start_chunk):
        if max_chunks is not None and c - start_chunk >= max_chunks:
            completed = False
            break
        s = c % shards
        if traced:
            _count_chunk(tracer, len(idx), workload)
        if use_prune:
            with jax.default_device(shard_device(devices, s)):
                for res, fidx, _aux in pruners[s].feed(cfg, idx, workload):
                    yield "chunk", s, (res, fidx)
            yield "retired", s, c
            continue
        with jax.default_device(shard_device(devices, s)):
            if traced:
                t_disp = tracer.now_ns()
                pending = _traced_dispatch(tracer, cfg, workload, model,
                                           chunk_size, track=f"shard{s}")
            else:
                t_disp = 0
                pending = dispatch_chunk(cfg, workload, model,
                                         pad_to=chunk_size)
        inflight.append((c, s, pending, idx, t_disp))
        if traced:
            tracer.gauge("pipeline.in_flight", len(inflight))
        while len(inflight) >= cap:
            fc, fs, out = _finish_one()
            if out is not None:
                yield "chunk", fs, out
            yield "retired", fs, fc
    while inflight:
        fc, fs, out = _finish_one()
        if out is not None:
            yield "chunk", fs, out
        yield "retired", fs, fc
    if use_prune and completed:
        for s in range(shards):
            for res, fidx, _aux in pruners[s].finish():
                yield "chunk", s, (res, fidx)


def sharded_space_stream(
        workload, space=None, surrogate=None,
        chunk_size: int = DEFAULT_CHUNK_SIZE,
        max_points: int | None = None, seed: int = 0,
        budget: Budget | None = None,
        budget_stats: BudgetStats | None = None, prune: bool = True,
        shards: int | None = None, devices: Sequence | None = None,
        pipeline_depth: int = DEFAULT_PIPELINE_DEPTH,
        telemetry=None,
) -> Iterator[tuple]:
    """Sharded drop-in for ``dse.evaluate_space_streaming``: yields the
    same ``(chunk_result, flat_indices)`` pairs (every lane bit-identical
    to the single-process walk; unpruned chunk order follows the walk,
    pruned flush boundaries follow each shard's survivor re-packing).
    Per-shard budget telemetry is merged into ``budget_stats`` once the
    stream is exhausted."""
    tr = as_tracer(telemetry)
    n_shards, devs = resolve_shards(shards, devices)
    model = as_cost_model(surrogate)
    use_prune = (budget is not None and prune
                 and bool(budget.config_constraints()))
    stats = [BudgetStats() for _ in range(n_shards)] \
        if budget is not None else None
    pruners = [TwoStagePruner(budget, chunk_size, model, stats[s],
                              telemetry=telemetry, track=f"shard{s}")
               for s in range(n_shards)] if use_prune else None
    for kind, _s, payload in _sharded_space_events(
            workload, space, model, chunk_size, max_points, seed, budget,
            stats, pruners, n_shards, devs, pipeline_depth, 0, None,
            tracer=tr):
        if kind == "chunk":
            yield payload
    if budget_stats is not None and stats is not None:
        for st in stats:
            budget_stats.merge(st)


def sharded_pareto_front(
        workload, space=None,
        metrics: tuple = ("perf_per_area", "neg_energy_j"),
        surrogate=None, chunk_size: int = DEFAULT_CHUNK_SIZE,
        max_points: int | None = None, seed: int = 0,
        budget: Budget | None = None,
        budget_stats: BudgetStats | None = None, prune: bool = True,
        shards: int | None = None, devices: Sequence | None = None,
        pipeline_depth: int = DEFAULT_PIPELINE_DEPTH,
        checkpoint_dir: str | None = None, checkpoint_every: int = 64,
        checkpoint_keep: int = 3, csv_path: str | None = None,
        max_chunks: int | None = None,
        telemetry=None,
) -> tuple[ParetoArchive, AcceleratorConfig]:
    """Sharded, pipelined, durable ``dse.pareto_front_streaming``.

    Same return contract (merged archive + decoded front configs) and
    bit-identical front for any shard count.  With ``checkpoint_dir``
    the walk state is snapshotted every ``checkpoint_every`` retired
    chunks and the walk RESUMES from the latest checkpoint automatically
    on restart; ``max_chunks`` truncates the walk after that many chunks
    (checkpoint + partial front returned) — the preemption primitive the
    kill/resume tests drive.  ``csv_path`` streams the decoded merged
    front at every checkpoint and at completion.
    """
    tr = as_tracer(telemetry)
    n_shards, devs = resolve_shards(shards, devices)
    model = as_cost_model(surrogate)
    use_prune = (budget is not None and prune
                 and bool(budget.config_constraints()))
    archives = [ParetoArchive(len(metrics)) for _ in range(n_shards)]
    stats = [BudgetStats() for _ in range(n_shards)] \
        if budget is not None else None
    ckpt = None
    cursor = 0
    pruner_states = None
    if checkpoint_dir is not None:
        ckpt = SweepCheckpointer(
            checkpoint_dir, every=checkpoint_every, keep=checkpoint_keep,
            signature=dict(
                kind="space", shards=n_shards, chunk_size=int(chunk_size),
                max_points=max_points, seed=int(seed),
                metrics=list(metrics), prune=bool(use_prune),
                budget=None if budget is None else budget.spec(),
                space=space_signature(space)))
        loaded = ckpt.load(telemetry=telemetry)
        if loaded is not None:
            cursor = int(loaded["cursor"])
            archives = [ParetoArchive.from_state(a)
                        for a in loaded["archives"]]
            if stats is not None and loaded.get("stats") is not None:
                stats = [BudgetStats.from_dict(d) for d in loaded["stats"]]
            pruner_states = loaded.get("pruners")
    pruners = None
    if use_prune:
        pruners = [TwoStagePruner(budget, chunk_size, model, stats[s],
                                  telemetry=telemetry, track=f"shard{s}")
                   for s in range(n_shards)]
        if pruner_states is not None:
            for p, st in zip(pruners, pruner_states):
                p.restore_state(st, workload)

    def _state() -> dict:
        st = dict(cursor=cursor,
                  archives=[a.state_dict() for a in archives])
        if stats is not None:
            st["stats"] = [s_.as_dict() for s_ in stats]
        if pruners is not None:
            st["pruners"] = [p.state_dict() for p in pruners]
        return st

    def _snapshot() -> None:
        if ckpt is not None:
            with tr.span("checkpoint", cursor=cursor):
                ckpt.save(cursor, _state(), telemetry=telemetry)
        if csv_path is not None:
            with tr.span("csv"):
                export_front_csv(csv_path,
                                 merge_archives(archives, len(metrics)),
                                 metrics, space=space)

    for kind, s, payload in _sharded_space_events(
            workload, space, model, chunk_size, max_points, seed, budget,
            stats, pruners, n_shards, devs, pipeline_depth, cursor,
            max_chunks, tracer=tr):
        if kind == "chunk":
            res, idx = payload
            with tr.span("archive"):
                archives[s].update(_objective_columns(res, metrics), idx)
        else:
            cursor = payload + 1
            if ckpt is not None and ckpt.due(cursor):
                _snapshot()
    _snapshot()
    if budget_stats is not None and stats is not None:
        for st in stats:
            budget_stats.merge(st)
    with tr.span("archive_merge"):
        merged = merge_archives(archives, len(metrics))
    return merged, space_points(merged.indices, space)


__all__ = [
    "DEFAULT_PIPELINE_DEPTH", "SweepCheckpointer", "export_front_csv",
    "export_front_parquet", "merge_archives", "merge_budget_stats",
    "resolve_shards", "shard_device", "sharded_pareto_front",
    "sharded_space_stream", "space_signature", "workloads_signature",
]
