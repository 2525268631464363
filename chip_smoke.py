#!/usr/bin/env python3
"""Smoke test of the QADAM engine on one TPU, checked against the CPU.

    python chip_smoke.py            # one chip: phases a-i below
    python chip_smoke.py --chips 4  # the 4-shard walk against one device

Everything runs in this one process, the CPU reference included: it runs
under ``jax.default_device(jax.devices("cpu")[0])``.  The phases drive
the public entry points at the sizes users run: the 13-model default axis
times the 27k paper grid (351k joint points, chunk 4096).

  a. device      platform, kind, count, versions; anything but a TPU
                 exits non-zero
  b. cache       the persistent compilation cache (compile_cache)
  c. joint sweep coexplore_front cold and warm; the LightPE claim holds
  d. contract    mixed walk == per-model walk, pruned == unpruned, bit
                 for bit (indices, objectives, row order)
  e. vs CPU      the joint sweep and a 65,536-point sample of evaluated
                 columns on both backends, within REL_TOL
  f. surrogate   fit_ppa_models on the chip, fig3's MAPE against the
                 CPU fit, and the joint sweep under the chip fit
  g. FrontServer coalesced budget queries, a repeat and a superset hit,
                 each bit-identical to a standalone sweep
  h. search      evolve and halving drivers on MAPPED_SPACE, rerun
                 without new compiles
  i. QAT         fig56's training step for each PE type, finite losses

The last line of standard output is a JSON object, printed only when
every phase passed.  Times printed here are smoke timings, not benchmark
numbers.
"""

from __future__ import annotations

import argparse
import json
import sys
import time
from importlib import metadata
from pathlib import Path
from typing import NamedTuple

import numpy as np

ROOT = Path(__file__).resolve().parent

# Largest relative difference allowed between a TPU-evaluated column and
# the CPU-evaluated one.  Both backends compute the same float32
# expressions; they differ in the last bits only where an operation is
# not correctly rounded on one of them (the TPU's divide, sqrt, log2,
# sin/cos), each within a few ulp (2**-23 = 1.2e-7 relative).  A design
# point's column folds at most 64 layers of such terms, all positive, so
# a few tens of ulp bound the honest drift: 1e-5 is ~84 ulp.  A flipped
# floor or a lost sign moves a column by whole percents.
REL_TOL = 1e-5
# Largest |MAPE(chip fit) - MAPE(CPU fit)| per (target, PE type) on
# fig3's evaluation sample: half the last digit fig3 prints (0.001).
MAPE_MARGIN = 5e-4
# Budgets of the FrontServer phase: three distinct queries coalesced on
# one walk, then a repeat and a budget every unconstrained-front point
# satisfies (served from the cached superset front).
SERVER_BUDGETS = (dict(area_mm2=2.0), dict(power_mw=250.0),
                  dict(area_mm2=0.9))
SUPERSET_BUDGET = dict(area_mm2=1e6)
TIMING = "(smoke timing, not a benchmark number)"


class SmokeFailure(Exception):
    """A phase's check failed."""


def check(cond, msg: str) -> None:
    if not cond:
        raise SmokeFailure(msg)


class Size(NamedTuple):
    """What the phases run at; ``FULL`` is what users run."""
    space: dict | None = None          # accelerator grid (None: paper 27k)
    models: int | None = None          # first N default models (None: all)
    chunk_size: int = 4096
    sample_points: int = 65_536        # phase e column sample
    fit_points: int = 600              # phase f surrogate fit sample
    eval_points: int = 1500            # phase f fig3 evaluation sample
    search_models: int = 3
    search_space: dict | None = None   # None: MAPPED_SPACE
    search_evals: int = 40_000
    qat_steps: int = 3


FULL = Size()


def say(phase: str, msg: str) -> None:
    print(f"[{phase}] {msg}", flush=True)


# -- a. device --------------------------------------------------------------

def device_info() -> dict:
    import jax
    import jaxlib
    devs = jax.devices()
    try:
        libtpu = metadata.version("libtpu")
    except metadata.PackageNotFoundError:
        libtpu = None
    return dict(platform=devs[0].platform, kind=devs[0].device_kind,
                count=len(devs), jax=jax.__version__,
                jaxlib=jaxlib.__version__, libtpu=libtpu)


# -- shared helpers ---------------------------------------------------------

def _timed(fn):
    t0 = time.perf_counter()
    out = fn()
    return out, time.perf_counter() - t0


def _compiles() -> int:
    from repro.core import ppa_trace_count, trace_count
    return trace_count() + ppa_trace_count()


def assert_same_front(a, b, what: str) -> None:
    """Indices and objectives equal bit for bit, row order included."""
    ai, bi = a.archive.indices, b.archive.indices
    check(np.array_equal(ai, bi),
          f"{what}: front indices differ ({len(ai)} vs {len(bi)} rows)")
    check(np.array_equal(a.archive.objectives, b.archive.objectives),
          f"{what}: front objectives differ")


def _models(size: Size):
    from repro.core import default_model_set
    models = default_model_set()
    return models if size.models is None else models[:size.models]


def _budget(spec: dict):
    from repro.core import Budget
    return Budget(**spec)


# -- c. joint sweep ---------------------------------------------------------

def phase_joint_sweep(models, size: Size):
    from repro.core import coexplore_front, coexplore_report
    front = None
    for run in ("cold", "warm"):
        c0 = _compiles()
        front, dt = _timed(lambda: coexplore_front(
            models, size.space, chunk_size=size.chunk_size))
        say("c", f"{run}: points={front.points_evaluated} "
                 f"buckets={'/'.join(str(b) for b, _ in front.buckets)} "
                 f"n_compiles={_compiles() - c0} front={len(front.archive)} "
                 f"seconds={dt:.3f} {TIMING}")
    claim = coexplore_report(front)["claim"]
    say("c", f"claim holds={claim['holds']} "
             f"indeterminate={claim['indeterminate']}: {claim['statement']}")
    check(claim["holds"], f"the LightPE claim does not hold: "
                          f"{claim['per_model']}")
    return front


# -- d. contract ------------------------------------------------------------

def phase_contract(models, size: Size, front) -> None:
    from repro.core import coexplore_front
    per_model = coexplore_front(models, size.space,
                                chunk_size=size.chunk_size, mix_models=False)
    assert_same_front(front, per_model, "mixed vs per-model walk")
    say("d", f"mixed == per-model walk: {len(front.archive)} rows identical")
    budget = _budget(dict(area_mm2=0.9))
    pruned = coexplore_front(models, size.space, chunk_size=size.chunk_size,
                             budget=budget)
    single = coexplore_front(models, size.space, chunk_size=size.chunk_size,
                             budget=budget, prune=False)
    assert_same_front(pruned, single, "pruned vs unpruned walk")
    say("d", f"pruned == unpruned walk (area_mm2<=0.9): "
             f"{len(pruned.archive)} rows identical, "
             f"pruned lanes={pruned.budget_stats.pruned}")


# -- e. chip against CPU ----------------------------------------------------

def evaluate_joint_sample(models, space, indices, chunk_size):
    """Evaluated ``DseResult`` columns of joint-space points ``indices``
    (host float64, in ``indices`` order) through the engine's chunk
    evaluator on the current default device."""
    from repro.core import (AcceleratorConfig, DseResult, evaluate_chunk,
                            joint_space_points, plan_joint_walk, take_config)
    walk = plan_joint_walk(models, space=space, chunk_size=chunk_size)
    mids, cfg = joint_space_points(indices, space, num_models=len(models))
    cfg = AcceleratorConfig(*[np.asarray(f) for f in cfg])
    cols = {f: np.empty(len(indices)) for f in DseResult._fields}
    bucket = np.asarray(walk.bucket_of)[mids]
    for b in np.unique(bucket):
        rows = np.flatnonzero(bucket == b)
        for lo in range(0, len(rows), chunk_size):
            r = rows[lo:lo + chunk_size]
            res = evaluate_chunk(take_config(cfg, r), walk.stacked[int(b)],
                                 model_ids=walk.local[mids[r]],
                                 pad_to=chunk_size)
            for f in DseResult._fields:
                cols[f][r] = getattr(res, f)
    return cols


def phase_vs_cpu(models, size: Size, front) -> None:
    import jax
    from repro.core import coexplore_front, joint_space_size
    cpu = jax.devices("cpu")[0]
    with jax.default_device(cpu):
        ref, dt = _timed(lambda: coexplore_front(
            models, size.space, chunk_size=size.chunk_size))
    a, b = set(front.archive.indices.tolist()), set(ref.archive.indices.tolist())
    say("e", f"joint sweep on CPU: front={len(ref.archive)} "
             f"(chip {len(front.archive)}), index overlap={len(a & b)} of "
             f"{len(a | b)}, seconds={dt:.3f} {TIMING}")
    n = joint_space_size(size.space, len(models))
    idx = np.sort(np.random.default_rng(0).choice(
        n, size=min(size.sample_points, n), replace=False))
    chip = evaluate_joint_sample(models, size.space, idx, size.chunk_size)
    with jax.default_device(cpu):
        host = evaluate_joint_sample(models, size.space, idx,
                                     size.chunk_size)
    beyond = np.zeros(len(idx), bool)
    worst = {}
    for f, want in host.items():
        rel = np.abs(chip[f] - want) / np.maximum(np.abs(want),
                                                  np.finfo(np.float64).tiny)
        worst[f] = float(rel.max())
        beyond |= rel > REL_TOL
    say("e", f"{len(idx)} sampled points, max relative difference per "
             f"column: " + " ".join(f"{f}={v:.3e}" for f, v in worst.items()))
    say("e", f"lanes beyond {REL_TOL:g}: {int(beyond.sum())}")
    check(not beyond.any(),
          f"{int(beyond.sum())} lanes differ from the CPU beyond {REL_TOL:g};"
          f" first joint indices {idx[beyond][:8].tolist()}")


# -- f. surrogate backend ---------------------------------------------------

def phase_surrogate(models, size: Size) -> None:
    import jax
    from benchmarks.fig3_ppa_fit import fit_errors
    from repro.core import coexplore_front, enumerate_space, fit_ppa_models

    def fit_and_score():
        fitted = fit_ppa_models(enumerate_space(max_points=size.fit_points,
                                                seed=1),
                                degrees=(1, 2, 3), k=5)
        sample = enumerate_space(max_points=size.eval_points, seed=0)
        return fitted, fit_errors(fitted, sample)

    (chip_fit, chip_err), dt = _timed(fit_and_score)
    say("f", f"surrogate fitted on the chip, seconds={dt:.3f} {TIMING}")
    with jax.default_device(jax.devices("cpu")[0]):
        _, cpu_err = fit_and_score()
    worst = 0.0
    for key, (_, m_chip, d_chip) in chip_err.items():
        _, m_cpu, d_cpu = cpu_err[key]
        worst = max(worst, abs(m_chip - m_cpu))
        say("f", f"{key[0]}/{key[1]}: mape chip={m_chip:.6f} "
                 f"cpu={m_cpu:.6f} degree chip={d_chip} cpu={d_cpu}")
        check(abs(m_chip - m_cpu) <= MAPE_MARGIN,
              f"surrogate MAPE for {key} differs from the CPU fit by "
              f"{abs(m_chip - m_cpu):.2e} > {MAPE_MARGIN:g}")
    say("f", f"max |MAPE chip - MAPE cpu| = {worst:.3e} "
             f"(margin {MAPE_MARGIN:g})")
    c0 = _compiles()
    front, dt = _timed(lambda: coexplore_front(
        models, size.space, surrogate=chip_fit, chunk_size=size.chunk_size))
    check(len(front.archive) > 0, "surrogate sweep returned an empty front")
    say("f", f"surrogate joint sweep: points={front.points_evaluated} "
             f"front={len(front.archive)} n_compiles={_compiles() - c0} "
             f"seconds={dt:.3f} {TIMING}")


# -- g. FrontServer ---------------------------------------------------------

def phase_frontserver(models, size: Size) -> None:
    from repro.core import coexplore_front
    from repro.serve.frontserver import FrontServer
    server = FrontServer(models, size.space, chunk_size=size.chunk_size)
    queries = [server.submit(_budget(b)) for b in SERVER_BUDGETS]
    _, dt = _timed(server.run)
    check(all(q.done for q in queries), "a coalesced query did not finish")
    say("g", f"{len(queries)} coalesced queries: chunk_evals="
             f"{server.chunk_evals} seconds={dt:.3f} {TIMING}")
    answers = [(spec, q.response) for spec, q in zip(SERVER_BUDGETS, queries)]
    for spec, kind in ((SERVER_BUDGETS[0], "cache:repeat"),
                       (SUPERSET_BUDGET, "cache:superset")):
        before = server.chunk_evals
        resp = server.query(_budget(spec))
        check(resp.served_from == kind,
              f"query {spec} was served from {resp.served_from}, not {kind}")
        check(server.chunk_evals == before,
              f"{kind} query evaluated {server.chunk_evals - before} chunks")
        say("g", f"{spec} served from {kind} with 0 chunk evaluations")
        answers.append((spec, resp))
    for spec, resp in answers:
        ref = coexplore_front(models, size.space, chunk_size=size.chunk_size,
                              budget=_budget(spec), prune=False)
        assert_same_front(resp, ref, f"FrontServer {spec}")
        say("g", f"{spec} ({resp.served_from}): {len(resp.archive)} rows "
                 f"identical to the standalone sweep")


# -- h. search --------------------------------------------------------------

def phase_search(models, size: Size) -> None:
    from repro.core import MAPPED_SPACE
    from repro.core.search import search_front
    space = MAPPED_SPACE if size.search_space is None else size.search_space
    for driver in ("evolve", "halving"):
        runs = []
        for attempt in ("first", "second"):
            c0 = _compiles()
            front, dt = _timed(lambda: search_front(
                models[:size.search_models], space=space, driver=driver,
                max_evals=size.search_evals, seed=0,
                chunk_size=size.chunk_size))
            runs.append((front, _compiles() - c0))
            say("h", f"{driver} {attempt}: evaluated="
                     f"{front.points_evaluated} front={len(front.archive)} "
                     f"n_compiles={runs[-1][1]} seconds={dt:.3f} {TIMING}")
            check(len(front.archive) > 0, f"{driver} found an empty front")
        check(runs[1][1] == 0,
              f"the second {driver} run compiled {runs[1][1]} times")
        assert_same_front(runs[0][0], runs[1][0], f"{driver} rerun")


# -- i. QAT -----------------------------------------------------------------

def phase_qat(size: Size) -> None:
    from benchmarks.fig56_pareto import PE_TYPES, QAT_BATCH, qat_setup
    from repro.data.synthetic import image_batch
    for pe in PE_TYPES:
        params, ostate, step = qat_setup(pe)
        losses = []
        for i in range(size.qat_steps):
            params, ostate, loss = step(params, ostate,
                                        image_batch(0, i, QAT_BATCH, 10))
            losses.append(float(loss))
        say("i", f"{pe}: losses {' '.join(f'{v:.4f}' for v in losses)}")
        check(np.isfinite(losses).all(), f"{pe} QAT loss is not finite")


# -- four chips -------------------------------------------------------------

def device_usage(devices) -> tuple[str, list]:
    """A per-device counter of the allocator (``memory_stats``) that grows
    when a device runs work: the allocation count where the backend
    reports one, else the peak bytes in use."""
    stats = [d.memory_stats() for d in devices]
    key = "num_allocs" if all("num_allocs" in s for s in stats) \
        else "peak_bytes_in_use"
    return key, [s[key] for s in stats]


def phase_sharded(models, size: Size, devices) -> None:
    """The 4-shard walk on four devices against the one-device walk (run
    second, so the allocator counters before the sharded walk are those
    of devices that have run nothing yet)."""
    from repro.core import coexplore_front
    key, before = device_usage(devices)
    sharded, dt4 = _timed(lambda: coexplore_front(
        models, size.space, chunk_size=size.chunk_size,
        shards=len(devices), devices=devices))
    grew = [a - b for a, b in zip(device_usage(devices)[1], before)]
    single, dt1 = _timed(lambda: coexplore_front(
        models, size.space, chunk_size=size.chunk_size))
    say("4", f"{len(devices)} shards: front={len(sharded.archive)} "
             f"seconds={dt4:.3f}; one device: front={len(single.archive)} "
             f"seconds={dt1:.3f} {TIMING}")
    say("4", f"{key} growth per device during the sharded walk: "
             + " ".join(f"{d.id}:{g}" for d, g in zip(devices, grew)))
    check(all(g > 0 for g in grew),
          f"some device ran no chunk of the sharded walk: {grew}")
    assert_same_front(sharded, single, "sharded vs one-device walk")
    say("4", f"sharded == one-device walk: {len(single.archive)} rows "
             f"identical")


# -- entry ------------------------------------------------------------------

def run_one_chip(size: Size = FULL) -> None:
    models = _models(size)
    front = phase_joint_sweep(models, size)
    phase_contract(models, size, front)
    phase_vs_cpu(models, size, front)
    phase_surrogate(models, size)
    phase_frontserver(models, size)
    phase_search(models, size)
    phase_qat(size)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the 4-shard walk and its reference")
    args = ap.parse_args(argv)

    info = device_info()
    say("a", " ".join(f"{k}={v}" for k, v in info.items()))
    if info["platform"] != "tpu":
        print(f"chip_smoke: no TPU found (first device is "
              f"{info['platform']}); nothing was run", file=sys.stderr)
        return 1
    if info["count"] < args.chips:
        print(f"chip_smoke: --chips {args.chips} needs {args.chips} devices,"
              f" found {info['count']}", file=sys.stderr)
        return 1

    sys.path.insert(0, str(ROOT / "src"))
    from repro.launch.compile_cache import enable_compile_cache
    say("b", f"compilation cache: {enable_compile_cache()}")

    import jax
    t0 = time.perf_counter()
    if args.chips == 4:
        phase_sharded(_models(FULL), FULL, jax.devices()[:4])
    else:
        run_one_chip(FULL)
    say("done", f"all phases passed in {time.perf_counter() - t0:.1f} s "
                f"{TIMING}")
    print(json.dumps(dict(ok=True, device=dict(
        platform=info["platform"], kind=info["kind"],
        count=info["count"]))))
    return 0


if __name__ == "__main__":
    sys.exit(main())
