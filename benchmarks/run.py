"""Benchmark harness: one module per paper table/figure + roofline.

Prints ``name,us_per_call,derived`` CSV rows (benchmarks/common.emit) and
writes the DSE-related rows to BENCH_dse.json.

  PYTHONPATH=src python -m benchmarks.run [--only fig4] [--fast]

--fast shrinks the QAT training budget AND caps every DSE sweep's point
count so the whole harness is CI-runnable in minutes; the default runs
the full 27k paper grid (and 216k in dse_scale).  Under --fast the WARM
rates of the unconstrained joint sweep, the constrained
(area/power-budgeted) sweep, the tight-budget two-stage PRUNED sweep,
the sharded multi-device sweep, the coalesced front-server query
storm (queries/sec) and the LLM-serving (decode/MoE) joint sweep are
guarded against the values committed in BENCH_dse.json (fails on a
>30% drop; BENCH_SKIP_REGRESSION=1 skips).

--telemetry-dir DIR turns on full sweep telemetry (benchmarks/common
``configure_telemetry``) and writes the observability artifacts after the
benches: ``events.jsonl`` (streamed as the run goes), ``trace.json``
(chrome://tracing / Perfetto, one lane per shard), ``sweep_report.json``
(phase attribution) and ``metrics.json`` (every registry aggregate —
the same registry the CSV rows printed from).
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import traceback

# DSE point cap + dse_scale sizes under --fast (full grids otherwise).
FAST_DSE_POINTS = 1500
FAST_SCALE_SIZES = (1000, 3000)
# --fast cap for the JOINT (model x accelerator) sweep: ~450 points per
# model of the default 10-model axis.
FAST_COEXPLORE_POINTS = 4500

# Benches whose rows land in BENCH_dse.json.
DSE_BENCHES = ("fig2", "fig4", "fig56", "dse_transformers", "dse_scale",
               "coexplore", "frontserver", "serving", "search")

# --fast regression guard: fail if a guarded warm rate drops more than
# this fraction below the value committed in BENCH_dse.json.  Each entry
# is (bench, row, rate_field): the unconstrained joint sweep, the
# constrained (budgeted) sweep, the tight-budget two-stage pruned sweep
# and the sharded multi-device sweep guard their warm pts/s, and the
# coalesced query storm guards its warm queries/sec — so neither a slow
# feasibility-mask path, a regressed pruner, a serialized shard pipeline,
# nor a de-coalesced front server can hide behind the unconstrained
# number.  BENCH_SKIP_REGRESSION=1 skips the check (noisy/underpowered
# runners).
REGRESSION_TOLERANCE = 0.30
GUARDED_ROWS = (("coexplore", "coexplore_joint_sweep_warm",
                 "points_per_sec"),
                ("coexplore", "coexplore_constrained_sweep_warm",
                 "points_per_sec"),
                ("coexplore", "coexplore_pruned_sweep_warm",
                 "points_per_sec"),
                ("dse_scale", "dse_scale_sharded_warm", "points_per_sec"),
                ("frontserver", "frontserver_storm_warm",
                 "queries_per_sec"),
                ("serving", "serving_decode_sweep_warm",
                 "points_per_sec"),
                # the budgeted-search row guards THREE fields: warm
                # throughput, the evals-vs-enumeration margin (0.05 /
                # evals_fraction — the <= 5%-of-enumeration acceptance
                # bar, so a driver that silently starts burning more
                # evaluations fails even at unchanged pts/s) and the
                # hypervolume ratio vs the enumerated reference front
                # (front RECOVERY, so a degenerate driver can't pass by
                # being fast and wrong)
                ("search", "search_evolve_warm", "points_per_sec"),
                ("search", "search_evolve_warm", "evals_budget_margin"),
                ("search", "search_evolve_warm", "hv_ratio"))


def _warm_row_fields(rows, guarded_row: str) -> dict | None:
    """key=value fields of one guarded warm row in a list of CSV rows."""
    for row in rows or ():
        if row.startswith(guarded_row + ","):
            return dict(part.split("=", 1)
                        for part in row.split(",", 2)[2].split(";")
                        if "=" in part)
    return None


def _check_regression(committed: dict, fresh: dict) -> list[str]:
    """Error strings for each guarded warm rate that regressed.

    ``fresh`` maps bench name -> its CSV rows (the dse_rows dict).  Only
    rows with the same evaluated point count are compared: a full
    (non---fast) run writes full-sweep numbers into BENCH_dse.json, and
    its warm rate is structurally higher than a --fast subsample's
    (less chunk padding) — comparing across modes would trip the guard
    on an unchanged engine.
    """
    errs = []
    for bench, guarded, rate_field in GUARDED_ROWS:
        ref = _warm_row_fields(committed.get(bench), guarded)
        got = _warm_row_fields(fresh.get(bench), guarded)
        if not ref or not got or rate_field not in ref \
                or rate_field not in got:
            continue  # no committed baseline / bench failed (reported anyway)
        if ref.get("points") != got.get("points"):
            print(f"regression guard: committed {guarded} baseline has "
                  f"points={ref.get('points')} but this run has points="
                  f"{got.get('points')} (different run mode) — skipping "
                  f"comparison", file=sys.stderr)
            continue
        ref_rate = float(ref[rate_field])
        got_rate = float(got[rate_field])
        if got_rate < (1.0 - REGRESSION_TOLERANCE) * ref_rate:
            errs.append(
                f"{guarded} {rate_field} regressed: {got_rate:.2f} < "
                f"{(1.0 - REGRESSION_TOLERANCE) * ref_rate:.2f} "
                f"(committed {ref_rate:.2f} - {REGRESSION_TOLERANCE:.0%}); "
                f"set BENCH_SKIP_REGRESSION=1 to skip on noisy runners")
    return errs


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default=None)
    ap.add_argument("--fast", action="store_true",
                    help="shrink the QAT training budget and cap DSE "
                         "point counts (CI mode)")
    ap.add_argument("--dse-json", default="BENCH_dse.json",
                    help="where to write the DSE bench rows")
    ap.add_argument("--telemetry-dir", default=None,
                    help="write events.jsonl / trace.json / "
                         "sweep_report.json / metrics.json here")
    args = ap.parse_args()

    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()

    from benchmarks import common
    if args.telemetry_dir:
        os.makedirs(args.telemetry_dir, exist_ok=True)
        common.configure_telemetry(args.telemetry_dir)

    from benchmarks import (coexplore, dse_scale, dse_transformers,
                            fig2_pe_spread, fig3_ppa_fit, fig4_dse,
                            fig56_pareto, frontserver, kernels_bench,
                            roofline, search, serving)
    mp = FAST_DSE_POINTS if args.fast else None
    benches = {
        "fig2": lambda: fig2_pe_spread.run(max_points=mp),
        "fig3": fig3_ppa_fit.run,
        "fig4": lambda: fig4_dse.run(max_points=mp),
        "fig56": (lambda: fig56_pareto.run(steps=60, max_points=mp,
                                           trials=1))
        if args.fast else fig56_pareto.run,
        "kernels": kernels_bench.run,
        "dse_transformers": lambda: dse_transformers.run(max_points=mp),
        "dse_scale": (lambda: dse_scale.run(sizes=FAST_SCALE_SIZES,
                                            giga=False))
        if args.fast else dse_scale.run,
        "coexplore": lambda: coexplore.run(
            max_points=FAST_COEXPLORE_POINTS if args.fast else None),
        "frontserver": lambda: frontserver.run(
            max_points=FAST_COEXPLORE_POINTS if args.fast else None),
        "serving": lambda: serving.run(
            max_points=FAST_COEXPLORE_POINTS if args.fast else None),
        "search": lambda: search.run(
            max_points=FAST_COEXPLORE_POINTS if args.fast else None),
        "roofline": roofline.run,
    }
    # committed baseline, read BEFORE the fresh rows overwrite the file
    try:
        with open(args.dse_json) as f:
            committed = json.load(f)
    except (OSError, ValueError):
        committed = {}

    print("name,us_per_call,derived")
    failed = []
    dse_rows = {}
    for name, fn in benches.items():
        if args.only and args.only not in name:
            continue
        try:
            rows = fn()
            if name in DSE_BENCHES and rows:
                dse_rows[name] = rows
        except Exception:  # noqa: BLE001
            failed.append(name)
            traceback.print_exc()

    # throughput regression guard (--fast only: committed numbers are the
    # --fast CI artifact, so the comparison is like-for-like)
    if (args.fast and dse_rows
            and not os.environ.get("BENCH_SKIP_REGRESSION")):
        errs = _check_regression(committed, dse_rows)
        for err in errs:
            print(f"REGRESSION: {err}", file=sys.stderr)
        if errs:
            failed.append("regression_guard")
    if dse_rows:
        if args.only or failed:  # partial run: merge, don't clobber
            try:
                with open(args.dse_json) as f:
                    dse_rows = {**json.load(f), **dse_rows}
            except (OSError, ValueError):
                pass
        with open(args.dse_json, "w") as f:
            json.dump(dse_rows, f, indent=2)
        print(f"wrote {args.dse_json}", file=sys.stderr)

    if args.telemetry_dir:
        from repro.obs import (build_sweep_report, write_chrome_trace,
                               write_sweep_report)
        tr = common.sweep_telemetry()
        tr.close()
        write_chrome_trace(os.path.join(args.telemetry_dir, "trace.json"), tr)
        write_sweep_report(
            os.path.join(args.telemetry_dir, "sweep_report.json"),
            build_sweep_report(tr))
        with open(os.path.join(args.telemetry_dir, "metrics.json"), "w") as f:
            json.dump(common.REGISTRY.as_dict(), f, indent=2)
        print(f"telemetry artifacts in {args.telemetry_dir}",
              file=sys.stderr)
    if failed:
        print(f"FAILED: {failed}", file=sys.stderr)
        sys.exit(1)


if __name__ == "__main__":
    main()
