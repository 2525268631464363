"""Instrumented DSE sweep: telemetry end to end in one screen.

  PYTHONPATH=src python examples/trace_sweep.py [--shards 4] [--max-points N]
                                                [--profile DIR]

Runs a sharded streaming Pareto sweep with a ``repro.obs.Tracer`` plugged
into the ``telemetry=`` knob, then shows every sink the tracer feeds:

  results/trace/events.jsonl   — streaming event log (one JSON per line)
  results/trace/trace.json     — open in chrome://tracing or
                                 https://ui.perfetto.dev (one lane per
                                 shard: dispatch spans + chunk residency)
  results/trace/sweep_report.json — phase attribution (load with
                                 repro.obs.load_sweep_report, render with
                                 scripts/gen_tables.py sweep_report)

and prints the attribution table: where the wall clock went
(decode/dispatch/device-wait/archive), compile events per layer bucket,
pts/s and RSS growth; "total accounted" is the share of the wall clock
inside top-level spans (the copies and the archive prefilter nest in
them).  ``--profile DIR`` runs the sweep under ``jax.profiler.trace``
with ``Tracer(annotate=True)``: every span is also a
``TraceAnnotation`` named ``<cat>.<name>``, so the profiler's trace in
DIR (TensorBoard / Perfetto) shows the program's spans beside the
device's work.  Telemetry never touches evaluated values — the front is
bit-identical with the knob off (asserted below).
"""

import argparse
import contextlib

import numpy as np

from repro.core import PAPER_WORKLOADS, pareto_front_streaming
from repro.obs import Tracer, build_sweep_report, write_chrome_trace, \
    write_sweep_report

ap = argparse.ArgumentParser()
ap.add_argument("--workload", default="resnet20-cifar10",
                choices=list(PAPER_WORKLOADS))
ap.add_argument("--shards", type=int, default=4)
ap.add_argument("--max-points", type=int, default=6000,
                help="subsample the 27k paper grid (default 6000)")
ap.add_argument("--profile", metavar="DIR",
                help="also capture a jax.profiler trace with the spans "
                     "as annotations")
args = ap.parse_args()

wl = PAPER_WORKLOADS[args.workload]()

profile = contextlib.nullcontext()
if args.profile:
    import jax
    profile = jax.profiler.trace(args.profile)

with profile, Tracer(jsonl_path="results/trace/events.jsonl",
                     annotate=bool(args.profile)) as tr:
    archive, front_cfg = pareto_front_streaming(
        wl, max_points=args.max_points, shards=args.shards, telemetry=tr)
    report = build_sweep_report(tr)
    write_chrome_trace("results/trace/trace.json", tr)
    write_sweep_report("results/trace/sweep_report.json", report)

print(report.render())
print(f"front: {len(archive)} points; "
      f"dropped events: {tr.dropped_events}")
print("wrote results/trace/{events.jsonl,trace.json,sweep_report.json}")
if args.profile:
    print(f"profiler trace with the spans as annotations under "
          f"{args.profile}")

# the off-switch contract: same front without telemetry, bit for bit
plain, _ = pareto_front_streaming(wl, max_points=args.max_points,
                                  shards=args.shards)
assert np.array_equal(plain.indices, archive.indices)
assert np.array_equal(plain.objectives, archive.objectives)
print("front bit-identical with telemetry off: True")
