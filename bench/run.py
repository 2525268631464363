"""Run one cell of the benchmark on the machine's TPU.

    python3 -m bench.run --workload paper_sweep --seed 7 --seconds 10 --trace 0

The cell (``BENCHMARK.json``'s ``workloads``) names a configuration and
a traffic mix.  One process: load, warm up every shape the window uses
(set-up), measure for ``--seconds``, compare what the window produced
with the plain reference, then print the compared numbers beside their
limits as the last lines of standard error and the result as one JSON
line, the last of standard output.  ``--trace 1`` runs the window under
the profiler and reports the per-layer metrics instead of the
end-to-end ones.  Without a TPU, or with fewer chips than the cell
asks for, it exits 2 and prints no result.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    for p in (str(ROOT / "src"), str(ROOT)):
        if p not in sys.path:
            sys.path.insert(0, p)
    from bench import registry
    spec = registry.load_benchmark(ROOT)
    cell = registry.workload(spec, args.workload)

    marks = {"imports": time.perf_counter()}
    import jax
    devices = jax.devices()
    marks["device_init"] = time.perf_counter()
    if devices[0].platform != "tpu":
        print(f"bench: no TPU (the first device is {devices[0].platform}); "
              f"nothing was measured", file=sys.stderr)
        return 2
    if len(devices) < cell["chips"]:
        print(f"bench: {args.workload} needs {cell['chips']} chips, found "
              f"{len(devices)}", file=sys.stderr)
        return 2

    peaks = json.loads((ROOT / "bench" / "peaks.json").read_text())
    if devices[0].device_kind not in peaks:
        print(f"bench: {devices[0].device_kind!r} is not in the peaks table "
              f"(bench/peaks.json)", file=sys.stderr)
        return 2
    from bench.harness import run_cell
    result, lines = run_cell(spec, args.workload, args.seed, args.seconds,
                             bool(args.trace), devices[:cell["chips"]],
                             T_START, marks=marks)
    for name, value, limit in lines:
        print(f"check {name} {value!r} limit {limit!r}", file=sys.stderr)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
