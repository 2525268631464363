"""Readings that the limits in ``limits.json`` are set from, on the chip.

    python3 -m bench.calibrate --workload paper_sweep --seeds 12 --control 3

In one process (a chip holds one): set-up once, then for each of
``--seeds`` seeds a short window of the cell's own traffic (one walk or
one search; ``--seconds`` of queries) compared with the reference as a
run compares it, and for the first ``--control`` seeds the control (the
reference in bfloat16 and float32, in the program's place) compared the
same way.  Prints one JSON line per reading, with whether each side is
``correct`` under ``limits.json``, and writes them all to ``--out``.
"""

from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, default=12)
    ap.add_argument("--control", type=int, default=3)
    ap.add_argument("--first-seed", type=int, default=2_000_000_000)
    ap.add_argument("--seconds", type=float, default=0.0)
    ap.add_argument("--out", default=None)
    args = ap.parse_args(argv)
    for p in (str(ROOT / "src"), str(ROOT)):
        if p not in sys.path:
            sys.path.insert(0, p)
    import jax
    from bench import harness, registry
    spec = registry.load_benchmark(ROOT)
    w = registry.workload(spec, args.workload)
    devices = jax.devices()
    if devices[0].platform != "tpu" or len(devices) < w["chips"]:
        print("calibrate: needs the cell's TPU chips", file=sys.stderr)
        return 2
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    traffic = registry.traffic(w["traffic"])
    drv = registry.driver(traffic["driver"])
    cell = harness.Cell(args.workload, registry.config(w["config"]),
                        traffic, args.first_seed, devices[:w["chips"]])
    drv.setup(cell)
    rows = []
    for k in range(args.seeds):
        cell.seed = args.first_seed + 7919 * k
        win = drv.window(cell, args.seconds, None)
        t0 = time.perf_counter()
        got = {}
        sides = [("program", drv.check)]
        if k < args.control:
            sides.append(("control", drv.control))
        for side, compare in sides:
            numbers = compare(cell, win)
            got[side] = numbers.values
            got[side + "_correct"] = numbers.correct()
        row = dict(seed=cell.seed, items=win.attempted,
                   check_s=time.perf_counter() - t0, **got)
        print(json.dumps(row), flush=True)
        rows.append(row)
    if args.out:
        Path(args.out).parent.mkdir(parents=True, exist_ok=True)
        Path(args.out).write_text(json.dumps(rows, indent=1))
    return 0


if __name__ == "__main__":
    sys.exit(main())
