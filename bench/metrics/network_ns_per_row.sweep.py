"""Device stages: device nanoseconds per layer row of the layer gather
(``_lane_layers`` / ``_broadcast_layers``) and the per-layer fold
(``_network_sums``), from the profiler's trace, over counter
``sweep.rows`` (each chunk's points times its bucket depth), summed over
the cell's chips: the network stage's cost per row, comparable across
bucket depths and model sets."""

NAMES = ("_lane_layers", "_broadcast_layers", "_network_sums")


def read(r):
    rows = r.counters.get("sweep.rows")
    if not rows or r.trace is None:
        return None
    s = sum(r.trace["exec_s"].get(n, 0.0) for n in NAMES)
    return s / rows * 1e9 if s > 0 else None
