"""Device stages: device microseconds of the layer gather
(``_lane_layers`` / ``_broadcast_layers``) and the per-layer fold
(``_network_sums``) per chunk, from the profiler's trace, summed over
the cell's chips."""

NAMES = ("_lane_layers", "_broadcast_layers", "_network_sums")


def read(r):
    if not r.chunks or r.trace is None:
        return None
    s = sum(r.trace["exec_s"].get(n, 0.0) for n in NAMES)
    return s / r.chunks * 1e6 if s > 0 else None
