"""Device stages: device microseconds of the PPA stage (``_ppa_stage``)
per chunk, from the profiler's trace, summed over the cell's chips."""


def read(r):
    if not r.chunks or r.trace is None:
        return None
    s = r.trace["exec_s"].get("_ppa_stage", 0.0)
    return s / r.chunks * 1e6 if s > 0 else None
