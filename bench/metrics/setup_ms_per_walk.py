"""Walk drivers: milliseconds of each walk's or search's own set-up
(``sweep.walk_setup``, ``search.setup``: plan, stacked workloads,
accuracy matrix, screen, driver reset) per walk or search."""

NAMES = ("sweep.walk_setup", "search.setup")


def read(r):
    n = sum(r.span_n(k) for k in NAMES)
    if not n:
        return None
    return sum(r.span_s(k) for k in NAMES) / n * 1e3
