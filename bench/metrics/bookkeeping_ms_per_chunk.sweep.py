"""Fold bookkeeping: milliseconds per chunk of the joint objectives
(``sweep.objectives``), the per-(model, PE type) bests (``sweep.best``)
and the search driver's ``observe`` (``search.observe``)."""

NAMES = ("sweep.objectives", "sweep.best", "search.observe")


def read(r):
    if not r.chunks or not r.span_n("sweep.objectives"):
        return None
    return sum(r.span_s(n) for n in NAMES) / r.chunks * 1e3
