"""Archive fold (``fold_budget_chunk``, ``ParetoArchive.update``):
milliseconds of ``archive`` spans per chunk."""


def read(r):
    if not r.chunks or not r.span_n("sweep.archive"):
        return None
    return r.span_s("sweep.archive") / r.chunks * 1e3
