"""Archive fold: milliseconds per chunk of ``ParetoArchive.update``'s
prefilter against the current front (``archive.prefilter``)."""


def read(r):
    if not r.chunks or not r.span_n("archive.prefilter"):
        return None
    return r.span_s("archive.prefilter") / r.chunks * 1e3
