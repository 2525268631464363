"""Archive fold: rows per chunk left after the prefilter against the
current front (counter ``archive.survivors``), the rows the survivors'
own front and the merge then handle."""


def read(r):
    n = r.counters.get("archive.survivors")
    if not r.chunks or n is None:
        return None
    return n / r.chunks
