"""Chunk decode in searches: milliseconds per chunk of the proposal's
bucket partition (``search.partition``) and each chunk's decode with its
copies (``search.decode``)."""


def read(r):
    if not r.chunks or not r.span_n("search.decode"):
        return None
    return (r.span_s("search.partition") + r.span_s("search.decode")) \
        / r.chunks * 1e3
