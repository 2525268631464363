"""Chunk decode (``arch.iter_joint_space_chunks``): host milliseconds of
the program's ``decode`` spans per chunk finished in the window."""


def read(r):
    if not r.chunks or not r.span_n("sweep.decode"):
        return None
    return r.span_s("sweep.decode") / r.chunks * 1e3
