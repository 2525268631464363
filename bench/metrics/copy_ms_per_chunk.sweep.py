"""Host-device copies: milliseconds per chunk of the decoded columns'
upload (``copy.upload``), the PE-code readback (``copy.codes``) and the
finish's reads (``copy.fetch``, which also waits for the chunk's device
work)."""

NAMES = ("copy.upload", "copy.codes", "copy.fetch")


def read(r):
    if not r.chunks or not any(r.span_n(n) for n in NAMES):
        return None
    return sum(r.span_s(n) for n in NAMES) / r.chunks * 1e3
