"""Walk drivers: host milliseconds per chunk of the traced window that
no top-level program span covers (the window's length less the
``trace.top_level_s`` counter, the seconds of spans no other span
encloses)."""


def read(r):
    top = r.counters.get("trace.top_level_s")
    if not r.chunks or r.trace is None or top is None:
        return None
    return (r.trace["window_s"] - top) / r.chunks * 1e3
