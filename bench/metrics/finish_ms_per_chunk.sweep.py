"""Host finish (``dse.finish_chunk``: the blocking transfer plus the
float64 ``_finish``): milliseconds of ``device_wait`` spans per chunk."""


def read(r):
    if not r.chunks:
        return None
    return r.span_s("sweep.device_wait") / r.chunks * 1e3
