"""FrontServer (``serve.frontserver``): host milliseconds of the
per-chunk query folds (``front.fold``) and mid-walk join replays
(``front.replay``) per chunk the shared walk evaluated."""


def read(r):
    chunks = r.counters.get("serve.front.chunk_evals", 0)
    if not chunks:
        return None
    return (r.span_s("serve.front.fold") + r.span_s("serve.front.replay")) \
        / chunks * 1e3
