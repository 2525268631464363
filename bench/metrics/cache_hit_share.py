"""FrontServer front cache: queries answered from the cache (repeat or
superset hits) over all answered queries."""


def read(r):
    answered = r.counters.get("serve.front.queries", 0)
    if not answered:
        return None
    return r.counters.get("serve.front.cache_hit", 0) / answered
