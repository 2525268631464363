"""Search driver (``core.search``): host milliseconds of the program's
``propose`` spans per search generation."""


def read(r):
    gens = r.counters.get("search.generations", 0)
    if not gens:
        return None
    return r.span_s("search.propose") / gens * 1e3
