"""Device: one minus the busy union over the traced window, averaged
over the cell's chips (the query cell)."""


def read(r):
    if r.trace is None or not r.trace["busy_s"]:
        return None
    busy = sum(r.trace["busy_s"].values()) / len(r.trace["busy_s"])
    return 1.0 - busy / r.trace["window_s"]
