"""Load generator: 95th percentile of how late each query was submitted
after its due time, in milliseconds (a late generator would read as a
fast server)."""

import numpy as np


def read(r):
    if not r.lags_s:
        return None
    return float(np.percentile(np.asarray(r.lags_s) * 1e3, 95))
