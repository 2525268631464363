"""Fig. 3: polynomial PPA models vs 'synthesis' ground truth, per PE type.

Paper claim: "the proposed polynomial model agrees closely with the actual
values extracted from the synthesis tools."  Reported: R^2 and MAPE per
(PE type x target), plus the k-fold-selected degree.
"""

from __future__ import annotations

import time

import numpy as np

from benchmarks.common import emit
from repro.core import (enumerate_space, fit_ppa_models, mape, r2,
                        synthesize)
from repro.core.arch import PE_TYPE_NAMES
from repro.core.ppa import TARGETS, config_features


# The evaluation sample the fit errors are reported on.
EVAL_POINTS = 1500


def fit_errors(models, space) -> dict:
    """``{(target, pe_name): (r2, mape, degree)}`` of fitted PPA
    surrogates against the synthesis oracle on the design sample
    ``space``, per PE type present in it."""
    truth = synthesize(space)
    pred = models.predict(space)
    pt = np.asarray(space.pe_type)
    out = {}
    for target in TARGETS:
        yt = np.asarray(getattr(truth, target))
        yp = np.asarray(getattr(pred, target))
        for code, name in enumerate(PE_TYPE_NAMES):
            sel = pt == code
            if sel.any():
                out[target, name] = (r2(yt[sel], yp[sel]),
                                     mape(yt[sel], yp[sel]),
                                     models.models[name][target].degree)
    return out


def run():
    rows = []
    space = enumerate_space(max_points=EVAL_POINTS, seed=0)
    t0 = time.perf_counter()
    models = fit_ppa_models(space, degrees=(1, 2, 3), k=5)
    fit_us = (time.perf_counter() - t0) * 1e6
    errs = fit_errors(models, space)
    for target in TARGETS:
        per_pe = [f"{name}:r2={e[0]:.4f},mape={e[1]:.3f},deg={e[2]}"
                  for (t, name), e in errs.items() if t == target]
        rows.append(emit(f"fig3_fit_{target}", fit_us / len(TARGETS),
                         ";".join(per_pe)))
    truth = synthesize(space)
    pred = models.predict(space)
    # headline: overall agreement
    overall = [f"{t}:r2={r2(np.asarray(getattr(truth, t)), np.asarray(getattr(pred, t))):.4f}"
               for t in TARGETS]
    rows.append(emit("fig3_overall", fit_us, ";".join(overall)
                     + ";paper_claim=agrees_closely"))
    return rows


if __name__ == "__main__":
    run()
