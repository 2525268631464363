"""Polynomial-regression PPA surrogate models (the paper's Sec. III-C).

The paper fits polynomial regression models of power, performance (clock)
and area against synthesis ground truth, selecting model complexity with
k-fold cross validation [Mosteller & Tukey].  This module reproduces that
methodology against the ``synth.py`` oracle:

  * per-PE-type models (the paper plots Fig. 3 per PE type),
  * full multivariate monomial basis up to a degree chosen per target by
    k-fold CV over {1, 2, 3},
  * ridge-regularized least squares (lstsq on the standardized design
    matrix),
  * fit-quality metrics (R^2, MAPE) reported by benchmarks/fig3_ppa_fit.py.

Implemented with jnp end-to-end; fitting a few hundred design points is
instant and differentiable (not that the paper needs gradients — but it
makes the surrogate usable inside jitted DSE loops).

Prediction is array-first and jit-native: ``surrogate_ppa`` is the pure
``(params, config_chunk) -> (power, clock, area)`` stage consumed by the
cost-model backend layer (``repro.core.costmodel``).  The fitted
per-(PE type, target) polynomials are packed into one pytree
(``PPAModels.ppa_params``) of coefficient/basis arrays, the design
matrix is evaluated for EVERY lane of the chunk inside the jit, and each
lane gathers its own PE type's prediction — so a mixed-type 4096-lane
chunk is one compiled computation instead of the historical host-numpy
path that re-dispatched eager kernels per (chunk, PE-type-subset) shape.
"""

from __future__ import annotations

import itertools
from dataclasses import dataclass, field
from functools import partial
from math import comb
from typing import Dict, Sequence

import jax
import jax.numpy as jnp
from jax.scipy.linalg import solve_triangular
import numpy as np

from repro.core.arch import AcceleratorConfig, PE_TYPE_NAMES
from repro.core.synth import LEAKAGE_MW_PER_MM2, SynthResult, synthesize

# Regression features: every knob except pe_type (models are per PE type).
FEATURE_FIELDS = ("pe_rows", "pe_cols", "gbuf_kb", "spad_ifmap",
                  "spad_filter", "spad_psum", "bandwidth_gbps")
TARGETS = ("power_mw", "clock_ghz", "area_mm2")


def config_features(cfg: AcceleratorConfig) -> jnp.ndarray:
    """(N, F) raw feature matrix from a batched config."""
    cols = [jnp.atleast_1d(getattr(cfg, f)).astype(jnp.float32)
            for f in FEATURE_FIELDS]
    return jnp.stack(cols, axis=-1)


def monomial_exponents(n_features: int, degree: int) -> np.ndarray:
    """All exponent tuples with total degree in [0, degree]."""
    exps = [e for e in itertools.product(range(degree + 1), repeat=n_features)
            if sum(e) <= degree]
    exps.sort(key=lambda e: (sum(e), e))
    return np.array(exps, dtype=np.int32)


# Contractions of the fit and the prediction: full f32 on every backend
# (the TPU's default f32 matmul takes bf16 passes, too coarse for the
# normal equations of a degree-3 fit).
_HIGHEST = jax.lax.Precision.HIGHEST


def _max_degree(n_monomials: int, n_features: int) -> int:
    """Total degree of a ``monomial_exponents`` basis, from its size."""
    d = 0
    while comb(n_features + d, d) < n_monomials:
        d += 1
    return d


def design_matrix(x: jnp.ndarray, exps: np.ndarray,
                  mu: jnp.ndarray, sigma: jnp.ndarray) -> jnp.ndarray:
    """Monomial basis on standardized features. x: (N, F) -> (N, M).

    Powers are repeated products, not ``pow``: standardized features are
    often negative, and a float ``pow`` lowers through ``log`` on some
    backends."""
    z = (x - mu) / sigma
    e = jnp.asarray(exps)[None, :, :]                  # (1, M, F)
    zb = jnp.broadcast_to(z[:, None, :], (z.shape[0],) + e.shape[1:])
    p = jnp.ones_like(zb)
    for k in range(_max_degree(e.shape[1], e.shape[2])):
        p = jnp.where(e > k, p * zb, p)
    return jnp.prod(p, axis=-1)


@dataclass
class PolyModel:
    """One fitted polynomial y ~ poly(x) for one (pe_type, target)."""
    degree: int
    exps: np.ndarray
    mu: jnp.ndarray
    sigma: jnp.ndarray
    coef: jnp.ndarray
    log_target: bool = True   # fit log(y): PPA spans decades, keeps MAPE low

    def predict(self, x: jnp.ndarray) -> jnp.ndarray:
        a = design_matrix(x, self.exps, self.mu, self.sigma)
        y = jnp.matmul(a, self.coef, precision=_HIGHEST)
        return jnp.exp(y) if self.log_target else y


def _fit_coef(a: jnp.ndarray, y: jnp.ndarray, ridge: float = 1e-6):
    """``argmin |a c - y|^2 + ridge |c|^2`` by QR of ``[a; sqrt(ridge) I]``.

    Not the normal equations: they square the design's condition number,
    which a degree-2 or -3 basis on ~100 rows takes past float32's reach,
    so their solution turns on rounding that differs between backends."""
    m = a.shape[1]
    aug = jnp.concatenate([a, jnp.sqrt(ridge) * jnp.eye(m, dtype=a.dtype)])
    rhs = jnp.concatenate([y, jnp.zeros(m, y.dtype)])
    with jax.default_matmul_precision("highest"):   # the QR's own dots
        q, r = jnp.linalg.qr(aug)
        return solve_triangular(
            r, jnp.matmul(q.T, rhs, precision=_HIGHEST), lower=False)


# The fit is compiled per (sample size, degree), and its rows are chosen
# by 0/1 weights rather than by slicing: every fold of every PE type's
# cross-validation then reuses one executable per degree.

@jax.jit
def _standardize(x, w):
    """Weighted feature mean and standard deviation of the rows w selects
    (one executable, so every target of a sample standardizes alike)."""
    n = jnp.sum(w)
    mu = jnp.sum(w[:, None] * x, axis=0) / n
    var = jnp.sum(w[:, None] * (x - mu) ** 2, axis=0) / n
    return mu, jnp.maximum(jnp.sqrt(var), 1e-6)


@partial(jax.jit, static_argnames=("degree", "log_target"))
def _fit_arrays(x, y, w, mu, sigma, ridge, degree, log_target):
    a = design_matrix(x, monomial_exponents(x.shape[1], degree), mu, sigma)
    t = jnp.log(jnp.maximum(y, 1e-12)) if log_target else y
    return _fit_coef(a * w[:, None], t * w, ridge)


def fit_poly(x: jnp.ndarray, y: jnp.ndarray, degree: int,
             log_target: bool = True, ridge: float = 1e-6,
             weights=None) -> PolyModel:
    """Ridge fit of one polynomial on the rows ``weights`` (0/1, default
    all) selects."""
    x = jnp.asarray(x, jnp.float32)
    y = jnp.asarray(y, jnp.float32)
    w = jnp.ones(x.shape[0], jnp.float32) if weights is None \
        else jnp.asarray(weights, jnp.float32)
    mu, sigma = _standardize(x, w)
    coef = _fit_arrays(x, y, w, mu, sigma, ridge, degree, log_target)
    return PolyModel(degree=degree,
                     exps=monomial_exponents(x.shape[1], degree), mu=mu,
                     sigma=sigma, coef=coef, log_target=log_target)


def kfold_mse(x: jnp.ndarray, y: jnp.ndarray, degree: int, k: int = 5,
              log_target: bool = True, weights=None) -> float:
    """k-fold CV mean squared error (in log space if log_target) over the
    rows ``weights`` (0/1, default all) selects.

    ``k`` is clamped to the sample count: with k > n, np.array_split
    would yield empty folds whose MSE is a mean over an empty array
    (NaN + RuntimeWarning), silently breaking degree selection in
    ``select_and_fit`` (NaN compares False, so the first degree always
    won).  Cross-validation needs at least 2 samples.
    """
    rows = np.arange(int(x.shape[0])) if weights is None \
        else np.flatnonzero(np.asarray(weights))
    n = len(rows)
    if n < 2:
        raise ValueError(f"kfold_mse needs >= 2 samples to hold one out, "
                         f"got {n}")
    k = min(k, n)
    idx = np.arange(n)
    rng = np.random.default_rng(0)
    rng.shuffle(idx)
    folds = np.array_split(rows[idx], k)
    y_host = np.asarray(y, np.float64)
    errs = []
    for f in folds:
        mask = np.zeros(int(x.shape[0]), bool)
        mask[rows] = True
        mask[f] = False
        model = fit_poly(x, y, degree, log_target, weights=mask)
        pred = np.asarray(model.predict(x))[f]
        t, p = (np.log(np.maximum(y_host[f], 1e-12)),
                np.log(np.maximum(pred, 1e-12))) \
            if log_target else (y_host[f], pred)
        errs.append(float(np.mean((t - p) ** 2)))
    return float(np.mean(errs))


def select_and_fit(x: jnp.ndarray, y: jnp.ndarray,
                   degrees: Sequence[int] = (1, 2, 3), k: int = 5,
                   log_target: bool = True, weights=None) -> PolyModel:
    """Model selection by k-fold CV (the paper's methodology), then refit."""
    best_d, best_mse = degrees[0], float("inf")
    for d in degrees:
        mse = kfold_mse(x, y, d, k, log_target, weights)
        if mse < best_mse:
            best_d, best_mse = d, mse
    return fit_poly(x, y, best_d, log_target, weights=weights)


def surrogate_ppa(params, cfg: AcceleratorConfig):
    """Batched PPA stage of the polynomial-surrogate backend.

    The ``CostModel.ppa_fn`` contract (see ``repro.core.costmodel``): a
    pure jit-safe ``(params, config_chunk) -> (power_mw, clock_ghz,
    area_mm2)`` function.  ``params`` is the ``PPAModels.ppa_params()``
    pytree — the design matrix is evaluated over ALL lanes for every
    fitted PE type's polynomial, and each lane then gathers its own
    type's row, so mixed-type chunks run as one compiled computation.
    Because the polynomial coefficients are pytree *arguments* (not
    closed-over constants), every fit with the same selected degrees
    reuses the same compiled executable.

    Lanes of an unfitted PE type are NOT handled here (a jitted function
    cannot raise on data): callers must pre-check with
    ``PPAModels.validate`` — the backend layer does this on every chunk.

    SHARED DESIGN MATRIX: ``monomial_exponents`` orders the basis by
    ``(total degree, lex)``, so a degree-d monomial set is a PREFIX of
    any higher-degree set over the same features.  When a PE type's
    three targets standardize identically (they always do — one fit
    sample per type) its entry carries ONE max-degree basis
    (``{"exps", "mu", "sigma", "targets"}``; see
    ``PPAModels.ppa_params``), the design matrix is evaluated once per
    type, and each target contracts its leading ``len(coef)`` columns —
    bit-identical to evaluating its own smaller matrix (column values
    are elementwise in the basis and the contraction covers the same
    terms in the same order), at a third of the basis-evaluation cost.
    Legacy per-target entries (``{target: (exps, mu, sigma, coef,
    log)}``) still evaluate their own matrices.
    """
    x = config_features(cfg)
    pt = jnp.atleast_1d(cfg.pe_type)
    pos = params["pos"][pt]                         # (N,) stack row per lane
    shared = [design_matrix(x, e["exps"], e["mu"], e["sigma"])
              if "targets" in e else None
              for e in params["types"]]             # one basis per PE type
    out = []
    for t in TARGETS:
        preds = []
        for entry, a in zip(params["types"], shared):
            if a is not None:
                coef, log = entry["targets"][t]
                v = jnp.matmul(a[:, :coef.shape[0]], coef,   # prefix basis
                               precision=_HIGHEST)
            else:
                exps, mu, sigma, coef, log = entry[t]
                v = jnp.matmul(design_matrix(x, exps, mu, sigma), coef,
                               precision=_HIGHEST)
            preds.append(jnp.where(log, jnp.exp(v), v))
        stacked = jnp.stack(preds)                  # (fitted types, N)
        out.append(jnp.take_along_axis(stacked, pos[None, :], axis=0)[0])
    power, clock, area = out                        # TARGETS order
    return power, clock, area


# Lane cap per jitted predict call: the design-matrix evaluation holds
# (N, monomials, features) intermediates — ~14 MB per (type, target) at
# degree 3 and N=4096 — so a 27k-point grid in ONE call would peak well
# over a GB of XLA temp buffers.  Bigger batches stream through in slices
# (the DSE paths never hit this: they already evaluate at chunk shape).
_PREDICT_CHUNK = 4096


def _ppa_stage_jit():
    """The evaluator's shared jitted PPA stage (``dse._ppa_stage``).

    Imported lazily: ``dse`` imports this module, and sharing ITS jit —
    rather than keeping a second ``jax.jit(surrogate_ppa)`` here — means
    a ``predict`` call and a DSE sweep over the same chunk shape compile
    the design-matrix graph once, and ``dse.ppa_trace_count`` covers
    ``predict`` traffic too.
    """
    from repro.core.dse import _ppa_stage
    return _ppa_stage


def _pack_type_entry(ms: Dict[str, "PolyModel"]) -> dict:
    """One PE type's targets as a ``surrogate_ppa`` params entry.

    Shared layout (the fast path) when every target standardized on the
    same features (equal mu/sigma) AND every target's exponent set is a
    prefix of the widest one — guaranteed by ``monomial_exponents``'s
    ``(total degree, lex)`` ordering for fits over a common sample, which
    is how ``fit_ppa_models`` always fits.  Falls back to the legacy
    per-target layout (own basis per target) for hand-assembled models
    that break either property, so exotic ``PPAModels`` keep working.
    """
    mx = max(ms.values(), key=lambda m: int(np.asarray(m.exps).shape[0]))
    shareable = all(
        np.array_equal(np.asarray(m.mu), np.asarray(mx.mu))
        and np.array_equal(np.asarray(m.sigma), np.asarray(mx.sigma))
        and np.array_equal(np.asarray(m.exps),
                           np.asarray(mx.exps)[:np.asarray(m.exps).shape[0]])
        for m in ms.values())
    if not shareable:
        return {t: (jnp.asarray(m.exps, jnp.int32),
                    jnp.asarray(m.mu, jnp.float32),
                    jnp.asarray(m.sigma, jnp.float32),
                    jnp.asarray(m.coef, jnp.float32),
                    jnp.asarray(m.log_target))
                for t, m in ms.items()}
    return {"exps": jnp.asarray(mx.exps, jnp.int32),
            "mu": jnp.asarray(mx.mu, jnp.float32),
            "sigma": jnp.asarray(mx.sigma, jnp.float32),
            "targets": {t: (jnp.asarray(m.coef, jnp.float32),
                            jnp.asarray(m.log_target))
                        for t, m in ms.items()}}


@dataclass
class PPAModels:
    """Per-PE-type surrogates for power / clock / area."""
    models: Dict[str, Dict[str, PolyModel]] = field(default_factory=dict)
    _params: dict | None = field(default=None, init=False, repr=False,
                                 compare=False)

    def validate(self, cfg: AcceleratorConfig) -> None:
        """Raise unless every PE type present in ``cfg`` has a fitted model.

        Lanes of an unfitted type would otherwise silently predict zero
        power/clock/area, i.e. a 1e6 ns critical path, zero area and a
        +inf perf/area objective that corrupts any Pareto front built on
        them.  Raises ``ValueError`` naming the missing types instead.
        """
        pt = np.atleast_1d(np.asarray(cfg.pe_type)).astype(int)
        codes = np.unique(pt)
        invalid = codes[(codes < 0) | (codes >= len(PE_TYPE_NAMES))]
        if invalid.size:
            # a negative code would alias a real type through the pos
            # gather (its lanes silently borrowing another type's
            # prediction); an oversized one would index out of range
            raise ValueError(
                f"pe_type codes {invalid.tolist()} are outside "
                f"[0, {len(PE_TYPE_NAMES)}) — not a known PE type")
        missing = sorted({PE_TYPE_NAMES[c] for c in codes
                          if PE_TYPE_NAMES[c] not in self.models})
        if missing:
            raise ValueError(
                f"PPAModels has no fitted model for PE type(s) "
                f"{missing} present in the config batch (fitted: "
                f"{sorted(self.models)}); predicting them would silently "
                f"yield zero power/clock/area — fit on a design sample "
                f"covering every PE type the DSE sweeps")

    def ppa_params(self) -> dict:
        """The fitted polynomials as one jit-consumable pytree (cached).

        ``pos`` maps a PE-type code to its row in the stacked per-type
        predictions (unfitted codes point at row 0 — ``validate`` keeps
        them out of any evaluated chunk); ``types`` holds one entry per
        fitted type in code order, packed by ``_pack_type_entry``: the
        shared max-degree basis (``exps``/``mu``/``sigma``) plus each
        target's ``(coef, log_target)`` when the three targets can share
        a design matrix (always true for ``fit_ppa_models`` output), or
        the legacy per-target ``(exps, mu, sigma, coef, log_target)``
        tuples when they cannot.  The arrays are device-resident and
        reused across chunks, so feeding the same ``PPAModels`` to a
        streaming walk never re-uploads coefficients.
        """
        if self._params is None:
            fitted = [(code, name) for code, name in enumerate(PE_TYPE_NAMES)
                      if name in self.models]
            if not fitted:
                raise ValueError("PPAModels has no fitted models")
            pos = np.zeros(len(PE_TYPE_NAMES), np.int32)
            types = []
            for row, (code, name) in enumerate(fitted):
                pos[code] = row
                types.append(_pack_type_entry(self.models[name]))
            self._params = {"pos": jnp.asarray(pos), "types": tuple(types)}
        return self._params

    def predict(self, cfg: AcceleratorConfig) -> SynthResult:
        """Surrogate SynthResult for a batched config (mixed PE types OK).

        Validation (``validate``) runs on host; the prediction itself is
        the jitted ``surrogate_ppa`` stage, run through the SAME compiled
        entry point as the DSE evaluator's backend path (one executable
        per chunk shape for both, counted by ``dse.ppa_trace_count``).
        Batches above ``_PREDICT_CHUNK`` lanes stream through in slices
        so the design-matrix temporaries stay bounded.
        """
        self.validate(cfg)
        ppa_stage = _ppa_stage_jit()
        params = self.ppa_params()
        n = np.shape(np.asarray(cfg.pe_type))[0] \
            if np.ndim(cfg.pe_type) else 1
        if n <= _PREDICT_CHUNK:
            power, clock, area, leak = ppa_stage(surrogate_ppa, params, cfg)
        else:
            parts = [ppa_stage(surrogate_ppa, params, AcceleratorConfig(
                *[f[lo:lo + _PREDICT_CHUNK] for f in cfg]))
                for lo in range(0, n, _PREDICT_CHUNK)]
            power, clock, area, leak = (jnp.concatenate(cols)
                                        for cols in zip(*parts))
        return SynthResult(area_mm2=area,
                           crit_path_ns=1.0 / jnp.maximum(clock, 1e-6),
                           clock_ghz=clock, power_mw=power,
                           leakage_mw=leak)


def fit_ppa_models(cfg: AcceleratorConfig,
                   degrees: Sequence[int] = (1, 2, 3), k: int = 5) -> PPAModels:
    """Fit per-PE-type PPA surrogates against the synthesis oracle."""
    truth = synthesize(cfg)
    x = config_features(cfg)
    pt = np.atleast_1d(np.asarray(cfg.pe_type))
    ys = {"power_mw": truth.power_mw, "clock_ghz": truth.clock_ghz,
          "area_mm2": truth.area_mm2}
    models: Dict[str, Dict[str, PolyModel]] = {}
    for code, name in enumerate(PE_TYPE_NAMES):
        sel = pt == code
        if not sel.any():
            continue
        models[name] = {
            t: select_and_fit(x, jnp.atleast_1d(ys[t]), degrees, k,
                              weights=sel)
            for t in TARGETS}
    return PPAModels(models=models)


# ---- fit-quality metrics ---------------------------------------------------

def r2(y_true, y_pred) -> float:
    y_true = np.asarray(y_true, np.float64)
    y_pred = np.asarray(y_pred, np.float64)
    ss_res = np.sum((y_true - y_pred) ** 2)
    ss_tot = np.sum((y_true - y_true.mean()) ** 2)
    return float(1.0 - ss_res / max(ss_tot, 1e-12))


def mape(y_true, y_pred) -> float:
    y_true = np.asarray(y_true, np.float64)
    y_pred = np.asarray(y_pred, np.float64)
    return float(np.mean(np.abs((y_pred - y_true) /
                                np.maximum(np.abs(y_true), 1e-12))))
