"""Plain reference of the joint (model x accelerator) design space:
index decoding, the accuracy axis, the three joint objectives, budgets
and the exact Pareto front.  Imports nothing of the program."""

from __future__ import annotations

import math

import numpy as np

from bench.reference import costmodel
from bench.reference.families import build

# FP32 top-1 seeds of the paper's CNNs (published results), and the
# mean accuracy change of each PE type against FP32 in percentage points
# (the QADAM paper's Figs. 5-6), as the QADAM reproduction states them.
BASE_ACC = {
    "resnet20-cifar10": 0.916, "resnet32-cifar10": 0.925,
    "resnet44-cifar10": 0.927, "resnet56-cifar10": 0.930,
    "resnet20-cifar100": 0.683, "resnet56-cifar100": 0.716,
    "vgg16-cifar10": 0.938, "vgg16-cifar100": 0.724,
    "vgg16-imagenet": 0.715, "resnet34-imagenet": 0.733,
    "resnet50-imagenet": 0.761,
}
ACC_DELTA_PP = (0.0, -0.1, -0.9, -0.4, -0.5)
# Quantization sensitivity of accuracy classes: default, attention,
# FFN, experts.
CLASS_SENS = (1.0, 1.3, 0.9, 1.15)
REF_MACS = 4.1e7


class Model:
    """One model of a configuration: its layer table, MACs per inference
    and accuracy under each PE type."""

    def __init__(self, spec: dict):
        t = build(spec["family"], spec["args"])
        self.name = t["name"]
        self.layers = {k: np.asarray(v, np.float64)
                       for k, v in t["layers"].items()}
        ly = self.layers
        e = np.floor((ly["H"] - ly["R"]) / ly["stride"]) + 1
        f = np.floor((ly["W"] - ly["S"]) / ly["stride"]) + 1
        macs = ly["K"] * ly["C"] * ly["R"] * ly["S"] * e * f * ly["count"]
        self.macs = float(macs.sum())          # per inference
        base = BASE_ACC.get(self.name)
        if base is None:
            base = float(np.clip(0.72 + 0.045 * math.log10(self.macs / 1e6),
                                 0.30, 0.99))
        mult = 1.0
        if spec.get("acc_classes"):
            mix = np.zeros(len(CLASS_SENS))
            np.add.at(mix, np.asarray(t["acc_class"]), macs)
            mult = float(np.dot(mix / mix.sum(), CLASS_SENS))
        scale = float(np.clip((REF_MACS / max(self.macs, 1.0)) ** 0.2,
                              0.25, 1.0))
        self.accuracy = np.array([base + d * scale * mult / 100.0
                                  for d in ACC_DELTA_PP])


def decode(space: dict, accel_idx: np.ndarray) -> dict:
    """Mixed-radix decode of accelerator indices (fields in
    ``costmodel.CONFIG_FIELDS`` order, the last varying fastest; a space
    without a mapping axis has the single code 0)."""
    axes = [np.asarray(space.get(f, (0.0,)), np.float64)
            for f in costmodel.CONFIG_FIELDS]
    idx = np.asarray(accel_idx, np.int64)
    out, rest = {}, idx.copy()
    for f, ax in reversed(list(zip(costmodel.CONFIG_FIELDS, axes))):
        out[f] = ax[rest % len(ax)]
        rest //= len(ax)
    return out


def space_size(space: dict) -> int:
    return int(np.prod([len(space.get(f, (0.0,)))
                        for f in costmodel.CONFIG_FIELDS]))


def subsample(n: int, k: int, seed: int) -> np.ndarray:
    """The walk's point set: a uniform draw of k of n indices without
    replacement from numpy's default generator at ``seed``, sorted."""
    if k >= n:
        return np.arange(n, dtype=np.int64)
    return np.sort(np.random.default_rng(seed).choice(n, size=k,
                                                      replace=False))


def evaluate(models, space: dict, joint_idx: np.ndarray, dt=np.float64,
             finish_dtype=np.float64, block: int = 32768) -> dict:
    """Metric columns, accuracy and the three objectives of joint points
    (model digit slowest), in ``joint_idx`` order, float64 on return.
    ``objectives`` is (N, 3), all higher-is-better: accuracy, MACs/s per
    mm^2, minus pJ per MAC."""
    idx = np.asarray(joint_idx, np.int64)
    a = space_size(space)
    mids = idx // a
    cols = {}
    for m in np.unique(mids):
        rows = np.flatnonzero(mids == m)
        for lo in range(0, len(rows), block):
            r = rows[lo:lo + block]
            cfg = decode(space, idx[r] % a)
            res = costmodel.evaluate(cfg, models[m].layers, dt, finish_dtype)
            res["accuracy"] = models[m].accuracy[
                cfg["pe_type"].astype(np.int64)]
            for k, v in res.items():
                cols.setdefault(k, np.empty(len(idx)))[r] = \
                    np.asarray(v, np.float64)
    cols["pe_type"] = decode(space, idx % a)["pe_type"].astype(np.int64)
    cols["model"] = mids
    lat = np.maximum(cols["latency_s"], 1e-12)
    cols["objectives"] = np.stack([
        cols["accuracy"],
        cols["macs"] / lat / np.maximum(cols["area_mm2"], 1e-9),
        -(cols["energy_j"] / np.maximum(cols["macs"], 1.0) * 1e12)], axis=1)
    return cols


# -- budgets ------------------------------------------------------------------

# budget field -> (column, +1 for an upper bound / -1 for a lower bound)
BUDGET_COLUMNS = {"area_mm2": ("area_mm2", 1), "power_mw": ("power_mw", 1),
                  "latency_s": ("latency_s", 1), "energy_j": ("energy_j", 1),
                  "min_utilization": ("utilization", -1),
                  "min_accuracy": ("accuracy", -1)}


def violation(cols: dict, budget: dict | None) -> np.ndarray:
    """Relative amount by which each point breaks the budget (<= 0:
    feasible)."""
    n = len(cols["objectives"])
    worst = np.full(n, -np.inf)
    for field, bound in (budget or {}).items():
        column, sign = BUDGET_COLUMNS[field]
        worst = np.maximum(worst, sign * (cols[column] - bound) / abs(bound))
    return worst


# -- Pareto fronts ------------------------------------------------------------

def pareto_front(obj: np.ndarray, block: int = 2048) -> np.ndarray:
    """Indices of the non-dominated rows of ``obj`` (all objectives
    higher-is-better; a row is dominated when another is >= in every
    objective and > in one, so equal rows all stay), ascending."""
    obj = np.asarray(obj, np.float64)
    order = np.lexsort(tuple(-obj[:, k] for k in range(obj.shape[1] - 1,
                                                       -1, -1)))
    front = np.empty((0,), np.int64)
    for lo in range(0, len(order), block):
        cand = order[lo:lo + block]
        c = obj[cand]
        if len(front):
            f = obj[front]
            dom = ((f[None, :, :] >= c[:, None, :]).all(-1)
                   & (f[None, :, :] > c[:, None, :]).any(-1)).any(1)
            cand, c = cand[~dom], c[~dom]
        # a lexicographically later row never dominates an earlier one
        dom = ((c[None, :, :] >= c[:, None, :]).all(-1)
               & (c[None, :, :] > c[:, None, :]).any(-1)).any(1)
        front = np.concatenate([front, cand[~dom]])
    return np.sort(front)


def cover_gap(ref_front: np.ndarray, got: np.ndarray) -> float:
    """How far ``got`` falls short of covering the reference front: the
    largest, over reference points, of the smallest relative shortfall
    of any ``got`` point in its worst objective (0: every reference
    point is matched or dominated)."""
    if not len(ref_front):
        return 0.0
    if not len(got):
        return math.inf
    short = (ref_front[:, None, :] - got[None, :, :]) \
        / np.abs(ref_front)[:, None, :]
    return float(max(0.0, short.max(-1).min(1).max()))


def excess_gap(ref_front: np.ndarray, got_ref: np.ndarray) -> float:
    """How strongly the reference dominates a returned front point: the
    largest relative margin, in the least-improved objective, by which a
    reference front point beats a returned point's reference objectives
    (0: no returned point is dominated)."""
    if not len(ref_front) or not len(got_ref):
        return 0.0
    margin = (ref_front[None, :, :] - got_ref[:, None, :]) \
        / np.abs(got_ref)[:, None, :]
    return float(max(0.0, margin.min(-1).max()))


def rel_err(got: np.ndarray, want: np.ndarray) -> float:
    """Largest relative difference, elementwise."""
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    if got.shape != want.shape:
        return math.inf
    if not got.size:
        return 0.0
    d = np.abs(got - want) / np.maximum(np.abs(want), 1e-300)
    d = np.where(np.isfinite(got), d, math.inf)
    return float(d.max())


def per_model_best(cols: dict) -> dict:
    """(model, PE type) -> (best MACs/s/mm^2, lowest pJ/MAC)."""
    key = cols["model"] * len(costmodel.PE_TYPES) + cols["pe_type"]
    out = {}
    for k in np.unique(key):
        sel = key == k
        m, t = divmod(int(k), len(costmodel.PE_TYPES))
        o = cols["objectives"][sel]
        out[(m, t)] = (float(o[:, 1].max()), float(-o[:, 2].max()))
    return out
