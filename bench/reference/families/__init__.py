"""Layer tables of the model families the configurations name.

One module per family, found by the family's name: ``build(**args)``
returns the model's name, its layer rows (``costmodel.LAYER_FIELDS``)
and each row's accuracy class."""

from __future__ import annotations

import importlib


def build(family: str, args: dict) -> dict:
    mod = importlib.import_module(f"bench.reference.families.{family}")
    return mod.build(**args)


def conv(h, w, c, k, r, stride=1, batch=1, count=1, valid=False):
    """A conv layer; 'same' padding is carried in the input size."""
    pad = 0 if valid else r - 1
    return dict(H=h + pad, W=w + pad, C=c, K=k, R=r, S=r, stride=stride,
                batch=batch, count=count, kind=0.0, stream_words=0.0,
                active_frac=1.0, acc_class=0)


def gemm(m, kd, n, batch=1, count=1, kind=1.0, stream_words=0.0,
         active_frac=1.0, acc_class=0):
    """(m x kd) @ (kd x n) as a 1x1 conv over a 1 x m input."""
    return dict(H=1, W=m, C=kd, K=n, R=1, S=1, stride=1, batch=batch,
                count=count, kind=float(kind),
                stream_words=float(stream_words),
                active_frac=float(active_frac), acc_class=acc_class)


def table(name: str, rows: list) -> dict:
    from bench.reference.costmodel import LAYER_FIELDS
    return dict(name=name,
                layers={f: [float(r[f]) for r in rows] for f in LAYER_FIELDS},
                acc_class=[int(r["acc_class"]) for r in rows])
