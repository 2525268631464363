"""Pallas TPU kernel: fused per-channel fake-quantization (QAT forward).

QAT runs quantize-dequantize on every weight tensor every step.  Unfused,
XLA materializes round/clip/mul intermediates in HBM; this kernel streams
(bk, bn) VMEM tiles and applies the whole chain in-register — one HBM read
+ one HBM write per element, the memory-roofline floor for an elementwise
op.  Scales are a per-channel (N,) vector computed once outside (a single
reduction XLA handles well).

Modes mirror repro.quant.fake_quant: 'affine' (int8/int16) and 'pow2'
(LightPE-1).  Backward is the STE (identity), applied by the caller.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.quant.fake_quant import POW2_LEVELS

DEFAULT_BK = 256
DEFAULT_BN = 256


def _affine_kernel(w_ref, s_ref, o_ref, *, qmax):
    s = s_ref[...]
    q = jnp.clip(jnp.round(w_ref[...] / s), -qmax, qmax)
    o_ref[...] = q * s


def _pow2_kernel(w_ref, emax_ref, o_ref):
    w = w_ref[...]
    e_max = emax_ref[...]
    e_min = e_max - (POW2_LEVELS - 1)
    mag = jnp.maximum(jnp.abs(w), 1e-12)
    e = jnp.clip(jnp.round(jnp.log2(mag)), e_min, e_max)
    o_ref[...] = jnp.sign(w) * jnp.exp2(e)


@functools.partial(jax.jit,
                   static_argnames=("mode", "bits", "bk", "bn", "interpret"))
def fake_quant(w: jnp.ndarray, scale: jnp.ndarray, *, mode: str = "affine",
               bits: int = 8, bk: int = DEFAULT_BK, bn: int = DEFAULT_BN,
               interpret: bool = False) -> jnp.ndarray:
    """Fused quantize-dequantize. w: (K, N); scale: (N,) (scale or e_max)."""
    k, n = w.shape
    assert k % bk == 0 and n % bn == 0, (k, n, bk, bn)
    grid = (k // bk, n // bn)
    w_spec = pl.BlockSpec((bk, bn), lambda i, j: (i, j))
    # (N,) scale as a (1, N) row: see quant_matmul's scale block
    s_spec = pl.BlockSpec((1, bn), lambda i, j: (0, j))
    if mode == "affine":
        kernel = functools.partial(_affine_kernel,
                                   qmax=2.0 ** (bits - 1) - 1.0)
    elif mode == "pow2":
        kernel = _pow2_kernel
    else:
        raise ValueError(f"unknown mode {mode}")
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[w_spec, s_spec],
        out_specs=w_spec,
        out_shape=jax.ShapeDtypeStruct((k, n), w.dtype),
        interpret=interpret,
    )(w, scale.reshape(1, n))


def _round_up(x: int, m: int) -> int:
    return (x + m - 1) // m * m


@functools.partial(jax.jit,
                   static_argnames=("mode", "bits", "bk", "bn", "interpret"))
def fake_quant_any(w: jnp.ndarray, scale: jnp.ndarray, *,
                   mode: str = "affine", bits: int = 8,
                   bk: int = DEFAULT_BK, bn: int = DEFAULT_BN,
                   interpret: bool = False) -> jnp.ndarray:
    """General-shape wrapper (zero padding; scale padded with ones)."""
    k, n = w.shape
    bk_eff = min(bk, _round_up(k, 8))
    bn_eff = min(bn, _round_up(n, 128))
    kp, np_ = _round_up(k, bk_eff), _round_up(n, bn_eff)
    wpad = jnp.pad(w, ((0, kp - k), (0, np_ - n)))
    spad = jnp.pad(scale, (0, np_ - n), constant_values=1.0)
    out = fake_quant(wpad, spad, mode=mode, bits=bits, bk=bk_eff, bn=bn_eff,
                     interpret=interpret)
    return out[:k, :n]
