"""Pallas TPU kernel: matmul with packed low-bit weights (LightPE on TPU).

The paper's LightPE replaces multipliers with shifts inside a custom PE.
A TPU has no custom multiplier — the transferable win is *memory*: weights
live in HBM as packed 4-bit codes (two per byte) and are unpacked +
dequantized in VMEM right before hitting the MXU.  HBM weight traffic
drops 4x vs bf16 / 8x vs fp32, which is the dominant term for decode-type
GEMMs (see EXPERIMENTS.md §Perf).

Layout: codes are packed along the REDUCTION axis K — a (bk/2, bn) uint8
VMEM tile unpacks to a (bk, bn) weight tile with rows interleaved
(2r, 2r+1), contiguous in VMEM.  Per-output-channel scale factors are
applied once on the final K step, so the inner loop is
unpack -> (sign, exp2 | int) -> MXU dot -> accumulate in an f32 scratch.

Grid: (M/bm, N/bn, K/bk), K innermost so the accumulator scratch carries
across the K steps of one (i, j) tile.  Block shapes default to MXU-
aligned (128, 128) tiles with bk=256 codes (128 packed rows).

Modes:
  int4 : two's-complement 4-bit codes, value = q * scale[n]
  pow2 : sign+3-bit-exponent codes (LightPE-1), value = +-2^(idx) *
         2^(e_max[n]-7) — the dequant is an exponent add, no multiply,
         mirroring the shift-only PE.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

DEFAULT_BM = 128
DEFAULT_BN = 128
DEFAULT_BK = 256  # unpacked K elements per step (128 packed rows)


def _unpack_tile(wp, bk):
    """(bk//2, bn) uint8 -> (bk, bn) int32 codes, rows (2r, 2r+1).

    Widened to int32 first: Mosaic has no 8-bit vector shifts."""
    wp = wp.astype(jnp.int32)
    lo = wp & 0xF
    hi = (wp >> 4) & 0xF
    inter = jnp.stack([lo, hi], axis=1)           # (bk//2, 2, bn)
    return inter.reshape(bk, wp.shape[-1])


def _mm_kernel_int4(x_ref, wp_ref, scale_ref, o_ref, acc_ref, *, bk, nk):
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _zero():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    codes = _unpack_tile(wp_ref[...], bk)
    q = jnp.where(codes >= 8, codes - 16, codes).astype(jnp.float32)  # 4b
    acc_ref[...] += jax.lax.dot(
        x_ref[...].astype(jnp.float32), q,
        preferred_element_type=jnp.float32)

    @pl.when(k == nk - 1)
    def _write():
        o_ref[...] = acc_ref[...] * scale_ref[...]


def _mm_kernel_pow2(x_ref, wp_ref, emax_ref, o_ref, acc_ref, *, bk, nk):
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _zero():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    codes = _unpack_tile(wp_ref[...], bk)
    idx = (codes & 0x7).astype(jnp.float32)
    sign = jnp.where((codes >> 3) & 1, -1.0, 1.0)
    w = sign * jnp.exp2(idx)                      # column 2^(e_max-7) deferred
    acc_ref[...] += jax.lax.dot(
        x_ref[...].astype(jnp.float32), w,
        preferred_element_type=jnp.float32)

    @pl.when(k == nk - 1)
    def _write():
        o_ref[...] = acc_ref[...] * jnp.exp2(emax_ref[...] - 7.0)


def _mm_kernel_int8(x_ref, w_ref, scale_ref, o_ref, acc_ref, *, nk):
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _zero():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] += jax.lax.dot(
        x_ref[...].astype(jnp.float32), w_ref[...].astype(jnp.float32),
        preferred_element_type=jnp.float32)

    @pl.when(k == nk - 1)
    def _write():
        o_ref[...] = acc_ref[...] * scale_ref[...]


@functools.partial(jax.jit,
                   static_argnames=("mode", "bm", "bn", "bk", "interpret"))
def quant_matmul(x: jnp.ndarray, w: jnp.ndarray, scale: jnp.ndarray,
                 *, mode: str = "int4", bm: int = DEFAULT_BM,
                 bn: int = DEFAULT_BN, bk: int = DEFAULT_BK,
                 interpret: bool = False) -> jnp.ndarray:
    """y = x @ dequant(w).  Shapes must be multiples of the block sizes
    (use ops.quant_matmul for the padded general-shape wrapper).

    x: (M, K) f32/bf16.
    w: int4/pow2 -> (K//2, N) uint8 packed codes; int8 -> (K, N) int8.
    scale: (N,) — float scale (int4/int8) or e_max (pow2).
    """
    m, kdim = x.shape
    n = w.shape[-1]
    assert m % bm == 0 and kdim % bk == 0 and n % bn == 0, (m, kdim, n)
    nk = kdim // bk
    grid = (m // bm, n // bn, nk)

    x_spec = pl.BlockSpec((bm, bk), lambda i, j, k: (i, k))
    if mode in ("int4", "pow2"):
        w_spec = pl.BlockSpec((bk // 2, bn), lambda i, j, k: (k, j))
    elif mode == "int8":
        w_spec = pl.BlockSpec((bk, bn), lambda i, j, k: (k, j))
    else:
        raise ValueError(f"unknown mode {mode}")
    # the (N,) scale rides as a (1, N) row: a 1-D block must match the
    # whole array's tiling on the TPU, a row block only the lane width
    s_spec = pl.BlockSpec((1, bn), lambda i, j, k: (0, j))
    o_spec = pl.BlockSpec((bm, bn), lambda i, j, k: (i, j))

    kernel = {"int4": functools.partial(_mm_kernel_int4, bk=bk, nk=nk),
              "pow2": functools.partial(_mm_kernel_pow2, bk=bk, nk=nk),
              "int8": functools.partial(_mm_kernel_int8, nk=nk)}[mode]

    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[x_spec, w_spec, s_spec],
        out_specs=o_spec,
        out_shape=jax.ShapeDtypeStruct((m, n), jnp.float32),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32)],
        interpret=interpret,
    )(x, w, scale.reshape(1, n))
