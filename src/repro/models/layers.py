"""Shared model layers (pure JAX) with quantization hooks.

Every dense projection goes through ``qdense`` so any architecture can be
instantiated under any of the paper's PE-type numerics (QuantConfig).
Params are plain pytrees (nested dicts of jnp arrays); init functions are
deterministic given a PRNG key; forward functions are pure.

Attention is one unified implementation covering the assigned zoo:
GQA (kv_heads <= n_heads), optional qk-norm (qwen3), optional sliding
window (gemma2/3), optional logit soft-capping (gemma2), causal /
bidirectional / cross (whisper), KV-cache decode, and standard or
multi-axis (M-RoPE, qwen2-vl) rotary embeddings.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Dict

import jax
import jax.numpy as jnp

from repro.quant.fake_quant import fake_quant_act, fake_quant_weight
from repro.quant.qconfig import QuantConfig

Params = Dict[str, Any]


# ---------------------------------------------------------------------------
# activation sharding context
#
# Sharding constraints applied OUTSIDE a jax.checkpoint body are NOT replayed
# when the forward is rematerialized — XLA is then free to replicate the
# recomputed activations across the data axis (observed in the dry-run HLO).
# Layer bodies therefore re-assert the batch sharding INSIDE the remat scope
# via shard_batch(); the spec comes from this context, set by the launcher.
# ---------------------------------------------------------------------------

import contextlib
import threading

_act_ctx = threading.local()


@contextlib.contextmanager
def activation_sharding(dp_axes, dp_total: int, mesh=None,
                        tp_axis: str = "model"):
    """Enable batch-dim sharding constraints inside layer bodies.

    dp_axes: mesh axis name(s) carrying the batch; dp_total: their product
    (used to skip non-divisible shapes, e.g. batch=1 decode). mesh/tp_axis
    are picked up by shard_map-based layers (EP MoE)."""
    old = getattr(_act_ctx, "cfg", None)
    old_mesh = getattr(_act_ctx, "mesh", None)
    _act_ctx.cfg = (tuple(dp_axes), int(dp_total)) if dp_axes else None
    _act_ctx.mesh = (mesh, tp_axis)
    try:
        yield
    finally:
        _act_ctx.cfg = old
        _act_ctx.mesh = old_mesh


def current_mesh():
    """(mesh, tp_axis) from the launcher context, or (None, None)."""
    m = getattr(_act_ctx, "mesh", None)
    return m if m is not None else (None, None)


def current_dp():
    cfg = getattr(_act_ctx, "cfg", None)
    return cfg[0] if cfg else ()


def shard_batch(x: jnp.ndarray) -> jnp.ndarray:
    """Constrain dim 0 (batch) onto the DP axes, if a context is active."""
    cfg = getattr(_act_ctx, "cfg", None)
    if cfg is None or x.ndim < 2 or x.shape[0] % cfg[1] != 0:
        return x
    from jax.sharding import PartitionSpec as P
    spec = P(cfg[0], *(None,) * (x.ndim - 1))
    return jax.lax.with_sharding_constraint(x, spec)


@contextlib.contextmanager
def compute_dtype(dtype):
    """Mixed-precision context: qdense casts weights + acts to `dtype`
    (f32 master weights stay in the optimizer; the cast sits INSIDE the
    step so FSDP all-gathers and activations move half the bytes).
    Set by the launcher / perf variants; None = full precision."""
    old = getattr(_act_ctx, "dtype", None)
    _act_ctx.dtype = jnp.dtype(dtype) if dtype is not None else None
    try:
        yield
    finally:
        _act_ctx.dtype = old


def _ctx_dtype():
    return getattr(_act_ctx, "dtype", None)


# ---------------------------------------------------------------------------
# initializers
# ---------------------------------------------------------------------------

def dense_init(key, d_in: int, d_out: int, dtype=jnp.float32):
    scale = 1.0 / jnp.sqrt(jnp.asarray(d_in, jnp.float32))
    return (jax.random.normal(key, (d_in, d_out), jnp.float32)
            * scale).astype(dtype)


def embed_init(key, vocab: int, d: int, dtype=jnp.float32):
    return (jax.random.normal(key, (vocab, d), jnp.float32) * 0.02).astype(dtype)


# ---------------------------------------------------------------------------
# quant-hooked dense
# ---------------------------------------------------------------------------

def qdense(x: jnp.ndarray, w: jnp.ndarray, qcfg: QuantConfig,
           cast=None) -> jnp.ndarray:
    """x @ w under the QuantConfig numerics (QAT fake-quant, STE grads).

    w may be a packed-code dict {"codes", "scale", "mode"} (serving path):
    dequantized inline — the graph then reads u8/s8 codes from HBM and
    dequantizes in VMEM, mirroring kernels/quant_matmul.
    """
    x, w = qoperands(x, w, qcfg, cast)
    return x @ w


def qoperands(x: jnp.ndarray, w, qcfg: QuantConfig, cast=None):
    """The two operands ``qdense`` multiplies, after dequantization,
    fake-quant and the compute-dtype cast; for callers that reorder the
    product (absorbed latent attention)."""
    if isinstance(w, dict):
        from repro.quant import pack as QP
        mode = next(k.split("__", 1)[1] for k in w if k.startswith("codes__"))
        dq = {"int4": QP.dequantize_int4, "pow2": QP.dequantize_pow2,
              "int8": QP.dequantize_int8}[mode]
        codes = w[f"codes__{mode}"]
        if codes.ndim == 3:  # stacked (L, K', N): per-layer dequant in scan
            w = jax.vmap(dq)(codes, w["scale"])
        else:
            w = dq(codes, w["scale"])
    if not qcfg.is_identity:
        w = fake_quant_weight(w, qcfg)
        x = fake_quant_act(x, qcfg)
    ct = cast if cast is not None else _ctx_dtype()
    if ct is not None:
        x = x.astype(ct)
        w = w.astype(ct)
    return x, w


# ---------------------------------------------------------------------------
# norms
# ---------------------------------------------------------------------------

def rmsnorm(x: jnp.ndarray, scale: jnp.ndarray, eps: float = 1e-6,
            zero_centered: bool = False) -> jnp.ndarray:
    dt = x.dtype
    x = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    x = x * jax.lax.rsqrt(var + eps)
    g = 1.0 + scale if zero_centered else scale  # gemma uses (1 + g)
    return (x * g).astype(dt)


def layernorm(x: jnp.ndarray, scale: jnp.ndarray, bias: jnp.ndarray,
              eps: float = 1e-5) -> jnp.ndarray:
    dt = x.dtype
    x = x.astype(jnp.float32)
    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.var(x, axis=-1, keepdims=True)
    return ((x - mu) * jax.lax.rsqrt(var + eps) * scale + bias).astype(dt)


# ---------------------------------------------------------------------------
# rotary embeddings
# ---------------------------------------------------------------------------

def rope_freqs(head_dim: int, theta: float = 10000.0) -> jnp.ndarray:
    return 1.0 / (theta ** (jnp.arange(0, head_dim, 2, dtype=jnp.float32)
                            / head_dim))


def apply_rope(x: jnp.ndarray, positions: jnp.ndarray,
               theta: float = 10000.0) -> jnp.ndarray:
    """x: (B, S, H, Dh); positions: (B, S) int32."""
    freqs = rope_freqs(x.shape[-1], theta)
    ang = positions[..., None].astype(jnp.float32) * freqs  # (B, S, Dh/2)
    cos = jnp.cos(ang)[:, :, None, :]
    sin = jnp.sin(ang)[:, :, None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


def apply_mrope(x: jnp.ndarray, positions: jnp.ndarray,
                sections=(16, 24, 24), theta: float = 10000.0) -> jnp.ndarray:
    """Multi-axis RoPE (qwen2-vl): positions (B, S, 3) = (t, h, w) ids.

    The Dh/2 frequency slots are split into `sections` groups, each rotated
    by its own position stream.
    """
    half = x.shape[-1] // 2
    assert sum(sections) == half, (sections, half)
    freqs = rope_freqs(x.shape[-1], theta)         # (half,)
    pos = positions.astype(jnp.float32)            # (B, S, 3)
    parts, off = [], 0
    for s_idx, width in enumerate(sections):
        parts.append(pos[..., s_idx:s_idx + 1]
                     * freqs[off:off + width][None, None, :])
        off += width
    ang = jnp.concatenate(parts, axis=-1)          # (B, S, half)
    cos = jnp.cos(ang)[:, :, None, :]
    sin = jnp.sin(ang)[:, :, None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


# ---------------------------------------------------------------------------
# unified attention
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class AttnSpec:
    n_heads: int
    kv_heads: int
    head_dim: int
    causal: bool = True
    window: int = 0             # 0 = global; >0 = sliding window width
    softcap: float = 0.0        # 0 = off (gemma2 uses 50.0)
    qk_norm: bool = False       # qwen3 per-head RMSNorm on q, k
    rope_theta: float = 10000.0
    mrope_sections: tuple = ()  # non-empty -> M-RoPE
    query_scale: float = 0.0    # 0 -> 1/sqrt(head_dim)


def attn_init(key, d_model: int, spec: AttnSpec, dtype=jnp.float32) -> Params:
    ks = jax.random.split(key, 4)
    p = {
        "wq": dense_init(ks[0], d_model, spec.n_heads * spec.head_dim, dtype),
        "wk": dense_init(ks[1], d_model, spec.kv_heads * spec.head_dim, dtype),
        "wv": dense_init(ks[2], d_model, spec.kv_heads * spec.head_dim, dtype),
        "wo": dense_init(ks[3], spec.n_heads * spec.head_dim, d_model, dtype),
    }
    if spec.qk_norm:
        p["q_norm"] = jnp.ones((spec.head_dim,), dtype)
        p["k_norm"] = jnp.ones((spec.head_dim,), dtype)
    return p


def _attend(q, k, v, spec: AttnSpec, q_positions, kv_positions, mask_mode):
    """Core attention. q: (B, Sq, Hq, Dh); k, v: (B, Skv, Hkv, Dh).

    mask_mode: 'causal' | 'full' (bidirectional / cross).
    Positions are absolute token indices, used for causal + window masks.
    """
    b, sq, hq, dh = q.shape
    skv, hkv = k.shape[1], k.shape[2]
    groups = hq // hkv
    scale = spec.query_scale or (1.0 / jnp.sqrt(jnp.asarray(dh, jnp.float32)))

    qg = q.reshape(b, sq, hkv, groups, dh)
    logits = jnp.einsum("bqhgd,bkhd->bhgqk", qg.astype(jnp.float32),
                        k.astype(jnp.float32)) * scale
    if spec.softcap > 0.0:
        logits = spec.softcap * jnp.tanh(logits / spec.softcap)

    qp = q_positions[:, None, None, :, None]       # (B, 1, 1, Sq, 1)
    kp = kv_positions[:, None, None, None, :]      # (B, 1, 1, 1, Skv)
    ok = jnp.ones((b, 1, 1, sq, skv), bool)
    if mask_mode == "causal":
        ok = ok & (kp <= qp)
    if spec.window > 0:
        ok = ok & (kp > qp - spec.window)
    logits = jnp.where(ok, logits, -1e30)

    probs = jax.nn.softmax(logits, axis=-1)
    out = jnp.einsum("bhgqk,bkhd->bqhgd", probs, v.astype(jnp.float32))
    return out.reshape(b, sq, hq, dh).astype(q.dtype)


def attention(params: Params, x: jnp.ndarray, spec: AttnSpec,
              qcfg: QuantConfig, positions: jnp.ndarray,
              cache: Params | None = None, cross_kv: jnp.ndarray | None = None,
              mask_mode: str = "causal"):
    """Unified attention layer.

    x: (B, S, D). positions: (B, S) or (B, S, 3) for M-RoPE.
    cache: None for train/prefill-without-cache; else dict with
      {"k": (B, Smax, Hkv, Dh), "v": ..., "index": scalar} — decode appends
      x's projections at `index` and attends over the first index+S entries
      (implemented with full-length masking, fixed shapes).
    cross_kv: (B, Senc, D) encoder states for cross attention (whisper).
    Returns (out, new_cache).
    """
    b, s, _ = x.shape
    hq, hkv, dh = spec.n_heads, spec.kv_heads, spec.head_dim

    q = qdense(x, params["wq"], qcfg).reshape(b, s, hq, dh)
    kv_src = cross_kv if cross_kv is not None else x
    k = qdense(kv_src, params["wk"], qcfg).reshape(b, kv_src.shape[1], hkv, dh)
    v = qdense(kv_src, params["wv"], qcfg).reshape(b, kv_src.shape[1], hkv, dh)

    if spec.qk_norm:
        q = rmsnorm(q, params["q_norm"])
        k = rmsnorm(k, params["k_norm"])

    pos2d = positions if positions.ndim == 2 else positions[..., 0]
    if cross_kv is None:
        if spec.mrope_sections:
            q = apply_mrope(q, positions, spec.mrope_sections, spec.rope_theta)
            k = apply_mrope(k, positions, spec.mrope_sections, spec.rope_theta)
        else:
            q = apply_rope(q, pos2d, spec.rope_theta)
            k = apply_rope(k, pos2d, spec.rope_theta)

    new_cache = cache
    if cache is not None and cross_kv is None:
        idx = cache["index"]
        ck = jax.lax.dynamic_update_slice_in_dim(
            cache["k"], k.astype(cache["k"].dtype), idx, axis=1)
        cv = jax.lax.dynamic_update_slice_in_dim(
            cache["v"], v.astype(cache["v"].dtype), idx, axis=1)
        new_cache = {"k": ck, "v": cv, "index": idx + s}
        k, v = ck, cv
        kv_positions = jnp.broadcast_to(
            jnp.arange(ck.shape[1], dtype=pos2d.dtype)[None, :],
            (b, ck.shape[1]))
        # entries beyond the write index are masked out by the causal check
    elif cross_kv is not None:
        kv_positions = jnp.broadcast_to(
            jnp.arange(k.shape[1], dtype=pos2d.dtype)[None, :],
            (b, k.shape[1]))
    else:
        kv_positions = pos2d

    out = _attend(q, k, v, spec, pos2d, kv_positions,
                  "full" if cross_kv is not None else mask_mode)
    out = qdense(out.reshape(b, s, hq * dh), params["wo"], qcfg)
    return out, new_cache


def make_cache(batch: int, max_len: int, spec: AttnSpec,
               dtype=jnp.bfloat16) -> Params:
    return {"k": jnp.zeros((batch, max_len, spec.kv_heads, spec.head_dim),
                           dtype),
            "v": jnp.zeros((batch, max_len, spec.kv_heads, spec.head_dim),
                           dtype),
            "index": jnp.zeros((), jnp.int32)}


# ---------------------------------------------------------------------------
# MLP (SwiGLU / GeLU)
# ---------------------------------------------------------------------------

def mlp_init(key, d_model: int, d_ff: int, gated: bool = True,
             dtype=jnp.float32) -> Params:
    ks = jax.random.split(key, 3)
    p = {"w_up": dense_init(ks[0], d_model, d_ff, dtype),
         "w_down": dense_init(ks[1], d_ff, d_model, dtype)}
    if gated:
        p["w_gate"] = dense_init(ks[2], d_model, d_ff, dtype)
    return p


def mlp(params: Params, x: jnp.ndarray, qcfg: QuantConfig,
        act: str = "silu") -> jnp.ndarray:
    up = qdense(x, params["w_up"], qcfg)
    if "w_gate" in params:
        gate = qdense(x, params["w_gate"], qcfg)
        h = (jax.nn.gelu(gate, approximate=True) if act == "gelu"
             else jax.nn.silu(gate)) * up
    else:
        h = jax.nn.gelu(up, approximate=True) if act == "gelu" \
            else jax.nn.silu(up)
    return qdense(h, params["w_down"], qcfg)


# ---------------------------------------------------------------------------
# losses
# ---------------------------------------------------------------------------

def softmax_xent(logits: jnp.ndarray, labels: jnp.ndarray,
                 softcap: float = 0.0) -> jnp.ndarray:
    """Mean next-token cross entropy. logits: (..., V); labels: (...) int32."""
    if softcap > 0.0:
        logits = softcap * jnp.tanh(logits / softcap)
    logits = logits.astype(jnp.float32)
    logz = jax.nn.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, labels[..., None], axis=-1)[..., 0]
    return jnp.mean(logz - gold)
