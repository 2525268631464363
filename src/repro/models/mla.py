"""Multi-head latent attention (DeepSeek-V2/V3).

Per position the layer caches one latent, shared by every head: the
``kv_lora_rank``-wide ``c_kv`` (RMS-normed) and the
``qk_rope_head_dim``-wide ``k_rope`` (rotated).  With ``nope`` =
``qk_nope_head_dim`` and ``rope`` = ``qk_rope_head_dim``:

  q            = wq_b(norm(wq_a x)), per head [q_nope | q_rope], rope on q_rope
  c_kv, k_rope = wkv_a x;  c_kv <- norm(c_kv);  k_rope <- rope(k_rope)
  [k_nope | v] = kv_b c_kv, per head (W_UK | W_UV)
  score        = (q_nope . k_nope + q_rope . k_rope) / sqrt(nope + rope)

Two forms of the same product:

* naive (training, forward, prefill): decompress the latent through
  ``kv_b`` into per-head keys and values, then attend per head;
* absorbed (``decode_step``): fold ``W_UK`` into the query and ``W_UV``
  after the value sum, so the heads read the cached latent directly:
  score = (q_nope W_UK^T) . c_kv + q_rope . k_rope and
  out_h = (p . c_kv) W_UV.

Both take ``kv_b`` and the latent through ``layers.qoperands``, so they
multiply the same operands under any PE type's numerics.  Rope uses the
zoo's half-split convention (a fixed permutation of the published
interleaved one's weight columns); YaRN scaling changes no shape and is
left out.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from repro.models import layers as L


def mla_init(key, cfg, dtype=jnp.float32) -> L.Params:
    d, hq = cfg.d_model, cfg.n_heads
    ql, r = cfg.q_lora_rank, cfg.kv_lora_rank
    nope, rope, dv = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.v_head_dim
    ks = jax.random.split(key, 5)
    return {"wq_a": L.dense_init(ks[0], d, ql, dtype),
            "q_norm": jnp.ones((ql,), dtype),
            "wq_b": L.dense_init(ks[1], ql, hq * (nope + rope), dtype),
            "wkv_a": L.dense_init(ks[2], d, r + rope, dtype),
            "kv_norm": jnp.ones((r,), dtype),
            "kv_b": L.dense_init(ks[3], r, hq * (nope + dv), dtype),
            "wo": L.dense_init(ks[4], hq * dv, d, dtype)}


def make_mla_cache(batch: int, max_len: int, cfg,
                   dtype=jnp.bfloat16) -> L.Params:
    """The latent cache: ``c_kv`` and ``k_rope`` per position, nothing
    per head."""
    return {"c_kv": jnp.zeros((batch, max_len, cfg.kv_lora_rank), dtype),
            "k_rope": jnp.zeros((batch, max_len, cfg.qk_rope_head_dim),
                                dtype),
            "index": jnp.zeros((), jnp.int32)}


def _project(p, x, cfg, qcfg, positions):
    """Per-head queries and the new positions' latent: q_nope (B, S, H,
    nope), q_rope (B, S, H, rope), c_kv (B, S, r), k_rope (B, S, rope)."""
    b, s, _ = x.shape
    nope, r = cfg.qk_nope_head_dim, cfg.kv_lora_rank
    q = L.qdense(L.rmsnorm(L.qdense(x, p["wq_a"], qcfg), p["q_norm"]),
                 p["wq_b"], qcfg).reshape(b, s, cfg.n_heads, -1)
    q_rope = L.apply_rope(q[..., nope:], positions, cfg.rope_theta)
    kv = L.qdense(x, p["wkv_a"], qcfg)
    c_kv = L.rmsnorm(kv[..., :r], p["kv_norm"])
    k_rope = L.apply_rope(kv[..., None, r:], positions, cfg.rope_theta)
    return q[..., :nope], q_rope, c_kv, k_rope[:, :, 0]


def mla_attention(p, x, cfg, qcfg, positions, cache=None,
                  absorb: bool = False):
    """x: (B, S, D), positions (B, S) -> (out (B, S, D), new cache).

    Without a cache the heads attend over x's own positions.  With one,
    x's latents are written at ``cache["index"]`` and the heads attend
    over the whole cache (positions past the index are masked): in the
    naive form for a prefill, in the absorbed form with ``absorb``."""
    b, s, _ = x.shape
    hq, nope = cfg.n_heads, cfg.qk_nope_head_dim
    q_nope, q_rope, c_kv, k_rope = _project(p, x, cfg, qcfg, positions)
    new_cache, kv_pos = None, positions
    if cache is not None:
        idx = cache["index"]
        c_kv = jax.lax.dynamic_update_slice_in_dim(
            cache["c_kv"], c_kv.astype(cache["c_kv"].dtype), idx, axis=1)
        k_rope = jax.lax.dynamic_update_slice_in_dim(
            cache["k_rope"], k_rope.astype(cache["k_rope"].dtype), idx,
            axis=1)
        new_cache = {"c_kv": c_kv, "k_rope": k_rope, "index": idx + s}
        kv_pos = jnp.broadcast_to(
            jnp.arange(c_kv.shape[1], dtype=positions.dtype)[None],
            (b, c_kv.shape[1]))
    lat = c_kv.astype(q_nope.dtype)
    logits = jnp.einsum("bshp,btp->bhst", q_rope, k_rope.astype(q_rope.dtype))
    if absorb:
        c, w = L.qoperands(lat, p["kv_b"], qcfg)
        w = w.reshape(w.shape[0], hq, -1)
        q_lat = jnp.einsum("bshn,rhn->bshr", q_nope, w[..., :nope])
        logits = logits + jnp.einsum("bshr,btr->bhst", q_lat, c)
    else:
        kv = L.qdense(lat, p["kv_b"], qcfg).reshape(b, lat.shape[1], hq, -1)
        logits = logits + jnp.einsum("bshn,bthn->bhst", q_nope,
                                     kv[..., :nope])
    logits = logits.astype(jnp.float32) / float(
        np.sqrt(nope + cfg.qk_rope_head_dim))
    ok = kv_pos[:, None, None, :] <= positions[:, None, :, None]
    probs = jax.nn.softmax(jnp.where(ok, logits, -1e30), axis=-1)
    if absorb:
        o_lat = jnp.einsum("bhst,btr->bshr", probs.astype(c.dtype), c)
        out = jnp.einsum("bshr,rhv->bshv", o_lat, w[..., nope:])
    else:
        out = jnp.einsum("bhst,bthv->bshv", probs.astype(kv.dtype),
                         kv[..., nope:])
    out = out.reshape(b, s, -1).astype(x.dtype)
    return L.qdense(out, p["wo"], qcfg), new_cache
