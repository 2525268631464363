"""Decoder-only transformer LM family (qwen3 / gemma2 / gemma3 / smollm /
qwen2-vl backbone / MoE variants / DeepSeek-V3's latent attention, in
``repro.models.mla``).

Layers are stacked along a leading L axis and executed with
``jax.lax.scan`` so the compiled HLO contains one layer body regardless of
depth (essential for the 512-device dry-run compiles).  Heterogeneous
layer patterns (gemma2 alternating local/global, gemma3 5:1) are expressed
as a per-layer ``is_global`` flag carried through the scan: local and
global layers share one attention code path differing only in the mask
width, so the scan body stays homogeneous.

All projections run under the arch's QuantConfig (the paper's PE-type
numerics).  Forward entry points:

  loss_fn(params, batch, cfg)          — training loss (next-token CE)
  prefill(params, tokens, cfg, cache)  — fill KV caches, return logits
  decode_step(params, token, cfg, cache) — one-token serve step
"""

from __future__ import annotations

from typing import Any, Dict

import jax
import jax.numpy as jnp
import numpy as np

from repro.models import layers as L
from repro.models import mla as MLA
from repro.models import moe as MOE
from repro.quant.qconfig import preset

Params = Dict[str, Any]


# ---------------------------------------------------------------------------
# layer pattern
# ---------------------------------------------------------------------------

def layer_is_global(cfg) -> np.ndarray:
    """(L,) bool: which layers use global attention."""
    n = cfg.n_layers
    if cfg.layer_pattern == "all_global" or cfg.window <= 0:
        return np.ones(n, bool)
    if cfg.layer_pattern == "alt_local_global":      # gemma2: L,G,L,G,...
        return np.arange(n) % 2 == 1
    if cfg.layer_pattern == "gemma3":                # 5 local : 1 global
        return np.arange(n) % 6 == 5
    raise ValueError(cfg.layer_pattern)


def attn_spec(cfg, is_global: bool = True) -> L.AttnSpec:
    return L.AttnSpec(
        n_heads=cfg.n_heads, kv_heads=cfg.kv_heads, head_dim=cfg.head_dim,
        causal=True, window=0 if is_global else cfg.window,
        softcap=cfg.attn_softcap, qk_norm=cfg.qk_norm,
        rope_theta=cfg.rope_theta, mrope_sections=tuple(cfg.mrope_sections),
        query_scale=cfg.query_scale)


def _attn_init(key, cfg, dtype) -> Params:
    """Attention params: latent attention when ``cfg.kv_lora_rank`` is
    set, else the unified GQA layer."""
    if cfg.kv_lora_rank:
        return MLA.mla_init(key, cfg, dtype)
    return L.attn_init(key, cfg.d_model, attn_spec(cfg), dtype)


def dataclasses_replace_kv(spec: L.AttnSpec, kv: int) -> L.AttnSpec:
    import dataclasses as _dc
    return _dc.replace(spec, kv_heads=kv)


# ---------------------------------------------------------------------------
# init
# ---------------------------------------------------------------------------

def _stacked_layer_init(key, cfg, n: int, moe: bool, dtype) -> Params:
    """Init n identical layers with params stacked on a leading axis."""
    def one(k):
        ka, km, k1, k2 = jax.random.split(k, 4)
        p = {"attn": _attn_init(ka, cfg, dtype),
             "ln1": jnp.zeros((cfg.d_model,), dtype) if cfg.zero_centered_norm
             else jnp.ones((cfg.d_model,), dtype),
             "ln2": jnp.zeros((cfg.d_model,), dtype) if cfg.zero_centered_norm
             else jnp.ones((cfg.d_model,), dtype)}
        if moe:
            p["moe"] = MOE.moe_init(km, cfg, dtype)
        else:
            p["mlp"] = L.mlp_init(km, cfg.d_model, cfg.d_ff, True, dtype)
        return p

    keys = jax.random.split(key, n)
    return jax.vmap(one)(keys)


def init_params(cfg, key) -> Params:
    dtype = jnp.float32
    k_embed, k_layers, k_dense, k_head = jax.random.split(key, 4)
    vp = cfg.padded_vocab
    is_moe = cfg.moe_experts > 0
    n_scan = cfg.n_layers - cfg.first_dense
    params: Params = {
        "embed": L.embed_init(k_embed, vp, cfg.d_model, dtype),
        "layers": _stacked_layer_init(k_layers, cfg, n_scan, is_moe, dtype),
        "final_norm": (jnp.zeros if cfg.zero_centered_norm else jnp.ones)(
            (cfg.d_model,), dtype),
    }
    if cfg.first_dense:  # deepseek: leading dense layer(s), unstacked
        def one_dense(k):
            ka, km = jax.random.split(k)
            return {"attn": _attn_init(ka, cfg, dtype),
                    "mlp": L.mlp_init(km, cfg.d_model,
                                      cfg.dense_d_ff or cfg.d_ff, True, dtype),
                    "ln1": jnp.ones((cfg.d_model,), dtype),
                    "ln2": jnp.ones((cfg.d_model,), dtype)}
        params["dense_layers"] = [
            one_dense(k) for k in jax.random.split(k_dense, cfg.first_dense)]
    if not cfg.tie_embeddings:
        params["lm_head"] = L.dense_init(k_head, cfg.d_model, vp, dtype)
    return params


# ---------------------------------------------------------------------------
# forward
# ---------------------------------------------------------------------------

def _block(p: Params, x, cfg, qcfg, positions, is_global, cache=None,
           moe: bool = False, attn_mode: str = "dyn", absorb: bool = False):
    """One transformer block.

    attn_mode: 'dyn' (traced is_global flag, scan-homogeneous masking —
    the baseline), 'local' (static block-banded window — perf variant),
    'global' (static full causal).  ``absorb`` runs latent attention in
    its absorbed (decode) form.
    """
    spec_g = attn_spec(cfg, True)
    if attn_mode == "dyn":
        # window = huge when global; masks from the traced flag so
        # local/global layers share the scan body.
        window = jnp.where(is_global, jnp.asarray(1 << 30, jnp.int32),
                           jnp.asarray(max(cfg.window, 1), jnp.int32))
    elif attn_mode == "local":
        window = cfg.window
    else:
        window = 1 << 30
    x = L.shard_batch(x)
    h = L.rmsnorm(x, p["ln1"], zero_centered=cfg.zero_centered_norm)
    if cfg.kv_lora_rank:
        attn_out, new_cache = MLA.mla_attention(
            p["attn"], h, cfg, qcfg, positions, cache, absorb)
    else:
        attn_out, new_cache = _attention_dynwin(
            p["attn"], h, spec_g, qcfg, positions, window, cache,
            block_local=(attn_mode == "local" and cache is None), cfg=cfg)
    x = x + attn_out.astype(x.dtype)
    h = L.rmsnorm(x, p["ln2"], zero_centered=cfg.zero_centered_norm)
    if moe:
        moe_fn = MOE.moe_apply_ep if cfg.moe_ep_shard_map else MOE.moe_apply
        ff = moe_fn(p["moe"], h, cfg, qcfg)
    else:
        ff = L.mlp(p["mlp"], h, qcfg, cfg.act)
    return x + ff.astype(x.dtype), new_cache


def _attention_dynwin(p, x, spec, qcfg, positions, window, cache,
                      block_local: bool = False, cfg=None):
    """Attention with a traced (baseline) or static window width."""
    b, s, _ = x.shape
    hq, hkv, dh = spec.n_heads, spec.kv_heads, spec.head_dim
    q = L.qdense(x, p["wq"], qcfg).reshape(b, s, hq, dh)
    k = L.qdense(x, p["wk"], qcfg).reshape(b, s, hkv, dh)
    v = L.qdense(x, p["wv"], qcfg).reshape(b, s, hkv, dh)
    if spec.qk_norm:
        q = L.rmsnorm(q, p["q_norm"])
        k = L.rmsnorm(k, p["k_norm"])
    pos2d = positions if positions.ndim == 2 else positions[..., 0]
    if spec.mrope_sections:
        # text-only stream: (B, S) positions -> identical t/h/w ids
        pos3 = positions if positions.ndim == 3 else \
            jnp.broadcast_to(positions[..., None], (*positions.shape, 3))
        q = L.apply_mrope(q, pos3, spec.mrope_sections, spec.rope_theta)
        k = L.apply_mrope(k, pos3, spec.mrope_sections, spec.rope_theta)
    else:
        q = L.apply_rope(q, pos2d, spec.rope_theta)
        k = L.apply_rope(k, pos2d, spec.rope_theta)

    # perf variant: pad KV heads up to the TP degree (replicated GQA
    # groups) so decode caches shard on heads -> local in-place updates
    kv_rep = getattr(cfg, "kv_replicate_to", 0) if cfg is not None else 0
    if kv_rep and kv_rep > hkv:
        rep = kv_rep // hkv
        k = jnp.repeat(k, rep, axis=2)
        v = jnp.repeat(v, rep, axis=2)
        hkv = kv_rep

    if block_local:
        groups = hq // hkv
        qg = q.reshape(b, s, hkv, groups, dh)
        from repro.models.block_attn import block_local_attention
        out = block_local_attention(qg, k, v, pos2d, int(window),
                                    spec.softcap, spec.query_scale)
        out = out.reshape(b, s, hq * dh).astype(x.dtype)
        return L.qdense(out, p["wo"], qcfg), cache

    # flash (chunked online-softmax) path: forward-only prefill with no
    # S^2 logits materialization (EXPERIMENTS.md §Dry-run caveats).
    # All-global patterns only; windowed archs use attn_block_local.
    if (cfg is not None and getattr(cfg, "attn_flash", False)
            and cache is None
            and (cfg.layer_pattern == "all_global" or cfg.window <= 0)):
        from repro.models.flash_attn import flash_attention
        groups = hq // hkv
        qg = q.reshape(b, s, hkv, groups, dh)
        win = int(window) if not hasattr(window, "dtype") else (1 << 30)
        out = flash_attention(qg, k, v, pos2d, pos2d, win, spec.softcap,
                              spec.query_scale)
        out = out.reshape(b, s, hq * dh).astype(x.dtype)
        return L.qdense(out, p["wo"], qcfg), cache

    new_cache = cache
    if cache is not None:
        idx = cache["index"]
        ck = jax.lax.dynamic_update_slice_in_dim(
            cache["k"], k.astype(cache["k"].dtype), idx, axis=1)
        cv = jax.lax.dynamic_update_slice_in_dim(
            cache["v"], v.astype(cache["v"].dtype), idx, axis=1)
        new_cache = {"k": ck, "v": cv, "index": idx + s}
        k, v = ck, cv
        kv_pos = jnp.broadcast_to(
            jnp.arange(ck.shape[1], dtype=pos2d.dtype)[None, :],
            (b, ck.shape[1]))
    else:
        kv_pos = pos2d

    groups = hq // hkv
    scale = spec.query_scale or (1.0 / float(np.sqrt(dh)))
    qg = q.reshape(b, s, hkv, groups, dh)
    # native-dtype inputs (bf16 cache reads stay bf16), f32 accumulation
    logits = jnp.einsum("bqhgd,bkhd->bhgqk", qg, k.astype(qg.dtype),
                        preferred_element_type=jnp.float32) * scale
    if spec.softcap > 0.0:
        logits = spec.softcap * jnp.tanh(logits / spec.softcap)
    qp = pos2d[:, None, None, :, None]
    kp = kv_pos[:, None, None, None, :]
    ok = (kp <= qp) & (kp > qp - window)
    logits = jnp.where(ok, logits, -1e30)
    probs = jax.nn.softmax(logits, axis=-1)
    out = jnp.einsum("bhgqk,bkhd->bqhgd", probs.astype(v.dtype), v,
                     preferred_element_type=jnp.float32)
    out = out.reshape(b, s, hq * dh).astype(x.dtype)
    return L.qdense(out, p["wo"], qcfg), new_cache


def _backbone(params, x, cfg, positions, caches=None, absorb=False):
    """Embed-less forward over all layers. x: (B, S, D) hidden states.

    caches: None (train/prefill-no-cache) or pytree with leading L axis for
    the scanned layers (+ list for dense layers). Returns (y, new_caches).
    absorb: latent attention in its absorbed form (decode steps).
    """
    qcfg = preset(cfg.pe_type)
    is_moe = cfg.moe_experts > 0
    flags = jnp.asarray(layer_is_global(cfg)[cfg.first_dense:])

    dense_caches = []
    for i in range(cfg.first_dense):
        p = params["dense_layers"][i]
        c = None if caches is None else caches["dense"][i]
        x, c = _block(p, x, cfg, qcfg, positions, jnp.asarray(True), c,
                      moe=False, absorb=absorb)
        dense_caches.append(c)

    # perf variant: pattern-grouped scan with static block-banded local
    # attention (no traced window; shapes differ local vs global)
    if cfg.attn_block_local and caches is None and cfg.window > 0 \
            and cfg.layer_pattern in ("gemma3", "alt_local_global"):
        return _backbone_grouped(params, x, cfg, qcfg, positions, is_moe), \
            None

    def body(carry, xs):
        h = carry
        layer_params, flag, cache = xs
        h, new_cache = _block(layer_params, h, cfg, qcfg, positions, flag,
                              cache, moe=is_moe, absorb=absorb)
        return h, new_cache

    scan_caches = None if caches is None else caches["scan"]
    xs = (params["layers"], flags, scan_caches)
    if caches is None:
        # remat each layer: activation memory = one layer's inputs per step
        body_fn = jax.checkpoint(body)
    else:
        body_fn = body
    x, new_scan_caches = jax.lax.scan(body_fn, x, xs)
    new_caches = None
    if caches is not None:
        new_caches = {"dense": dense_caches, "scan": new_scan_caches}
    return x, new_caches


def _backbone_grouped(params, x, cfg, qcfg, positions, is_moe):
    """Scan over pattern periods: (p-1) block-local layers + 1 global.

    gemma3: 4 groups of (5 local + 1 global) + 2 leftover locals;
    gemma2: 21 groups of (1 local + 1 global)."""
    period = {"gemma3": 6, "alt_local_global": 2}[cfg.layer_pattern]
    n_groups = cfg.n_layers // period
    leftover = cfg.n_layers - n_groups * period
    grouped = jax.tree.map(
        lambda a: a[:n_groups * period].reshape(n_groups, period,
                                                *a.shape[1:]),
        params["layers"])
    tail = jax.tree.map(lambda a: a[n_groups * period:], params["layers"]) \
        if leftover else None

    def local_body(h, lp):
        h, _ = _block(lp, h, cfg, qcfg, positions, None, moe=is_moe,
                      attn_mode="local")
        return h, None

    def group_body(h, gp):
        locals_p = jax.tree.map(lambda a: a[:period - 1], gp)
        global_p = jax.tree.map(lambda a: a[period - 1], gp)
        h, _ = jax.lax.scan(jax.checkpoint(local_body), h, locals_p)
        h, _ = _block(global_p, h, cfg, qcfg, positions, None, moe=is_moe,
                      attn_mode="global")
        return h, None

    x, _ = jax.lax.scan(jax.checkpoint(group_body), x, grouped)
    if leftover:
        x, _ = jax.lax.scan(jax.checkpoint(local_body), x, tail)
    return x


def _logits(params, x, cfg):
    qcfg = preset(cfg.pe_type)
    x = L.rmsnorm(x, params["final_norm"], zero_centered=cfg.zero_centered_norm)
    if cfg.tie_embeddings:
        w = params["embed"].T
        logits = L.qdense(x, w, qcfg)
    else:
        logits = L.qdense(x, params["lm_head"], qcfg)
    if cfg.final_softcap > 0.0:
        logits = cfg.final_softcap * jnp.tanh(logits / cfg.final_softcap)
    return logits


def _embed(params, tokens, cfg):
    x = params["embed"][tokens]
    if cfg.embed_scale:
        x = x * jnp.asarray(np.sqrt(cfg.d_model), x.dtype)
    return x.astype(jnp.dtype(cfg.dtype))


def forward(params, tokens, cfg, positions=None):
    """tokens: (B, S) -> logits (B, S, Vp)."""
    b, s = tokens.shape[:2]
    if positions is None:
        positions = jnp.broadcast_to(jnp.arange(s, dtype=jnp.int32)[None],
                                     (b, s))
    x = _embed(params, tokens, cfg)
    x, _ = _backbone(params, x, cfg, positions)
    return _logits(params, x, cfg)


def loss_fn(params, batch, cfg):
    """batch: {'tokens': (B, S), 'labels': (B, S)} -> scalar CE loss."""
    positions = batch.get("positions")
    logits = forward(params, batch["tokens"], cfg, positions)
    return L.softmax_xent(logits, batch["labels"])


# ---------------------------------------------------------------------------
# serving
# ---------------------------------------------------------------------------

def init_cache(cfg, batch: int, max_len: int, dtype=jnp.bfloat16):
    spec = attn_spec(cfg)
    if cfg.kv_replicate_to and cfg.kv_replicate_to > spec.kv_heads:
        spec = dataclasses_replace_kv(spec, cfg.kv_replicate_to)
    n_scan = cfg.n_layers - cfg.first_dense

    def one(_=None):
        if cfg.kv_lora_rank:  # the latent cache: no per-head K/V
            return MLA.make_mla_cache(batch, max_len, cfg, dtype)
        return L.make_cache(batch, max_len, spec, dtype)

    scan_caches = jax.vmap(one)(jnp.arange(n_scan))
    # vmap over make_cache gives index shape (n_scan,) — keep per-layer idx
    dense = [one() for _ in range(cfg.first_dense)]
    return {"dense": dense, "scan": scan_caches}


def prefill(params, tokens, cfg, cache, positions=None):
    """Fill caches with a prompt; returns (logits_last, cache)."""
    b, s = tokens.shape[:2]
    if positions is None:
        positions = jnp.broadcast_to(jnp.arange(s, dtype=jnp.int32)[None],
                                     (b, s))
    x = _embed(params, tokens, cfg)
    x, cache = _backbone(params, x, cfg, positions, cache)
    return _logits(params, x[:, -1:], cfg), cache


def decode_step(params, token, cfg, cache, positions=None):
    """token: (B, 1) -> (logits (B, 1, V), new cache)."""
    b = token.shape[0]
    if positions is None:
        idx = jax.tree.leaves(cache["scan"]["index"])[0]
        pos = (idx[0] if idx.ndim else idx).astype(jnp.int32)
        positions = jnp.full((b, 1), pos, jnp.int32)
    x = _embed(params, token, cfg)
    x, cache = _backbone(params, x, cfg, positions, cache, absorb=True)
    return _logits(params, x, cfg), cache
