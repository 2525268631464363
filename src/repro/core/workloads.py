"""Layer-wise DNN workload extraction.

The paper feeds QADAM "layer-wise DNN configurations" for VGG-16 and
ResNet-20/34/50/56 (CIFAR-10/100 + ImageNet).  Those exact CNNs are built
here, plus — beyond the paper — GEMM workload extraction for the assigned
transformer / MoE / SSM architectures so the same DSE runs over the modern
zoo (DESIGN.md §2).

A workload is a stack of layer specs (conv or GEMM-as-1x1-conv) with a
``count`` multiplicity, kept as parallel jnp arrays so the dataflow cost
model evaluates all layers of a network in one vmapped call.

Phase-aware layer IR
--------------------
Beyond the conv shape, every layer carries operand-residency fields that
tell the cost model how its *second* operand behaves (``LAYER_KINDS``):

* ``conv`` / ``gemm`` — the second operand is a resident weight tensor:
  stationary in the array, replayed through the gbuf (the paper's model,
  unchanged — these two kinds cost identically);
* ``attn_kv`` — the second operand is a per-sequence KV-cache block:
  ``stream_words`` words are STREAMED from DRAM once per batch element at
  activation width, with no cross-batch reuse (decode-phase attention);
* ``moe_expert`` — the layer shape describes the ACTIVE (top-k-gated)
  GEMM, while weight traffic follows the TOUCHED experts:
  ``active_frac`` = active-compute fraction per weight read (1/touched
  experts), so DRAM/gbuf weight traffic is divided by it.

``acc_class`` (``ACC_CLASSES``) tags the layer's accuracy-sensitivity
class (attention / FFN / expert) for ``accuracy.AccuracySurrogate``'s
per-class precision priors; it never enters the cost model.

All four fields default to neutral values (resident weights, fully
active, default class) under which the cost model is BIT-IDENTICAL to
the pre-IR conv-only model — the padding/bit-identity contracts of
``pad_workload`` and the one-compile joint sweeps are unchanged.
"""

from __future__ import annotations

from typing import NamedTuple, Sequence

import jax.numpy as jnp
import numpy as np

# Layer kinds: how the second operand resides (codes stored as floats in
# the stacked arrays; conv and gemm share the resident-weight cost path).
LAYER_KINDS = ("conv", "gemm", "attn_kv", "moe_expert")
KIND_CONV, KIND_GEMM, KIND_ATTN_KV, KIND_MOE_EXPERT = range(len(LAYER_KINDS))

# Accuracy-sensitivity classes (see accuracy.ACC_CLASS_SENS for the
# per-class quantization-sensitivity priors).
ACC_CLASSES = ("default", "attn", "ffn", "expert")
ACC_DEFAULT, ACC_ATTN, ACC_FFN, ACC_EXPERT = range(len(ACC_CLASSES))


def floor_div(a, b):
    """``floor(a / b)`` for ``b > 0``, exact on every backend wherever
    ``a`` and ``b`` are integers with products below 2**24.

    Such quotients are often whole numbers (96 / 32), and a divide that
    is not correctly rounded — the TPU's f32 divide — may land one ulp
    below one, which ``floor`` would turn into the next integer down.  Two
    compare-and-step corrections restore the exact floor; on a correctly
    rounded backend they never fire for integer operands."""
    q = jnp.floor(a / b)
    q = jnp.where(q * b > a, q - 1.0, q)
    return jnp.where((q + 1.0) * b <= a, q + 1.0, q)


def ceil_div(a, b):
    """``ceil(a / b)`` for ``b > 0``, exact like ``floor_div``."""
    q = jnp.ceil(a / b)
    q = jnp.where((q - 1.0) * b >= a, q - 1.0, q)
    return jnp.where(q * b < a, q + 1.0, q)


class LayerSpec(NamedTuple):
    """One conv layer: input HxWxC, K filters of RxS, given stride & batch.

    A GEMM (M x Kd) @ (Kd x N) is the degenerate conv
    H=1, W=M, C=Kd, K=N, R=S=stride=1  (so E=1, F=M, MACs = M*Kd*N*batch).

    The trailing phase-aware IR fields (defaults = neutral / legacy):

    * ``kind`` — ``LAYER_KINDS`` code (conv/gemm resident, attn_kv
      streamed, moe_expert gated);
    * ``stream_words`` — words of the streamed second operand per batch
      element (attn_kv: KV-cache length x head_dim; 0 otherwise);
    * ``active_frac`` — active-MAC fraction per weight read for gated
      expert layers (1/touched experts; 1.0 = dense reuse);
    * ``acc_class`` — ``ACC_CLASSES`` code for the accuracy surrogate.
    """

    H: jnp.ndarray
    W: jnp.ndarray
    C: jnp.ndarray
    K: jnp.ndarray
    R: jnp.ndarray
    S: jnp.ndarray
    stride: jnp.ndarray
    batch: jnp.ndarray
    count: jnp.ndarray  # multiplicity (identical repeated layers)
    kind: jnp.ndarray = 0.0          # LAYER_KINDS code
    stream_words: jnp.ndarray = 0.0  # streamed operand words / batch elem
    active_frac: jnp.ndarray = 1.0   # active-MAC fraction per weight read
    acc_class: jnp.ndarray = 0.0     # ACC_CLASSES code

    def out_hw(self):
        E = floor_div(self.H - self.R, self.stride) + 1.0
        F = floor_div(self.W - self.S, self.stride) + 1.0
        return E, F

    def macs(self):
        E, F = self.out_hw()
        return self.batch * self.K * self.C * self.R * self.S * E * F * self.count


class Workload(NamedTuple):
    name: str
    layers: LayerSpec           # stacked, leading dim = n_layers
    layer_names: tuple


# Neutral IR defaults, applied by _stack to row dicts that predate the
# phase-aware fields (and by pad_workload to padding rows).
_IR_DEFAULTS = dict(kind=float(KIND_CONV), stream_words=0.0,
                    active_frac=1.0, acc_class=float(ACC_DEFAULT))


def _stack(rows: Sequence[dict], name: str, names: Sequence[str]) -> Workload:
    fields = LayerSpec._fields
    arr = {f: jnp.asarray(np.array([r.get(f, _IR_DEFAULTS.get(f))
                                    for r in rows], np.float64), jnp.float32)
           for f in fields}
    return Workload(name=name, layers=LayerSpec(**arr), layer_names=tuple(names))


def conv(H, W, C, K, R=3, S=None, stride=1, batch=1, count=1):
    S = R if S is None else S
    return dict(H=H + (R - 1), W=W + (S - 1),  # 'same' padding baked into H,W
                C=C, K=K, R=R, S=S, stride=stride, batch=batch, count=count)


def conv_valid(H, W, C, K, R, S=None, stride=1, batch=1, count=1):
    S = R if S is None else S
    return dict(H=H, W=W, C=C, K=K, R=R, S=S, stride=stride, batch=batch,
                count=count)


def gemm(M, Kd, N, batch=1, count=1, kind=KIND_GEMM, stream_words=0.0,
         active_frac=1.0, acc_class=ACC_DEFAULT):
    return dict(H=1, W=M, C=Kd, K=N, R=1, S=1, stride=1, batch=batch,
                count=count, kind=float(kind),
                stream_words=float(stream_words),
                active_frac=float(active_frac), acc_class=float(acc_class))


# ---------------------------------------------------------------------------
# The paper's CNNs
# ---------------------------------------------------------------------------

def _scale_suffix(width_mult: float, resolution: int | None,
                  base_res: int) -> str:
    """Name suffix for scaled family members ('' for the canonical member)."""
    parts = []
    if width_mult != 1.0:
        parts.append(f"w{width_mult:g}")
    if resolution is not None and resolution != base_res:
        parts.append(f"r{resolution}")
    return "".join(f"-{p}" for p in parts)


def vgg16(dataset: str = "imagenet", batch: int = 1,
          width_mult: float = 1.0, resolution: int | None = None) -> Workload:
    """VGG-16, optionally width- and resolution-scaled (family member).

    ``width_mult`` scales every conv/fc channel count; ``resolution``
    overrides the dataset's native input size.  Defaults reproduce the
    paper's VGG-16 exactly.
    """
    if dataset == "imagenet":
        base_res, n_cls, fc_w = 224, 1000, 4096
    else:  # cifar10 / cifar100
        base_res = 32
        n_cls, fc_w = (100 if dataset == "cifar100" else 10), 512
    hw = base_res if resolution is None else resolution
    if hw < 16:
        # the 5th conv block runs at hw >> 4: below 16 its input collapses
        # to 0x0 and the cost model degenerates to NaN
        raise ValueError(f"vgg16 needs resolution >= 16, got {hw}")
    w = lambda k: max(1, round(k * width_mult))  # noqa: E731
    rows, names = [], []
    cfg = [(64, 2), (128, 2), (256, 3), (512, 3), (512, 3)]
    c, h = 3, hw
    for blk, (k, reps) in enumerate(cfg):
        for r in range(reps):
            rows.append(conv(h, h, c, w(k), 3, batch=batch))
            names.append(f"conv{blk + 1}_{r + 1}")
            c = w(k)
        h //= 2  # maxpool
    fc_in = max(h, 1) ** 2 * c
    if dataset == "imagenet":
        fcs = [(fc_in, w(fc_w)), (w(fc_w), w(fc_w)), (w(fc_w), n_cls)]
    else:
        fcs = [(fc_in, w(fc_w)), (w(fc_w), n_cls)]
    for i, (m, n) in enumerate(fcs):
        rows.append(gemm(1, m, n, batch=batch))
        names.append(f"fc{i + 1}")
    name = f"vgg16-{dataset}" + _scale_suffix(width_mult, resolution, base_res)
    return _stack(rows, name, names)


def resnet_cifar(depth: int, dataset: str = "cifar10", batch: int = 1,
                 width_mult: float = 1.0, resolution: int = 32) -> Workload:
    """ResNet-20/56 for CIFAR (He et al.): 3 stages of n=(depth-2)/6 blocks.

    Depth (20/32/44/56/...), ``width_mult`` (stage channels 16/32/64 scaled)
    and input ``resolution`` span the paper-faithful model family used for
    co-exploration; defaults reproduce the paper's models exactly.
    """
    n = (depth - 2) // 6
    n_cls = 100 if dataset == "cifar100" else 10
    if resolution < 4:
        # stage 3 runs at resolution/4: below 4 its input collapses to 0x0
        raise ValueError(f"resnet_cifar needs resolution >= 4, got {resolution}")
    w = lambda k: max(1, round(k * width_mult))  # noqa: E731
    rows = [conv(resolution, resolution, 3, w(16), 3, batch=batch)]
    names = ["stem"]
    c, h = w(16), resolution
    for stage, k0 in enumerate((16, 32, 64)):
        k = w(k0)
        for b in range(n):
            s = 2 if (stage > 0 and b == 0) else 1
            rows.append(conv(h // s if s == 1 else h, h // s if s == 1 else h,
                             c, k, 3, stride=s, batch=batch))
            h = h // s
            rows.append(conv(h, h, k, k, 3, batch=batch))
            names += [f"s{stage}b{b}c1", f"s{stage}b{b}c2"]
            if s == 2 or c != k:
                rows.append(conv(h * s, h * s, c, k, 1, stride=s, batch=batch))
                names.append(f"s{stage}b{b}sc")
            c = k
    rows.append(gemm(1, w(64), n_cls, batch=batch))
    names.append("fc")
    name = (f"resnet{depth}-{dataset}"
            + _scale_suffix(width_mult, resolution, 32))
    return _stack(rows, name, names)


def resnet34(batch: int = 1) -> Workload:
    rows = [conv_valid(230, 230, 3, 64, 7, stride=2, batch=batch)]
    names = ["stem"]
    c, h = 64, 56
    for stage, (k, reps) in enumerate([(64, 3), (128, 4), (256, 6), (512, 3)]):
        for b in range(reps):
            s = 2 if (stage > 0 and b == 0) else 1
            rows.append(conv(h, h, c, k, 3, stride=s, batch=batch))
            h = h // s
            rows.append(conv(h, h, k, k, 3, batch=batch))
            names += [f"s{stage}b{b}c1", f"s{stage}b{b}c2"]
            if c != k:
                rows.append(conv(h * s, h * s, c, k, 1, stride=s, batch=batch))
                names.append(f"s{stage}b{b}sc")
            c = k
    rows.append(gemm(1, 512, 1000, batch=batch))
    names.append("fc")
    return _stack(rows, "resnet34-imagenet", names)


def resnet50(batch: int = 1) -> Workload:
    rows = [conv_valid(230, 230, 3, 64, 7, stride=2, batch=batch)]
    names = ["stem"]
    c, h = 64, 56
    for stage, (k, reps) in enumerate([(64, 3), (128, 4), (256, 6), (512, 3)]):
        for b in range(reps):
            s = 2 if (stage > 0 and b == 0) else 1
            rows.append(conv(h, h, c, k, 1, batch=batch))          # reduce
            rows.append(conv(h, h, k, k, 3, stride=s, batch=batch))
            h = h // s
            rows.append(conv(h, h, k, 4 * k, 1, batch=batch))      # expand
            names += [f"s{stage}b{b}c1", f"s{stage}b{b}c2", f"s{stage}b{b}c3"]
            if c != 4 * k:
                rows.append(conv(h * s, h * s, c, 4 * k, 1, stride=s, batch=batch))
                names.append(f"s{stage}b{b}sc")
            c = 4 * k
    rows.append(gemm(1, 2048, 1000, batch=batch))
    names.append("fc")
    return _stack(rows, "resnet50-imagenet", names)


PAPER_WORKLOADS = {
    "vgg16-cifar10": lambda batch=1: vgg16("cifar10", batch),
    "vgg16-cifar100": lambda batch=1: vgg16("cifar100", batch),
    "vgg16-imagenet": lambda batch=1: vgg16("imagenet", batch),
    "resnet20-cifar10": lambda batch=1: resnet_cifar(20, "cifar10", batch),
    "resnet20-cifar100": lambda batch=1: resnet_cifar(20, "cifar100", batch),
    "resnet56-cifar10": lambda batch=1: resnet_cifar(56, "cifar10", batch),
    "resnet56-cifar100": lambda batch=1: resnet_cifar(56, "cifar100", batch),
    "resnet34-imagenet": lambda batch=1: resnet34(batch),
    "resnet50-imagenet": lambda batch=1: resnet50(batch),
}


# ---------------------------------------------------------------------------
# Beyond the paper: transformer-family GEMM extraction (assigned archs)
# ---------------------------------------------------------------------------

def touched_experts(experts: int, topk: int, routed_tokens: int) -> float:
    """Expected number of DISTINCT experts touched by ``routed_tokens``
    independent top-k routings over ``experts`` choices (uniform router).

    The MoE traffic model's host-side constant: weight DRAM traffic
    follows touched experts while compute follows active (token, expert)
    pairs.  Decode (one token) touches exactly ``topk`` experts; prefill
    with many tokens saturates toward all ``experts``.
    """
    if experts <= 0 or topk <= 0 or routed_tokens <= 0:
        return 0.0
    frac = min(float(topk) / float(experts), 1.0)
    t = float(experts) * (1.0 - (1.0 - frac) ** float(routed_tokens))
    return float(np.clip(t, float(min(topk, experts)), float(experts)))


def _shared_stream_rows(add, tag: str, heads: int, k_width: int,
                        v_width: int, kvlen: int, count: int) -> None:
    """Decode score and value rows of ``heads`` query heads that share one
    streamed cache: M = heads, so each cache word is read once per layer
    and sequence and feeds ``heads`` MACs.  The score product streams
    ``kvlen`` x ``k_width`` words, the value product ``kvlen`` x
    ``v_width``; both are ``attn_kv`` rows."""
    ir = dict(kind=KIND_ATTN_KV, acc_class=ACC_ATTN)
    add(f"qk_{tag}", heads, k_width, kvlen, count,
        stream_words=float(kvlen) * k_width, **ir)
    add(f"av_{tag}", heads, kvlen, v_width, count,
        stream_words=float(kvlen) * v_width, **ir)


def _mla_rows(add, cfg, tokens: int, kvlen: int, decode: bool) -> None:
    """Attention rows of multi-head latent attention (DeepSeek-V2/V3).

    Per layer the query goes through its low-rank path (``wq_a`` then
    ``wq_b``) and the new position's latent is ``wkv_a``'s output: the
    ``kv_lora_rank``-wide ``c_kv`` plus the ``qk_rope_head_dim``-wide
    ``k_rope``, which is all the cache holds.  Decode runs the absorbed
    form: ``q_absorb`` maps each head's no-rope query into the latent
    (``W_UK``), every head scores against the one shared latent stream
    (``qk_latent``) and sums ``c_kv`` by its probabilities (``av_latent``),
    and ``v_absorb`` maps the result out per head (``W_UV``).  Prefill
    and training run the naive form: ``kv_b`` decompresses the latent into
    per-head keys and values, then per-head score and value products on
    the resident path, as the other attention rows do."""
    L, hq, d = cfg.n_layers, cfg.n_heads, cfg.d_model
    ql, r = cfg.q_lora_rank, cfg.kv_lora_rank
    nope, rope, dv = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.v_head_dim
    if ql <= 0:
        raise ValueError(f"{cfg.name}: latent attention is priced with a "
                         f"query low-rank path (q_lora_rank > 0)")
    attn = dict(acc_class=ACC_ATTN)
    add("wq_a", tokens, d, ql, L, **attn)
    add("wq_b", tokens, ql, hq * (nope + rope), L, **attn)
    add("wkv_a", tokens, d, r + rope, L, **attn)
    if decode:
        add("q_absorb", tokens, nope, r, L * hq, **attn)
        _shared_stream_rows(add, "latent", hq, r + rope, r, kvlen, L)
        add("v_absorb", tokens, r, dv, L * hq, **attn)
    else:
        add("kv_b", tokens, r, hq * (nope + dv), L, **attn)
        add("qk", tokens, nope + rope, kvlen, L * hq, **attn)
        add("av", tokens, kvlen, dv, L * hq, **attn)
    add("wo", tokens, hq * dv, d, L, **attn)


def transformer_workload(cfg, seq: int, batch: int, mode: str = "train",
                         name: str | None = None) -> Workload:
    """Extract per-layer GEMMs from a repro.configs ArchConfig-like object.

    mode: 'train'/'prefill' use full seq; 'decode' uses one token against a
    seq-long KV cache (attention GEMMs become matrix-vector, and the
    score/value GEMMs are emitted as ``attn_kv`` layers: the K/V cache is
    a per-sequence STREAMED operand, not a resident weight).
    Counts forward MACs only (training multiplies by 3 in the cost model if
    requested by the caller).

    MoE configs (``cfg.moe_experts > 0``) honor ``cfg.first_dense`` /
    ``cfg.dense_d_ff`` (leading dense layers with their own FFN width —
    DeepSeekMoE's layer 0); routed experts are emitted as ``moe_expert``
    layers shaped by the ACTIVE top-k compute with ``active_frac`` set
    from the expected touched-expert count, and always-on shared experts
    as plain resident GEMMs.

    Configs with ``cfg.kv_lora_rank > 0`` use multi-head latent
    attention (``_mla_rows``): absorbed rows over one shared latent cache
    in decode, the naive decompressed rows in prefill and training.
    """
    d, L = cfg.d_model, cfg.n_layers
    hq, hkv = cfg.n_heads, cfg.kv_heads
    dh = getattr(cfg, "head_dim", d // max(hq, 1))
    decode = mode == "decode"
    tokens = 1 if decode else seq
    kvlen = seq
    rows, names = [], []

    def add(tag, M, Kd, N, count=1, **ir):
        rows.append(gemm(M, Kd, N, batch=batch, count=count, **ir))
        names.append(tag)

    attn_layers = getattr(cfg, "attn_layers", L if hq > 0 else 0)
    if getattr(cfg, "kv_lora_rank", 0):
        _mla_rows(add, cfg, tokens, kvlen, decode)
    elif attn_layers:
        add("wq", tokens, d, hq * dh, attn_layers, acc_class=ACC_ATTN)
        add("wk", tokens, d, hkv * dh, attn_layers, acc_class=ACC_ATTN)
        add("wv", tokens, d, hkv * dh, attn_layers, acc_class=ACC_ATTN)
        add("wo", tokens, hq * dh, d, attn_layers, acc_class=ACC_ATTN)
        # attention score/value GEMMs (per head, batched over heads).
        # Decode streams the KV cache (kvlen x head_dim per sequence);
        # prefill computes K/V on the fly — resident-operand costing.
        kv_ir = dict(kind=KIND_ATTN_KV, stream_words=float(kvlen) * dh,
                     acc_class=ACC_ATTN) if decode \
            else dict(acc_class=ACC_ATTN)
        add("qk", tokens, dh, kvlen, attn_layers * hq, **kv_ir)
        add("av", tokens, kvlen, dh, attn_layers * hq, **kv_ir)
    # FFN: dense layers (all of them for non-MoE; cfg.first_dense leading
    # layers at cfg.dense_d_ff width for MoE configs), then routed experts
    if cfg.moe_experts:
        n_dense = min(int(getattr(cfg, "first_dense", 0) or 0), L)
        n_moe = L - n_dense
        dense_ff = int(getattr(cfg, "dense_d_ff", 0) or 0) or cfg.d_ff
    else:
        n_dense, n_moe, dense_ff = L, 0, cfg.d_ff
    if n_dense:
        add("ffn_in", tokens, d, dense_ff * 2, n_dense,
            acc_class=ACC_FFN)   # gate+up (SwiGLU)
        add("ffn_out", tokens, dense_ff, d, n_dense, acc_class=ACC_FFN)
    if n_moe:
        experts, topk = cfg.moe_experts, cfg.moe_topk
        shared = getattr(cfg, "moe_shared", 0)
        touched = touched_experts(experts, topk, tokens * batch)
        gated = dict(kind=KIND_MOE_EXPERT,
                     active_frac=1.0 / max(touched, 1.0),
                     acc_class=ACC_EXPERT)
        add("moe_in", tokens * topk, d, cfg.moe_d_ff * 2, n_moe, **gated)
        add("moe_out", tokens * topk, cfg.moe_d_ff, d, n_moe, **gated)
        if shared:  # always-active shared experts: dense resident weights
            add("moe_shared_in", tokens, d, cfg.moe_d_ff * 2,
                n_moe * shared, acc_class=ACC_EXPERT)
            add("moe_shared_out", tokens, cfg.moe_d_ff, d,
                n_moe * shared, acc_class=ACC_EXPERT)
        add("router", tokens, d, experts, n_moe, acc_class=ACC_FFN)
    # embeddings / head
    add("lm_head", tokens, d, cfg.vocab, 1)
    return _stack(rows, name or f"{cfg.name}-{mode}", names)


# ---------------------------------------------------------------------------
# Parameterized model families: the workload axis of the joint
# (model x accelerator) co-exploration space (QUIDAM/QAPPA-style).
# ---------------------------------------------------------------------------

class _TfmSpec(NamedTuple):
    """Minimal ArchConfig-like stand-in for ``transformer_workload``."""
    name: str
    d_model: int
    n_layers: int
    n_heads: int
    kv_heads: int
    d_ff: int
    vocab: int
    moe_experts: int = 0


def transformer_gemm(seq: int = 512, d_model: int = 512, n_layers: int = 8,
                     n_heads: int = 8, d_ff: int = 2048, vocab: int = 32000,
                     batch: int = 1, mode: str = "prefill",
                     name: str | None = None) -> Workload:
    """Self-contained decoder-block GEMM workload, seq-length-scaled.

    The transformer member of the co-exploration model family: no
    ``repro.configs`` object needed — sweep ``seq`` (and width/depth via
    ``d_model``/``n_layers``) to generate the model axis.  Reuses the same
    GEMM extraction as ``transformer_workload``.
    """
    spec = _TfmSpec(name=name or f"tfm-d{d_model}-L{n_layers}",
                    d_model=d_model, n_layers=n_layers, n_heads=n_heads,
                    kv_heads=n_heads, d_ff=d_ff, vocab=vocab)
    return transformer_workload(
        spec, seq=seq, batch=batch, mode=mode,
        name=name or f"tfm-d{d_model}-L{n_layers}-s{seq}-{mode}")


# ---------------------------------------------------------------------------
# LLM serving families (ROADMAP item 3): decode-phase and MoE workloads
# instantiated from the repro.configs registry on the phase-aware IR.
# ---------------------------------------------------------------------------

def _arch_config(arch):
    """Resolve an ``llm_*`` family's ``arch`` argument: a CLI id / module
    name (``repro.configs.get``) or an ArchConfig-like object passed
    through."""
    if isinstance(arch, str):
        from repro.configs import get as _get
        return _get(arch)
    return arch


def llm_decode(arch="qwen3-32b", context: int = 4096, batch: int = 1,
               name: str | None = None) -> Workload:
    """Decode-phase serving member: one generated token against a
    ``context``-long KV cache.

    The batch x context knobs span the family: per-step attention traffic
    is KV-READ dominated (``attn_kv`` streamed operands grow linearly in
    ``context`` while per-step compute stays matrix-vector), so long
    contexts sit far down the arithmetic-intensity cliff — the regime
    where the memory-bound term, not the PE array, sets latency.
    """
    cfg = _arch_config(arch)
    return transformer_workload(
        cfg, seq=context, batch=batch, mode="decode",
        name=name or f"{cfg.name}-decode-c{context}-b{batch}")


def llm_moe(arch="deepseek-moe-16b", experts: int | None = None,
            topk: int | None = None, seq: int = 512, batch: int = 1,
            mode: str = "decode", name: str | None = None) -> Workload:
    """MoE serving member: top-k-gated expert layers on the phase-aware IR.

    The expert-count x top-k knobs span the family: active MACs scale
    with ``topk`` while expert weight traffic follows the TOUCHED experts
    (``touched_experts``), so decode-phase members have active compute
    far below their streamed weight bytes — the sparsity-gated regime.
    """
    cfg = _arch_config(arch)
    if experts is not None or topk is not None:
        cfg = cfg.replace(
            moe_experts=cfg.moe_experts if experts is None else int(experts),
            moe_topk=cfg.moe_topk if topk is None else int(topk))
    if cfg.moe_experts <= 0 or cfg.moe_topk <= 0:
        raise ValueError(f"llm_moe needs an MoE config (moe_experts/moe_topk"
                         f" > 0), got {cfg.name} with "
                         f"experts={cfg.moe_experts} topk={cfg.moe_topk}")
    tag = (f"{cfg.name}-moe-e{cfg.moe_experts}k{cfg.moe_topk}"
           f"-{mode}-s{seq}-b{batch}")
    return transformer_workload(cfg, seq=seq, batch=batch, mode=mode,
                                name=name or tag)


def acc_class_mix(wl: Workload) -> tuple:
    """MAC-weighted fraction of each ``ACC_CLASSES`` accuracy class.

    The workload-side input to ``AccuracySurrogate``'s per-class
    precision-sensitivity priors: ``sum(mix) == 1`` and an all-default
    workload returns ``(1, 0, 0, ...)`` (which the surrogate maps to the
    exact legacy scalar delta)."""
    macs = np.asarray(wl.layers.macs(), np.float64)
    cls = np.asarray(wl.layers.acc_class, np.float64).astype(np.int64)
    mix = np.zeros(len(ACC_CLASSES), np.float64)
    np.add.at(mix, np.clip(cls, 0, len(ACC_CLASSES) - 1), macs)
    total = mix.sum()
    if total <= 0.0:
        return tuple(1.0 if i == ACC_DEFAULT else 0.0
                     for i in range(len(ACC_CLASSES)))
    return tuple(float(v) for v in mix / total)


# family name -> constructor; each constructor's keyword grid generates the
# model axis (depth/width/resolution for the CNNs, seq/d_model/n_layers for
# the transformer GEMMs, arch x batch x context / expert-count x top-k for
# the LLM serving families).
MODEL_FAMILIES = {
    "resnet-cifar": resnet_cifar,
    "vgg16": vgg16,
    "transformer-gemm": transformer_gemm,
    "llm-decode": llm_decode,
    "llm-moe": llm_moe,
}


# ---------------------------------------------------------------------------
# Layer-count padding + bucketing: the workload side of one-compile joint
# sweeps.  Zero-count padding layers are masked to exact 0.0 in
# dataflow.reduce_layer_costs and the layer fold is strictly sequential,
# so models with different depths can share a fixed (M, L) evaluation
# shape — and one XLA compilation — without perturbing a single result
# (see pad_workload for the exact bit-identity contract).
# ---------------------------------------------------------------------------

# Padding row: every field at its smallest legal value, count=0.  count=0
# zeroes MACs and every traffic/energy term exactly; the remaining fields
# just have to keep the cost model finite (H=R=S=1 -> 1x1 output; the IR
# fields at their neutral values keep the padding on the legacy resident-
# weight path).
_PAD_ROW = dict(H=1.0, W=1.0, C=1.0, K=1.0, R=1.0, S=1.0,
                stride=1.0, batch=1.0, count=0.0, **_IR_DEFAULTS)


def workload_layers(wl: Workload) -> int:
    """Number of stacked layers (including any padding rows)."""
    return int(np.shape(wl.layers.H)[0])


def pad_layers(layers: LayerSpec, n_layers: int) -> LayerSpec:
    """Append zero-cost ``_PAD_ROW`` layers to a stacked (L,) LayerSpec
    up to ``n_layers`` (jit-safe: the depth is a static shape)."""
    pad = n_layers - int(np.shape(layers.H)[0])
    return LayerSpec(*[
        jnp.concatenate([getattr(layers, f),
                         jnp.full((pad,), _PAD_ROW[f], jnp.float32)])
        for f in LayerSpec._fields])


def pad_workload(wl: Workload, n_layers: int) -> Workload:
    """Pad a workload to ``n_layers`` with zero-cost (count=0) layers.

    The padding contract (property-tested): padding rows contribute exact
    0.0 to every summed cost field and weight 0 to the MAC-weighted
    utilization, so ``network_cost`` of the padded workload is
    BIT-IDENTICAL to the unpadded oracle under eager execution and under
    any fixed compiled evaluator shape.  The DSE evaluator pads every
    workload to its ``layer_bucket`` depth, so walks that are compared
    with each other run one compiled shape per bucket.
    Idempotent for ``n_layers`` equal to the current depth; refuses to
    truncate.
    """
    n = workload_layers(wl)
    if n_layers < n:
        raise ValueError(f"cannot pad {wl.name} ({n} layers) down to "
                         f"{n_layers}")
    if n_layers == n:
        return wl
    names = wl.layer_names + tuple(f"pad{i}" for i in range(n_layers - n))
    return Workload(name=wl.name, layers=pad_layers(wl.layers, n_layers),
                    layer_names=names)


def layer_bucket(n_layers: int,
                 buckets: Sequence[int] | None = None) -> int:
    """Canonical padded depth for an ``n_layers``-deep model.

    Default policy: next power of two, floored at 8 — the whole model zoo
    collapses to a handful of canonical depths (the 9-model default axis
    lands on {16, 32, 64} = at most 3 XLA compilations), and a new model
    almost always reuses an existing compiled shape.  Pass explicit
    ``buckets`` (ascending sizes) to override; counts above the largest
    bucket fall back to the power-of-two policy.
    """
    if n_layers < 1:
        raise ValueError(f"n_layers must be >= 1, got {n_layers}")
    if buckets is not None:
        for b in sorted(buckets):
            if n_layers <= b:
                return int(b)
    return max(8, 1 << (n_layers - 1).bit_length())


class StackedWorkload(NamedTuple):
    """M workloads padded to one shared depth and stacked: leaves (M, L).

    The model-lane form consumed by ``dse.evaluate_chunk(model_ids=...)``:
    each evaluation lane gathers its row inside the jitted function, so a
    chunk freely mixes models while hitting one compiled executable.
    """
    names: tuple            # model names, in stack order
    layers: LayerSpec       # stacked+padded, leaves (M, L)
    n_layers: tuple         # true (pre-padding) depth per model


def stack_workloads(workloads: Sequence[Workload],
                    pad_to: int | None = None,
                    buckets: Sequence[int] | None = None) -> StackedWorkload:
    """Stack workloads into an (M, L) pytree at one bucketed depth.

    ``pad_to`` fixes the shared depth explicitly; the default buckets the
    deepest member via ``layer_bucket`` so equal-bucket model sets stack
    to the same shape (= the same compilation).
    """
    workloads = tuple(workloads)
    if not workloads:
        raise ValueError("need at least one workload to stack")
    counts = [workload_layers(w) for w in workloads]
    depth = layer_bucket(max(counts), buckets) if pad_to is None else pad_to
    padded = [pad_workload(w, depth) for w in workloads]
    layers = LayerSpec(*[
        jnp.stack([getattr(p.layers, f) for p in padded])
        for f in LayerSpec._fields])
    return StackedWorkload(names=tuple(w.name for w in workloads),
                           layers=layers, n_layers=tuple(counts))


def workload_macs(wl: Workload, per_inference: bool = False) -> float:
    """Total forward MACs of the workload (the per-model normalizer).

    ``LayerSpec.macs()`` includes the batch factor; ``per_inference=True``
    divides it back out — use that for batch-invariant model properties
    (the accuracy surrogate's capacity), the default for total-work
    normalization matching the cost model's ``res.macs``."""
    m = np.asarray(wl.layers.macs(), np.float64)
    if per_inference:
        m = m / np.asarray(wl.layers.batch, np.float64)
    return float(np.sum(m))
