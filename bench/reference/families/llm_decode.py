"""One decode step of a decoder-only transformer, as GEMMs: a single
new token per sequence against a ``context``-long KV cache.

Per layer: the Q, K, V and output projections; per head, the score
(q . K^T) and value (p . V) products, whose second operand is the
sequence's KV cache streamed from memory at activation width
(``kind`` 2, ``stream_words`` = context x head_dim); the FFN (gate and
up fused, then down).  In a mixture-of-experts layer the routed experts
compute for top-k (token, expert) pairs while their weights are read
once per touched expert (``kind`` 3, ``active_frac`` = 1 / experts
touched), the shared experts are plain
resident GEMMs, and the router is a d x experts GEMM.  The first
``first_k_dense_replace`` layers of a MoE model are dense.  Then the LM
head.  Widths are the published ``config.json`` values in ``widths``.

Accuracy classes: 1 attention, 2 FFN and router, 3 experts, 0 head."""

from bench.reference.families import gemm, table

ATTN, FFN, EXPERT = 1, 2, 3


def build(name: str, widths: dict, context: int, batch: int = 1) -> dict:
    d, n_layers = widths["hidden_size"], widths["num_hidden_layers"]
    hq, hkv = widths["num_attention_heads"], widths["num_key_value_heads"]
    dh = widths.get("head_dim", d // hq)
    experts = widths.get("n_routed_experts", widths.get("num_local_experts", 0))
    topk = widths.get("num_experts_per_tok", 0)
    rows = []

    def add(m, kd, n, count, **ir):
        rows.append(gemm(m, kd, n, batch=batch, count=count, **ir))

    for kd, n in ((d, hq * dh), (d, hkv * dh), (d, hkv * dh), (hq * dh, d)):
        add(1, kd, n, n_layers, acc_class=ATTN)
    kv = dict(kind=2.0, stream_words=float(context * dh), acc_class=ATTN)
    add(1, dh, context, n_layers * hq, **kv)
    add(1, context, dh, n_layers * hq, **kv)
    if experts:
        n_dense = widths.get("first_k_dense_replace", 0)
        moe_ff = widths.get("moe_intermediate_size",
                            widths["intermediate_size"])
        dense_ff = widths["intermediate_size"]
    else:
        n_dense, dense_ff = n_layers, widths["intermediate_size"]
    n_moe = n_layers - n_dense
    if n_dense:
        add(1, d, 2 * dense_ff, n_dense, acc_class=FFN)
        add(1, dense_ff, d, n_dense, acc_class=FFN)
    if n_moe:
        # a uniform router's expected distinct experts over `batch`
        # tokens: E (1 - (1 - k/E)^T), at least k
        touched = max(experts * (1.0 - (1.0 - topk / experts) ** batch),
                      float(topk))
        gated = dict(kind=3.0, active_frac=1.0 / touched, acc_class=EXPERT)
        add(topk, d, 2 * moe_ff, n_moe, **gated)
        add(topk, moe_ff, d, n_moe, **gated)
        shared = widths.get("n_shared_experts", 0)
        if shared:
            add(1, d, 2 * moe_ff, n_moe * shared, acc_class=EXPERT)
            add(1, moe_ff, d, n_moe * shared, acc_class=EXPERT)
        add(1, d, experts, n_moe, acc_class=FFN)
    add(1, d, widths["vocab_size"], 1)
    return table(name, rows)
