"""One decode step of a DeepSeek-V2/V3-style model with multi-head latent
attention (MLA), as GEMMs: one new token per sequence against a
``context``-long latent cache.

Per layer, from the published ``config.json`` widths in ``widths``:
the query's low-rank path, hidden -> q_lora_rank -> heads x (nope +
rope); the new position's latent, hidden -> kv_lora_rank + rope.  The
cache holds that latent alone, shared by every head, and decode runs
attention in its absorbed form:

* each head's no-rope query is mapped into the latent, nope ->
  kv_lora_rank (``W_UK`` folded into the query);
* the score product, all heads against the cached latent: M = heads,
  (kv_lora_rank + rope) x context, the latent streamed from memory at
  activation width (``kind`` 2, ``stream_words`` = context x
  (kv_lora_rank + rope)), read once per sequence whatever the head count;
* the value product, the probabilities against the cached ``c_kv``: M =
  heads, context x kv_lora_rank, streaming context x kv_lora_rank words;
* each head's latent sum mapped out, kv_lora_rank -> v_head_dim
  (``W_UV``), then the output projection, heads x v_head_dim -> hidden.

Then the FFN: the first ``first_k_dense_replace`` layers dense (gate and
up fused, then down); the rest routed experts that compute for top-k
(token, expert) pairs while their weights are read once per touched
expert (``kind`` 3, ``active_frac`` = 1 / experts a uniform router
touches over ``batch`` tokens), the shared experts as plain resident
GEMMs, and the router, hidden x experts.  Then the LM head.

Accuracy classes: 1 attention, 2 FFN and router, 3 experts, 0 head."""

from bench.reference.families import gemm, table

ATTN, FFN, EXPERT = 1, 2, 3


def build(name: str, widths: dict, context: int, batch: int = 1) -> dict:
    d, n_layers = widths["hidden_size"], widths["num_hidden_layers"]
    heads = widths["num_attention_heads"]
    q_rank, kv_rank = widths["q_lora_rank"], widths["kv_lora_rank"]
    nope, rope = widths["qk_nope_head_dim"], widths["qk_rope_head_dim"]
    v_dim = widths["v_head_dim"]
    experts, topk = widths["n_routed_experts"], widths["num_experts_per_tok"]
    n_dense = widths["first_k_dense_replace"]
    n_moe = n_layers - n_dense
    rows = []

    def add(m, kd, n, count, **ir):
        rows.append(gemm(m, kd, n, batch=batch, count=count, **ir))

    add(1, d, q_rank, n_layers, acc_class=ATTN)
    add(1, q_rank, heads * (nope + rope), n_layers, acc_class=ATTN)
    add(1, d, kv_rank + rope, n_layers, acc_class=ATTN)
    add(1, nope, kv_rank, n_layers * heads, acc_class=ATTN)
    add(heads, kv_rank + rope, context, n_layers, kind=2.0,
        stream_words=float(context * (kv_rank + rope)), acc_class=ATTN)
    add(heads, context, kv_rank, n_layers, kind=2.0,
        stream_words=float(context * kv_rank), acc_class=ATTN)
    add(1, kv_rank, v_dim, n_layers * heads, acc_class=ATTN)
    add(1, heads * v_dim, d, n_layers, acc_class=ATTN)

    dense_ff, moe_ff = widths["intermediate_size"], \
        widths["moe_intermediate_size"]
    add(1, d, 2 * dense_ff, n_dense, acc_class=FFN)
    add(1, dense_ff, d, n_dense, acc_class=FFN)
    # a uniform router's expected distinct experts over `batch` tokens:
    # E (1 - (1 - k/E)^T), at least k
    touched = max(experts * (1.0 - (1.0 - topk / experts) ** batch),
                  float(topk))
    gated = dict(kind=3.0, active_frac=1.0 / touched, acc_class=EXPERT)
    add(topk, d, 2 * moe_ff, n_moe, **gated)
    add(topk, moe_ff, d, n_moe, **gated)
    shared = widths["n_shared_experts"]
    add(1, d, 2 * moe_ff, n_moe * shared, acc_class=EXPERT)
    add(1, moe_ff, d, n_moe * shared, acc_class=EXPERT)
    add(1, d, experts, n_moe, acc_class=FFN)
    add(1, d, widths["vocab_size"], 1)
    return table(name, rows)
