"""DeepSeek-V3 [moe]: 61L d_model=7168 128H multi-head latent attention
(q_lora 1536, kv_lora 512, qk_nope 128, qk_rope 64, v_head 128)
vocab=129280 — 256 routed experts top-8 of width 2048 + 1 shared; the
first 3 layers dense (d_ff 18432).  [arXiv:2412.19437; hf]"""

from repro.configs.base import ArchConfig

ARCH = ArchConfig(
    name="deepseek-v3", family="moe",
    n_layers=61, d_model=7168, n_heads=128, kv_heads=128, head_dim=192,
    d_ff=18432, vocab=129280,
    q_lora_rank=1536, kv_lora_rank=512, qk_nope_head_dim=128,
    qk_rope_head_dim=64, v_head_dim=128,
    moe_experts=256, moe_topk=8, moe_d_ff=2048, moe_shared=1,
    first_dense=3, dense_d_ff=18432,
)


def reduced():
    return ARCH.replace(n_layers=3, d_model=64, n_heads=4, kv_heads=4,
                        head_dim=24, d_ff=128, vocab=256,
                        q_lora_rank=32, kv_lora_rank=16, qk_nope_head_dim=16,
                        qk_rope_head_dim=8, v_head_dim=16,
                        moe_experts=8, moe_topk=2, moe_d_ff=32,
                        moe_shared=1, first_dense=1, dense_d_ff=128)
