"""deepseek-moe-16b [moe]: 28 layers, the first dense (SiLU-gated d_ff
10944) and 27 MoE; d_model=2048, 16 heads of 128 (MHA), vocab 102400,
untied head.  Each MoE layer routes over 64 experts of width 1408, top-6
by softmax, the six gates *not* renormalised (``norm_topk_prob`` false),
plus 2 shared experts applied as one 2816-wide SiLU-gated MLP;
rms_norm_eps 1e-6.  [hf:deepseek-ai/deepseek-moe-16b-base config.json;
DeepSeekMoE, arXiv:2401.06066]"""
from repro.models.config import ModelConfig

CONFIG = ModelConfig(
    name="deepseek-moe-16b", family="moe",
    num_layers=28, d_model=2048, num_heads=16, num_kv_heads=16,
    d_ff=10944, vocab_size=102400, head_dim=128,
    num_experts=64, num_shared_experts=2, top_k=6, moe_d_ff=1408,
    first_dense_layers=1, norm_topk_prob=False, norm_eps=1e-6,
    remat="dots",
)

SMOKE = ModelConfig(
    name="deepseek-moe-smoke", family="moe",
    num_layers=3, d_model=64, num_heads=4, num_kv_heads=4,
    d_ff=128, vocab_size=256, head_dim=16,
    num_experts=16, num_shared_experts=2, top_k=3, moe_d_ff=32,
    first_dense_layers=1, norm_topk_prob=False, norm_eps=1e-6,
    attn_chunk=32,
)
