"""deepseek-v2-lite — multi-head latent attention + fine-grained MoE.
[arXiv:2405.04434; hf deepseek-ai/DeepSeek-V2-Lite]  27L d_model=2048 16H,
MLA (kv_lora_rank 512, no q compression, qk 128 + rope 64, v 128, YaRN x40
over 4096), first layer dense (d_ff 10944), then 64 routed experts top-6
(d_ff 1408, softmax gates not renormalised) + 2 shared, vocab 102400."""
import jax.numpy as jnp
from .base import ModelConfig, Yarn

CONFIG = ModelConfig(
    name="deepseek-v2-lite", family="moe",
    n_layers=27, d_model=2048, n_heads=16, n_kv_heads=16,
    d_ff=1408, moe_d_ff=1408, vocab_size=102400,
    n_experts=64, n_shared_experts=2, top_k=6,
    first_k_dense=1, dense_d_ff=10944,
    norm_topk_prob=False, moe_aux="seq", aux_loss_coef=0.001,
    kv_lora_rank=512, qk_nope_head_dim=128, qk_rope_head_dim=64, v_head_dim=128,
    yarn=Yarn(factor=40.0, original_max_position=4096, beta_fast=32.0,
              beta_slow=1.0, mscale=0.707, mscale_all_dim=0.707),
    dtype=jnp.bfloat16, remat=True,
    source="https://huggingface.co/deepseek-ai/DeepSeek-V2-Lite (arXiv:2405.04434)",
)

REDUCED = CONFIG.replace(
    n_layers=2, d_model=128, n_heads=4, n_kv_heads=4,
    moe_d_ff=32, d_ff=32, dense_d_ff=128, n_experts=4, top_k=2,
    kv_lora_rank=32, qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=16,
    vocab_size=256, dtype=jnp.float32, remat=False,
)
