"""deepseek-moe-16b — fine-grained MoE: 2 shared + 64 routed top-6.
[arXiv:2401.06066]  28L d_model=2048 16H (MHA) per-expert d_ff=1408
vocab=102400, first layer dense (d_ff 10944)."""
import jax.numpy as jnp
from .base import ModelConfig

CONFIG = ModelConfig(
    name="deepseek-moe-16b", family="moe",
    n_layers=28, d_model=2048, n_heads=16, n_kv_heads=16,
    d_ff=1408, moe_d_ff=1408, vocab_size=102400,
    n_experts=64, n_shared_experts=2, top_k=6,
    first_k_dense=1, dense_d_ff=10944,
    dtype=jnp.bfloat16, remat=True, source="arXiv:2401.06066",
)

REDUCED = CONFIG.replace(
    n_layers=2, d_model=256, n_heads=4, n_kv_heads=4,
    moe_d_ff=128, d_ff=128, dense_d_ff=512, n_experts=4, top_k=2,
    n_shared_experts=1, vocab_size=512, dtype=jnp.float32, remat=False,
)
