"""kimi-k2-1t-a32b — Kimi K2 trillion-param MoE (paper-table spec).
[hf moonshotai/Kimi-K2-Base]  61L d_model=7168 64H (GQA kv=8) vocab=163840,
MoE 384 routed experts top-8 (d_ff 2048) + 1 shared, first layer dense.
Assignment specifies GQA attention (the K2 release uses MLA; we follow the
assigned spec)."""
import jax.numpy as jnp
from .base import ModelConfig

CONFIG = ModelConfig(
    name="kimi-k2-1t-a32b", family="moe",
    n_layers=61, d_model=7168, n_heads=64, n_kv_heads=8,
    d_ff=2048, moe_d_ff=2048, vocab_size=163840,
    n_experts=384, n_shared_experts=1, top_k=8,
    first_k_dense=1, dense_d_ff=18432,
    rope_theta=50_000.0, dtype=jnp.bfloat16, remat=True,
    source="https://huggingface.co/moonshotai/Kimi-K2-Base",
)

REDUCED = CONFIG.replace(
    n_layers=2, d_model=256, n_heads=4, n_kv_heads=2, head_dim=None,
    moe_d_ff=128, d_ff=128, dense_d_ff=512, n_experts=4, top_k=2,
    n_shared_experts=1, vocab_size=512, dtype=jnp.float32, remat=False,
)
