"""qwen3-4b — dense, qk-norm, GQA. [hf:Qwen/Qwen3-8B (family card)]
36L d_model=2560 32H (GQA kv=8) d_ff=9728 vocab=151936, head_dim=128,
tied embeddings.  For the long_500k shape we run the sliding-window
variant (window=4096) — see DESIGN.md shape-skip table."""
import jax.numpy as jnp
from .base import ModelConfig

CONFIG = ModelConfig(
    name="qwen3-4b", family="dense",
    n_layers=36, d_model=2560, n_heads=32, n_kv_heads=8, head_dim=128,
    d_ff=9728, vocab_size=151936, qk_norm=True, tie_embeddings=True,
    rope_theta=1_000_000.0, dtype=jnp.bfloat16, remat=True,
    source="hf:Qwen/Qwen3-8B",
)

# sliding-window variant for the long_500k shape (configs.INPUT_SHAPES)
LONG_CONTEXT = CONFIG.replace(window=4096)

REDUCED = CONFIG.replace(
    n_layers=2, d_model=256, n_heads=4, n_kv_heads=2, head_dim=64,
    d_ff=512, vocab_size=512, dtype=jnp.float32, remat=False,
)
