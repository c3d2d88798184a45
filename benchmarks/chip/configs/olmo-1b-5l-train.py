"""olmo-1b (arXiv:2402.00838; hf allenai/OLMo-1B-hf) at its published widths,
depth cut to 5 of its 16 layers, trained by SlowMo: its plain reference and
its operation counts.  The sizes are in the JSON file beside this one."""
from __future__ import annotations

from reference import dense, slowmo


def program_sizes(config: dict) -> dict:
    """The sizes the program's model must have, by its own attribute names."""
    a = dense.Arch.from_config(config)
    return {"n_layers": a.layers, "d_model": a.d_model, "n_heads": a.heads,
            "n_kv_heads": a.kv_heads, "resolved_head_dim": a.head_dim,
            "d_ff": a.d_ff, "vocab_size": a.vocab, "qk_norm": a.qk_norm,
            "norm_type": a.norm, "tie_embeddings": True}


def params(config: dict) -> int:
    a = dense.Arch.from_config(config)
    attn = a.d_model * a.head_dim * (2 * a.heads + 2 * a.kv_heads)
    return a.layers * (attn + 3 * a.d_model * a.d_ff) + a.vocab * a.d_model


def train_flops_per_token(config: dict, seq: int) -> float:
    """Operations of the forward and backward passes per token (3x the
    forward; recomputation not counted): the matrix products, the tied
    output head, and causal attention (position i attends to i + 1 keys)."""
    a = dense.Arch.from_config(config)
    matmul = params(config)  # every parameter is in one product (tied head)
    attn = a.layers * 2 * a.heads * a.head_dim * (seq + 1)  # QK and PV, causal mean
    return 3.0 * (2.0 * matmul + attn)


def reference_train(config, traffic, key, sampler, *, rounds, workers, rows, seq,
                    lowp=False, half_batch=False):
    sm = config["slowmo"]
    opt = slowmo.Opt(tau=sm["tau"], lr=sm["lr"], alpha=sm["alpha"], beta=sm["beta"],
                     momentum=sm["momentum"], param_dtype=sm["worker_param_dtype"])
    return slowmo.run(dense.Arch.from_config(config), opt, key,
                      lambda r: sampler(r, sm["tau"], rows, seq), rounds, workers,
                      lowp=lowp, half_batch=half_batch)
