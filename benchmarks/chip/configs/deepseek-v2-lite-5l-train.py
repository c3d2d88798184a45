"""DeepSeek-V2-Lite (arXiv:2405.04434; hf deepseek-ai/DeepSeek-V2-Lite) at
its published widths, cut to 5 of its 27 layers (the dense layer and 4 MoE
layers), 8 of each MoE layer's 64 experts held, and an eighth of the
vocabulary, trained by SlowMo: its plain reference and its operation
counts.  The sizes are in the JSON file beside this one."""
from __future__ import annotations

from reference import latent_moe, slowmo_loop


def program_sizes(config: dict) -> dict:
    """The sizes the program's model must have, by its own attribute names."""
    a = latent_moe.Arch.from_config(config)
    return {"n_layers": a.layers, "first_k_dense": a.dense_layers,
            "d_model": a.d_model, "n_heads": a.heads,
            "kv_lora_rank": a.kv_lora_rank, "qk_nope_head_dim": a.qk_nope,
            "qk_rope_head_dim": a.qk_rope, "v_head_dim": a.v_dim,
            "dense_d_ff": a.d_ff, "moe_d_ff": a.moe_d_ff, "n_experts": a.experts,
            "held_experts": a.held, "top_k": a.top_k, "n_shared_experts": a.shared,
            "vocab_size": a.vocab, "rope_theta": a.rope_theta,
            "norm_topk_prob": config["norm_topk_prob"],
            "aux_loss_coef": a.aux_alpha, "tie_embeddings": config["tie_word_embeddings"]}


def _layer_params(a) -> dict:
    d, H = a.d_model, a.heads
    attn = (d * H * (a.qk_nope + a.qk_rope) + d * (a.kv_lora_rank + a.qk_rope)
            + a.kv_lora_rank * H * (a.qk_nope + a.v_dim) + H * a.v_dim * d)
    return {"attn": attn, "dense_mlp": 3 * d * a.d_ff, "router": d * a.experts,
            "expert": 3 * d * a.moe_d_ff, "shared": 3 * d * a.shared * a.moe_d_ff,
            "norms": 2 * d + a.kv_lora_rank}


def params(config: dict) -> int:
    """Parameters held on the chip: the held experts, not the absent ones."""
    a = latent_moe.Arch.from_config(config)
    p = _layer_params(a)
    dense = p["attn"] + p["dense_mlp"] + p["norms"]
    moe = p["attn"] + p["router"] + a.held * p["expert"] + p["shared"] + p["norms"]
    Lm = a.layers - a.dense_layers
    return a.dense_layers * dense + Lm * moe + 2 * a.vocab * a.d_model + a.d_model


def train_flops_per_token(config: dict, seq: int) -> float:
    """Operations of the forward and backward passes per token (3x the
    forward; recomputation not counted): the matrix products, with the held
    experts at the balanced routed count (top_k * held / experts a token),
    the untied output head, and causal attention (position i attends to
    i + 1 keys; q.k over dn + dr dims, p.v over dv)."""
    a = latent_moe.Arch.from_config(config)
    p = _layer_params(a)
    routed = a.top_k * a.held / a.experts * p["expert"]
    Lm = a.layers - a.dense_layers
    matmul = (a.dense_layers * (p["attn"] + p["dense_mlp"])
              + Lm * (p["attn"] + p["router"] + routed + p["shared"])
              + a.d_model * a.vocab)
    attn = a.layers * a.heads * (a.qk_nope + a.qk_rope + a.v_dim) * (seq + 1)
    return 3.0 * (2.0 * matmul + attn)


def expert_gmm_flops_per_round(config: dict, traffic: dict) -> float:
    """Operations of every ``expert_gmm`` call in one round at the balanced
    routed count (rows a step: tokens * top_k * held / experts, padding not
    counted): per MoE layer and step the forward (gate/up, then down: 6
    rows d f), its recompute under remat (6 rows d f) and the backward's two
    products per forward product (12 rows d f)."""
    a = latent_moe.Arch.from_config(config)
    tokens = traffic["workers"] * traffic["rows_per_device"] * traffic["seq"]
    rows = tokens * a.top_k * a.held / a.experts
    per_layer_step = 24.0 * rows * a.d_model * a.moe_d_ff
    return config["slowmo"]["tau"] * (a.layers - a.dense_layers) * per_layer_step


def reference_train(config, traffic, key, sampler, *, rounds, workers, rows, seq,
                    lowp=False, half_batch=False):
    sm = config["slowmo"]
    opt = slowmo_loop.Opt(tau=sm["tau"], lr=sm["lr"], alpha=sm["alpha"], beta=sm["beta"],
                          momentum=sm["momentum"], param_dtype=sm["worker_param_dtype"])
    return slowmo_loop.run(latent_moe, latent_moe.Arch.from_config(config), opt, key,
                           lambda r: sampler(r, sm["tau"], rows, seq), rounds, workers,
                           lowp=lowp, half_batch=half_batch)
