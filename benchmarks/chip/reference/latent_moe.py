"""Plain reference of a DeepSeek-V2 decoder (multi-head latent attention,
one leading dense layer, then fine-grained MoE layers), in float32.

Written from the paper (arXiv:2405.04434, sections 2.1-2.2) and the
published ``modeling_deepseek.py`` (``DeepseekV2Attention``,
``DeepseekV2YarnRotaryEmbedding``, ``MoEGate``, ``DeepseekV2MoE``), in
``jax.numpy`` with no kernels, cache, batching or grouping.  It imports
nothing of the program under test.  Every matrix product goes through
``mm``/``einsum`` under ``jax.default_matmul_precision("highest")`` (set by
the callers); ``lowp=True`` is the control, with every operand of every
product rounded to float8 (e4m3) first.

A layer holds experts 0 .. H-1 of the router's E: the router scores all E
and picks the top k, and the layer adds the held experts' part of
sum_e g_e FFN_e(x), computed densely for every token and masked by the
gates (no capacity, nothing dropped).  Departures from the published code,
shared with the program: the rope columns are in the half-split layout
(pairs (i, i + dr/2)), a fixed relabelling of the published interleaved
ones; the balance loss enters the gradient and not the loss value, as
``AddAuxiliaryLoss`` does, with alpha from the configuration; norms are
stored as 1 + scale.

Parameter trees are named as the program names them, with a leading layer
axis on block leaves; ``trunc_normal_init`` draws them from a key as the
program does (its convention, restated here).
"""
from __future__ import annotations

import dataclasses
import math

import jax
import jax.numpy as jnp

F8 = jnp.float8_e4m3fn


@dataclasses.dataclass(frozen=True)
class Arch:
    layers: int
    dense_layers: int
    d_model: int
    heads: int
    kv_lora_rank: int
    qk_nope: int
    qk_rope: int
    v_dim: int
    d_ff: int  # the dense layers' SwiGLU width
    moe_d_ff: int  # one expert's width
    experts: int  # the router's outputs
    held: int  # experts 0 .. held - 1 computed here
    top_k: int
    shared: int  # shared experts, one SwiGLU of shared * moe_d_ff
    vocab: int
    rope_theta: float
    yarn: tuple  # (factor, original max position, beta_fast, beta_slow, mscale, mscale_all_dim)
    eps: float
    aux_alpha: float

    @classmethod
    def from_config(cls, c: dict) -> "Arch":
        """From a configuration file's keys (Hugging Face names); a cut
        ``n_routed_experts`` is the experts held, beside the published count
        the router keeps."""
        y = c["rope_scaling"]
        return cls(
            layers=c["num_hidden_layers"],
            dense_layers=c["first_k_dense_replace"],
            d_model=c["hidden_size"],
            heads=c["num_attention_heads"],
            kv_lora_rank=c["kv_lora_rank"],
            qk_nope=c["qk_nope_head_dim"],
            qk_rope=c["qk_rope_head_dim"],
            v_dim=c["v_head_dim"],
            d_ff=c["intermediate_size"],
            moe_d_ff=c["moe_intermediate_size"],
            experts=c.get("published_n_routed_experts", c["n_routed_experts"]),
            held=c["n_routed_experts"],
            top_k=c["num_experts_per_tok"],
            shared=c["n_shared_experts"],
            vocab=c["vocab_size"],
            rope_theta=float(c["rope_theta"]),
            yarn=(float(y["factor"]), int(y["original_max_position_embeddings"]),
                  float(y["beta_fast"]), float(y["beta_slow"]), float(y["mscale"]),
                  float(y["mscale_all_dim"])),
            eps=float(c["rms_norm_eps"]),
            aux_alpha=float(c["aux_loss_alpha"]),
        )


def mm(a, b, lowp: bool = False):
    if lowp:
        a = a.astype(F8).astype(jnp.float32)
        b = b.astype(F8).astype(jnp.float32)
    return jnp.matmul(a, b)


def einsum(spec, a, b, lowp: bool = False):
    if lowp:
        a = a.astype(F8).astype(jnp.float32)
        b = b.astype(F8).astype(jnp.float32)
    return jnp.einsum(spec, a, b)


def rms(x, scale, eps):
    y = x / jnp.sqrt(jnp.mean(jnp.square(x), -1, keepdims=True) + eps)
    return y if scale is None else y * (1.0 + scale)


# --- YaRN (DeepseekV2YarnRotaryEmbedding) ----------------------------------

def _yarn_get_mscale(scale: float, mscale: float) -> float:
    return 0.1 * mscale * math.log(scale) + 1.0 if scale > 1 else 1.0


def _find_correction_dim(num_rotations, dim, base, max_pos):
    return (dim * math.log(max_pos / (num_rotations * 2 * math.pi))) / (2 * math.log(base))


def yarn_inv_freq(arch: Arch):
    """The (dr/2,) inverse frequencies of the rope dims."""
    dim, base = arch.qk_rope, arch.rope_theta
    factor, orig, beta_fast, beta_slow, _, _ = arch.yarn
    freq_extra = 1.0 / (base ** (jnp.arange(0, dim, 2, dtype=jnp.float32) / dim))
    freq_inter = 1.0 / (factor * base ** (jnp.arange(0, dim, 2, dtype=jnp.float32) / dim))
    low = max(math.floor(_find_correction_dim(beta_fast, dim, base, orig)), 0)
    high = min(math.ceil(_find_correction_dim(beta_slow, dim, base, orig)), dim - 1)
    if low == high:
        high += 0.001
    ramp = jnp.clip((jnp.arange(dim // 2, dtype=jnp.float32) - low) / (high - low), 0, 1)
    inv_freq_mask = 1.0 - ramp
    return freq_inter * (1 - inv_freq_mask) + freq_extra * inv_freq_mask


def rope(x, positions, arch: Arch):
    """x (T, heads, dr) rotated at ``positions`` (T,): pairs (i, i + dr/2)
    by the YaRN frequencies, cos and sin scaled by mscale/mscale_all_dim."""
    factor, _, _, _, mscale, mscale_all = arch.yarn
    m = _yarn_get_mscale(factor, mscale) / _yarn_get_mscale(factor, mscale_all)
    ang = positions[:, None].astype(jnp.float32) * yarn_inv_freq(arch)
    cos = (jnp.cos(ang) * m)[:, None]
    sin = (jnp.sin(ang) * m)[:, None]
    d = x.shape[-1]
    x1, x2 = x[..., : d // 2], x[..., d // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def softmax_scale(arch: Arch) -> float:
    factor, _, _, _, _, mscale_all = arch.yarn
    s = (arch.qk_nope + arch.qk_rope) ** -0.5
    if mscale_all:
        m = _yarn_get_mscale(factor, mscale_all)
        s = s * m * m
    return s


# --- layers over one sequence x (T, d) --------------------------------------

def mla(arch: Arch, a: dict, h, positions, lowp: bool = False):
    T = h.shape[0]
    H, dn, dr, dv, r = arch.heads, arch.qk_nope, arch.qk_rope, arch.v_dim, arch.kv_lora_rank
    q = mm(h, a["wq"], lowp).reshape(T, H, dn + dr)
    q_nope, q_pe = q[..., :dn], q[..., dn:]
    ckv = mm(h, a["wkv_a"], lowp)
    c, k_pe = ckv[:, :r], ckv[:, r:]
    kv = mm(rms(c, a["kv_norm"], arch.eps), a["wkv_b"], lowp).reshape(T, H, dn + dv)
    k_nope, v = kv[..., :dn], kv[..., dn:]
    q_pe = rope(q_pe, positions, arch)
    k_pe = rope(k_pe[:, None, :], positions, arch)
    qf = jnp.concatenate([q_nope, q_pe], -1)
    kf = jnp.concatenate([k_nope, jnp.broadcast_to(k_pe, (T, H, dr))], -1)
    s = einsum("qhd,khd->hqk", qf, kf, lowp) * softmax_scale(arch)
    s = jnp.where(positions[None, :, None] >= positions[None, None, :], s, -jnp.inf)
    o = einsum("hqk,khd->qhd", jax.nn.softmax(s, axis=-1), v, lowp)
    return mm(o.reshape(T, H * dv), a["wo"], lowp)


def swiglu(h, w_gate, w_up, wo, lowp: bool = False):
    return mm(jax.nn.silu(mm(h, w_gate, lowp)) * mm(h, w_up, lowp), wo, lowp)


def gate(arch: Arch, router, h, lowp: bool = False):
    """(T, E) gates: the softmax values of each token's top k experts (not
    renormalised), zero elsewhere; and the sequence's balance loss."""
    T = h.shape[0]
    probs = jax.nn.softmax(mm(h, router, lowp), axis=-1)
    vals, idx = jax.lax.top_k(probs, arch.top_k)
    g = jnp.zeros_like(probs).at[jnp.arange(T)[:, None], idx].set(vals)
    picks = jnp.zeros((arch.experts,)).at[idx.reshape(-1)].add(1.0)
    ce = picks / (T * arch.top_k / arch.experts)
    aux = jnp.sum(ce * jnp.mean(probs, axis=0))
    return g, aux


def experts(arch: Arch, p: dict, h, lowp: bool = False):
    """The held experts' part of sum_e g_e FFN_e(h), every expert over every
    token, masked by the gates; plus the shared experts; and the loss."""
    g, aux = gate(arch, p["router"], h, lowp)
    f = arch.moe_d_ff
    wi = p["wi"]  # (held, d, 2f): gate columns, then up
    a = jax.nn.silu(einsum("td,edf->tef", h, wi[..., :f], lowp)) * einsum(
        "td,edf->tef", h, wi[..., f:], lowp)
    y = einsum("tef,efd->ted", a, p["wo"], lowp)
    out = jnp.einsum("te,ted->td", g[:, : arch.held], y)
    s = p["shared"]
    return out + swiglu(h, s["w_gate"], s["w_up"], s["wo"], lowp), aux


def dense_block(arch: Arch, p: dict, x, positions, lowp: bool = False):
    x = x + mla(arch, p["attn"], rms(x, p["ln1"], arch.eps), positions, lowp)
    m = p["mlp"]
    return x + swiglu(rms(x, p["ln2"], arch.eps), m["w_gate"], m["w_up"], m["wo"], lowp)


def moe_block(arch: Arch, p: dict, x, positions, lowp: bool = False):
    x = x + mla(arch, p["attn"], rms(x, p["ln1"], arch.eps), positions, lowp)
    ff, aux = experts(arch, p, rms(x, p["ln2"], arch.eps), lowp)
    return x + ff, aux


def logits_and_aux(arch: Arch, params: dict, tokens, lowp: bool = False, remat: bool = False):
    """Logits (T, V) of one sequence and its balance loss summed over the
    MoE layers."""
    x = params["embed"][tokens]
    pos = jnp.arange(tokens.shape[0])
    dbody = lambda x, p: (dense_block(arch, p, x, pos, lowp), None)  # noqa: E731
    mbody = lambda x, p: moe_block(arch, p, x, pos, lowp)  # noqa: E731
    if remat:
        dbody, mbody = jax.checkpoint(dbody), jax.checkpoint(mbody)
    x, _ = jax.lax.scan(dbody, x, params["dense_blocks"])
    x, auxs = jax.lax.scan(mbody, x, params["moe_blocks"])
    x = rms(x, params["final_norm"], arch.eps)
    return mm(x, params["lm_head"], lowp), jnp.sum(auxs)


def loss(arch: Arch, params: dict, tokens, lowp: bool = False):
    """Mean next-token cross-entropy of one sequence; the balance loss
    times alpha adds to the gradient and not to the value.  Every token is
    an input, as the published model takes ``input_ids``: the router sees
    the last one too, whose logits predict nothing."""
    lg, aux = logits_and_aux(arch, params, tokens, lowp, remat=True)
    lg = lg[:-1]
    lse = jax.nn.logsumexp(lg, axis=-1)
    gold = jnp.take_along_axis(lg, tokens[1:, None], axis=-1)[:, 0]
    return jnp.mean(lse - gold) + arch.aux_alpha * (aux - jax.lax.stop_gradient(aux))


def trunc_normal_init(arch: Arch, key) -> dict:
    """Random initial parameters as the training program draws them from
    ``key``: truncated normal in [-2, 2] times fan-in^-1/2 for matrices
    (fan-in: the second-last dim), N(0, 0.02) for the embedding, zeros for
    norm scales; the keys split as the program splits them."""

    def dense(k, shape):
        return jax.random.truncated_normal(k, -2.0, 2.0, shape) * shape[-2] ** -0.5

    d, H = arch.d_model, arch.heads
    dn, dr, dv, r = arch.qk_nope, arch.qk_rope, arch.v_dim, arch.kv_lora_rank

    def attn(k, L):
        ks = jax.random.split(k, 4)
        return {
            "wq": dense(ks[0], (L, d, H * (dn + dr))),
            "wkv_a": dense(ks[1], (L, d, r + dr)),
            "kv_norm": jnp.zeros((L, r)),
            "wkv_b": dense(ks[2], (L, r, H * (dn + dv))),
            "wo": dense(ks[3], (L, H * dv, d)),
        }

    keys = jax.random.split(key, 12)
    Ld, Lm = arch.dense_layers, arch.layers - arch.dense_layers
    f, fs = arch.moe_d_ff, arch.shared * arch.moe_d_ff
    ks = jax.random.split(keys[0], 5)
    k1, k2, k3 = jax.random.split(ks[4], 3)
    moe = {
        "attn": attn(ks[0], Lm),
        "router": dense(ks[1], (Lm, d, arch.experts)),
        "wi": dense(ks[2], (Lm, arch.held, d, 2 * f)),
        "wo": dense(ks[3], (Lm, arch.held, f, d)),
        "ln1": jnp.zeros((Lm, d)),
        "ln2": jnp.zeros((Lm, d)),
        "shared": {
            "w_gate": dense(k1, (Lm, d, fs)),
            "w_up": dense(k3, (Lm, d, fs)),
            "wo": dense(k2, (Lm, fs, d)),
        },
    }
    km, kw = jax.random.split(keys[2])
    kg, ku = jax.random.split(km)
    dense_blocks = {
        "attn": attn(keys[1], Ld),
        "mlp": {
            "w_gate": dense(kg, (Ld, d, arch.d_ff)),
            "w_up": dense(ku, (Ld, d, arch.d_ff)),
            "wo": dense(kw, (Ld, arch.d_ff, d)),
        },
        "ln1": jnp.zeros((Ld, d)),
        "ln2": jnp.zeros((Ld, d)),
    }
    return {
        "moe_blocks": moe,
        "dense_blocks": dense_blocks,
        "embed": jax.random.normal(keys[3], (arch.vocab, d)) * 0.02,
        "lm_head": dense(keys[4], (d, arch.vocab)),
        "final_norm": jnp.zeros((d,)),
    }
