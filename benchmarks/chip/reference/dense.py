"""Plain reference of a dense decoder-only transformer, in float32.

Written from the published descriptions (OLMo: non-parametric LayerNorm,
SwiGLU, RoPE, tied embeddings; Qwen3: RMSNorm, per-head q/k RMSNorm, GQA,
SwiGLU, RoPE, tied embeddings), in ``jax.numpy`` with no kernels, cache or
batching.  It imports nothing of the program under test.  Every matrix
product goes through ``mm`` under ``jax.default_matmul_precision("highest")``
(set by the callers), so on a TPU it runs in full float32.

``lowp=True`` is the control: every operand of every matrix product is
rounded to float8 (e4m3) first, the nearest precision below the bfloat16
that the configurations state for their compute.

``Arch`` holds the sizes; parameter trees are named as the program names
them (``blocks/attn/wq`` ...), with a leading layer axis on block leaves.
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp

F8 = jnp.float8_e4m3fn


@dataclasses.dataclass(frozen=True)
class Arch:
    layers: int
    d_model: int
    heads: int
    kv_heads: int
    head_dim: int
    d_ff: int
    vocab: int
    norm: str  # "nonparam_ln" (OLMo) | "rmsnorm" (Qwen3)
    qk_norm: bool
    rope_theta: float
    ln_eps: float

    @classmethod
    def from_config(cls, c: dict) -> "Arch":
        """From a configuration file's keys (Hugging Face names)."""
        heads = c["num_attention_heads"]
        return cls(
            layers=c["num_hidden_layers"],
            d_model=c["hidden_size"],
            heads=heads,
            kv_heads=c.get("num_key_value_heads", heads),
            head_dim=c.get("head_dim") or c["hidden_size"] // heads,
            d_ff=c["intermediate_size"],
            vocab=c["vocab_size"],
            norm="rmsnorm" if "rms_norm_eps" in c else "nonparam_ln",
            qk_norm=c.get("model_type") == "qwen3",
            rope_theta=float(c["rope_theta"]),
            ln_eps=float(c.get("rms_norm_eps", 1e-5)),
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


def norm(arch: Arch, x, scale=None):
    if arch.norm == "nonparam_ln":
        mu = jnp.mean(x, -1, keepdims=True)
        var = jnp.mean(jnp.square(x - mu), -1, keepdims=True)
        return (x - mu) / jnp.sqrt(var + arch.ln_eps)
    return rms(x, scale, arch.ln_eps)


def rms(x, scale, eps):
    y = x / jnp.sqrt(jnp.mean(jnp.square(x), -1, keepdims=True) + eps)
    # scales are stored zero-centred: the weight is 1 + scale
    return y if scale is None else y * (1.0 + scale)


def rope(x, positions, theta: float):
    """Rotary embedding, halves rotated (x: (T, H, D), positions: (T,))."""
    d = x.shape[-1]
    inv = theta ** (-jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    ang = positions[:, None].astype(jnp.float32) * inv  # (T, D/2)
    cos, sin = jnp.cos(ang)[:, None], jnp.sin(ang)[:, None]
    x1, x2 = x[..., : d // 2], x[..., d // 2 :]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def block(arch: Arch, p: dict, x, lowp: bool = False):
    """One layer over one sequence x (T, d); ``p`` holds this layer's leaves
    (no layer axis).  Causal attention from position 0."""
    T = x.shape[0]
    a, m = p["attn"], p["mlp"]
    h = norm(arch, x, p.get("ln1"))
    q = mm(h, a["wq"], lowp).reshape(T, arch.heads, arch.head_dim)
    k = mm(h, a["wk"], lowp).reshape(T, arch.kv_heads, arch.head_dim)
    v = mm(h, a["wv"], lowp).reshape(T, arch.kv_heads, arch.head_dim)
    if arch.qk_norm:
        q = rms(q, a["q_norm"], arch.ln_eps)
        k = rms(k, a["k_norm"], arch.ln_eps)
    pos = jnp.arange(T)
    q, k = rope(q, pos, arch.rope_theta), rope(k, pos, arch.rope_theta)
    group = arch.heads // arch.kv_heads
    k, v = jnp.repeat(k, group, axis=1), jnp.repeat(v, group, axis=1)
    s = einsum("qhd,khd->hqk", q * arch.head_dim**-0.5, k, lowp)
    s = jnp.where(pos[None, :, None] >= pos[None, None, :], s, -jnp.inf)
    o = einsum("hqk,khd->qhd", jax.nn.softmax(s, axis=-1), v, lowp)
    x = x + mm(o.reshape(T, -1), a["wo"], lowp)
    h = norm(arch, x, p.get("ln2"))
    g = jax.nn.silu(mm(h, m["w_gate"], lowp)) * mm(h, m["w_up"], lowp)
    return x + mm(g, m["wo"], lowp)


def logits(arch: Arch, params: dict, tokens, lowp: bool = False, remat: bool = False):
    """Logits (T, V) of one sequence through every layer."""
    x = params["embed"][tokens]
    body = lambda x, p: (block(arch, p, x, lowp), None)  # noqa: E731
    if remat:
        body = jax.checkpoint(body)
    x, _ = jax.lax.scan(body, x, params["blocks"])
    x = norm(arch, x, params.get("final_norm"))
    return mm(x, params["embed"].T, lowp)


def loss(arch: Arch, params: dict, tokens, lowp: bool = False):
    """Mean next-token cross-entropy of one sequence."""
    lg = logits(arch, params, tokens[:-1], lowp, remat=True)
    lse = jax.nn.logsumexp(lg, axis=-1)
    gold = jnp.take_along_axis(lg, tokens[1:, None], axis=-1)[:, 0]
    return jnp.mean(lse - gold)


def trunc_normal_init(arch: Arch, key) -> dict:
    """Random initial parameters as the training program draws them from
    ``key`` (its published init convention, restated here): truncated
    normal in [-2, 2] times fan-in^-1/2 for matrices, N(0, 0.02) for the
    embedding, zeros for norm scales."""

    def dense(k, shape):
        return jax.random.truncated_normal(k, -2.0, 2.0, shape) * shape[-2] ** -0.5

    L, d, hd = arch.layers, arch.d_model, arch.head_dim
    keys = jax.random.split(key, 8)
    ka = jax.random.split(keys[0], 4)
    attn = {
        "wq": dense(ka[0], (L, d, arch.heads * hd)),
        "wk": dense(ka[1], (L, d, arch.kv_heads * hd)),
        "wv": dense(ka[2], (L, d, arch.kv_heads * hd)),
        "wo": dense(ka[3], (L, arch.heads * hd, d)),
    }
    if arch.qk_norm:
        attn["q_norm"] = jnp.zeros((L, hd))
        attn["k_norm"] = jnp.zeros((L, hd))
    k1, k2 = jax.random.split(keys[1])
    kg, ku = jax.random.split(k1)
    mlp = {
        "w_gate": dense(kg, (L, d, arch.d_ff)),
        "w_up": dense(ku, (L, d, arch.d_ff)),
        "wo": dense(k2, (L, arch.d_ff, d)),
    }
    blocks = {"attn": attn, "mlp": mlp}
    params = {"blocks": blocks, "embed": jax.random.normal(keys[2], (arch.vocab, d)) * 0.02}
    if arch.norm != "nonparam_ln":
        blocks["ln1"] = jnp.zeros((L, d))
        blocks["ln2"] = jnp.zeros((L, d))
        params["final_norm"] = jnp.zeros((d,))
    return params


def leaf_names(tree: dict, prefix: str = "") -> dict:
    """``{"blocks/attn/wq": leaf, ...}`` of a nested dict."""
    out = {}
    for k, v in tree.items():
        name = f"{prefix}/{k}" if prefix else k
        if isinstance(v, dict):
            out.update(leaf_names(v, name))
        else:
            out[name] = v
    return out


def leaf_norms(tree: dict) -> dict:
    """Float32 2-norm of every leaf, by name, as Python floats."""
    named = leaf_names(tree)
    vals = jax.jit(lambda t: {k: jnp.sqrt(jnp.sum(jnp.square(v.astype(jnp.float32))))
                              for k, v in t.items()})(named)
    return {k: float(v) for k, v in vals.items()}

