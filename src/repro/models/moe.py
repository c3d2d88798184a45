"""Fine-grained Mixture-of-Experts LM — deepseek-moe-16b, kimi-k2-1t-a32b
and deepseek-v2-lite.

Structure follows DeepSeekMoE: ``first_k_dense`` leading dense-FFN layers,
then MoE layers with ``n_shared_experts`` always-on shared experts (merged
into one wide FFN) plus ``n_experts`` routed experts.  Attention is MHA/GQA,
or multi-head latent attention (``mla``) where ``kv_lora_rank > 0``, in the
dense and the MoE layers alike.

Routing is dropless: softmax over all E router outputs, greedy top-k, and
every assignment to an expert the layer holds is computed.  The layer holds
experts 0 .. H-1 (``experts_held``; all by default): under expert
parallelism, one chip's share, while the router still scores all E.  The
assignments to held experts are sorted by expert into groups padded to
whole tiles (``plan``) and run through grouped products: the ``expert_gmm``
Pallas kernel on a TPU, XLA's ``ragged_dot`` elsewhere.  The plan's rows are
bounded for the worst case; the layer runs over the smallest of a few static
bounds (``ladder``) that holds the rows this routing filled.  The balance loss is
Switch-style (E sum_e f_e p_e over top-1 picks, in the loss) or, with
``moe_aux="seq"``, DeepSeek-V2's per-sequence loss, which (as the published
``AddAuxiliaryLoss`` does) enters the gradient but not the loss value.
"""
from __future__ import annotations

import contextlib
import functools
from typing import Any, NamedTuple

import jax
import jax.numpy as jnp

from ..configs.base import ModelConfig
from . import common, mla

PyTree = Any

# rows of a grouped-product tile: each held expert's rows pad to a multiple
TILE_ROWS = 128


# ---------------------------------------------------------------------------
# router
# ---------------------------------------------------------------------------

def route(cfg: ModelConfig, router_w, x):
    """x: (B, S, d). Returns the top-k experts (B, S, k) int32, their gates
    (B, S, k) f32 and the balance loss (before ``aux_loss_coef``)."""
    B, S, _ = x.shape
    E, k = cfg.n_experts, cfg.top_k
    logits = x.astype(jnp.float32) @ router_w.astype(jnp.float32)
    probs = jax.nn.softmax(logits, axis=-1)  # (B, S, E)
    gates, idx = jax.lax.top_k(probs, k)
    if cfg.norm_topk_prob:
        gates = gates / jnp.maximum(gates.sum(-1, keepdims=True), 1e-9)
    if cfg.moe_aux == "seq":
        # per sequence: each expert's share of the picks over its balanced
        # share, times its mean probability; mean over sequences
        picks = jax.nn.one_hot(idx, E, dtype=jnp.float32).sum(axis=(1, 2))  # (B, E)
        ce = picks / (S * k / E)
        aux = jnp.mean(jnp.sum(ce * jnp.mean(probs, axis=1), axis=-1))
    else:
        top1 = jax.nn.one_hot(idx[..., 0], E, dtype=jnp.float32)
        aux = E * jnp.sum(jnp.mean(top1, axis=(0, 1)) * jnp.mean(probs, axis=(0, 1)))
    return idx.astype(jnp.int32), gates, aux


# ---------------------------------------------------------------------------
# dropless grouped dispatch over the held experts
# ---------------------------------------------------------------------------

class Plan(NamedTuple):
    """Where each top-k assignment of T tokens sits among M sorted rows.

    ``slot`` (T, k): its row (0, a row of an active tile, for assignments
    to experts not held, whose ``held`` is False); ``src`` (M,): each row's
    token (T for padding and inactive rows); ``tile_group`` (M / tm,): each
    tile's expert; ``n_tiles``: the active tiles; ``group_rows`` (H,): each
    expert's rows with padding; ``counts`` (H,): each expert's assignments.
    """

    slot: jax.Array
    held: jax.Array
    src: jax.Array
    tile_group: jax.Array
    n_tiles: jax.Array
    group_rows: jax.Array
    counts: jax.Array


def plan(idx, held_experts: int, tm: int = TILE_ROWS) -> Plan:
    """Sort the assignments ``idx`` (T, k) to experts 0 .. H-1 by expert,
    each expert's rows padded to whole tiles of ``tm`` and every expert
    given at least one tile.  M bounds the rows of any routing:
    T * min(k, H) assignments plus a tile of padding per expert."""
    T, k = idx.shape
    H = held_experts
    M = (-(-T * min(k, H) // tm) + H) * tm
    e = idx.reshape(-1)
    held = e < H
    key = jnp.where(held, e, H)
    onehot = jax.nn.one_hot(key, H + 1, dtype=jnp.int32)  # (T k, H + 1)
    counts = onehot.sum(0)[:H]
    rank = jnp.sum((jnp.cumsum(onehot, axis=0) - 1) * onehot, axis=-1)
    tiles = jnp.maximum(1, -(-counts // tm))
    starts = jnp.cumsum(tiles * tm) - tiles * tm
    dest = jnp.where(held, starts[jnp.minimum(key, H - 1)] + rank, M)
    src = jnp.full((M,), T, jnp.int32).at[dest].set(
        jnp.arange(T * k, dtype=jnp.int32) // k, mode="drop"
    )
    tile_end = jnp.cumsum(tiles)
    tile_group = jnp.minimum(
        jnp.searchsorted(tile_end, jnp.arange(M // tm), side="right"), H - 1
    ).astype(jnp.int32)
    return Plan(
        slot=jnp.where(held, dest, 0).reshape(T, k).astype(jnp.int32),
        held=held.reshape(T, k),
        src=src,
        tile_group=tile_group,
        n_tiles=tile_end[-1].astype(jnp.int32),
        group_rows=(tiles * tm).astype(jnp.int32),
        counts=counts,
    )


@jax.custom_vjp
def dispatch(x, p: Plan):
    """The rows (M, d): token ``src[m]``'s row of x (T, d), zero for T."""
    return jnp.concatenate([x, jnp.zeros_like(x[:1])])[p.src]


def _dispatch_fwd(x, p):
    return dispatch(x, p), p


def _dispatch_bwd(p, g):
    # each token gathers its held assignments' rows: no scatter, and rows
    # past the active tiles (undefined) are never read
    dx = jnp.sum(jnp.where(p.held[..., None], g[p.slot], 0), axis=1)
    return dx, None


dispatch.defvjp(_dispatch_fwd, _dispatch_bwd)


@jax.custom_vjp
def combine(ys, w, p: Plan):
    """(T, d): each token's sum over its assignments of ``w`` (T, k) times
    its row of ys (M, d); ``w`` is zero where the expert is not held."""
    return jnp.einsum("tk,tkd->td", w, ys[p.slot].astype(jnp.float32)).astype(ys.dtype)


def _combine_fwd(ys, w, p):
    return combine(ys, w, p), (ys, w, p)


def _combine_bwd(res, g):
    ys, w, p = res
    gf = g.astype(jnp.float32)
    w_row = jnp.zeros((ys.shape[0],), jnp.float32).at[
        jnp.where(p.held, p.slot, ys.shape[0]).reshape(-1)
    ].set(w.reshape(-1), mode="drop")
    g_ext = jnp.concatenate([gf, jnp.zeros_like(gf[:1])])
    dys = (w_row[:, None] * g_ext[p.src]).astype(ys.dtype)
    dw = jnp.einsum("td,tkd->tk", gf, ys[p.slot].astype(jnp.float32))
    return dys, jnp.where(p.held, dw, 0.0), None


combine.defvjp(_combine_fwd, _combine_bwd)


def grouped_matmul(rows, w, p: Plan):
    """rows (M, K) times their expert's w (H, K, N), over the active tiles:
    the ``expert_gmm`` kernel on a TPU, ``ragged_dot`` elsewhere (zero rows
    past the groups)."""
    from ..kernels import ops as kops

    if not kops._interpret():  # on a TPU
        from ..kernels.expert_gmm import expert_gmm

        return expert_gmm(rows, w.astype(rows.dtype), p.tile_group, p.n_tiles,
                          int(rows.shape[0] // p.tile_group.shape[0]))
    return jax.lax.ragged_dot(rows, w.astype(rows.dtype), p.group_rows)


def ladder(tokens: int, top_k: int, held: int, experts: int,
           tm: int = TILE_ROWS) -> tuple:
    """Static bounds on the rows of ``plan``, smallest first: the tiles of
    the balanced held rows (tokens * top_k * held / experts) plus a tile an
    expert, doubling while under the worst case M, then M.  M alone where
    the lowest bound is more than half of M (every expert held, or few
    tokens)."""
    worst = (-(-tokens * min(top_k, held) // tm) + held) * tm
    b = (-(-tokens * top_k * held // (experts * tm)) + held) * tm
    rungs = []
    if 2 * b <= worst:
        while b < worst:
            rungs.append(b)
            b *= 2
    return (*rungs, worst)


def rung(p: Plan, rungs: tuple) -> jax.Array:
    """The index of the smallest of ``rungs`` that holds the active tiles."""
    tm = p.src.shape[0] // p.tile_group.shape[0]
    return jnp.sum(jnp.asarray(rungs[:-1]) < p.n_tiles * tm).astype(jnp.int32)


def _experts(bound: int, x, w, p: Plan, wi, wo):
    """The held experts' part (T, d) over the first ``bound`` of the plan's
    rows, which hold its active tiles: dispatch, the grouped SwiGLU and
    combine.  Every row read lies in the active tiles, so any such bound
    gives the same result."""
    tm = p.src.shape[0] // p.tile_group.shape[0]
    p = p._replace(src=p.src[:bound], tile_group=p.tile_group[:bound // tm])
    h = grouped_matmul(dispatch(x, p), wi, p)
    gate, up = jnp.split(h, 2, axis=-1)
    h = jax.nn.silu(gate.astype(jnp.float32)).astype(x.dtype) * up
    return combine(grouped_matmul(h, wo, p), w, p)


def experts(rungs: tuple, x, w, p: Plan, wi, wo):
    """``_experts`` over the smallest of ``rungs`` that holds the active
    tiles; over the one bound where ``rungs`` has one."""
    if len(rungs) == 1:
        return _experts(rungs[0], x, w, p, wi, wo)
    return _experts_switch(rungs, x, w, p, wi, wo)


def _switch(rungs: tuple, fn, index, *args):
    """``fn(rungs[index], *args)`` through ``lax.switch``.  Under ``vmap``
    (the round maps its loss over the workers a device holds) every member
    runs at the largest bound any of them needs: a batched index would turn
    the switch into a select over every bound."""

    @jax.custom_batching.custom_vmap
    def switch(index, *args):
        branches = [functools.partial(fn, b) for b in rungs]
        return jax.lax.switch(index, branches, *args)

    @switch.def_vmap
    def _batched(axis_size, in_batched, index, *args):
        index = jnp.max(index) if in_batched[0] else index
        axes = jax.tree.map(lambda b: 0 if b else None, tuple(in_batched[1:]))
        branches = [jax.vmap(functools.partial(fn, b), in_axes=axes) for b in rungs]
        out = jax.lax.switch(index, branches, *args)
        return out, jax.tree.map(lambda _: True, out)

    return switch(index, *args)


# One custom_vjp over the whole switch: its residuals are its inputs, the
# same at every bound.  Differentiated as a plain ``lax.switch``, each
# branch would also write zero-filled residuals of every other branch's
# shapes, the worst case's among them.
@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def _experts_switch(rungs, x, w, p, wi, wo):
    return _switch(rungs, _experts, rung(p, rungs), x, w, p, wi, wo)


def _experts_switch_fwd(rungs, x, w, p, wi, wo):
    return _experts_switch(rungs, x, w, p, wi, wo), (x, w, p, wi, wo)


def _pull(bound, x, w, p, wi, wo, g):
    """The forward again at ``bound``, and its VJP in x, w, wi and wo."""
    f = lambda x, w, wi, wo: _experts(bound, x, w, p, wi, wo)  # noqa: E731
    return jax.vjp(f, x, w, wi, wo)[1](g)


def _experts_switch_bwd(rungs, res, g):
    x, w, p, wi, wo = res
    dx, dw, dwi, dwo = _switch(rungs, _pull, rung(p, rungs), x, w, p, wi, wo, g)
    return dx, dw, None, dwi, dwo


_experts_switch.defvjp(_experts_switch_fwd, _experts_switch_bwd)


STATS = ("held_share", "held_load_max", "rows_bound_share")


def routing_stats(p: Plan, top_k: int, rungs: tuple) -> dict:
    """The share of top-k assignments that landed on held experts, the
    largest held expert's assignments over the held experts' mean, and the
    rows of the bound the layer ran over, over the worst case's."""
    counts = p.counts.astype(jnp.float32)
    T = p.slot.shape[0]
    bound = jnp.asarray(rungs, jnp.float32)[rung(p, rungs)]
    return {
        "held_share": jnp.sum(counts) / (T * top_k),
        "held_load_max": jnp.max(counts) / jnp.maximum(jnp.mean(counts), 1e-9),
        "rows_bound_share": bound / rungs[-1],
    }


def moe_ffn(cfg: ModelConfig, p, x):
    """x: (B, S, d). The held routed experts' part of sum_e g_e FFN_e(x),
    dropping no token, plus the shared experts.  Returns (out, aux, stats)."""
    B, S, d = x.shape
    with jax.named_scope("moe_route"):
        idx, gates, aux = route(cfg, p["router"], x)
        pl = plan(idx.reshape(B * S, -1), cfg.held_experts)
        w = jnp.where(pl.held, gates.reshape(B * S, -1), 0.0)
    rungs = ladder(B * S, cfg.top_k, cfg.held_experts, cfg.n_experts)
    with jax.named_scope("moe_experts"):
        out = experts(rungs, x.reshape(B * S, d).astype(cfg.dtype), w, pl,
                      p["wi"], p["wo"]).reshape(B, S, d)
    if cfg.n_shared_experts:
        with jax.named_scope("moe_shared"):
            out = out + common.mlp(cfg, p["shared"], x)
    return out, aux, routing_stats(pl, cfg.top_k, rungs)


# ---------------------------------------------------------------------------
# params / blocks
# ---------------------------------------------------------------------------

def init_params(cfg: ModelConfig, key) -> PyTree:
    keys = jax.random.split(key, 12)
    L_dense = cfg.first_k_dense
    L_moe = cfg.n_layers - L_dense
    d, E, f = cfg.d_model, cfg.n_experts, cfg.moe_d_ff
    H = cfg.held_experts

    def moe_block_params(k):
        ks = jax.random.split(k, 5)
        p = {
            "attn": _init_attn(cfg, ks[0], L_moe),
            "router": common.dense_init(ks[1], (L_moe, d, E)),
            "wi": common.dense_init(ks[2], (L_moe, H, d, 2 * f)),
            "wo": common.dense_init(ks[3], (L_moe, H, f, d)),
            "ln1": jnp.zeros((L_moe, d), jnp.float32),
            "ln2": jnp.zeros((L_moe, d), jnp.float32),
        }
        if cfg.n_shared_experts:
            # shared experts run through common.mlp -> de-fused swiglu layout
            fs = cfg.n_shared_experts * f
            k1, k2, k3 = jax.random.split(ks[4], 3)
            p["shared"] = {
                "w_gate": common.dense_init(k1, (L_moe, d, fs)),
                "w_up": common.dense_init(k3, (L_moe, d, fs)),
                "wo": common.dense_init(k2, (L_moe, fs, d)),
            }
        return p

    params = {"moe_blocks": moe_block_params(keys[0])}
    if L_dense:
        params["dense_blocks"] = {
            "attn": _init_attn(cfg, keys[1], L_dense),
            "mlp": common.init_mlp(_dense_cfg(cfg), keys[2], layers=L_dense),
            "ln1": jnp.zeros((L_dense, d), jnp.float32),
            "ln2": jnp.zeros((L_dense, d), jnp.float32),
        }
    params["embed"] = common.embed_init(keys[3], (cfg.vocab_size, d))
    params["lm_head"] = common.dense_init(keys[4], (d, cfg.vocab_size))
    params["final_norm"] = jnp.zeros((d,), jnp.float32)
    return params


def _dense_cfg(cfg: ModelConfig) -> ModelConfig:
    return cfg.replace(d_ff=cfg.dense_d_ff or cfg.d_ff)


def _init_attn(cfg: ModelConfig, key, layers: int):
    if cfg.kv_lora_rank:
        return mla.init_mla(cfg, key, layers=layers)
    return common.init_attn(cfg, key, layers=layers)


def _qkv(cfg: ModelConfig, p, h, positions):
    """q, k, v of the layer's attention kind, and its softmax scale."""
    if cfg.kv_lora_rank:
        return (*mla.mla_qkv(cfg, p, h, positions), mla.softmax_scale(cfg))
    return (*common.qkv_project(cfg, p, h, positions), None)


def _attention(cfg: ModelConfig, p, h, positions):
    """The attention sublayer (latent where ``kv_lora_rank > 0``)."""
    scope = jax.named_scope("mla") if cfg.kv_lora_rank else contextlib.nullcontext()
    with scope:
        q, k, v, scale = _qkv(cfg, p, h, positions)
        return common.attn_out(cfg, p, common.attention(cfg, q, k, v, scale=scale))


def _dense_block(cfg: ModelConfig, x, positions, bp):
    x = x + _attention(cfg, bp["attn"], common.apply_norm(cfg, x, bp["ln1"]), positions)
    h = common.apply_norm(cfg, x, bp["ln2"])
    return x + common.mlp(_dense_cfg(cfg), bp["mlp"], h)


def _moe_block(cfg: ModelConfig, x, positions, bp):
    x = x + _attention(cfg, bp["attn"], common.apply_norm(cfg, x, bp["ln1"]), positions)
    h = common.apply_norm(cfg, x, bp["ln2"])
    ff, aux, stats = moe_ffn(cfg, bp, h)
    return x + ff, (aux, stats)


def backbone(cfg: ModelConfig, params, x, positions):
    """Returns (x, the balance losses summed over layers, routing stats:
    the held share's and the rows bound's means and the largest held load's
    max over layers)."""
    if cfg.first_k_dense:
        block = functools.partial(_dense_block, cfg)
        if cfg.remat:
            block = jax.checkpoint(block)

        def dbody(carry, bp):
            return block(carry, positions, bp), None

        x, _ = jax.lax.scan(dbody, x, params["dense_blocks"], unroll=cfg.unroll_layers)

    block = functools.partial(_moe_block, cfg)
    if cfg.remat:
        block = jax.checkpoint(block)

    def body(carry, bp):
        return block(carry, positions, bp)

    x, (auxs, stats) = jax.lax.scan(body, x, params["moe_blocks"], unroll=cfg.unroll_layers)
    x = common.apply_norm(cfg, x, params["final_norm"])
    stats = {"held_share": jnp.mean(stats["held_share"]),
             "held_load_max": jnp.max(stats["held_load_max"]),
             "rows_bound_share": jnp.mean(stats["rows_bound_share"])}
    return x, jnp.sum(auxs), stats


def _forward(cfg: ModelConfig, params, batch, last_only: bool = False):
    x = params["embed"][batch["tokens"]].astype(cfg.dtype)
    positions = jnp.arange(x.shape[1], dtype=jnp.int32)[None]
    x, aux, stats = backbone(cfg, params, x, positions)
    if last_only:
        x = x[:, -1:]
    return x @ params["lm_head"].astype(x.dtype), aux, stats


def forward(cfg: ModelConfig, params, batch, last_only: bool = False):
    """(logits, balance loss)."""
    logits, aux, _ = _forward(cfg, params, batch, last_only)
    return logits, aux


def loss_with_stats(cfg: ModelConfig, params, batch):
    """(loss, routing stats); the trainer's round returns the stats."""
    logits, aux, stats = _forward(cfg, params, batch)
    ce = common.next_token_loss(logits, batch["tokens"])
    if cfg.moe_aux == "seq":  # in the gradient, not in the value
        aux = aux - jax.lax.stop_gradient(aux)
    return ce + cfg.aux_loss_coef * aux, stats


def loss_fn(cfg: ModelConfig, params, batch):
    return loss_with_stats(cfg, params, batch)[0]


# ---------------------------------------------------------------------------
# decode
# ---------------------------------------------------------------------------

def init_cache(cfg: ModelConfig, batch_size: int, max_len: int) -> PyTree:
    """Per-head k and v of every layer (latent attention: k of dn + dr and
    v of dv, not the compressed latent)."""
    if cfg.kv_lora_rank:
        dk, dv = cfg.qk_nope_head_dim + cfg.qk_rope_head_dim, cfg.v_head_dim
    else:
        dk = dv = cfg.resolved_head_dim
    shape = lambda L, w: (L, batch_size, max_len, cfg.n_kv_heads, w)  # noqa: E731
    L_moe = cfg.n_layers - cfg.first_k_dense
    cache = {
        "k_moe": jnp.zeros(shape(L_moe, dk), cfg.dtype),
        "v_moe": jnp.zeros(shape(L_moe, dv), cfg.dtype),
        "pos": jnp.zeros((), jnp.int32),
    }
    if cfg.first_k_dense:
        cache["k_dense"] = jnp.zeros(shape(cfg.first_k_dense, dk), cfg.dtype)
        cache["v_dense"] = jnp.zeros(shape(cfg.first_k_dense, dv), cfg.dtype)
    return cache


def _decode_attention(cfg: ModelConfig, p, h, positions, kc, vc, pos):
    q, k, v, scale = _qkv(cfg, p, h, positions)
    kc = jax.lax.dynamic_update_slice_in_dim(kc, k, pos, axis=1)
    vc = jax.lax.dynamic_update_slice_in_dim(vc, v, pos, axis=1)
    o = common.decode_attention(q, kc, vc, pos, scale=scale)
    return common.attn_out(cfg, p, o), kc, vc


def decode_step(cfg: ModelConfig, params, cache, tokens):
    x = params["embed"][tokens].astype(cfg.dtype)
    pos = cache["pos"]
    positions = jnp.full(tokens.shape, pos, jnp.int32)

    def layer(ffn):
        def body(carry, layer):
            bp, kc, vc = layer
            h = common.apply_norm(cfg, carry, bp["ln1"])
            o, kc, vc = _decode_attention(cfg, bp["attn"], h, positions, kc, vc, pos)
            x = carry + o
            return x + ffn(bp, common.apply_norm(cfg, x, bp["ln2"])), (kc, vc)

        return body

    new_cache = dict(cache, pos=pos + 1)
    if cfg.first_k_dense:
        dense = layer(lambda bp, h: common.mlp(_dense_cfg(cfg), bp["mlp"], h))
        x, (kd, vd) = jax.lax.scan(
            dense, x, (params["dense_blocks"], cache["k_dense"], cache["v_dense"]),
            unroll=cfg.unroll_layers,
        )
        new_cache.update(k_dense=kd, v_dense=vd)
    # the decode step runs the same dropless grouped layer over its B tokens
    routed = layer(lambda bp, h: moe_ffn(cfg, bp, h)[0])
    x, (km, vm) = jax.lax.scan(
        routed, x, (params["moe_blocks"], cache["k_moe"], cache["v_moe"]),
        unroll=cfg.unroll_layers,
    )
    new_cache.update(k_moe=km, v_moe=vm)
    x = common.apply_norm(cfg, x, params["final_norm"])
    return x @ params["lm_head"].astype(x.dtype), new_cache
