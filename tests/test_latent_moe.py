"""DeepSeek-V2-Lite's layers against the plain float32 reference
(``benchmarks/chip/reference/latent_moe.py``, written from the paper and the
published modeling code, importing nothing of the program), at the REDUCED
size on seeded random weights: YaRN rope, latent attention, the dropless
expert layer over a held share of the experts, the whole loss and its
gradients; the share identity; the grouped-product kernel in interpret mode
against XLA's grouped product; and the launcher's cuts.

Tolerances: program and reference both compute in float32 (matrix products
at "highest" precision), so what is left is summation order: 1e-5
relative on layer outputs, 1e-4 on gradients that sum over every token.
"""
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.kernels import expert_gmm as eg
from repro.models import build_model, mla
from repro.models import moe as moe_mod

BENCH = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
                     "benchmarks", "chip")
if BENCH not in sys.path:
    sys.path.insert(0, BENCH)

from reference import dense as ref_dense  # noqa: E402
from reference import latent_moe as ref  # noqa: E402

RTOL = 1e-5  # float32 both sides: summation order only
GRAD_RTOL = 1e-4  # gradients sum over every token and layer


def _hf(cfg, held=None) -> dict:
    """The reference's configuration keys for a program config."""
    y = cfg.yarn
    return {
        "num_hidden_layers": cfg.n_layers, "first_k_dense_replace": cfg.first_k_dense,
        "hidden_size": cfg.d_model, "num_attention_heads": cfg.n_heads,
        "kv_lora_rank": cfg.kv_lora_rank, "qk_nope_head_dim": cfg.qk_nope_head_dim,
        "qk_rope_head_dim": cfg.qk_rope_head_dim, "v_head_dim": cfg.v_head_dim,
        "intermediate_size": cfg.dense_d_ff, "moe_intermediate_size": cfg.moe_d_ff,
        "published_n_routed_experts": cfg.n_experts,
        "n_routed_experts": held or cfg.held_experts,
        "num_experts_per_tok": cfg.top_k, "n_shared_experts": cfg.n_shared_experts,
        "vocab_size": cfg.vocab_size, "rope_theta": cfg.rope_theta,
        "rope_scaling": {"factor": y.factor,
                         "original_max_position_embeddings": y.original_max_position,
                         "beta_fast": y.beta_fast, "beta_slow": y.beta_slow,
                         "mscale": y.mscale, "mscale_all_dim": y.mscale_all_dim},
        "rms_norm_eps": 1e-6, "aux_loss_alpha": cfg.aux_loss_coef,
    }


def _setup(held=None, **kw):
    cfg = get_config("deepseek-v2-lite", reduced=True).replace(**kw)
    if held:
        cfg = cfg.replace(experts_held=held)
    arch = ref.Arch.from_config(_hf(cfg))
    params = build_model(cfg).init(jax.random.PRNGKey(0))
    return cfg, arch, params


def _layer(tree, i=0):
    return jax.tree.map(lambda x: x[i], tree)


def _close(a, b, rtol):
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    scale = max(np.abs(b).max(), 1e-30)
    np.testing.assert_allclose(a, b, rtol=0, atol=rtol * scale)


class TestYarn:
    @pytest.mark.parametrize("reduced", [False, True])
    def test_frequencies_match_reference(self, reduced):
        cfg = get_config("deepseek-v2-lite", reduced=reduced)
        arch = ref.Arch.from_config(_hf(cfg))
        _close(mla.rope_frequencies(cfg), ref.yarn_inv_freq(arch), 1e-6)
        assert mla.softmax_scale(cfg) == pytest.approx(ref.softmax_scale(arch), rel=1e-12)

    def test_published_scale(self):
        """192^-1/2 times mscale^2, mscale = 0.1 * 0.707 * ln 40 + 1."""
        m = 0.1 * 0.707 * np.log(40.0) + 1
        assert m == pytest.approx(1.2608, abs=1e-4)
        cfg = get_config("deepseek-v2-lite")
        assert mla.softmax_scale(cfg) == pytest.approx(192**-0.5 * m * m, rel=1e-12)

    def test_rope_past_the_original_length(self):
        """Positions beyond 4,096 (the original context YaRN stretches)."""
        cfg = get_config("deepseek-v2-lite")
        arch = ref.Arch.from_config(_hf(cfg))
        pos = jnp.array([0, 1, 4095, 4096, 4097, 6000, 8191], jnp.int32)
        x = jax.random.normal(jax.random.PRNGKey(1), (pos.shape[0], 3, cfg.qk_rope_head_dim))
        from repro.models import common

        with jax.default_matmul_precision("highest"):
            got = common.apply_rope(x[None], pos, cfg.rope_theta, mla.rope_frequencies(cfg))[0]
            want = ref.rope(x, pos, arch)
        # the two compute the same frequencies by different float32 formulas
        # (an ulp apart), so the angle pos * freq may differ by up to
        # 8191 * 2^-23 rad: 1e-3 of |x|
        _close(got, want, 1e-3)


class TestLayers:
    def test_mla_matches_reference(self):
        cfg, arch, params = _setup()
        p = _layer(params["moe_blocks"]["attn"])
        h = jax.random.normal(jax.random.PRNGKey(2), (2, 24, cfg.d_model))
        pos = jnp.arange(24, dtype=jnp.int32)[None]
        with jax.default_matmul_precision("highest"):
            got = moe_mod._attention(cfg, p, h, pos)
            want = jnp.stack([ref.mla(arch, p, h[b], jnp.arange(24)) for b in range(2)])
        _close(got, want, RTOL)

    def test_chunked_attention_takes_the_latent_dims(self):
        """The chunked path (long sequences) with q.k of dn + dr and v of dv
        and the MLA scale equals the full one."""
        cfg, _, params = _setup()
        p = _layer(params["moe_blocks"]["attn"])
        h = jax.random.normal(jax.random.PRNGKey(3), (1, 40, cfg.d_model))
        pos = jnp.arange(40, dtype=jnp.int32)[None]
        full = moe_mod._attention(cfg.replace(attention_impl="xla"), p, h, pos)
        chunked = moe_mod._attention(
            cfg.replace(attention_impl="chunked", attn_chunk=16), p, h, pos)
        _close(chunked, full, 1e-5)

    def test_flash_kernel_takes_the_latent_dims(self):
        """The flash kernel (the TPU's path; interpreted here) with q.k of
        dn + dr, v of dv and the MLA scale equals the full attention, and so
        do the gradients of the layer's parameters through it."""
        cfg, _, params = _setup()
        p = _layer(params["moe_blocks"]["attn"])
        h = jax.random.normal(jax.random.PRNGKey(5), (1, 40, cfg.d_model))
        pos = jnp.arange(40, dtype=jnp.int32)[None]

        def out(impl):
            def f(p):
                return moe_mod._attention(cfg.replace(attention_impl=impl), p, h, pos)
            with jax.default_matmul_precision("highest"):
                return f(p), jax.grad(lambda p: jnp.sum(jnp.sin(f(p))))(p)

        (full, g_full), (flash, g_flash) = out("xla"), out("pallas")
        _close(flash, full, 1e-5)
        for a, b in zip(jax.tree.leaves(g_flash), jax.tree.leaves(g_full)):
            _close(a, b, 1e-4)

    @pytest.mark.parametrize("held", [None, 2])
    def test_expert_layer_matches_reference(self, held):
        cfg, arch, params = _setup(held)
        p = _layer(params["moe_blocks"])
        h = jax.random.normal(jax.random.PRNGKey(4), (2, 32, cfg.d_model))
        with jax.default_matmul_precision("highest"):
            got, aux, stats = moe_mod.moe_ffn(cfg, p, h)
            want = [ref.experts(arch, p, h[b]) for b in range(2)]
        _close(got, jnp.stack([w[0] for w in want]), RTOL)
        assert float(aux) == pytest.approx(np.mean([float(w[1]) for w in want]), rel=1e-5)
        if held:
            assert 0.0 < float(stats["held_share"]) < 1.0
        else:
            assert float(stats["held_share"]) == 1.0

    def test_droplessness(self):
        """A router that sends every token to expert 0 first: no capacity, so
        every token still gets its whole top-k, as in the reference."""
        cfg, arch, params = _setup()
        p = _layer(params["moe_blocks"])
        d = cfg.d_model
        base = jax.random.normal(jax.random.PRNGKey(5), (d,))
        router = p["router"].at[:, 0].set(base * 50.0)
        p = dict(p, router=router)
        h = base * 3.0 + 0.1 * jax.random.normal(jax.random.PRNGKey(6), (2, 32, d))
        idx, _, _ = moe_mod.route(cfg, router, h)
        assert bool(jnp.all(idx[..., 0] == 0))
        with jax.default_matmul_precision("highest"):
            got, _, stats = moe_mod.moe_ffn(cfg, p, h)
            want = jnp.stack([ref.experts(arch, p, h[b])[0] for b in range(2)])
        _close(got, want, RTOL)
        # expert 0 took every token: 64 rows against a mean of 64 * 2 / 4
        assert float(stats["held_load_max"]) == pytest.approx(2.0)

    def test_share_identity(self):
        """The 8 shares' routed parts, with the shared experts counted once,
        add up to the uncut reference layer."""
        shares, per = 8, 2
        cfg, arch, params = _setup(n_experts=shares * per, top_k=4)
        p = _layer(params["moe_blocks"])
        h = jax.random.normal(jax.random.PRNGKey(7), (2, 16, cfg.d_model))
        with jax.default_matmul_precision("highest"):
            shared = moe_mod.common.mlp(cfg, p["shared"], h)
            total = shared
            for j in range(shares):
                # share j holds experts j*per .. : its router sees them first
                pj = dict(p, router=jnp.roll(p["router"], -j * per, axis=1),
                          wi=p["wi"][j * per:(j + 1) * per],
                          wo=p["wo"][j * per:(j + 1) * per])
                out, _, _ = moe_mod.moe_ffn(cfg.replace(experts_held=per), pj, h)
                total = total + (out - shared)
            want = jnp.stack([ref.experts(arch, p, h[b])[0] for b in range(2)])
        _close(total, want, 1e-5)

    def test_aux_seq_loss_uniform_router_near_one(self):
        """The per-sequence loss: with a uniform router each expert's share of
        the picks over its balanced share is ~1 and its mean probability
        1/E, so the sum is ~1 (perfectly balanced)."""
        cfg = get_config("deepseek-v2-lite", reduced=True)
        x = jax.random.normal(jax.random.PRNGKey(2), (2, 256, cfg.d_model))
        _, _, aux = moe_mod.route(cfg, jnp.zeros((cfg.d_model, cfg.n_experts)), x)
        assert abs(float(aux) - 1.0) < 0.15


class TestRowBounds:
    """The expert layer runs over the smallest of a few static row bounds
    that holds the active tiles.  A layer of 2 x 512 tokens, top-4 of 16
    experts, 2 held, at 128-row tiles: bounds 768, 1,536 and the worst case
    2,304."""

    B, S = 2, 512
    LADDER = (768, 1536, 2304)

    def _layer(self, router_bias):
        cfg, arch, params = _setup(2, n_experts=16, top_k=4)
        p = _layer(params["moe_blocks"])
        d = cfg.d_model
        base = jax.random.normal(jax.random.PRNGKey(20), (d,))
        h = jax.random.normal(jax.random.PRNGKey(21), (self.B, self.S, d))
        # tokens that share ``base`` (|base|^2 ~ d), and router columns along
        # it, put held experts first or last for every token; the softmax
        # keeps the others above zero
        h = h + 3.0 * base if router_bias else h
        router = p["router"]
        for e, scale in router_bias.items():
            router = router.at[:, e].set(scale * base)
        return cfg, arch, dict(p, router=router), h

    def test_ladder_from_shapes(self):
        assert moe_mod.ladder(self.B * self.S, 4, 2, 16) == self.LADDER
        # the benchmark cell: 2 x 4096 tokens, top-6, 8 of 64 experts held
        assert moe_mod.ladder(8192, 6, 8, 64) == (7168, 14336, 28672, 50176)

    @pytest.mark.parametrize("router_bias, want", [
        ({}, 0),  # few: a random router sends 2/16 of the picks to the held two
        ({0: 0.1, 1: -0.1}, 1),  # some: expert 0 takes every token, expert 1 none
        ({0: 0.1, 1: 0.08}, 2),  # all: both take every token
    ], ids=["few", "some", "all"])
    def test_each_rung_matches_the_worst_case_and_reference(self, router_bias, want,
                                                            monkeypatch):
        cfg, arch, p, h = self._layer(router_bias)
        idx, _, _ = moe_mod.route(cfg, p["router"], h)
        pl = moe_mod.plan(idx.reshape(self.B * self.S, -1), 2)
        need = int(pl.n_tiles) * moe_mod.TILE_ROWS
        assert min(i for i, b in enumerate(self.LADDER) if b >= need) == want
        assert int(moe_mod.rung(pl, self.LADDER)) == want

        def run():
            def f(p, h):
                out, _, stats = moe_mod.moe_ffn(cfg, p, h)
                return jnp.sum(jnp.sin(out)), (out, stats)

            with jax.default_matmul_precision("highest"):
                (_, (out, stats)), grads = jax.jit(
                    jax.value_and_grad(f, (0, 1), has_aux=True))(p, h)
            return out, stats, grads

        out, stats, grads = run()
        assert float(stats["rows_bound_share"]) == pytest.approx(
            self.LADDER[want] / self.LADDER[-1], rel=1e-6)
        ladder = moe_mod.ladder
        monkeypatch.setattr(moe_mod, "ladder", lambda *a: ladder(*a)[-1:])
        worst, worst_stats, worst_grads = run()
        assert float(worst_stats["rows_bound_share"]) == 1.0
        _close(out, worst, 1e-6)
        for a, b in zip(jax.tree.leaves(grads), jax.tree.leaves(worst_grads)):
            _close(a, b, 1e-6)

        def ref_f(p, h):
            out = jnp.stack([ref.experts(arch, p, h[b])[0] for b in range(self.B)])
            return jnp.sum(jnp.sin(out)), out

        with jax.default_matmul_precision("highest"):
            (_, want_out), want_grads = jax.jit(
                jax.value_and_grad(ref_f, (0, 1), has_aux=True))(p, h)
        _close(out, want_out, RTOL)
        for a, b in zip(jax.tree.leaves(grads), jax.tree.leaves(want_grads)):
            _close(a, b, GRAD_RTOL)

    def test_no_worst_case_residuals_under_remat(self):
        """The gradient of a checkpointed layer writes no zero-filled buffer
        of the worst case's rows: a plain ``lax.switch`` would fill every
        other bound's residuals in each branch."""
        cfg, _, p, h = self._layer({})
        worst = self.LADDER[-1]

        def loss(p, h):
            return jnp.sum(moe_mod.moe_ffn(cfg, p, h)[0])

        jaxpr = jax.make_jaxpr(jax.grad(jax.checkpoint(loss)))(p, h)
        fills, conds = [], 0
        for eqn in _eqns(jaxpr.jaxpr):
            conds += eqn.primitive.name == "cond"
            if eqn.primitive.name != "broadcast_in_dim":
                continue
            shape = eqn.outvars[0].aval.shape
            if len(shape) >= 2 and shape[0] == worst and eqn.invars[0].aval.shape == ():
                fills.append(shape)
        assert conds >= 2  # the forward's and the backward's switch
        assert not fills, fills

    def test_workers_mapped_share_one_switch(self):
        """Under ``vmap`` (the round maps the loss over a device's workers)
        the bound stays a switch, at the largest bound any worker needs: a
        select would run every bound."""
        cfg, _, p, h = self._layer({})
        _, _, p_all, h_all = self._layer({0: 0.1, 1: 0.08})
        ps = jax.tree.map(lambda a, b: jnp.stack([a, b]), p, p_all)
        hs = jnp.stack([h, h_all])

        def loss(p, h):
            out, _, stats = moe_mod.moe_ffn(cfg, p, h)
            return jnp.sum(jnp.sin(out)), (out, stats["rows_bound_share"])

        def conds(f, *args):
            return sum(e.primitive.name == "cond" for e in _eqns(jax.make_jaxpr(f)(*args).jaxpr))

        one = jax.value_and_grad(jax.checkpoint(loss), has_aux=True)
        step = jax.vmap(one)
        assert conds(step, ps, hs) == conds(one, p, h) >= 2
        with jax.default_matmul_precision("highest"):
            (_, (out, share)), grads = jax.jit(step)(ps, hs)
            (_, (out0, share0)), grads0 = jax.jit(jax.value_and_grad(loss, has_aux=True))(p, h)
        # each worker counts its own bound; both ran at the largest
        assert [float(v) for v in share] == [pytest.approx(768 / 2304), 1.0]
        assert float(share0) == pytest.approx(768 / 2304)
        _close(out[0], out0, 1e-6)
        for a, b in zip(jax.tree.leaves(grads), jax.tree.leaves(grads0)):
            _close(a[0], b, 1e-6)

    @pytest.mark.parametrize("tokens, held", [(1024, 16), (4, 8), (64, 8)],
                             ids=["all-held", "decode-4", "decode-64"])
    def test_one_rung_runs_the_worst_case(self, tokens, held):
        """Every expert held, or a decode step's few tokens at the published
        layer: one bound, the worst case, and no switch."""
        E, k = (16, 4) if held == 16 else (64, 6)
        assert len(moe_mod.ladder(tokens, k, held, E)) == 1
        cfg, _, params = _setup(held, n_experts=E, top_k=k)
        p = _layer(params["moe_blocks"])
        h = jax.random.normal(jax.random.PRNGKey(22), (1, tokens, cfg.d_model))
        jaxpr = jax.make_jaxpr(lambda p, h: moe_mod.moe_ffn(cfg, p, h))(p, h)
        assert not [e for e in _eqns(jaxpr.jaxpr) if e.primitive.name == "cond"]
        _, _, stats = moe_mod.moe_ffn(cfg, p, h)
        assert float(stats["rows_bound_share"]) == 1.0


def _eqns(jaxpr):
    """Every equation of ``jaxpr`` and of the jaxprs inside it."""
    for eqn in jaxpr.eqns:
        yield eqn
        for v in eqn.params.values():
            for sub in v if isinstance(v, (tuple, list)) else (v,):
                if hasattr(sub, "eqns"):
                    yield from _eqns(sub)
                elif hasattr(sub, "jaxpr") and hasattr(sub.jaxpr, "eqns"):
                    yield from _eqns(sub.jaxpr)


class TestWholeModel:
    @pytest.mark.parametrize("held", [None, 2])
    def test_loss_and_grads_match_reference(self, held):
        cfg, arch, params = _setup(held)
        ours = ref_dense.leaf_names(ref.trunc_normal_init(arch, jax.random.PRNGKey(0)))
        theirs = ref_dense.leaf_names(params)
        assert set(ours) == set(theirs)
        for k in ours:  # the reference draws the program's initial tree
            assert (np.asarray(ours[k]) == np.asarray(theirs[k])).all(), k
        m = build_model(cfg)
        tokens = jax.random.randint(jax.random.PRNGKey(8), (2, 33), 0, cfg.vocab_size)
        with jax.default_matmul_precision("highest"):
            loss, grads = jax.value_and_grad(m.loss_fn)(params, {"tokens": tokens})
            rl, rg = zip(*[jax.value_and_grad(lambda p, t: ref.loss(arch, p, t))(params, tokens[b])
                           for b in range(2)])
        assert float(loss) == pytest.approx(float(np.mean(rl)), rel=1e-6)
        want = jax.tree.map(lambda a, b: (a + b) / 2, rg[0], rg[1])
        for name, g in ref_dense.leaf_names(grads).items():
            _close(g, ref_dense.leaf_names(want)[name], GRAD_RTOL)

    def test_balance_loss_enters_the_gradient_only(self):
        cfg, _, params = _setup()
        m = build_model(cfg)
        batch = {"tokens": jax.random.randint(jax.random.PRNGKey(9), (2, 17), 0, cfg.vocab_size)}
        base = m.loss_fn(params, batch)
        g0 = jax.grad(m.loss_fn)(params, batch)
        strong = build_model(cfg.replace(aux_loss_coef=10.0))
        assert float(strong.loss_fn(params, batch)) == pytest.approx(float(base), rel=1e-6)
        g1 = jax.grad(strong.loss_fn)(params, batch)
        r0, r1 = g0["moe_blocks"]["router"], g1["moe_blocks"]["router"]
        assert float(jnp.abs(r0 - r1).max()) > 1e-6


class TestExpertGmm:
    """The Pallas grouped product (interpret mode) against XLA's
    ``ragged_dot`` over the same plan, forward and custom_vjp gradients."""

    @pytest.mark.parametrize("empty", [False, True])
    def test_kernel_matches_ragged_dot(self, empty):
        T, k, H, E, K, N, tm = 40, 3, 4, 8, 32, 48, 16
        idx = jax.random.randint(jax.random.PRNGKey(10), (T, k), 0, E)
        if empty:  # experts 1 and 3 get no row
            idx = jnp.where((idx == 1) | (idx == 3), E - 1, idx)
        pl = moe_mod.plan(idx, H, tm)
        counts = np.asarray(pl.counts)
        assert (counts[[1, 3]] == 0).all() == empty
        rows = int(pl.n_tiles) * tm
        x = jax.random.normal(jax.random.PRNGKey(11), (pl.src.shape[0], K))
        x = jnp.where((pl.src < T)[:, None], x, 0.0)  # padding rows are zero
        w = jax.random.normal(jax.random.PRNGKey(12), (H, K, N))
        got = eg.expert_gmm(x, w, pl.tile_group, pl.n_tiles, tm, True)
        want = jax.lax.ragged_dot(x, w, pl.group_rows)
        _close(got[:rows], want[:rows], 1e-5)

        ct = jax.random.normal(jax.random.PRNGKey(13), (pl.src.shape[0], N))
        ct = ct.at[rows:].set(0.0)  # rows past the active tiles are never read

        def f(x, w):
            return jnp.sum(eg.expert_gmm(x, w, pl.tile_group, pl.n_tiles, tm, True)[:rows]
                           * ct[:rows])

        def g(x, w):
            return jnp.sum(jax.lax.ragged_dot(x, w, pl.group_rows) * ct)

        (dx, dw), (rx, rw) = jax.grad(f, (0, 1))(x, w), jax.grad(g, (0, 1))(x, w)
        _close(dx[:rows], rx[:rows], 1e-5)
        _close(dw, rw, 1e-5)  # an empty expert's gradient is zero in both
        if empty:
            assert float(jnp.abs(dw[1]).max()) == 0.0

    def test_plan_is_dropless_and_sorted(self):
        T, k, H, E, tm = 50, 4, 3, 6, 8
        idx = jax.random.randint(jax.random.PRNGKey(14), (T, k), 0, E)
        pl = moe_mod.plan(idx, H, tm)
        idx, src, slot = np.asarray(idx), np.asarray(pl.src), np.asarray(pl.slot)
        tg = np.asarray(pl.tile_group)
        held = idx < H
        assert int(np.asarray(pl.counts).sum()) == held.sum()
        for t, j in zip(*np.nonzero(held)):  # each held assignment has its row
            assert src[slot[t, j]] == t
            assert tg[slot[t, j] // tm] == idx[t, j]
        assert (src != T).sum() == held.sum()


class TestLauncherCuts:
    def _trainer(self, extra):
        from repro.launch import train as train_launch

        return train_launch.build_trainer(train_launch.build_parser().parse_args(
            ["--arch", "deepseek-v2-lite", "--workers", "1", "--tau", "1"] + extra))

    def test_cuts_reach_the_model(self):
        t = self._trainer(["--full", "--layers", "3", "--experts-held", "8",
                           "--vocab", "12800"])
        cfg = t.model.config
        assert (cfg.n_layers, cfg.held_experts, cfg.n_experts, cfg.vocab_size) == (
            3, 8, 64, 12800)
        shapes = jax.eval_shape(t.model.init, jax.random.PRNGKey(0))
        assert shapes["moe_blocks"]["wi"].shape == (2, 8, 2048, 2 * 1408)
        assert shapes["moe_blocks"]["router"].shape == (2, 2048, 64)
        assert shapes["embed"].shape == (12800, 2048)

    @pytest.mark.parametrize("extra", [["--experts-held", "2"], ["--vocab", "64"],
                                       ["--full", "--experts-held", "65"]])
    def test_bad_cuts_refused(self, extra):
        with pytest.raises(SystemExit):
            self._trainer(extra)
