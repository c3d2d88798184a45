"""Per-architecture smoke tests (reduced configs) + family integration tests:
decode-vs-teacher-forcing consistency, chunkwise-vs-sequential recurrences,
MoE routing invariants, chunked-attention equivalence."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import ARCH_IDS, get_config
from repro.models import build_model, make_batch, param_count
from repro.models import common, moe as moe_mod, rglru as rg_mod, xlstm as xl_mod


class TestSmokeAllArchs:
    """One reduced-config forward + train step per assigned architecture."""

    @pytest.mark.parametrize("arch", ARCH_IDS)
    def test_forward_and_grad_step(self, arch):
        cfg = get_config(arch, reduced=True)
        m = build_model(cfg)
        params = m.init(jax.random.PRNGKey(0))
        assert param_count(params) > 0
        batch = make_batch(cfg, jax.random.PRNGKey(1), 2, 32)

        loss, grads = jax.jit(jax.value_and_grad(m.loss_fn))(params, batch)
        assert np.isfinite(float(loss))
        # one SGD step decreases nothing catastrophic & keeps finiteness
        params2 = jax.tree.map(lambda p, g: p - 0.01 * g.astype(p.dtype), params, grads)
        loss2 = jax.jit(m.loss_fn)(params2, batch)
        assert np.isfinite(float(loss2))
        # gradients flow to every leaf
        gnorms = [float(jnp.sum(jnp.abs(g))) for g in jax.tree.leaves(grads)]
        assert all(np.isfinite(gnorms))

    @pytest.mark.parametrize("arch", ARCH_IDS)
    def test_forward_shapes(self, arch):
        cfg = get_config(arch, reduced=True)
        m = build_model(cfg)
        params = m.init(jax.random.PRNGKey(0))
        batch = make_batch(cfg, jax.random.PRNGKey(1), 2, 16)
        out = jax.jit(m.forward)(params, batch)
        logits = out[0] if isinstance(out, tuple) else out
        assert logits.shape == (2, 16, cfg.vocab_size)
        assert bool(jnp.all(jnp.isfinite(logits.astype(jnp.float32))))


class TestDecodeConsistency:
    """Step-by-step decode must reproduce teacher-forcing logits."""

    @pytest.mark.parametrize(
        "arch,atol",
        [
            ("olmo-1b", 2e-4),           # dense MHA, nonparam LN
            ("qwen3-4b", 2e-4),          # GQA + qk-norm + tied embeddings
            ("qwen2-7b", 2e-4),          # GQA + qkv bias
            ("recurrentgemma-2b", 5e-4), # RG-LRU + local attention
            ("xlstm-1.3b", 5e-4),        # chunkwise mLSTM vs recurrent step
            ("deepseek-moe-16b", 5e-3),  # MoE, dropless in both paths
            ("deepseek-v2-lite", 5e-3),  # latent attention + dropless MoE
        ],
    )
    def test_decode_matches_forward(self, arch, atol):
        cfg = get_config(arch, reduced=True)
        m = build_model(cfg)
        params = m.init(jax.random.PRNGKey(0))
        B, S = 2, 12
        tokens = jax.random.randint(jax.random.PRNGKey(7), (B, S), 0, cfg.vocab_size)
        ref_logits = m.forward(params, {"tokens": tokens})
        if isinstance(ref_logits, tuple):
            ref_logits = ref_logits[0]

        cache = m.init_cache(B, 32)
        step = jax.jit(m.decode_step)
        outs = []
        for t in range(S):
            logits, cache = step(params, cache, tokens[:, t : t + 1])
            outs.append(logits[:, 0])
        dec_logits = jnp.stack(outs, axis=1)
        np.testing.assert_allclose(
            np.asarray(dec_logits, np.float32),
            np.asarray(ref_logits, np.float32),
            rtol=1e-3,
            atol=atol,
        )


class TestRecurrences:
    def test_mlstm_chunkwise_equals_stepwise(self):
        """The stabilized chunkwise form must equal the sequential recurrence."""
        B, S, H, hd, chunk = 2, 32, 2, 16, 8
        key = jax.random.PRNGKey(0)
        ks = jax.random.split(key, 5)
        q = jax.random.normal(ks[0], (B, S, H, hd))
        k = jax.random.normal(ks[1], (B, S, H, hd))
        v = jax.random.normal(ks[2], (B, S, H, hd))
        log_i = jax.random.normal(ks[3], (B, S, H))
        log_f = jax.nn.log_sigmoid(jax.random.normal(ks[4], (B, S, H)) + 2.0)

        state0 = (
            jnp.zeros((B, H, hd, hd)),
            jnp.zeros((B, H, hd)),
            jnp.full((B, H), -1e30),
        )
        h_chunk, state_c = xl_mod.mlstm_chunkwise(q * hd**0.5, k, v, log_i, log_f, state0, chunk)
        # note: chunkwise scales q internally; pass unscaled there
        h_chunk, state_c = xl_mod.mlstm_chunkwise(q, k, v, log_i, log_f, state0, chunk)

        state = state0
        hs = []
        for t in range(S):
            h, state = xl_mod.mlstm_step(
                q[:, t], k[:, t], v[:, t], log_i[:, t], log_f[:, t], state
            )
            hs.append(h)
        h_seq = jnp.stack(hs, axis=1)
        np.testing.assert_allclose(np.asarray(h_chunk), np.asarray(h_seq), rtol=2e-4, atol=2e-4)
        for a, b in zip(state_c, state):
            if a.ndim == b.ndim and a.shape == b.shape and a.ndim >= 2:
                # C and n are stabilizer-scaled; compare true values C * e^m
                pass
        # compare de-stabilized states
        Cc, nc, mc = state_c
        Cs, ns, ms = state
        np.testing.assert_allclose(
            np.asarray(Cc * np.exp(np.asarray(mc))[..., None, None]),
            np.asarray(Cs * np.exp(np.asarray(ms))[..., None, None]),
            rtol=1e-3, atol=1e-4,
        )

    def test_rglru_scan_equals_stepwise(self):
        cfg = get_config("recurrentgemma-2b", reduced=True)
        key = jax.random.PRNGKey(0)
        bp = rg_mod.init_rec_block(cfg, key)
        B, S = 2, 16
        W = cfg.lru_width
        x = jax.random.normal(jax.random.fold_in(key, 1), (B, S, W))
        h0 = jnp.zeros((B, W))
        h_seq, h_last = rg_mod.rg_lru_seq(bp, x, h0)
        h = h0
        outs = []
        for t in range(S):
            out, h = rg_mod.rg_lru_step(bp, x[:, t], h)
            outs.append(out)
        np.testing.assert_allclose(
            np.asarray(h_seq), np.asarray(jnp.stack(outs, 1)), rtol=1e-4, atol=1e-5
        )
        np.testing.assert_allclose(np.asarray(h_last), np.asarray(h), rtol=1e-4, atol=1e-5)

    def test_lru_scan_matches_loop(self):
        B, S, W = 2, 20, 8
        a = jax.nn.sigmoid(jax.random.normal(jax.random.PRNGKey(0), (B, S, W)))
        b = jax.random.normal(jax.random.PRNGKey(1), (B, S, W))
        h = jnp.zeros((B, W))
        ref = []
        for t in range(S):
            h = a[:, t] * h + b[:, t]
            ref.append(h)
        out = rg_mod.lru_scan(a, b, jnp.zeros((B, W)))
        np.testing.assert_allclose(np.asarray(out), np.asarray(jnp.stack(ref, 1)), rtol=1e-5, atol=1e-6)


class TestMoERouting:
    def _cfg(self):
        return get_config("deepseek-moe-16b", reduced=True)

    def test_aux_loss_uniform_router_near_one(self):
        """With a uniform router, E * sum f_e p_e ~= 1 (perfectly balanced)."""
        cfg = self._cfg()
        B, S, d = 1, 256, cfg.d_model
        router = jnp.zeros((d, cfg.n_experts))  # uniform logits
        x = jax.random.normal(jax.random.PRNGKey(2), (B, S, d))
        _, _, aux = moe_mod.route(cfg, router, x)
        assert abs(float(aux) - 1.0) < 0.15

    def test_moe_ffn_zero_router_matches_shared_only_plus_uniform(self):
        cfg = self._cfg()
        m = build_model(cfg)
        params = m.init(jax.random.PRNGKey(0))
        batch = make_batch(cfg, jax.random.PRNGKey(1), 2, 32)
        out, aux = m.forward(params, batch)
        assert np.isfinite(np.asarray(out, np.float32)).all()


class TestChunkedAttention:
    @pytest.mark.parametrize("S,chunk", [(64, 16), (100, 32), (128, 128)])
    @pytest.mark.parametrize("window", [None, 24])
    def test_chunked_matches_full(self, S, chunk, window):
        B, Hq, Hkv, D = 2, 4, 2, 16
        q = jax.random.normal(jax.random.PRNGKey(0), (B, S, Hq, D))
        k = jax.random.normal(jax.random.PRNGKey(1), (B, S, Hkv, D))
        v = jax.random.normal(jax.random.PRNGKey(2), (B, S, Hkv, D))
        full = common.attention_full(q, k, v, causal=True, window=window)
        chunked = common.attention_chunked(q, k, v, causal=True, window=window, chunk=chunk)
        np.testing.assert_allclose(np.asarray(chunked), np.asarray(full), rtol=2e-4, atol=2e-4)

    def test_decode_attention_matches_full_last_row(self):
        B, S, Hq, Hkv, D = 2, 24, 4, 2, 16
        q = jax.random.normal(jax.random.PRNGKey(0), (B, S, Hq, D))
        k = jax.random.normal(jax.random.PRNGKey(1), (B, S, Hkv, D))
        v = jax.random.normal(jax.random.PRNGKey(2), (B, S, Hkv, D))
        full = common.attention_full(q, k, v, causal=True, window=None)
        dec = common.decode_attention(q[:, -1:], k, v, S - 1)
        np.testing.assert_allclose(np.asarray(dec[:, 0]), np.asarray(full[:, -1]), rtol=2e-4, atol=2e-4)


class TestSlidingWindowDecode:
    def test_dense_window_decode_matches_forward(self):
        """qwen3-4b long-context variant: ring-buffer window cache decode must
        reproduce teacher-forcing logits with the same window mask."""
        cfg = get_config("qwen3-4b", reduced=True).replace(window=8)
        m = build_model(cfg)
        params = m.init(jax.random.PRNGKey(0))
        B, S = 2, 20
        tokens = jax.random.randint(jax.random.PRNGKey(3), (B, S), 0, cfg.vocab_size)
        ref = m.forward(params, {"tokens": tokens})
        cache = m.init_cache(B, 64)  # clipped to window internally
        assert cache["k"].shape[2] == 8
        step = jax.jit(m.decode_step)
        outs = []
        for t_ in range(S):
            logits, cache = step(params, cache, tokens[:, t_ : t_ + 1])
            outs.append(logits[:, 0])
        dec = jnp.stack(outs, axis=1)
        np.testing.assert_allclose(
            np.asarray(dec, np.float32), np.asarray(ref, np.float32), rtol=1e-3, atol=3e-4
        )
