"""Per-kernel allclose tests: Pallas (interpret=True) vs the pure-jnp oracle,
swept over shapes and dtypes, plus hypothesis property tests on the math."""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from _hyp import given, settings, st  # skips property tests if no hypothesis

from repro.kernels import flash_attention as fa
from repro.kernels import fused_nesterov as fn
from repro.kernels import ops, ref
from repro.kernels import slowmo_update as su


def rnd(key, shape, dtype=jnp.float32):
    return jax.random.normal(jax.random.PRNGKey(key), shape).astype(dtype)


class TestSlowMoUpdateKernel:
    @pytest.mark.parametrize("rows", [8, 64, 256, 512])
    @pytest.mark.parametrize("beta", [0.0, 0.6, 0.95])
    def test_matches_ref_2d(self, rows, beta):
        shape = (rows, su.LANES)
        x0, xt, u = rnd(0, shape), rnd(1, shape), rnd(2, shape)
        br = min(rows, 64)
        x_k, u_k = su.slowmo_update_2d(
            x0, xt, u, jnp.float32(0.05), alpha=1.0, beta=beta,
            block_rows=br, interpret=True,
        )
        x_r, u_r = ref.slowmo_outer_update_ref(x0, xt, u, gamma=0.05, alpha=1.0, beta=beta)
        np.testing.assert_allclose(np.asarray(x_k), np.asarray(x_r), rtol=1e-6, atol=1e-6)
        np.testing.assert_allclose(np.asarray(u_k), np.asarray(u_r), rtol=1e-6, atol=1e-6)

    @pytest.mark.parametrize(
        "shapes", [[(3,)], [(5, 7), (130,)], [(2, 3, 5), (1025,), (4096,)]]
    )
    def test_pytree_wrapper_ragged_shapes(self, shapes):
        x0 = {f"p{i}": rnd(i, s) for i, s in enumerate(shapes)}
        xt = {f"p{i}": rnd(i + 10, s) for i, s in enumerate(shapes)}
        u = {f"p{i}": rnd(i + 20, s) for i, s in enumerate(shapes)}
        xk, uk = ops.slowmo_outer_update(x0, xt, u, gamma=0.1, alpha=0.5, beta=0.7, use_pallas=True)
        xr, ur = ops.slowmo_outer_update(x0, xt, u, gamma=0.1, alpha=0.5, beta=0.7, use_pallas=False)
        for k in x0:
            np.testing.assert_allclose(np.asarray(xk[k]), np.asarray(xr[k]), rtol=1e-6, atol=1e-6)
            np.testing.assert_allclose(np.asarray(uk[k]), np.asarray(ur[k]), rtol=1e-6, atol=1e-6)

    @given(
        gamma=st.floats(1e-4, 2.0),
        alpha=st.floats(0.1, 1.0),
        beta=st.floats(0.0, 0.99),
    )
    @settings(max_examples=25, deadline=None)
    def test_property_beta0_alpha1_returns_xtau(self, gamma, alpha, beta):
        """beta=0, alpha=1 => x' = x_tau exactly (Local SGD recovery), and the
        general update is linear in (x0, x_tau, u)."""
        shape = (4, 16)
        x0, xt, u = rnd(0, shape), rnd(1, shape), rnd(2, shape)
        x_new, u_new = ref.slowmo_outer_update_ref(x0, xt, u, gamma=gamma, alpha=1.0, beta=0.0)
        np.testing.assert_allclose(np.asarray(x_new), np.asarray(xt), rtol=1e-5, atol=1e-6)
        # linearity: scaling all inputs by c scales both outputs by c
        c = 3.0
        xs, us = ref.slowmo_outer_update_ref(c * x0, c * xt, c * u, gamma=gamma, alpha=alpha, beta=beta)
        x1, u1 = ref.slowmo_outer_update_ref(x0, xt, u, gamma=gamma, alpha=alpha, beta=beta)
        np.testing.assert_allclose(np.asarray(xs), c * np.asarray(x1), rtol=1e-4, atol=1e-5)
        np.testing.assert_allclose(np.asarray(us), c * np.asarray(u1), rtol=1e-4, atol=1e-5)


class TestFusedNesterovKernel:
    @pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
    @pytest.mark.parametrize("wd", [0.0, 1e-2])
    def test_matches_ref(self, dtype, wd):
        shape = (16, fn.LANES)
        x = rnd(0, shape, dtype)
        h = rnd(1, shape)
        g = rnd(2, shape, dtype)
        xk, hk = fn.fused_nesterov_2d(
            x, h, g, jnp.float32(0.1), momentum=0.9, weight_decay=wd,
            block_rows=8, interpret=True,
        )
        xr, hr = ref.fused_nesterov_ref(x, h, g, lr=0.1, momentum=0.9, weight_decay=wd)
        np.testing.assert_allclose(
            np.asarray(xk, np.float32), np.asarray(xr, np.float32), rtol=2e-2 if dtype == jnp.bfloat16 else 1e-6, atol=1e-5
        )
        np.testing.assert_allclose(np.asarray(hk), np.asarray(hr), rtol=1e-5, atol=1e-5)

    @pytest.mark.parametrize(
        "shape,copy_free",
        [
            ((1, 2, 256, 512), True),  # olmo-like (W, L, d, d_ff) leaves
            ((1, 2, 512, 256), True),
            ((1, 1000, 256), True),  # one full-height block
            ((4, 512, 256), True),  # two blocks
            ((3, 100), False),  # odd width: flattened and padded
            ((7,), False),
        ],
    )
    @pytest.mark.parametrize("g_dtype", [jnp.bfloat16, jnp.float32], ids=["g_bf16", "g_f32"])
    @pytest.mark.parametrize("wd", [0.0, 1e-4])
    def test_tree_wrapper_is_bitwise_ref(self, shape, copy_free, g_dtype, wd):
        """Each leaf in its own layout and dtype: the kernel's results equal
        the oracle's bit for bit, and the tally says which path it took."""
        x = rnd(0, shape, jnp.bfloat16)
        h = rnd(1, shape)
        g = rnd(2, shape, g_dtype)
        with ops.tally() as tally:
            xk, hk = ops.fused_nesterov_update(
                {"w": x}, {"w": h}, {"w": g}, lr=0.1, momentum=0.9,
                weight_decay=wd, use_pallas=True,
            )
        # the oracle compiled, as the kernel's body is: op by op, eagerly,
        # each product is rounded on its own, which a fusion need not do
        oracle = jax.jit(functools.partial(
            ref.fused_nesterov_ref, momentum=0.9, weight_decay=wd))
        xr, hr = oracle(x, h, g, lr=jnp.float32(0.1))
        assert xk["w"].dtype == x.dtype and hk["w"].dtype == jnp.float32
        np.testing.assert_array_equal(
            np.asarray(xk["w"]).view(np.uint16), np.asarray(xr).view(np.uint16)
        )
        np.testing.assert_array_equal(
            np.asarray(hk["w"]).view(np.uint32), np.asarray(hr).view(np.uint32)
        )
        nbytes = x.size * (2 + 4 + g.dtype.itemsize + 2 + 4)
        taken, other = ("copy_free", "padded") if copy_free else ("padded", "copy_free")
        assert tally.counts == {"fused_nesterov": {taken: [1, nbytes], other: [0, 0]}}


class TestFlashAttentionKernel:
    @pytest.mark.parametrize(
        "B,S,Hq,Hkv,D",
        [
            (1, 128, 4, 4, 64),  # MHA
            (2, 256, 8, 2, 64),  # GQA 4:1
            (1, 200, 4, 1, 80),  # ragged seq + MQA + non-128 head dim
            (1, 384, 8, 8, 128),
        ],
    )
    @pytest.mark.parametrize("causal", [True, False])
    def test_matches_ref_self_attention(self, B, S, Hq, Hkv, D, causal):
        q = rnd(0, (B, S, Hq, D))
        k = rnd(1, (B, S, Hkv, D))
        v = rnd(2, (B, S, Hkv, D))
        out_k = fa.flash_attention(q, k, v, causal=causal, block_q=128, block_k=128, interpret=True)
        out_r = ref.flash_attention_ref(q, k, v, causal=causal)
        np.testing.assert_allclose(np.asarray(out_k), np.asarray(out_r), rtol=2e-4, atol=2e-4)

    @pytest.mark.parametrize("window", [64, 128])
    def test_sliding_window(self, window):
        B, S, H, D = 1, 320, 4, 64
        q, k, v = rnd(0, (B, S, H, D)), rnd(1, (B, S, H, D)), rnd(2, (B, S, H, D))
        out_k = fa.flash_attention(q, k, v, causal=True, window=window, interpret=True)
        out_r = ref.flash_attention_ref(q, k, v, causal=True, window=window)
        np.testing.assert_allclose(np.asarray(out_k), np.asarray(out_r), rtol=2e-4, atol=2e-4)

    def test_bfloat16(self):
        B, S, H, D = 1, 256, 4, 64
        q = rnd(0, (B, S, H, D), jnp.bfloat16)
        k = rnd(1, (B, S, H, D), jnp.bfloat16)
        v = rnd(2, (B, S, H, D), jnp.bfloat16)
        out_k = fa.flash_attention(q, k, v, causal=True, interpret=True)
        out_r = ref.flash_attention_ref(q, k, v, causal=True)
        np.testing.assert_allclose(
            np.asarray(out_k, np.float32), np.asarray(out_r, np.float32), rtol=3e-2, atol=3e-2
        )

    def test_row_sums_to_convex_combination(self):
        """Attention output rows lie in the convex hull of V rows: with V = const
        vector c, output must equal c everywhere (softmax weights sum to 1)."""
        B, S, H, D = 1, 256, 2, 64
        q, k = rnd(0, (B, S, H, D)), rnd(1, (B, S, H, D))
        v = jnp.ones((B, S, H, D)) * 2.5
        out = fa.flash_attention(q, k, v, causal=True, interpret=True)
        np.testing.assert_allclose(np.asarray(out), 2.5 * np.ones_like(out), rtol=1e-5)

    @pytest.mark.parametrize(
        "Hq,Hkv,S,D,Dv,scale,window,dtype",
        [
            (4, 4, 256, 64, 64, None, None, jnp.float32),  # MHA, causal
            (4, 2, 256, 64, 64, None, None, jnp.float32),  # GQA 2:1
            (4, 4, 320, 64, 64, None, 96, jnp.float32),  # window across blocks
            (4, 2, 256, 64, 64, None, 128, jnp.float32),  # GQA + window
            (2, 2, 256, 192, 128, 0.07, None, jnp.float32),  # latent attention's dims
            (4, 2, 200, 192, 128, 0.07, None, jnp.float32),  # not a block multiple
            (4, 2, 200, 64, 64, None, 64, jnp.float32),  # ragged + window
            (4, 4, 256, 64, 64, None, None, jnp.bfloat16),
            (4, 2, 200, 192, 128, 0.07, 96, jnp.bfloat16),
        ],
        ids=["mha", "gqa", "window", "gqa-window", "dv128-scale", "ragged",
             "ragged-window", "bf16", "bf16-gqa-ragged-window"],
    )
    def test_matches_full_attention_and_its_gradients(self, Hq, Hkv, S, D, Dv, scale,
                                                      window, dtype):
        """Output and d/dq, d/dk, d/dv of the kernel (128-row blocks, so
        several q and kv blocks, masked and unmasked tiles) against float32
        ``attention_full`` under ``highest`` precision."""
        from repro.models.common import attention_full

        q, k, v = rnd(0, (2, S, Hq, D), dtype), rnd(1, (2, S, Hkv, D), dtype), rnd(2, (2, S, Hkv, Dv), dtype)
        w = rnd(3, (2, S, Hq, Dv))

        def kernel(q, k, v):
            o = fa.flash_attention(q, k, v, causal=True, scale=scale, window=window,
                                   block_q=128, block_k=128, interpret=True)
            return jnp.sum(o.astype(jnp.float32) * w), o

        def full(q, k, v):
            f32 = [x.astype(jnp.float32) for x in (q, k, v)]
            o = attention_full(*f32, causal=True, window=window, scale=scale)
            return jnp.sum(o * w), o

        with jax.default_matmul_precision("highest"):
            (_, o_k), g_k = jax.value_and_grad(kernel, (0, 1, 2), has_aux=True)(q, k, v)
            (_, o_r), g_r = jax.value_and_grad(full, (0, 1, 2), has_aux=True)(q, k, v)
        tol = 1e-4 if dtype == jnp.float32 else 3e-2
        for got, want in zip((o_k, *g_k), (o_r, *g_r)):
            assert got.dtype == dtype
            got, want = np.asarray(got, np.float32), np.asarray(want, np.float32)
            assert np.max(np.abs(got - want)) <= tol * np.max(np.abs(want))
