"""Cross-layer integration tests: kernels inside the SlowMo round, variants
equivalence, and end-to-end round behaviour on a real model."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.core import slowmo
from repro.models import build_model, make_batch


def tiny_model():
    cfg = get_config("olmo-1b", reduced=True).replace(
        vocab_size=32, d_model=64, d_ff=128, n_heads=2, n_kv_heads=2
    )
    return cfg, build_model(cfg)


class TestPallasInRound:
    def test_pallas_outer_update_matches_jnp(self):
        """SlowMo rounds with the fused Pallas outer update (interpret mode)
        must match the pure-jnp path on a real model."""
        cfg, model = tiny_model()
        batch = {
            "tokens": jnp.broadcast_to(
                make_batch(cfg, jax.random.PRNGKey(1), 4, 16)["tokens"][None, None],
                (2, 4, 4, 16),
            )
        }
        results = {}
        for use_pallas in (False, True):
            smcfg = dataclasses.replace(
                slowmo.preset("local_sgd+slowmo", num_workers=4, tau=2, beta=0.6),
                use_pallas=use_pallas,
            )
            state = slowmo.init_slowmo(smcfg, model.init(jax.random.PRNGKey(0)))
            round_fn = jax.jit(slowmo.make_slowmo_round(smcfg, model.loss_fn))
            state, _ = round_fn(state, batch, 0.1)
            results[use_pallas] = state
        for a, b in zip(
            jax.tree.leaves(results[False].outer_params),
            jax.tree.leaves(results[True].outer_params),
        ):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-5, atol=1e-6)
        for a, b in zip(
            jax.tree.leaves(results[False].slow_u), jax.tree.leaves(results[True].slow_u)
        ):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-5, atol=1e-6)


class TestVariantEquivalences:
    def test_unroll_inner_matches_fori(self):
        cfg, model = tiny_model()
        batch = {"tokens": make_batch(cfg, jax.random.PRNGKey(1), 1, 16)["tokens"]}
        batch = {"tokens": jnp.broadcast_to(batch["tokens"][None, None], (3, 4, 1, 16))}
        outs = {}
        for unroll in (False, True):
            smcfg = dataclasses.replace(
                slowmo.preset("sgp+slowmo", num_workers=4, tau=3, beta=0.5),
                unroll_inner=unroll,
            )
            state = slowmo.init_slowmo(smcfg, model.init(jax.random.PRNGKey(0)))
            round_fn = jax.jit(slowmo.make_slowmo_round(smcfg, model.loss_fn))
            state, m = round_fn(state, batch, 0.05)
            outs[unroll] = (state, float(m["loss"]))
        assert outs[False][1] == pytest.approx(outs[True][1], rel=1e-6)
        for a, b in zip(
            jax.tree.leaves(outs[False][0].params), jax.tree.leaves(outs[True][0].params)
        ):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-5, atol=1e-6)

    def test_bf16_average_close_to_f32(self):
        cfg, model = tiny_model()
        batch = {"tokens": jnp.broadcast_to(
            make_batch(cfg, jax.random.PRNGKey(1), 2, 16)["tokens"][None, None], (2, 4, 2, 16))}
        outs = {}
        for dt in (None, jnp.bfloat16):
            smcfg = dataclasses.replace(
                slowmo.preset("local_sgd+slowmo", num_workers=4, tau=2),
                average_dtype=dt,
            )
            state = slowmo.init_slowmo(smcfg, model.init(jax.random.PRNGKey(0)))
            round_fn = jax.jit(slowmo.make_slowmo_round(smcfg, model.loss_fn))
            state, _ = round_fn(state, batch, 0.1)
            outs[dt is None] = state
        for a, b in zip(
            jax.tree.leaves(outs[True].outer_params), jax.tree.leaves(outs[False].outer_params)
        ):
            np.testing.assert_allclose(
                np.asarray(a), np.asarray(b), rtol=2e-2, atol=2e-2
            )
