"""Plain reference of SlowMo (Wang et al., ICLR 2020, Algorithm 1) with
SGD-Nesterov as the base optimizer and the ``reset`` buffer strategy, over
any plain reference model: ``run`` takes the model's module, which gives
``trunc_normal_init(arch, key)`` and ``loss(arch, params, tokens, lowp)``
(``reference.dense``, ``reference.latent_moe``).  The loop is
``reference.slowmo.run``'s, which calls ``dense`` by name.

For each round t, every worker i starts from the outer iterate x_{t,0}
(stored in the worker parameter dtype the configuration states), takes tau
Nesterov steps on its own rows

    h <- m h + g ;  d = m h + g ;  x <- x - gamma d        (Table C.1)

then the workers' endpoints are averaged exactly (line 6) and the slow
momentum update applies (lines 7-8):

    u <- beta u + (x_{t,0} - mean_i x_{t,tau}^(i)) / gamma
    x_{t+1,0} <- x_{t,0} - alpha gamma u

A worker's gradient is the mean over its rows of each row's loss.  Written
from the paper; imports nothing of the program under test.  Runs one row at
a time, so that it fits beside nothing else on one chip.
"""
from __future__ import annotations

import time

import jax
import jax.numpy as jnp
import numpy as np

from .dense import leaf_norms
from .slowmo import Opt, Readings

__all__ = ["Opt", "Readings", "run"]


def run(model, arch, opt: Opt, key, batches, rounds: int, workers: int,
        lowp: bool = False, half_batch: bool = False) -> Readings:
    """``model`` is the reference module and ``arch`` its sizes;
    ``batches(r)`` gives round r's tokens, (tau, workers, rows, seq).
    ``lowp`` runs the control; ``half_batch`` the fault that drops the
    second half of every worker's rows."""
    pdt = jnp.dtype(opt.param_dtype)
    with jax.default_matmul_precision("highest"):
        init = jax.jit(model.trunc_normal_init, static_argnums=0)
        outer = init(arch, key)
        u = jax.tree.map(jnp.zeros_like, outer)
        grad = jax.jit(jax.value_and_grad(lambda p, t: model.loss(arch, p, t, lowp)))
        add = jax.jit(lambda a, b: jax.tree.map(jnp.add, a, b), donate_argnums=0)

        @jax.jit
        def nesterov(x, h, g, n):
            g = jax.tree.map(lambda t: t / n, g)
            h = jax.tree.map(lambda hh, gg: opt.momentum * hh + gg, h, g)
            d = jax.tree.map(lambda hh, gg: opt.momentum * hh + gg, h, g)
            x = jax.tree.map(
                lambda xx, dd: (xx.astype(jnp.float32) - opt.lr * dd).astype(pdt), x, d
            )
            return x, h

        @jax.jit
        def outer_step(outer, u, x_tau):
            u = jax.tree.map(lambda uu, o, x: opt.beta * uu + (o - x) / opt.lr, u, outer, x_tau)
            outer = jax.tree.map(lambda o, uu: o - opt.alpha * opt.lr * uu, outer, u)
            return outer, u

        cast = jax.jit(lambda t: jax.tree.map(lambda x: x.astype(pdt), t))
        up = jax.jit(lambda t: jax.tree.map(lambda x: x.astype(jnp.float32), t))
        losses, first, t0, first_step = [], None, time.perf_counter(), None
        for r in range(rounds):
            toks = np.asarray(batches(r))
            xs = [cast(outer) for _ in range(workers)]
            hs = [jax.tree.map(jnp.zeros_like, outer) for _ in range(workers)]
            # the outer state waits on the host while the workers step
            outer, u = jax.device_get((outer, u))
            total = 0.0
            for k in range(opt.tau):
                for w in range(workers):
                    rows = toks[k, w]
                    if half_batch:
                        rows = rows[: max(1, rows.shape[0] // 2)]
                    g, xw = None, up(xs[w])
                    for row in rows:
                        lv, gr = grad(xw, jnp.asarray(row))
                        total += float(lv) / (len(rows) * workers)
                        g = gr if g is None else add(g, gr)
                    del xw
                    xs[w], hs[w] = nesterov(xs[w], hs[w], g, float(len(rows)))
                    del g
                    if first_step is None:
                        first_step = time.perf_counter() - t0
            x_tau = up(xs[0])
            for w in range(1, workers):
                x_tau = add(x_tau, up(xs[w]))
            x_tau = jax.tree.map(lambda t: t / workers, x_tau)
            del xs, hs
            outer, u = jax.device_put((outer, u))
            outer, u = outer_step(outer, u, x_tau)
            del x_tau
            losses.append(total / opt.tau)
            if r == 0:
                first = leaf_norms(u)
        start = init(arch, key)
        change = leaf_norms(jax.tree.map(jnp.subtract, outer, start))
    return Readings(losses=losses, first_grad=first, change=change, first_step_s=first_step)
