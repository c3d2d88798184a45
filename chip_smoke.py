"""Smoke run of SlowMo training and paged serving on a TPU.

    python chip_smoke.py                    # one chip
    python chip_smoke.py --four-chips       # the worker meshes of a four-chip host
    python chip_smoke.py --lr-sweep 0.1 0.3 # one-chip training rounds per lr

One chip: the Pallas kernels of the main path against their references at
real widths; a few SlowMo rounds (local SGD + slow momentum, packed state,
bf16 worker params, fused kernels compiled) at olmo-1b's published width
with the depth cut to what one chip holds, then the first round again
against the same round on XLA's elementwise path; then the
continuous-batching engine serving full-depth olmo-1b, prefill through the
flash kernel.  Both go through the launchers' own code
(``repro.launch.train`` and ``repro.launch.serve``).

``--four-chips`` runs only the worker-mesh path: flat W=4 and hierarchical
(2 pods x 2 data) rounds at the same width, then the same rounds on the
REDUCED config against the array-axis oracle on the same seeds.

Exits non-zero, printing no result, unless JAX's first device is a TPU.  The
last line of standard output is a JSON object naming the device.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import math
import os
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)), "src"))

from repro.configs import get_config  # noqa: E402
from repro.core import packing  # noqa: E402
from repro.kernels import flash_attention, fused_nesterov, ref, slowmo_update  # noqa: E402
from repro.launch import serve as serve_launch  # noqa: E402
from repro.launch import train as train_launch  # noqa: E402
from repro.launch.compile_cache import enable_compile_cache  # noqa: E402
from repro.models import build_model, param_count  # noqa: E402
from repro.train.trainer import Trainer  # noqa: E402

# one chip holds olmo-1b's SlowMo state (bf16 worker params, f32 Nesterov
# buffer, f32 outer params, f32 slow momentum) for 5 of its 16 layers; the
# AOT memory analysis puts 6 within 0.6 GB of the 16 GiB of HBM
TRAIN_LAYERS = 5
TRAIN = dict(seq=2048, batch=2, tau=4, rounds=12)
# SGD-Nesterov under slow momentum from a random init, on a TPU v5e
# (--lr-sweep 0.01 0.03 0.1 0.3): at lr 0.1 the 5-layer cut holds its loss
# for about 5 rounds, then falls by 0.15 nats by round 12; at 0.01-0.03 it
# does not move in 12 rounds, and at 0.3 it falls sooner and climbs back
LR = "0.1"
SERVE = dict(requests=8, slots=4, prompt=512, tokens=32, page=16)


def check(ok: bool, what: str) -> None:
    if not ok:
        raise RuntimeError(f"chip_smoke: {what}")


class CompileWatch:
    """Compile seconds and persistent-cache hits, from JAX's monitoring."""

    def __init__(self):
        self.seconds = 0.0
        self.hits = 0
        self.misses = 0
        jax.monitoring.register_event_listener(self._event)
        jax.monitoring.register_event_duration_secs_listener(self._duration)

    def _event(self, event, **kw):
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.misses += 1

    def _duration(self, event, secs, **kw):
        if event == "/jax/core/compile/backend_compile_duration":
            self.seconds += secs

    def line(self) -> str:
        return (
            f"compile {self.seconds:.1f} s, persistent cache "
            f"{self.hits} hits / {self.misses} misses"
        )


def peak_bytes() -> str:
    stats = jax.devices()[0].memory_stats()  # None where the backend keeps none
    return str(stats["peak_bytes_in_use"]) if stats else "not reported"


def check_kernels(seq=2048):
    """Each Pallas kernel of the main path, compiled, against its reference."""
    interpret = jax.default_backend() != "tpu"  # compiled on the chip
    k = jax.random.split(jax.random.PRNGKey(0), 6)
    shape = (4096, 1024)
    x0, xt, u = (jax.random.normal(k[i], shape) for i in range(3))
    got = slowmo_update.slowmo_update_2d(
        x0, xt, u, 0.1, alpha=1.0, beta=0.7, interpret=interpret
    )
    want = ref.slowmo_outer_update_ref(x0, xt, u, gamma=0.1, alpha=1.0, beta=0.7)
    for g, w in zip(got, want):
        np.testing.assert_allclose(np.asarray(g), np.asarray(w), rtol=1e-6, atol=1e-6)

    x = x0.astype(jnp.bfloat16)
    got = fused_nesterov.fused_nesterov_2d(
        x, u, xt, 0.1, momentum=0.9, interpret=interpret
    )
    want = ref.fused_nesterov_ref(x, u, xt, lr=0.1, momentum=0.9)
    np.testing.assert_allclose(
        np.asarray(got[0], np.float32), np.asarray(want[0], np.float32),
        rtol=2e-2, atol=1e-5,
    )
    np.testing.assert_allclose(np.asarray(got[1]), np.asarray(want[1]), rtol=1e-6, atol=1e-6)

    # olmo-1b prefill: (batch, seq, heads, head_dim) in bf16
    q, kk, v = (
        jax.random.normal(k[3 + i], (1, seq, 16, 128), jnp.bfloat16) for i in range(3)
    )
    got = flash_attention.flash_attention(q, kk, v, causal=True, interpret=interpret)
    with jax.default_matmul_precision("highest"):
        want = ref.flash_attention_ref(
            q.astype(jnp.float32), kk.astype(jnp.float32), v.astype(jnp.float32)
        )
    err = float(jnp.max(jnp.abs(got.astype(jnp.float32) - want)))
    check(err < 2e-2, f"flash attention off its reference by {err}")
    print(f"kernels: slowmo_update, fused_nesterov, flash_attention match "
          f"their references (flash max err {err:.2e})")


F32_REL, BF16_REL = 2.0**-20, 2.0**-7  # 8 f32 ulps; one to two bf16 ulps


@jax.jit
def _deviation(a, b, ref, units, rel):
    """Share of elements with units * |a - b| above rel * (|ref| + mean |ref|
    / 1024), and the worst element's multiple of that bound."""
    d = jnp.abs(a.astype(jnp.float32) - b.astype(jnp.float32)) * units
    r = jnp.abs(ref.astype(jnp.float32))
    bound = rel * (r + jnp.mean(r) / 1024)
    return jnp.mean(d > bound), jnp.max(d / bound)


def _compare(what, got, want, ref=None, units=1.0):
    """Per buffer: the share of elements beyond 8 f32 ulps of ``ref`` (the
    bf16 casts a last-bit difference tipped) and the worst element in bf16
    ulps of ``ref``."""
    ref = want if ref is None else ref
    out = []
    for a, b, r in zip(jax.tree.leaves(got), jax.tree.leaves(want), jax.tree.leaves(ref)):
        tipped, _ = _deviation(a, b, r, units, F32_REL)
        _, worst = _deviation(a, b, r, units, BF16_REL)
        print(f"  {what} {a.dtype} {a.shape}: {float(tipped):.2e} of elements beyond "
              f"8 f32 ulps, worst {float(worst):.3f} bf16 ulps")
        out.append((float(tipped), float(worst)))
    return out


def kernels_on_state(trainer, params, outer_params, slow_u):
    """The fused kernels against XLA's elementwise path (``use_pallas=False``)
    on the same inputs at this width: a Nesterov step from the trained
    packed parameters with a real gradient (the momentum buffer holding
    that gradient too), then the lines 7-8 update on the trained outer
    state towards the stepped parameters.
    f32 outputs agree to 8 f32 ulps; bf16 parameters differ only where a
    last f32 bit tips the cast (at most 0.1% of elements, one bf16 ulp
    each).  ``slow_u`` is compared in parameter units (x gamma)."""
    from repro.kernels import ops

    cfg, pack = trainer.smcfg, trainer.pack
    check(cfg.use_pallas and pack is not None and cfg.inner.nesterov,
          "the trainer does not run the fused kernels on packed state")
    gamma, inner, tc = float(trainer.lr_fn(0)), cfg.inner, trainer.tc
    batch = {"tokens": trainer.sampler(2**30 + 1, 1, tc.per_worker_batch, tc.seq_len)[0, 0]}
    tree = jax.tree.map(lambda p: p[0], pack.unpack(params))
    grads = jax.jit(jax.grad(trainer.model.loss_fn))(tree, batch)
    g = pack.pack(jax.tree.map(lambda t: t[None], grads), dtype=jnp.float32)
    del tree, grads

    # arrays go in as arguments: a closed-over array would be baked into
    # the program as a constant
    nesterov = jax.jit(lambda x, h, g, use_pallas: ops.fused_nesterov_update(
        x, h, g, lr=gamma, momentum=inner.momentum,
        weight_decay=inner.weight_decay, use_pallas=use_pallas), static_argnums=3)
    outer = jax.jit(lambda x0, xt, u, use_pallas: ops.slowmo_outer_update(
        x0, xt, u, gamma=gamma, alpha=cfg.alpha, beta=cfg.beta,
        use_pallas=use_pallas), static_argnums=3)
    xk, hk = nesterov(params, g, g, True)
    x, h = nesterov(params, g, g, False)
    bf16 = _compare("Nesterov step x", xk, x)
    f32 = _compare("Nesterov step h", hk, h)
    del xk, hk, h, g
    x_tau = jax.tree.map(lambda p: p[0].astype(jnp.float32), x)
    del x
    xk, uk = outer(outer_params, x_tau, slow_u, True)
    xr, ur = outer(outer_params, x_tau, slow_u, False)
    f32 += _compare("lines 7-8 outer params", xk, xr)
    f32 += _compare("lines 7-8 slow_u", uk, ur, xr, gamma)
    check(all(t == 0.0 for t, _ in f32) and all(t <= 1e-3 and w <= 1.0 for t, w in bf16),
          "a fused kernel left XLA's result on the same inputs")
    print("fused vs XLA on the same inputs: the kernels agree")


def _norm(tree) -> float:
    return math.sqrt(sum(float(jnp.sum(jnp.square(jnp.asarray(t, jnp.float32))))
                         for t in jax.tree.leaves(tree)))


def round_fused_vs_xla(trainer):
    """Round 0 through the fused kernels against round 0 through XLA's
    elementwise path, from the same init, batches and lr.  Beyond the
    kernels the two programs differ where XLA fuses and lays out the bf16
    backward around the kernels' custom calls, which rounds the gradients
    differently, and the inner steps amplify that; so this bounds the
    round as a whole: the loss to 1e-3, and the round's movement (slow_u,
    and the outer parameters' step alpha * gamma * slow_u, from slow_u = 0)
    to 25% of its norm."""
    cfg, gamma = trainer.smcfg, float(trainer.lr_fn(0))
    fused = jax.device_get(trainer.run(rounds=1))
    fused_loss = trainer.history[-1]["loss"]
    twin = Trainer(trainer.model, dataclasses.replace(cfg, use_pallas=False),
                   trainer.tc, trainer.sampler, layout=trainer.layout)
    xla = twin.run(rounds=1)
    dloss = abs(fused_loss - twin.history[-1]["loss"])
    diff = lambda a, b: jax.tree.map(lambda s, t: jnp.asarray(s) - t, a, b)  # noqa: E731
    step = _norm(xla.slow_u)
    du = _norm(diff(fused.slow_u, xla.slow_u)) / step
    dx = _norm(diff(fused.outer_params, xla.outer_params)) / (cfg.alpha * gamma * step)
    tipped = [float(_deviation(a, b, b, 1.0, F32_REL)[0]) for a, b in
              zip(jax.tree.leaves(fused.outer_params), jax.tree.leaves(xla.outer_params))]
    print(f"fused vs XLA round 0: loss difference {dloss:.2e} (bound 1e-3); movement "
          f"differs by {du:.3e} (slow_u) and {dx:.3e} (outer params) of its norm "
          f"(bound 0.25); outer params beyond 8 f32 ulps in {tipped} of elements")
    check(dloss < 1e-3 and du <= 0.25 and dx <= 0.25,
          "the fused-kernel round left the XLA round's bound")


def train_rounds(argv, rounds, learn=True):
    """Build the trainer ``repro.launch.train`` builds for ``argv`` and run
    ``rounds`` rounds; every loss must be finite.  Each round's training
    loss is on fresh data, so it is noisy; with ``learn`` the check is on
    one fixed batch that no round draws, scored at the outer parameters
    after every round: it must fall."""
    trainer = train_launch.build_trainer(train_launch.build_parser().parse_args(argv))
    check(
        trainer.smcfg.use_pallas == (jax.default_backend() == "tpu"),
        "the launcher did not select the fused kernels by platform",
    )
    tc = trainer.tc
    held_out = {"tokens": trainer.sampler(2**30, 1, tc.per_worker_batch, tc.seq_len)[0, 0]}
    score = jax.jit(trainer.model.loss_fn)
    trainer.eval_fn = lambda params: score(params, held_out)
    t0 = time.perf_counter()
    state = trainer.run(rounds=1)
    jax.block_until_ready(state)
    first = time.perf_counter() - t0
    t0 = time.perf_counter()
    state = trainer.run(state=state, rounds=rounds - 1)
    jax.block_until_ready(state)
    rest = (time.perf_counter() - t0) / max(rounds - 1, 1)
    losses = [h["loss"] for h in trainer.history]
    held = [h["eval"] for h in trainer.history]
    check(all(map(math.isfinite, losses + held)), f"non-finite loss {losses} {held}")
    check(not learn or held[-1] < held[0], f"held-out loss did not fall: {held}")
    print(f"rounds: first {first:.2f} s (compile included), then {rest:.3f} s "
          f"each (held-out scoring included)")
    print(f"  training loss per round {losses}")
    print(f"  held-out loss per round {held}")
    return trainer, state


def train_phase(watch, layers=TRAIN_LAYERS, seq=TRAIN["seq"],
                batch=TRAIN["batch"], tau=TRAIN["tau"], rounds=TRAIN["rounds"],
                lr=LR, sweep=False):
    """The training rounds, then the fused kernels against XLA's path; ``sweep``
    runs the rounds alone and does not require the loss to fall."""
    full = get_config("olmo-1b")
    print(f"train: olmo-1b widths (d_model {full.d_model}, {full.n_heads} heads, "
          f"d_ff {full.d_ff}, vocab {full.vocab_size}), depth cut to {layers} "
          f"of {full.n_layers} layers; seq {seq}, batch {batch}, tau {tau}, lr {lr}")
    c0 = watch.seconds
    trainer, state = train_rounds([
        "--arch", "olmo-1b", "--full", "--layers", str(layers),
        "--algo", "local_sgd+slowmo", "--mesh", "host", "--workers", "1",
        "--packed", "--tau", str(tau), "--seq", str(seq), "--batch", str(batch),
        "--rounds", str(rounds), "--lr", lr,
    ], rounds, learn=not sweep)
    if not sweep:
        # one chip holds one copy of the state: keep only what the checks read
        parts = (state.params, state.outer_params, state.slow_u)
        del state
        kernels_on_state(trainer, *parts)
        del parts
        round_fused_vs_xla(trainer)
    print(f"train: {watch.seconds - c0:.1f} s compiling; "
          f"peak_bytes_in_use {peak_bytes()}")


def serve_phase(watch, full=True, requests=SERVE["requests"],
                slots=SERVE["slots"], prompt=SERVE["prompt"],
                tokens=SERVE["tokens"], page=SERVE["page"]):
    """``launch/serve.py --continuous`` with prefill through the flash kernel."""
    pages = slots * -(-(prompt + tokens + 1) // page)
    argv = [
        "--arch", "olmo-1b", "--continuous", "--requests", str(requests),
        "--num-slots", str(slots), "--chunk", str(prompt),
        "--prompt-len", str(prompt), "--tokens", str(tokens),
        "--page-size", str(page), "--num-pages", str(pages), "--temperature", "0",
    ] + (["--full"] if full else [])
    args = serve_launch.build_parser().parse_args(argv)
    cfg = get_config("olmo-1b", reduced=not full).replace(attention_impl="pallas")
    model = build_model(cfg)
    params = serve_launch.load_params(args, cfg, model)
    print(f"serve: olmo-1b, {cfg.n_layers} layers, {param_count(params)/1e6:.1f}M "
          f"params in {cfg.dtype.__name__}; {requests} requests of {prompt} "
          f"prompt + {tokens} new tokens over {slots} slots")
    c0 = watch.seconds
    results, stats = serve_launch.run_continuous(args, cfg, model, params)
    check(stats["num_requests"] == requests and len(results) == requests,
          f"served {len(results)} of {requests} requests")
    for rid, toks in results.items():
        check(len(toks) == tokens and int(toks.min()) >= 0
              and int(toks.max()) < cfg.vocab_size,
              f"request {rid} returned {toks}")
    print(f"serve: {len(results)} requests completed, {stats['tokens_per_s']:.1f} "
          f"tokens/s, ttft p50 {stats['ttft_p50']*1e3:.1f} ms; "
          f"{watch.seconds - c0:.1f} s compiling; peak_bytes_in_use {peak_bytes()}")


def compare_states(a, b, what) -> bool:
    """Leaf-by-leaf agreement within the tolerances of tests/test_spmd.py
    (|a - b| <= 1e-5 + 1e-5 |b|); prints the worst leaf's share of it."""
    worst = 0.0
    for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b)):
        x, y = np.asarray(x, np.float32), np.asarray(y, np.float32)
        share = np.abs(x - y) / (1e-5 + 1e-5 * np.abs(y))
        worst = max(worst, float(np.max(share, initial=0.0)))
    print(f"{what}: worst leaf deviation {worst:.3f} of the bound")
    return worst <= 1.0


def four_chip_phase(watch, width=None, tau=TRAIN["tau"],
                    rounds=TRAIN["rounds"], oracle_rounds=3):
    """Flat W=4 and hierarchical 2x2 rounds at olmo-1b width (``width``:
    the launcher's size arguments); then the same layouts on the REDUCED
    config against the array-axis oracle on the same seeds."""
    if width is None:
        width = ["--full", "--layers", str(TRAIN_LAYERS), "--seq", str(TRAIN["seq"]),
                 "--batch", str(TRAIN["batch"])]

    flat = ["--mesh", "host", "--workers", "4"]
    hier = ["--mesh", "host", "--layout", "hierarchical", "--pods", "2",
            "--dp", "2", "--workers", "2"]
    common = ["--arch", "olmo-1b", "--algo", "local_sgd+slowmo", "--packed",
              "--tau", str(tau), "--lr", LR]
    for name, lay in (("flat W=4", flat), ("hierarchical 2x2", hier)):
        print(f"four chips, {name}: {' '.join(width)}")
        trainer, state = train_rounds(
            common + lay + width + ["--rounds", str(rounds)], rounds
        )
        del trainer, state
    print(f"four chips: {watch.seconds:.1f} s compiling; peak_bytes_in_use {peak_bytes()}")

    agree = True
    small = ["--seq", "128", "--batch", "4", "--rounds", str(oracle_rounds)]
    oracles = (("flat W=4", flat, ["--mesh", "none", "--workers", "4"]),
               ("hierarchical 2x2", hier, ["--mesh", "none", "--workers", "2"]))
    with jax.default_matmul_precision("highest"):
        for name, mesh_argv, axis_argv in oracles:
            out = {}
            for side, lay in (("mesh", mesh_argv), ("oracle", axis_argv)):
                trainer, state = train_rounds(
                    common + lay + small, oracle_rounds, learn=False
                )
                out[side] = (
                    packing.unpack_state(trainer.pack, state),
                    [h["loss"] for h in trainer.history],
                )
            (sm, lm), (so, lo) = out["mesh"], out["oracle"]
            dloss = max(abs(x - y) for x, y in zip(lm, lo))
            print(f"REDUCED {name} mesh vs oracle: max loss difference {dloss:.2e} (bound 1e-4)")
            agree &= compare_states(sm, so, f"REDUCED {name} mesh vs oracle")
            agree &= dloss < 1e-4
    check(agree, "a mesh round left the array-axis oracle's tolerance")


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the four-chip worker-mesh path and its oracle")
    ap.add_argument("--lr-sweep", nargs="+", metavar="LR",
                    help="run only the one-chip training rounds, once per "
                         "learning rate, printing each loss trajectory")
    args = ap.parse_args()

    cache_dir = enable_compile_cache()
    devices = jax.devices()
    if devices[0].platform != "tpu":
        sys.exit(f"chip_smoke: no TPU found (JAX's first device is "
                 f"{devices[0].platform}); this check runs on the chip only")
    want = 4 if args.four_chips else 1
    if len(devices) < want:
        sys.exit(f"chip_smoke: --four-chips needs 4 TPU chips, found {len(devices)}")
    print(f"device: {devices[0].device_kind} x{len(devices)}; compile cache {cache_dir}")
    watch = CompileWatch()
    if args.four_chips:
        four_chip_phase(watch)
    elif args.lr_sweep:
        for lr in args.lr_sweep:
            train_phase(watch, lr=lr, sweep=True)
    else:
        check_kernels()
        train_phase(watch)
        serve_phase(watch)
    print(f"all phases passed; {watch.line()}")
    print(json.dumps({"ok": True, "device": {
        "platform": devices[0].platform,
        "kind": devices[0].device_kind,
        "count": len(devices),
    }}))


if __name__ == "__main__":
    main()
