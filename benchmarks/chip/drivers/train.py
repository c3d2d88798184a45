"""Training cells: SlowMo rounds through the training launcher's own path,
``launch/train.build_trainer`` -> ``Trainer`` -> the compiled round.

The configuration file gives the model (``program_args``: the launcher
arguments that select it) and the SlowMo settings; the traffic file gives
the job: sequence length, rows per device, the workers (one per chip),
the token stream, how many rounds of tokens set-up draws, how many rounds
set-up drives (and the reference follows), how many rounds a traced run
traces, and the limit of each compared number.

Set-up draws ``token_rounds`` rounds of tokens on the device, builds the
trainer, draws its state from the seed and runs the first ``check_rounds``
rounds through ``Trainer.run``, keeping what the comparison reads.  The
window then runs whole rounds through the same trainer and state until
``--seconds`` have passed, round r reading the tokens drawn for round
r mod ``token_rounds``: the window times the round program alone.  After
it, with the program's state freed, the plain reference follows the first
rounds from the same seed and tokens, and the run compares:

* ``loss_gap``: the largest gap between a round's mean loss and the
  reference's, in nats;
* ``first_grad_gap``: the first pseudo-gradient as the outer optimizer
  receives it (slow momentum after round 1), leaf by leaf: the gap between
  the program's norm and the reference's, over the larger of the
  reference's norm of that leaf and of the median leaf; the worst leaf;
* ``change_gap``: the same for the outer parameters' change over the
  ``check_rounds`` rounds.

Leaves whose reference first gradient is under a thousandth of the median
leaf's are left out of both.
"""
from __future__ import annotations

import functools
import gc
import math
import shutil
import statistics
import tempfile
import time

import harness
from harness import Check, Outcome, log


def make_sampler(seed: int, vocab: int, workers: int, markov: dict):
    """``sample(r, tau, rows, seq)``: round r's tokens, (tau, workers, rows,
    seq) int32 on the device, keyed by the seed and r: every row a fresh
    walk of one random first-order Markov chain drawn from the seed, whose
    transition logits from token a to token b are E_in[a] . E_out[b] /
    temperature with (vocab, rank) standard normal factors, scaled to unit
    variance.  A stream with
    something to learn, and a few nats of it, so that SGD at the
    configuration's rate stays stable over a run."""
    import jax
    import jax.numpy as jnp

    key = jax.random.PRNGKey(harness.derive_seed(seed, "tokens"))
    k_in, k_out, k_walk = jax.random.split(key, 3)
    rank = markov["rank"]
    e_in = jax.random.normal(k_in, (vocab, rank)) * rank**-0.5 / markov["temperature"]
    e_out = jax.random.normal(k_out, (vocab, rank))

    # the key is an argument, not a constant of the program, so that every
    # seed runs the one program the persistent cache holds
    @functools.partial(jax.jit, static_argnums=(4, 5, 6))
    def sample(e_in, e_out, k_walk, r, tau, rows, seq):
        k0, k1 = jax.random.split(jax.random.fold_in(k_walk, r))
        first = jax.random.randint(k0, (tau * workers * rows,), 0, vocab)

        def step(tok, k):
            nxt = jax.random.categorical(k, e_in[tok] @ e_out.T).astype(jnp.int32)
            return nxt, nxt

        _, rest = jax.lax.scan(step, first, jax.random.split(k1, seq - 1))
        walk = jnp.concatenate([first[None], rest]).T  # (N, seq)
        return walk.reshape(tau, workers, rows, seq)

    return lambda r, tau, rows, seq: sample(e_in, e_out, k_walk, r, tau, rows, seq)


def draw_rounds(sample, rounds: int, tau: int, rows: int, seq: int):
    """The tokens of rounds 0 .. rounds - 1, drawn now and kept on the
    device, as a sampler of the training loop's signature that hands round
    r the tokens of round r mod ``rounds``."""
    import jax

    pool = [sample(r, tau, rows, seq) for r in range(rounds)]
    jax.block_until_ready(pool)
    return lambda r, *_: pool[r % rounds]


def launcher_args(config: dict, traffic: dict) -> list:
    """The training launcher's arguments for this cell."""
    sm = config["slowmo"]
    argv = list(config["program_args"]) + [
        "--algo", sm["algo"], "--tau", str(sm["tau"]), "--alpha", str(sm["alpha"]),
        "--beta", str(sm["beta"]), "--lr", str(sm["lr"]), "--seq", str(traffic["seq"]),
        "--mesh", "host", "--workers", str(traffic["workers"]),
        "--batch", str(traffic["rows_per_device"]),
    ]
    return argv + (["--packed"] if sm.get("packed") else [])


def _named(tree) -> dict:
    from reference import dense

    return dense.leaf_names(tree)


def worst_leaf_gap(prog: dict, ref: dict, keep) -> float:
    """Largest |prog - ref| of a leaf's norm, over the larger of the
    reference's norm of that leaf and of the median leaf."""
    med = statistics.median(ref[k] for k in keep)
    return max(abs(prog[k] - ref[k]) / max(ref[k], med) for k in keep)


def compared_leaves(ref_first: dict) -> list:
    """Leaves whose reference first gradient is at least a thousandth of
    the median leaf's (others move by round-off alone)."""
    med = statistics.median(ref_first.values())
    return sorted(k for k, v in ref_first.items() if v >= 1e-3 * med)


def run(cell, args, *, watch, clock, devices) -> Outcome:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.launch import train as train_launch
    from repro.launch.compile_cache import enable_compile_cache

    config, traffic = cell.config, cell.traffic
    enable_compile_cache()
    argv = launcher_args(config, traffic)
    log(f"launcher arguments: {' '.join(argv)}")
    trainer = train_launch.build_trainer(train_launch.build_parser().parse_args(argv))
    harness.check_sizes(cell.config_mod.program_sizes(config), trainer.model.config)
    sm = trainer.smcfg
    W, tc = sm.num_workers, trainer.tc
    check_rounds = traffic["check_rounds"]
    assert traffic["token_rounds"] >= check_rounds, "the checked rounds read distinct rows"
    tokens = draw_rounds(
        make_sampler(args.seed, trainer.model.config.vocab_size, W, traffic["markov"]),
        traffic["token_rounds"], sm.tau, tc.per_worker_batch, tc.seq_len)
    trainer.sampler = tokens
    tokens_per_round = sm.tau * W * tc.per_worker_batch * tc.seq_len
    pack = trainer.pack
    norms = jax.jit(lambda t: jax.tree.map(
        lambda x: jnp.sqrt(jnp.sum(jnp.square(x.astype(jnp.float32)))), t))

    key = jax.random.PRNGKey(harness.derive_seed(args.seed, "init"))
    state = trainer.init_state(key)
    x0 = {k: np.asarray(v) for k, v in _named(pack.unpack(state.outer_params)).items()}
    state = trainer.run(state=state, rounds=1)
    first = {k: float(v) for k, v in _named(norms(pack.unpack(state.slow_u))).items()}
    state = trainer.run(state=state, rounds=check_rounds - 1)
    outer = _named(pack.unpack(state.outer_params))
    change = {k: float(np.linalg.norm(np.asarray(v) - x0[k])) for k, v in outer.items()}
    del outer, x0
    losses = [h["loss"] for h in trainer.history[:check_rounds]]
    setup_s = clock.now()
    watch.mark()

    counts = {"tokens_per_round": tokens_per_round,
              "flops_per_token": cell.config_mod.train_flops_per_token(config, tc.seq_len)}
    trace = None
    rounds = failed = 0
    t0 = time.perf_counter()
    if args.trace:
        tdir = tempfile.mkdtemp(prefix="bench-trace-")
        jax.profiler.start_trace(tdir)
        with jax.profiler.TraceAnnotation("bench:window"):
            for _ in range(traffic["trace_rounds"]):
                state = trainer.run(state=state, rounds=1)
                rounds += 1
            jax.block_until_ready(state)
        jax.profiler.stop_trace()
        from tracing import Trace

        trace = Trace.from_dir(tdir, len(devices))
        shutil.rmtree(tdir, ignore_errors=True)
    else:
        while True:
            state = trainer.run(state=state, rounds=1)
            rounds += 1
            if time.perf_counter() - t0 >= args.seconds:
                break
        jax.block_until_ready(state)
    window_s = time.perf_counter() - t0
    in_window = watch.since_mark()
    failed = sum(not math.isfinite(h["loss"]) for h in trainer.history[check_rounds:])
    counts["rounds_traced"] = rounds if args.trace else 0
    peak = harness.memory_peak_bytes(devices)
    notes = [f"set-up {setup_s:.3f} s; window {window_s:.3f} s, {rounds} rounds of "
             f"{tokens_per_round} tokens; programs compiled or loaded in the window: "
             f"{in_window}"]
    del state, trainer
    gc.collect()

    t_ref = time.perf_counter()
    ref = cell.config_mod.reference_train(
        config, traffic, key, tokens,
        rounds=check_rounds, workers=W, rows=tc.per_worker_batch, seq=tc.seq_len)
    notes.append(f"reference: {time.perf_counter() - t_ref:.1f} s for {check_rounds} rounds, "
                 f"{ref.first_step_s:.1f} s of it the first step")
    keep = compared_leaves(ref.first_grad)
    checks = compare(losses, first, change, ref, keep, traffic["limits"])
    notes.append(f"program losses {losses}; reference {ref.losses}")
    for k in keep:
        notes.append(f"leaf {k}: first grad {first[k]:.6g} vs {ref.first_grad[k]:.6g}; "
                     f"change {change[k]:.6g} vs {ref.change[k]:.6g}")
    e2e = {"train_tokens_per_s": rounds * tokens_per_round / window_s,
           "setup_s": setup_s}
    return Outcome(attempted=rounds, failed=failed, end_to_end=e2e, checks=checks,
                   memory_peak_bytes=peak, counts=counts, trace=trace, notes=notes)


def compare(losses, first, change, ref, keep, limits) -> list:
    return [
        Check("loss_gap", max(abs(a - b) for a, b in zip(losses, ref.losses)),
              limits["loss_gap"]),
        Check("first_grad_gap", worst_leaf_gap(first, ref.first_grad, keep),
              limits["first_grad_gap"]),
        Check("change_gap", worst_leaf_gap(change, ref.change, keep),
              limits["change_gap"]),
    ]
