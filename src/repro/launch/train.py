"""Training launcher: pick an architecture + SlowMo algorithm and train.

By default it runs the REDUCED configs; ``--full`` takes the published
widths, and ``--layers N``, ``--experts-held N`` (MoE) and ``--vocab N``
cut a full config's depth, experts and vocabulary to what the chips hold.
On a TPU the fused Pallas kernels (lines 7-8, the Nesterov inner step, the
expert layer's grouped products) run compiled; elsewhere the same math
runs as XLA ops.

    PYTHONPATH=src python -m repro.launch.train --arch olmo-1b --algo sgp+slowmo \
        --rounds 20 --workers 8 --tau 12
"""
from __future__ import annotations

import argparse
import dataclasses

import jax
import jax.numpy as jnp

from ..configs import ARCH_IDS, get_config
from ..core import slowmo
from ..data import MarkovLMConfig, make_audio_sampler, make_markov_sampler
from ..kernels import ops as kernel_ops
from ..models import build_model, param_count
from ..train import TrainConfig, Trainer
from ..train import checkpoint as ckpt_lib
from .compile_cache import enable_compile_cache


def build_parser() -> argparse.ArgumentParser:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="olmo-1b", choices=ARCH_IDS)
    ap.add_argument("--algo", default="local_sgd+slowmo")
    ap.add_argument("--workers", type=int, default=8)
    ap.add_argument("--tau", type=int, default=12)
    ap.add_argument(
        "--beta",
        type=float,
        default=0.7,
        help="slow momentum (paper sweeps 0.4-0.8; Table 2 uses 0.7)",
    )
    ap.add_argument("--alpha", type=float, default=1.0)
    ap.add_argument("--rounds", type=int, default=20)
    ap.add_argument("--lr", type=float, default=0.2)
    ap.add_argument("--seq", type=int, default=64)
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--full", action="store_true", help="full-size config (TPU)")
    ap.add_argument(
        "--layers",
        type=int,
        default=None,
        help="with --full: keep the published widths and cut the depth to "
        "this many layers",
    )
    ap.add_argument(
        "--experts-held",
        type=int,
        default=None,
        help="with --full, MoE: each layer holds experts 0..N-1, one chip's "
        "share under expert parallelism; the router still scores them all",
    )
    ap.add_argument(
        "--vocab",
        type=int,
        default=None,
        help="with --full: keep this many token ids, a slice of the vocabulary",
    )
    ap.add_argument(
        "--packed",
        action="store_true",
        help="flat-buffer state: one kernel launch and one collective per "
        "SlowMo boundary instead of one per parameter leaf",
    )
    ap.add_argument(
        "--overlap-boundary",
        action="store_true",
        help="staleness-1 boundary: issue the line-6 exact average at the "
        "top of the round and consume it after the inner steps, so the "
        "slow-momentum update applies the PREVIOUS round's average "
        "(docs/architecture.md section 6); exact-average algos only",
    )
    ap.add_argument(
        "--compress-ratio",
        type=float,
        default=None,
        help="top-k boundary compression: average only this fraction of "
        "each worker's boundary delta per block (error feedback carries "
        "the remainder; docs/architecture.md section 7); 1.0 = dense-"
        "equivalent, unset = dense all-reduce; exact-average algos only",
    )
    ap.add_argument("--ckpt", default="")
    ap.add_argument(
        "--mesh",
        default="none",
        choices=("none", "host"),
        help="'host': lower rounds with shard_map over a device mesh (CPU: "
        "export XLA_FLAGS=--xla_force_host_platform_device_count=<devices> "
        "first); 'none': array-axis oracle",
    )
    ap.add_argument(
        "--layout",
        default="flat",
        choices=("flat", "hierarchical"),
        help="how --mesh host maps workers to devices: 'flat' = one worker "
        "per device (--workers devices); 'hierarchical' = one worker per pod "
        "of --pods x --dp devices, gradients all-reduced over the pod's --dp "
        "data shards every inner step",
    )
    ap.add_argument("--pods", type=int, default=2, help="hierarchical: worker (pod) count")
    ap.add_argument("--dp", type=int, default=2, help="hierarchical: data shards per pod")
    ap.add_argument(
        "--tp",
        type=int,
        default=1,
        help="tensor-parallel degree: every worker becomes a group of --tp "
        "devices along a 'model' mesh axis holding Megatron-style shards of "
        "its parameters (column-parallel qkv/gate/up, row-parallel out/down, "
        "vocab-parallel embed/CE; activations psum over 'model' only), so "
        "hierarchical meshes are (--pods x --dp x --tp) and flat meshes "
        "(--workers x --tp).  Needs --mesh host and a dense-family arch — "
        "the whole text family qualifies, swiglu included (de-fused "
        "w_gate/w_up), plus hubert-xlarge; MoE expert parallelism is a "
        "ROADMAP item",
    )
    ap.add_argument(
        "--elastic",
        action="store_true",
        help="run the elastic loop: heartbeat/evict dead workers at round "
        "boundaries, mask stragglers out of the exact average, retry flaky "
        "boundaries with backoff (docs/architecture.md section 5)",
    )
    ap.add_argument(
        "--fault",
        action="append",
        default=[],
        metavar="SPEC",
        help="inject a deterministic fault (repeatable; implies --elastic): "
        "kill:W@R, delay:W@R+STEPS, flaky:@R*N, rejoin:W@R",
    )
    ap.add_argument(
        "--fault-seed",
        type=int,
        default=None,
        help="draw a random-but-reproducible FaultPlan from this seed "
        "instead of explicit --fault specs (implies --elastic)",
    )
    ap.add_argument(
        "--timeout-rounds",
        type=int,
        default=1,
        help="elastic: rounds of heartbeat silence before eviction",
    )
    ap.add_argument(
        "--min-workers",
        type=int,
        default=1,
        help="elastic: abort rather than evict below this many survivors",
    )
    return ap


def build_trainer(args) -> Trainer:
    """The Trainer the parsed launcher ``args`` describe (mesh, model, data,
    SlowMo config); ``main`` runs it."""
    if args.tp > 1 and args.mesh != "host":
        raise SystemExit("--tp needs --mesh host (tensor parallelism is a mesh-path feature)")

    layout = None
    if args.mesh == "host":
        if args.layout == "hierarchical":
            from .mesh import make_hierarchical_layout

            layout = make_hierarchical_layout(args.pods, args.dp, args.tp)
            if args.workers != layout.num_workers:
                print(
                    f"hierarchical layout: num_workers := {layout.num_workers} "
                    f"pods (ignoring --workers {args.workers}); each worker's "
                    f"batch splits over {args.dp} devices"
                    + (f", params over {args.tp} model shards" if args.tp > 1 else "")
                )
                args.workers = layout.num_workers
        else:
            from .mesh import make_spmd_layout

            layout = make_spmd_layout(args.workers, args.tp)
        print(f"mesh path ({args.layout}): {args.workers} workers over {layout.mesh}")

    cfg = get_config(args.arch, reduced=not args.full)
    cuts = {"--layers": ("n_layers", args.layers),
            "--experts-held": ("experts_held", args.experts_held),
            "--vocab": ("vocab_size", args.vocab)}
    for flag, (field, value) in cuts.items():
        if value is None:
            continue
        if not args.full:
            raise SystemExit(f"{flag} cuts a --full config")
        if field == "experts_held" and not 0 < value <= cfg.n_experts:
            raise SystemExit(f"--experts-held needs 1..{cfg.n_experts} experts")
        cfg = cfg.replace(**{field: value})
    model = build_model(cfg)
    n = param_count(jax.eval_shape(model.init, jax.random.PRNGKey(0)))
    size = f"{cfg.n_layers} layers" if args.full else "reduced"
    print(f"{args.arch} ({size}): {n/1e6:.1f}M params")

    if cfg.modality == "audio":
        sampler = make_audio_sampler(cfg.vocab_size, cfg.frontend_dim, args.workers)
    else:
        data = MarkovLMConfig(vocab_size=cfg.vocab_size, temperature=0.8)
        sampler = make_markov_sampler(data, args.workers)

    smcfg = dataclasses.replace(
        slowmo.preset(args.algo, num_workers=args.workers, tau=args.tau, beta=args.beta),
        alpha=args.alpha,
        param_dtype=cfg.dtype if args.full else jnp.float32,
        use_pallas=jax.default_backend() == "tpu",
        packed=args.packed,
        overlap_boundary=args.overlap_boundary,
        compress_ratio=args.compress_ratio,
    )
    tc = TrainConfig(
        total_rounds=args.rounds, per_worker_batch=args.batch, seq_len=args.seq,
        lr=args.lr, log_every=max(args.rounds // 10, 1),
        ckpt_every=10 if args.ckpt else 0, ckpt_path=args.ckpt,
    )

    elastic = faults = None
    if args.elastic or args.fault or args.fault_seed is not None:
        from ..elastic import ElasticConfig
        from ..elastic.faults import FaultPlan

        elastic = ElasticConfig(
            timeout_rounds=args.timeout_rounds, min_workers=args.min_workers
        )
        if args.fault_seed is not None:
            faults = FaultPlan.from_seed(
                args.fault_seed, args.workers, args.rounds,
                min_workers=args.min_workers,
            )
        elif args.fault:
            faults = FaultPlan.parse(args.fault)
        if faults:
            print(f"elastic: injecting {len(faults.events)} fault(s)")

    return Trainer(
        model, smcfg, tc, sampler, layout=layout, elastic=elastic, faults=faults
    )


def main(argv=None):
    args = build_parser().parse_args(argv)
    enable_compile_cache()
    trainer = build_trainer(args)

    state = None
    if args.ckpt and ckpt_lib.exists(args.ckpt):
        # checkpoints are always tree-layout: validate against an unpacked
        # template and let restore_state re-pack for a --packed trainer.
        template = trainer.init_state()
        if trainer.pack is not None:
            from ..core import packing

            template = packing.unpack_state(trainer.pack, template)
        state, meta = ckpt_lib.restore_state(
            args.ckpt, like=template, pack=trainer.pack
        )
        done = int(meta.get("step") or 0)
        print(f"resuming from {args.ckpt} at round {done}")
        if done >= args.rounds:
            print("checkpoint already past --rounds; nothing to do")
            return
        state = jax.tree.map(jnp.asarray, state)
    rounds = args.rounds if state is None else args.rounds - int(state.outer_step)
    # the tally is taken while the round traces; it is printed when the run
    # returns, as splitting the run would restart the elastic loop's state
    with kernel_ops.tally() as tally:
        trainer.run(state=state, rounds=rounds)
    if trainer.smcfg.use_pallas:
        print(f"fused kernels, as traced: {tally.summary()}")
    routed = [h for h in trainer.history if "held_share" in h]
    if routed:
        bounds = [h["rows_bound_share"] for h in routed]
        print(
            "routing over the run: "
            f"{100 * sum(h['held_share'] for h in routed) / len(routed):.2f}% of "
            "top-k assignments on held experts; largest held expert's load "
            f"{max(h['held_load_max'] for h in routed):.3f}x the held mean; "
            f"expert rows bounded at {sum(bounds) / len(bounds):.4f} of the worst "
            f"case (rounds {min(bounds):.4f}-{max(bounds):.4f})"
        )


if __name__ == "__main__":
    main()
