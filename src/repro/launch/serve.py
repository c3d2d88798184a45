"""Serving launcher: static batched decode or continuous batching, with TP.

Static batch (any decoder architecture, the ``DecodeEngine`` path):

    PYTHONPATH=src python -m repro.launch.serve --arch qwen3-4b --batch 4 --tokens 32

Continuous batching (dense family, paged cache + chunked prefill):

    PYTHONPATH=src python -m repro.launch.serve --arch qwen3-4b --continuous \\
        --requests 16 --num-slots 4 --chunk 16

Tensor-parallel continuous serving (``--tp M`` builds a
``make_spmd_layout(1, M)`` mesh; the process must see >= M devices — on a
CPU box set ``XLA_FLAGS=--xla_force_host_platform_device_count=8`` BEFORE
launching):

    XLA_FLAGS=--xla_force_host_platform_device_count=8 \\
        PYTHONPATH=src python -m repro.launch.serve --continuous --tp 2
"""
from __future__ import annotations

import argparse

import jax
import jax.numpy as jnp
import numpy as np

from ..configs import ARCH_IDS, get_config
from ..models import build_model, param_count
from ..serve import (
    ContinuousConfig,
    ContinuousEngine,
    DecodeEngine,
    Request,
    ServeConfig,
)
from ..train import checkpoint
from .compile_cache import enable_compile_cache


def _run_static(args, cfg, model, params):
    engine = DecodeEngine(
        model, params,
        ServeConfig(max_len=args.prompt_len + args.tokens + 1,
                    temperature=args.temperature),
    )
    prompts = jax.random.randint(
        jax.random.PRNGKey(1), (args.batch, args.prompt_len), 0, cfg.vocab_size
    )
    _, stats = engine.generate(prompts, args.tokens)
    print(f"prefill {stats['prefill_s']*1e3:.1f} ms "
          f"({stats['prefill_tps']:.1f} tok/s) | "
          f"decode {stats['decode_s']*1e3:.1f} ms "
          f"({stats['decode_tps']:.1f} tok/s) | "
          f"end-to-end {stats['tokens_per_s']:.1f} tok/s")


def run_continuous(args, cfg, model, params):
    """Serve ``args.requests`` synthetic requests through the continuous
    engine; prints the engine's stats and returns ``(results, stats)`` as
    ``ContinuousEngine.run`` does."""
    layout = None
    if args.tp > 1:
        from ..launch.mesh import make_spmd_layout

        if jax.device_count() < args.tp:
            raise SystemExit(
                f"--tp {args.tp} needs {args.tp} devices but jax sees "
                f"{jax.device_count()}; set XLA_FLAGS="
                f"--xla_force_host_platform_device_count=8 before launching"
            )
        layout = make_spmd_layout(1, args.tp)
    ccfg = ContinuousConfig(
        num_slots=args.num_slots, chunk=args.chunk, page_size=args.page_size,
        num_pages=args.num_pages,
        max_len=args.prompt_len + args.tokens + 1,
        temperature=args.temperature,
    )
    engine = ContinuousEngine(model, params, ccfg, layout=layout)
    engine.warmup()
    rng = np.random.default_rng(1)
    reqs = [
        Request(
            rid=i,
            prompt=rng.integers(0, cfg.vocab_size, args.prompt_len).astype(np.int32),
            max_new=args.tokens,
        )
        for i in range(args.requests)
    ]
    results, stats = engine.run(reqs)
    print(f"{stats['num_requests']} requests in {stats['steps']} steps | "
          f"{stats['tokens_per_s']:.1f} tok/s | "
          f"latency p50 {stats['latency_p50']*1e3:.1f} ms "
          f"p99 {stats['latency_p99']*1e3:.1f} ms | "
          f"ttft p50 {stats['ttft_p50']*1e3:.1f} ms")
    return results, stats


def build_parser() -> argparse.ArgumentParser:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen3-4b", choices=ARCH_IDS)
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=8)
    ap.add_argument("--tokens", type=int, default=32)
    ap.add_argument("--temperature", type=float, default=0.8)
    ap.add_argument("--full", action="store_true")
    ap.add_argument("--ckpt", default="", help="restore params from checkpoint")
    ap.add_argument("--continuous", action="store_true",
                    help="continuous batching (paged cache, dense family)")
    ap.add_argument("--requests", type=int, default=16,
                    help="synthetic request count (--continuous)")
    ap.add_argument("--num-slots", type=int, default=4)
    ap.add_argument("--chunk", type=int, default=16)
    ap.add_argument("--page-size", type=int, default=16)
    ap.add_argument("--num-pages", type=int, default=128)
    ap.add_argument("--tp", type=int, default=1,
                    help="tensor-parallel degree (--continuous only)")
    return ap


def load_params(args, cfg, model):
    """Checkpointed or seeded params; a --full config holds its weights in
    its compute dtype (bf16 for the published configs)."""
    if args.ckpt and checkpoint.exists(args.ckpt):
        params, _ = checkpoint.restore(args.ckpt)
    else:
        params = model.init(jax.random.PRNGKey(0))
    if args.full:
        params = jax.tree.map(lambda x: jnp.asarray(x, cfg.dtype), params)
    return params


def main(argv=None):
    args = build_parser().parse_args(argv)
    enable_compile_cache()
    cfg = get_config(args.arch, reduced=not args.full)
    model = build_model(cfg)
    if model.decode_step is None:
        raise SystemExit(f"{args.arch} is encoder-only: no decode path")
    params = load_params(args, cfg, model)
    print(f"{args.arch}: {param_count(params)/1e6:.1f}M params")

    if args.continuous:
        run_continuous(args, cfg, model, params)
    else:
        if args.tp > 1:
            raise SystemExit("--tp requires --continuous (the paged TP step)")
        _run_static(args, cfg, model, params)


if __name__ == "__main__":
    main()
