import os

os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

"""Multi-pod dry-run: lower + compile every (architecture x input-shape) pair
on the production meshes and record memory / cost / collective analysis.

Usage:
    PYTHONPATH=src python -m repro.launch.dryrun --arch qwen3-8b --shape train_4k --mesh single
    PYTHONPATH=src python -m repro.launch.dryrun --all --mesh single
    PYTHONPATH=src python -m repro.launch.dryrun --all --mesh multi --layout flat

Shapes lower different entry points (see DESIGN.md):
    train_4k     -> one SlowMo round (tau inner steps + outer update)
    prefill_32k  -> forward(..., last_only=True)
    decode_32k / long_500k -> decode_step with a seq_len cache

Principled skips (encoder-only decode; quadratic attention at 500k) are
recorded as status='skip' artifacts.
"""
import argparse  # noqa: E402
import json  # noqa: E402
import time  # noqa: E402
import traceback  # noqa: E402

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
from jax.sharding import NamedSharding, PartitionSpec as P  # noqa: E402

from ..configs import ARCH_IDS, INPUT_SHAPES, get_config  # noqa: E402
from ..configs import qwen3_4b as _q34  # noqa: E402
from ..core import slowmo  # noqa: E402
from ..core.base_opt import InnerOptConfig  # noqa: E402
from ..distributed import hlo_analysis, sharding  # noqa: E402
from ..models import api as model_api  # noqa: E402
from ..models import build_model  # noqa: E402
from .mesh import WorkerLayout, make_layout, make_production_mesh  # noqa: E402

DEFAULT_TAU = 2  # dry-run tau (unrolled for cost analysis; FLOPs scale linearly)


def skip_reason(arch: str, shape_name: str) -> str | None:
    cfg = get_config(arch)
    shape = INPUT_SHAPES[shape_name]
    if shape.kind == "decode" and not cfg.has_decode:
        return "encoder-only architecture: no decode step"
    if shape_name == "long_500k":
        if arch == "qwen3-4b":
            return None  # runs the sliding-window variant
        if not cfg.sub_quadratic:
            return "full quadratic attention at 524k context: principled skip"
    return None


def resolve_config(arch: str, shape_name: str, unroll: bool = True, overrides: dict | None = None):
    cfg = _q34.LONG_CONTEXT if (arch == "qwen3-4b" and shape_name == "long_500k") else get_config(arch)
    # unroll layer/tau loops so XLA cost analysis counts true work (it counts
    # while-loop bodies ONCE); inner seq-scans (chunked attention, recurrences)
    # stay rolled and are corrected analytically in the roofline report.
    # The multi-pod coherence pass runs rolled (fast compile, same sharding).
    cfg = cfg.replace(unroll_layers=unroll)
    if overrides:
        cfg = cfg.replace(**overrides)
    return cfg


# ---------------------------------------------------------------------------
# lowerings
# ---------------------------------------------------------------------------

def lower_train(cfg, shape, layout: WorkerLayout, *, base: str, tau: int,
                beta: float, shard_outer: bool, exact_average: bool = True,
                average_dtype=None):
    model = build_model(cfg)
    W = max(layout.num_workers, 1)
    assert shape.global_batch % W == 0, (shape.global_batch, W)
    per_worker = shape.global_batch // W
    smcfg = slowmo.SlowMoConfig(
        num_workers=W,
        tau=tau,
        alpha=1.0,
        beta=beta,
        base=base,
        inner=InnerOptConfig(kind="sgd", momentum=0.9, nesterov=True, weight_decay=1e-4),
        param_dtype=cfg.dtype,
        exact_average=exact_average,
        average_dtype=average_dtype,
        unroll_inner=True,
    )
    round_fn = slowmo.make_slowmo_round(smcfg, model.loss_fn)
    state_shapes = jax.eval_shape(
        lambda k: slowmo.init_slowmo(smcfg, model.init(k)), jax.random.PRNGKey(0)
    )
    state_sh = sharding.slowmo_state_shardings(layout, state_shapes, shard_outer=shard_outer)
    one = model_api.batch_spec(cfg, per_worker, shape.seq_len)
    batch_shapes = {
        k: jax.ShapeDtypeStruct((tau, W) + v.shape, v.dtype) for k, v in one.items()
    }
    batch_sh = sharding.batch_shardings(layout, batch_shapes)
    lr_shape = jax.ShapeDtypeStruct((), jnp.float32)
    lowered = jax.jit(
        round_fn,
        in_shardings=(state_sh, batch_sh, NamedSharding(layout.mesh, P())),
        out_shardings=(state_sh, None),
    ).lower(state_shapes, batch_shapes, lr_shape)
    meta = {
        "entry": "slowmo_round",
        "num_workers": W,
        "per_worker_batch": per_worker,
        "tau": tau,
        "base": base,
        "tokens_per_round": tau * shape.global_batch * shape.seq_len,
    }
    return lowered, meta


def lower_prefill(cfg, shape, layout: WorkerLayout):
    model = build_model(cfg)

    def prefill(params, batch):
        fam = __import__(
            f"repro.models.{cfg.family}", fromlist=["forward"]
        )
        return fam.forward(cfg, params, batch, last_only=True)

    param_shapes = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    param_sh = sharding.serve_param_shardings(layout, param_shapes)
    one = model_api.batch_spec(cfg, shape.global_batch, shape.seq_len)
    if cfg.modality == "audio":
        one = {"features": one["features"]}  # prefill = encode, no labels
    batch_sh = sharding.serve_token_shardings(layout, one, shape.global_batch)
    lowered = jax.jit(prefill, in_shardings=(param_sh, batch_sh)).lower(param_shapes, one)
    return lowered, {
        "entry": "prefill_forward",
        "tokens": shape.global_batch * shape.seq_len,
    }


def lower_decode(cfg, shape, layout: WorkerLayout):
    model = build_model(cfg)
    B = shape.global_batch
    param_shapes = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    param_sh = sharding.serve_param_shardings(layout, param_shapes)
    cache_shapes = jax.eval_shape(lambda: model.init_cache(B, shape.seq_len))
    cache_sh = sharding.serve_cache_shardings(layout, cache_shapes, B)
    tok_shape = jax.ShapeDtypeStruct((B, 1), jnp.int32)
    tok_sh = sharding.serve_token_shardings(layout, tok_shape, B)
    lowered = jax.jit(
        model.decode_step,
        in_shardings=(param_sh, cache_sh, tok_sh),
        out_shardings=(None, cache_sh),
    ).lower(param_shapes, cache_shapes, tok_shape)
    return lowered, {"entry": "serve_step", "tokens": B, "cache_len": shape.seq_len}


# ---------------------------------------------------------------------------
# analysis + driver
# ---------------------------------------------------------------------------

def memory_summary(compiled):
    try:
        ma = compiled.memory_analysis()
    except Exception:
        return {}
    if ma is None:
        return {}
    out = {}
    for k in (
        "argument_size_in_bytes",
        "output_size_in_bytes",
        "temp_size_in_bytes",
        "alias_size_in_bytes",
        "generated_code_size_in_bytes",
    ):
        v = getattr(ma, k, None)
        if v is not None:
            out[k] = int(v)
    return out


def run_pair(arch: str, shape_name: str, mesh_kind: str, layout_style: str,
             base: str, tau: int, beta: float, shard_outer: bool,
             exact_average: bool, out_dir: str, *, unroll: bool = True,
             lower_only: bool = False, cfg_overrides: dict | None = None,
             average_dtype=None) -> dict:
    shape = INPUT_SHAPES[shape_name]
    rec = {
        "arch": arch,
        "shape": shape_name,
        "mesh": mesh_kind,
        "layout": layout_style,
        "status": "ok",
    }
    reason = skip_reason(arch, shape_name)
    if reason:
        rec.update(status="skip", reason=reason)
        return rec

    mesh = make_production_mesh(multi_pod=(mesh_kind == "multi"))
    layout = make_layout(mesh, layout_style if shape.kind == "train" else "flat")
    cfg = resolve_config(arch, shape_name, unroll, cfg_overrides)
    rec["unrolled"] = unroll
    rec["cfg_overrides"] = cfg_overrides or {}
    t0 = time.perf_counter()
    with mesh:
        if shape.kind == "train":
            lowered, meta = lower_train(
                cfg, shape, layout, base=base, tau=tau, beta=beta,
                shard_outer=shard_outer, exact_average=exact_average,
                average_dtype=average_dtype,
            )
        elif shape.kind == "prefill":
            lowered, meta = lower_prefill(cfg, shape, layout)
        else:
            lowered, meta = lower_decode(cfg, shape, layout)
        rec["lower_s"] = time.perf_counter() - t0
        if lower_only:
            rec["status"] = "lowered"
            rec.update(meta)
            return rec
        t1 = time.perf_counter()
        compiled = lowered.compile()
        rec["compile_s"] = time.perf_counter() - t1

    rec.update(meta)
    rec["memory"] = memory_summary(compiled)
    hlo = compiled.as_text()
    roof = hlo_analysis.roofline_from_compiled(compiled, hlo)
    rec["roofline"] = roof.as_dict()

    # MODEL_FLOPS yardstick
    param_shapes = jax.eval_shape(build_model(cfg).init, jax.random.PRNGKey(0))
    n_active = model_api.active_param_count(cfg, param_shapes)
    n_total = model_api.param_count(param_shapes)
    tokens = meta.get("tokens_per_round", meta.get("tokens", 0))
    mult = 6.0 if shape.kind == "train" else 2.0
    mf = mult * n_active * tokens
    n_dev = mesh.devices.size
    rec["params_total"] = int(n_total)
    rec["params_active"] = int(n_active)
    rec["model_flops_global"] = mf
    rec["hlo_flops_global"] = roof.flops * n_dev
    rec["useful_flops_ratio"] = mf / max(roof.flops * n_dev, 1.0)
    return rec


def main():
    p = argparse.ArgumentParser()
    p.add_argument("--arch", default=None)
    p.add_argument("--shape", default=None, choices=list(INPUT_SHAPES))
    p.add_argument("--mesh", default="single", choices=["single", "multi"])
    p.add_argument("--layout", default="flat", choices=["flat", "hierarchical"])
    p.add_argument("--base", default="sgp", choices=["local", "sgp", "osgp", "dpsgd", "ar"])
    p.add_argument("--tau", type=int, default=DEFAULT_TAU)
    p.add_argument("--beta", type=float, default=0.6)
    p.add_argument("--shard-outer", action="store_true", help="ZeRO-shard outer state (beyond-paper)")
    p.add_argument("--noaverage", action="store_true", help="SlowMo-noaverage variant (paper §6)")
    p.add_argument("--all", action="store_true")
    p.add_argument("--rolled", action="store_true", help="keep loops rolled (fast compile; coherence-only pass)")
    p.add_argument("--chunk-size", type=int, default=None, help="override xlstm chunk")
    p.add_argument("--attn-chunk", type=int, default=None)
    p.add_argument("--avg-dtype", default=None, choices=["bf16"], help="boundary all-reduce dtype")
    p.add_argument("--lower-only", action="store_true", help="lower without compiling (fast sharding validation)")
    p.add_argument("--out", default="artifacts/dryrun")
    p.add_argument("--tag", default="")
    args = p.parse_args()

    pairs = []
    archs = ARCH_IDS if (args.all or not args.arch) else [args.arch]
    shapes = list(INPUT_SHAPES) if (args.all or not args.shape) else [args.shape]
    for a in archs:
        for s in shapes:
            pairs.append((a, s))

    os.makedirs(args.out, exist_ok=True)
    results = []
    for arch, shape_name in pairs:
        tag = f"{args.mesh}_{args.layout}" + (f"_{args.tag}" if args.tag else "")
        fname = os.path.join(args.out, f"{tag}__{arch}__{shape_name}.json")
        print(f"=== {arch} x {shape_name} [{args.mesh}/{args.layout}] ===", flush=True)
        try:
            overrides = {}
            if args.chunk_size:
                overrides["chunk_size"] = args.chunk_size
            if args.attn_chunk:
                overrides["attn_chunk"] = args.attn_chunk
            rec = run_pair(
                arch, shape_name, args.mesh, args.layout, args.base, args.tau,
                args.beta, args.shard_outer, not args.noaverage, args.out,
                unroll=not args.rolled, lower_only=args.lower_only,
                cfg_overrides=overrides or None,
                average_dtype=jnp.bfloat16 if args.avg_dtype == "bf16" else None,
            )
        except Exception as e:  # noqa: BLE001
            rec = {
                "arch": arch, "shape": shape_name, "mesh": args.mesh,
                "layout": args.layout, "status": "error",
                "error": f"{type(e).__name__}: {e}",
                "traceback": traceback.format_exc()[-4000:],
            }
        with open(fname, "w") as f:
            json.dump(rec, f, indent=2, default=str)
        status = rec["status"]
        extra = ""
        if status == "lowered":
            extra = f" lower={rec.get('lower_s', 0):.1f}s"
        elif status == "ok":
            r = rec["roofline"]
            extra = (
                f" compute={r['compute_s']:.3e}s memory={r['memory_s']:.3e}s"
                f" coll={r['collective_s']:.3e}s dom={r['dominant']}"
                f" compile={rec.get('compile_s', 0):.1f}s"
            )
        elif status == "skip":
            extra = f" ({rec['reason']})"
        else:
            extra = f" ({rec['error']})"
        print(f"--- {status}{extra}", flush=True)
        results.append(rec)

    n_ok = sum(r["status"] in ("ok", "lowered") for r in results)
    n_skip = sum(r["status"] == "skip" for r in results)
    n_err = sum(r["status"] == "error" for r in results)
    print(f"\nDONE: {n_ok} ok / {n_skip} skip / {n_err} error")
    return 0 if n_err == 0 else 1


if __name__ == "__main__":
    raise SystemExit(main())
