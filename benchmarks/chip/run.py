"""Run one cell of the on-chip benchmark once.

    python3 benchmarks/chip/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

from the root of a checkout.  The cell is found by name in the checkout's
``BENCHMARK.json``; its configuration, traffic mix and per-layer metrics are
files under this directory, found by the names there (see ``harness.py``).

With ``--trace 0`` the run reports the cell's end-to-end metrics; with
``--trace 1`` it traces a short window with JAX's profiler and reports the
per-layer metrics read from that trace.  Either way it then checks what the
timed path produced against the configuration's plain reference.  The last
line of standard output is one JSON object: ``correct``, ``attempted``,
``failed``, ``metrics``, ``device`` (and ``breakdown`` when traced), then
``checks``, each number compared beside its limit.  The same checks are the
last lines of standard error.

Exits non-zero and prints no result where JAX finds no TPU, or fewer chips
than the cell asks for.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
CHECKOUT = os.path.dirname(os.path.dirname(HERE))


def _environment() -> None:
    """Fixed places inside the checkout for what JAX and the TPU runtime
    keep, set before JAX is imported."""
    # the persistent compile cache is keyed by its path: one fixed directory
    # per checkout, so the second run of a cell compiles nothing
    os.environ["JAX_COMPILATION_CACHE_DIR"] = os.path.join(CHECKOUT, ".jax_cache")
    os.environ["JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS"] = "0"
    os.environ["JAX_PERSISTENT_CACHE_MIN_ENTRY_SIZE_BYTES"] = "0"
    # no size limit, so no eviction: an evicting cache needs an access-time
    # file beside every entry, and entries written without one stop it
    os.environ["JAX_COMPILATION_CACHE_MAX_SIZE"] = "-1"
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    for path in (os.path.join(CHECKOUT, "src"), HERE):
        if path not in sys.path:
            sys.path.insert(0, path)


def parse_args(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def main(argv=None, *, bench_path=None, root=None, require_chip=True, clock=None):
    """One run; returns the result object it printed.  ``require_chip=False``
    (tests only) skips the look for a TPU and the peaks table."""
    args = parse_args(argv)
    _environment()
    import harness
    import tracing

    bench_path = bench_path or os.path.join(CHECKOUT, "BENCHMARK.json")
    cell = harness.load_cell(args.workload, bench_path, root or HERE)

    import jax

    devices = jax.devices()
    if require_chip:
        if devices[0].platform != "tpu":
            raise harness.SetupError(
                f"no TPU: JAX's first device is {devices[0].platform}"
            )
        if len(devices) < cell.chips:
            raise harness.SetupError(
                f"{cell.name} needs {cell.chips} chips, JAX finds {len(devices)}"
            )
        peaks = harness.load_peaks(devices[0].device_kind, cell.root)
    else:
        peaks = None
    used = devices[: cell.chips]
    watch = harness.CompileWatch()
    clock = clock or harness.Clock(T_START)
    outcome = cell.driver.run(cell, args, watch=watch, clock=clock, devices=used)

    metrics = {}
    device = {
        "platform": used[0].platform,
        "kind": used[0].device_kind,
        "count": len(used),
        "memory_peak_bytes": outcome.memory_peak_bytes,
    }
    result = {"correct": all(c.ok for c in outcome.checks) and bool(outcome.checks)}
    result["attempted"] = outcome.attempted
    result["failed"] = outcome.failed
    if args.trace:
        run = tracing.Reading(cell=cell, trace=outcome.trace, counts=outcome.counts,
                              peaks=peaks, chips=cell.chips)
        for m in cell.per_layer():
            reader = harness.load_module(
                os.path.join(cell.root, "metrics", m["name"] + ".py")
            )
            value = reader.read(run)
            if value is not None:
                metrics[m["name"]] = {"value": float(value), "unit": m["unit"]}
        if outcome.trace is not None:
            device["busy_s"] = outcome.trace.busy_s()
            device["window_s"] = outcome.trace.window_s()
    else:
        for m in cell.end_to_end():
            if m["name"] in outcome.end_to_end:
                metrics[m["name"]] = {
                    "value": float(outcome.end_to_end[m["name"]]),
                    "unit": m["unit"],
                }
    result["metrics"] = metrics
    result["device"] = device
    if args.trace and outcome.trace is not None:
        result["breakdown"] = outcome.trace.breakdown()
    for note in outcome.notes:
        harness.log(note)
    harness.log(watch.line())
    harness.log(f"host peak resident memory {harness.host_peak_rss_gb():.2f} GB")
    result["checks"] = {
        c.name: {"value": c.value, "limit": c.limit} for c in outcome.checks
    }
    sys.stdout.flush()
    for c in outcome.checks:
        print(f"check {c.name}: {c.value} (limit {c.limit}) "
              f"{'ok' if c.ok else 'FAILED'}", file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result))
    sys.stdout.flush()
    return result


if __name__ == "__main__":
    _environment()
    import harness  # noqa: E402

    try:
        main()
    except harness.SetupError as e:
        print(f"run.py: {e}", file=sys.stderr)
        sys.exit(2)
