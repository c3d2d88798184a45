"""The readings that a training cell's limits are set from, on the chip, at
the cell's own size: the program's compared numbers over many seeds, and
the control's and a planted fault's over a few.

    python3 benchmarks/chip/control.py --workload <cell> --mode <mode> \\
        --seconds <s> --seeds <n> [<n> ...]

Modes:

* ``program``: a run of the cell per seed (set-up, a window of
  ``--seconds``, the comparison), in one process; prints each compared
  number.  Its largest over a dozen seeds or more is a limit's lower
  reading.
* ``faults``: per seed, the float32 reference once, then in the program's
  place (a) the control, the reference with float8 operands in every
  matrix product, and (b) the fault that leaves out half of each worker's
  rows and takes the mean over the rest; prints each one's compared
  numbers against the float32 reference.  No program run.

The cells' own runs never run this.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time
import types


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--mode", choices=("program", "faults"), required=True)
    ap.add_argument("--seconds", type=float, default=1.0)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    args = ap.parse_args(argv)
    here = os.path.dirname(os.path.abspath(__file__))
    sys.path.insert(0, here)
    import run

    run._environment()
    import jax

    import harness

    cell = harness.load_cell(args.workload, os.path.join(run.CHECKOUT, "BENCHMARK.json"))
    devices = jax.devices()
    if devices[0].platform != "tpu" or len(devices) < cell.chips:
        raise SystemExit(f"control.py: {cell.name} needs {cell.chips} TPU chips")
    used = devices[: cell.chips]
    for seed in args.seeds:
        ns = types.SimpleNamespace(seed=seed, seconds=args.seconds, trace=0)
        t0 = time.perf_counter()
        if args.mode == "faults":
            rows = faults(cell, ns)
        else:
            out = cell.driver.run(cell, ns, watch=harness.CompileWatch(),
                                  clock=harness.Clock(), devices=used)
            rows = {"program": {c.name: c.value for c in out.checks}}
        for variant, row in rows.items():
            print(json.dumps({"workload": cell.name, "variant": variant, "seed": seed,
                              "seconds": round(time.perf_counter() - t0, 1), **row}),
                  flush=True)


def faults(cell, args) -> dict:
    """The compared numbers of the reference with the control's precision,
    and with half of each worker's rows left out, in the program's place,
    against the float32 reference: ``{variant: {number: value}}``."""
    import jax

    import harness
    from drivers import train

    config, traffic = cell.config, cell.traffic
    workers, rows = traffic["workers"], traffic["rows_per_device"]
    tau = config["slowmo"]["tau"]
    key = jax.random.PRNGKey(harness.derive_seed(args.seed, "init"))
    tokens = train.draw_rounds(
        train.make_sampler(args.seed, config["vocab_size"], workers, traffic["markov"]),
        traffic["check_rounds"], tau, rows, traffic["seq"])
    kw = dict(rounds=traffic["check_rounds"], workers=workers, rows=rows, seq=traffic["seq"])
    ref = cell.config_mod.reference_train(config, traffic, key, tokens, **kw)
    keep = train.compared_leaves(ref.first_grad)
    out = {}
    for variant, flags in (("control", {"lowp": True}), ("half_batch", {"half_batch": True})):
        var = cell.config_mod.reference_train(config, traffic, key, tokens, **flags, **kw)
        checks = train.compare(var.losses, var.first_grad, var.change, ref, keep,
                               traffic["limits"])
        out[variant] = {c.name: c.value for c in checks}
    return out


if __name__ == "__main__":
    main()
