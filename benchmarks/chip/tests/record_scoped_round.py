"""Records ``data/scoped_round_tpu.xplane.pb``, the TPU trace that the
self-tests read the program's named scopes and kernels from: one warm round
of a tiny packed local-SGD + SlowMo trainer (a linear model, one worker,
two inner steps through the fused Nesterov kernel, then lines 7-8), through
``Trainer.run`` and the compiled round of the training path.

    python3 benchmarks/chip/tests/record_scoped_round.py <out.xplane.pb>

from the root of a checkout, on a TPU.
"""
from __future__ import annotations

import glob
import os
import shutil
import sys
import tempfile

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
sys.path[:0] = [BENCH, os.path.join(os.path.dirname(os.path.dirname(BENCH)), "src")]

import dataclasses  # noqa: E402

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

import scopes  # noqa: E402
from repro.core import slowmo  # noqa: E402
from repro.models.api import ModelBundle  # noqa: E402
from repro.train.trainer import TrainConfig, Trainer  # noqa: E402

D, ROWS, TAU = 256, 8, 2


def init(key):
    # a leaf off the (rows, 1024) tiling, so the kernel's tile conversion
    # (``layout`` inside ``inner_opt``) does work
    return {"w": jax.random.normal(key, (D, D)) * D**-0.5, "b": jnp.zeros((D + 3,))}


def loss_fn(params, batch):
    pred = batch["x"] @ params["w"] + params["b"][:D]
    return jnp.mean((pred - batch["y"]) ** 2)


def sampler(r, tau, rows, seq):
    x = jax.random.normal(jax.random.PRNGKey(r), (tau, 1, rows, D))
    return {"x": x, "y": jnp.tanh(x)}


def main(out: str) -> None:
    assert jax.devices()[0].platform == "tpu", "records a TPU trace"
    model = ModelBundle(None, init, loss_fn, None, None, None)
    cfg = dataclasses.replace(
        slowmo.preset("local_sgd+slowmo", num_workers=1, tau=TAU),
        packed=True, use_pallas=True, param_dtype=jnp.bfloat16)
    tc = TrainConfig(per_worker_batch=ROWS, seq_len=1, lr=0.05, log_every=0)
    trainer = Trainer(model, cfg, tc, sampler)
    state = trainer.run(state=trainer.init_state(), rounds=2)  # compiled, warm
    jax.block_until_ready(state)
    tdir = tempfile.mkdtemp()
    # no Python function events: they would outweigh the rest of the file
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    jax.profiler.start_trace(tdir, profiler_options=options)
    with jax.profiler.TraceAnnotation("bench:window"):
        state = trainer.run(state=state, rounds=1)
        jax.block_until_ready(state)
    jax.profiler.stop_trace()
    (path,) = glob.glob(os.path.join(tdir, "**", "*.xplane.pb"), recursive=True)
    with open(path, "rb") as f:
        buf = f.read()
    with open(out, "wb") as f:
        f.write(keep_planes(buf, ("/device:TPU:0", "/host:CPU")))
    shutil.rmtree(tdir, ignore_errors=True)
    print(f"{out}: {os.path.getsize(out)} bytes of {len(buf)}")


def keep_planes(buf: bytes, names) -> bytes:
    """The ``XSpace`` in ``buf`` with only the planes (field 1) named in
    ``names`` (a plane's name is its field 2)."""
    out = bytearray()
    for field, span in scopes._fields(buf, 0, len(buf)):
        if field != 1:
            continue
        name = dict(scopes._fields(buf, *span)).get(2, (0, 0))
        if scopes._str(buf, name) in names:
            out += _varint(1 << 3 | 2) + _varint(span[1] - span[0]) + buf[span[0]:span[1]]
    return bytes(out)


def _varint(n: int) -> bytes:
    out = bytearray()
    while True:
        out.append(n & 0x7F | (0x80 if n > 0x7F else 0))
        n >>= 7
        if not n:
            return bytes(out)


if __name__ == "__main__":
    main(sys.argv[1])
