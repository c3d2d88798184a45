"""The program's own names in a profile: the named scopes of the compiled
round, the named Pallas kernels, and the trainer's host spans.

* Scopes: the compiled round's op paths (HLO ``op_name`` metadata, which a
  TPU trace carries as each op's ``tf_op``) hold one token per layer —
  ``fwd_bwd``, ``grad_sync``, ``inner_opt``, ``gossip``, ``layout``,
  ``boundary`` with ``line6`` and ``lines7_8`` — and every matrix product
  lies under ``fwd_bwd``, the backward ones under ``transpose(``.
* Kernels: in interpret mode the kernel's ``name=`` is a scope of its ops.
  The compiled TPU custom calls are checked in ``test_tpu_compile.py``,
  the one file that loads the TPU compiler.
* Host spans: ``Trainer`` wraps each round in the step span
  ``train_round`` (``step_num`` = the round) over ``train:sample``,
  ``train:dispatch`` and ``train:sync``, plus ``train:reconfigure`` where
  the elastic loop rebuilds the round.
"""
import dataclasses
import glob
import os
import re

import jax
import jax.numpy as jnp
import pytest

from repro.configs import get_config
from repro.core import slowmo
from repro.elastic import ElasticConfig, FaultPlan
from repro.models import build_model
from repro.models.api import ModelBundle
from repro.train.trainer import TrainConfig, Trainer

# an HLO instruction line: its opcode and its op path
_INSTR = re.compile(r"= \S+ ([a-z][a-z0-9-]*)\(.*op_name=\"([^\"]*)\"")


def _compiled_ops(preset, workers, use_pallas=True):
    """(opcode, op path) of every instruction of the compiled packed round
    of the reduced olmo-1b (2 layers, d_model 256), on the CPU."""
    model = build_model(get_config("olmo-1b", reduced=True))
    cfg = dataclasses.replace(
        slowmo.preset(preset, num_workers=workers, tau=2),
        packed=True, use_pallas=use_pallas,
    )
    pshape = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    pack = slowmo.make_state_pack_spec(cfg, pshape)
    state = jax.eval_shape(lambda p: slowmo.init_slowmo(cfg, p, pack=pack), pshape)
    batches = {"tokens": jax.ShapeDtypeStruct((2, workers, 2, 16), jnp.int32)}
    fn = jax.jit(slowmo.make_slowmo_round(cfg, model.loss_fn, pack=pack))
    text = fn.lower(state, batches, jax.ShapeDtypeStruct((), jnp.float32)).compile().as_text()
    return [m.groups() for m in map(_INSTR.search, text.splitlines()) if m]


@pytest.fixture(scope="module")
def slowmo_round_ops():
    return _compiled_ops("local_sgd+slowmo", workers=2)


def _has(path, token):
    return token in path.split("/")


def test_round_scopes(slowmo_round_ops):
    paths = [p for _, p in slowmo_round_ops]
    for token in ("fwd_bwd", "inner_opt", "layout", "boundary", "line6", "lines7_8"):
        assert any(_has(p, token) for p in paths), token
    # the kernel step takes each leaf as it is: no tile conversion, so no
    # layout op inside the inner optimizer
    assert not any(_has(p, "inner_opt") and _has(p, "layout") for p in paths)


@pytest.mark.parametrize(
    "preset,token", [("ar_sgd", "grad_sync"), ("sgp+slowmo", "gossip")]
)
def test_per_step_collective_scopes(preset, token):
    """The local base syncs nothing per step; the bases that do put it under
    its own scope."""
    paths = [p for _, p in _compiled_ops(preset, workers=2, use_pallas=False)]
    assert any(_has(p, token) for p in paths), token


def test_matrix_products_lie_in_fwd_bwd(slowmo_round_ops):
    dots = [p for op, p in slowmo_round_ops if op == "dot"]
    assert dots and all(_has(p, "fwd_bwd") for p in dots), dots
    backward = [p for p in dots if "transpose(" in p]
    forward = [p for p in dots if "transpose(" not in p]
    # each forward product has two in the backward pass (input and weight
    # gradients), bar the ones whose input needs no gradient
    assert forward and len(backward) >= len(forward)


def test_kernel_names_are_scopes_in_interpret_mode(slowmo_round_ops):
    paths = [p for _, p in slowmo_round_ops]
    assert any(re.search(r"/inner_opt/fused_nesterov(/|$)", p) for p in paths)
    assert any(re.search(r"/boundary/lines7_8/slowmo_update(/|$)", p) for p in paths)


# -- host spans ---------------------------------------------------------------

D = 8


def _linear_trainer(workers=2, **kw):
    def init(key):
        return {"w": jax.random.normal(key, (D,)), "b": jnp.zeros(())}

    def loss_fn(params, batch):
        return jnp.mean((batch["x"] @ params["w"] + params["b"] - batch["y"]) ** 2)

    def sampler(r, tau, rows, seq):
        x = jax.random.normal(jax.random.PRNGKey(r), (tau, workers, rows, D))
        return {"x": x, "y": x.sum(-1) * 0.1}

    model = ModelBundle(None, init, loss_fn, None, None, None)
    cfg = slowmo.preset("local_sgd+slowmo", num_workers=workers, tau=2)
    tc = TrainConfig(per_worker_batch=2, seq_len=1, lr=0.05, log_every=0)
    return Trainer(model, cfg, tc, sampler, **kw)


def _traced_host_spans(tmp_path, run):
    """Run ``run()`` under the profiler; the trainer's spans read back."""
    from jax.profiler import ProfileData

    jax.profiler.start_trace(str(tmp_path))
    try:
        run()
    finally:
        jax.profiler.stop_trace()
    (path,) = glob.glob(os.path.join(str(tmp_path), "**", "*.xplane.pb"), recursive=True)
    spans = []
    for plane in ProfileData.from_file(path).planes:
        if plane.name != "/host:CPU":
            continue
        for line in plane.lines:
            for e in line.events:
                if e.name == "train_round" or e.name.startswith("train:"):
                    spans.append((e.name, e.start_ns, e.start_ns + e.duration_ns,
                                  dict(e.stats).get("step_num")))
    return spans


def _children(spans, rnd):
    (_, s, e, _), = [x for x in spans if x[0] == "train_round" and x[3] == rnd]
    return {name for name, cs, ce, _ in spans if name != "train_round" and s <= cs and ce <= e}


def test_round_spans(tmp_path):
    trainer = _linear_trainer()
    state = trainer.init_state()
    spans = _traced_host_spans(tmp_path, lambda: trainer.run(state=state, rounds=2))
    assert sorted(x[3] for x in spans if x[0] == "train_round") == [0, 1]
    for rnd in (0, 1):
        assert {"train:sample", "train:dispatch", "train:sync"} <= _children(spans, rnd)
    assert not any(x[0] == "train:reconfigure" for x in spans)
    assert all("wall_s" not in rec for rec in trainer.history)


def test_elastic_rebuild_is_a_span(tmp_path):
    """Worker 1 dies at round 1 and is evicted after a round of silence:
    the round that rebuilds the survivor program shows it as a span."""
    trainer = _linear_trainer(
        elastic=ElasticConfig(timeout_rounds=1), faults=FaultPlan.parse(["kill:1@1"])
    )
    state = trainer.init_state()
    spans = _traced_host_spans(tmp_path, lambda: trainer.run(state=state, rounds=3))
    rebuilt = [r for r in range(3) if "train:reconfigure" in _children(spans, r)]
    assert len(rebuilt) == 1
    assert trainer.history[rebuilt[0]]["workers"] == 1
    assert {"train:sample", "train:dispatch", "train:sync"} <= _children(spans, rebuilt[0])
