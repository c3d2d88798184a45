"""Self-tests of ``scopes.py``, the reading of the program's named scopes
and kernels from a device trace, on the CPU.

    PYTHONPATH=src JAX_PLATFORMS=cpu python -m pytest -q benchmarks/chip/tests

Two recorded TPU v5e traces: ``small_tpu.xplane.pb`` (two anonymous kernels
and a matrix product, from before the program named anything) and
``scoped_round_tpu.xplane.pb`` (one round of a tiny scoped trainer, written
by ``record_scoped_round.py``).
"""
from __future__ import annotations

import os
import sys
import types

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
if BENCH not in sys.path:
    sys.path.insert(0, BENCH)

import harness  # noqa: E402
import scopes  # noqa: E402
import tracing  # noqa: E402

SMALL = os.path.join(HERE, "data", "small_tpu.xplane.pb")
SCOPED = os.path.join(HERE, "data", "scoped_round_tpu.xplane.pb")


def test_op_paths_of_a_recorded_trace():
    """The ``tf_op`` stat of each op, ``<path>:<type>``, read as its path,
    on exactly the intervals ``tracing.Trace`` keeps."""
    ops = scopes.xla_ops(SMALL, devices=1)[0]
    t = tracing.Trace(SMALL, devices=1)
    assert [(o.name, o.start, o.end) for o in ops] == [(e.name, e.start, e.end) for e in t.ops[0]]
    by_op = {}
    for o in ops:
        by_op.setdefault(tracing.opcode(o.name), set()).add(o.path)
    assert by_op["custom-call"] == {"jit(<lambda>)/pallas_call"}
    assert by_op["fusion"] == {"jit(<lambda>)/dot_general"}
    assert by_op["copy-start"] == by_op["copy-done"] == {""}


@pytest.mark.parametrize("path,scope", [
    ("jit(round_fn)/while/body/closed_call/fwd_bwd/vmap(transpose(jvp()))/dot_general",
     "fwd_bwd"),
    ("jit(round_fn)/while/body/closed_call/inner_opt/layout/reshape", "layout"),
    ("jit(round_fn)/while/body/closed_call/inner_opt/fused_nesterov/pallas_call",
     "inner_opt"),
    ("jit(round_fn)/boundary/lines7_8/slowmo_update/pallas_call", "lines7_8"),
    ("jit(round_fn)/boundary/layout/reshape", "layout"),
    ("jit(f)/vmap(layout)/slice", "layout"),
    ("jit(round_fn)/while/body/add", None),
    ("", None),
])
def test_innermost_scope(path, scope):
    assert scopes.scope_of(path) == scope


def _op(path, start, end, name="%x = f32[] add()"):
    return scopes.Op(name, start, end, path)


def test_scoped_seconds():
    ops = [
        _op("jit(r)/fwd_bwd/vmap(jvp())/dot_general", 0.0, 2.0),
        _op("jit(r)/fwd_bwd/vmap(transpose(jvp()))/dot_general", 2.0, 5.0),
        _op("jit(r)/inner_opt/layout/reshape", 5.0, 6.0),
        _op("jit(r)/inner_opt/fused_nesterov/pallas_call", 6.0, 6.5),
        _op("jit(r)/boundary/line6/mul", 7.0, 7.25),
        _op("jit(r)/boundary/lines7_8/slowmo_update/pallas_call", 7.25, 7.5),
        _op("jit(r)/add", 8.0, 9.0),
    ]
    s = lambda *a, **k: scopes.scoped_s(ops, 1.0, 8.5, *a, **k)  # noqa: E731
    assert s("fwd_bwd", transposed=False) == pytest.approx(1.0)  # clipped at 1
    assert s("fwd_bwd", transposed=True) == pytest.approx(3.0)
    assert s("inner_opt") == pytest.approx(0.5)
    assert s("layout") == pytest.approx(1.0)
    assert s(("boundary", "line6", "lines7_8")) == pytest.approx(0.5)
    assert s("grad_sync") == 0.0
    unscoped = [o for o in ops if not scopes.scope_of(o.path)]
    assert scopes.scoped_s(unscoped, 0.0, 10.0, "fwd_bwd") is None


def _reading(ops, rounds=2, lo=0.0, hi=10.0):
    t = tracing.Trace.__new__(tracing.Trace)
    t.lo, t.hi, t.host, t.modules, t.ops = lo, hi, [], {}, {0: ops}
    return tracing.Reading(cell=None, trace=t, counts={"rounds_traced": rounds},
                           peaks=None, chips=1)


@pytest.mark.parametrize("metric,kernel", [
    ("fused_nesterov_ms.train", "fused_nesterov"), ("slowmo_update_ms.train", "slowmo_update"),
])
def test_kernel_time_metrics(metric, kernel):
    read = harness.load_module(os.path.join(BENCH, "metrics", metric + ".py")).read
    E = tracing.Event
    ops = [
        E(f"%{kernel}.3 = (f32[64,1024]) custom-call(f32[1,1] %c)", 1.0, 1.5),
        E(f"%{kernel} = (f32[64,1024]) custom-call(f32[1,1] %c)", 2.0, 2.125),
        E(f"%{kernel}.3.clone = (f32[64,1024]) custom-call(f32[1,1] %c)", 2.125, 2.25),
        E(f"%{kernel}_fusion = f32[8] fusion(f32[8] %y)", 3.0, 4.0),
        E("%fusion.2 = f32[8] fusion(f32[8] %y)", 4.0, 5.0),
    ]
    assert read(_reading(ops)) == pytest.approx(1e3 * 0.75 / 2)
    assert read(_reading(ops[3:])) is None  # a program whose kernels are unnamed
    assert read(types.SimpleNamespace(trace=None, counts={})) is None
    # the anonymous kernels of a trace recorded before the names
    assert read(tracing.Reading(None, tracing.Trace(SMALL, 1), {"rounds_traced": 2},
                                None, 1)) is None


@pytest.fixture(scope="module")
def scoped_round():
    return tracing.Trace(SCOPED, devices=1), scopes.xla_ops(SCOPED, devices=1)[0]


def test_recorded_scoped_round(scoped_round):
    """One round of the tiny scoped trainer on a TPU v5e: each scope holds
    device time; the tile conversions inside the inner optimizer count as
    layout; the named kernels are found under their scopes; the scopes and
    the unscoped rest together make up the busy time."""
    t, ops = scoped_round
    lo, hi = t.lo, t.hi
    inside = [o for o in ops if o.end > lo and o.start < hi]
    nested = [o for o in inside if "/inner_opt/" in o.path and "/layout/" in o.path]
    assert nested and all(scopes.scope_of(o.path) == "layout" for o in nested)
    for token in ("fwd_bwd", "inner_opt", "layout", "boundary", "line6", "lines7_8"):
        assert scopes.scoped_s(ops, lo, hi, token) > 0, token
    assert scopes.scoped_s(ops, lo, hi, "fwd_bwd", transposed=True) > 0
    assert scopes.scoped_s(ops, lo, hi, "fwd_bwd", transposed=False) > 0
    run = tracing.Reading(None, t, {"rounds_traced": 1}, None, 1)
    for kernel in ("fused_nesterov", "slowmo_update"):
        assert scopes.kernel_ms(run, kernel) > 0, kernel
    assert {scopes.scope_of(o.path) for o in inside if o.name.startswith("%fused_nesterov")} \
        == {"inner_opt"}
    assert {scopes.scope_of(o.path) for o in inside if o.name.startswith("%slowmo_update")} \
        == {"lines7_8"}
    # the trainer's spans lie on the main thread's line, named after the
    # command that ran the recorder: ``python3``
    from jax.profiler import ProfileData

    (cpu,) = [p for p in ProfileData.from_file(SCOPED).planes if p.name == "/host:CPU"]
    lines = {line.name: {e.name for e in line.events} for line in cpu.lines}
    assert {"train_round", "train:sample", "train:dispatch", "train:sync"} <= lines["python3"]
    parts = [scopes.scoped_s(ops, lo, hi, s) for s in scopes.SCOPES]
    rest = tracing.union_length(tracing.clip(
        [o for o in inside if scopes.scope_of(o.path) is None], lo, hi))
    assert sum(parts) + rest == pytest.approx(t.busy_s(), rel=1e-9)
