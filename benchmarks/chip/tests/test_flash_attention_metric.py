"""Self-tests of the ``flash_attention_ms.train`` reader, on the CPU.

    PYTHONPATH=src JAX_PLATFORMS=cpu python -m pytest -q benchmarks/chip/tests

The reader sums the device time of the ``pallas_call`` kernels named
``flash_attention`` (forward, remat recompute and the backward kernels) per
traced round.  It reads None untraced, and None on a trace whose training
round has no such kernel, as the recorded ``scoped_round_tpu.xplane.pb``.
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
import tracing  # noqa: E402

SCOPED = os.path.join(HERE, "data", "scoped_round_tpu.xplane.pb")


def _read():
    return harness.load_module(
        os.path.join(BENCH, "metrics", "flash_attention_ms.train.py")).read


def _reading(ops, rounds=2, lo=0.0, hi=10.0):
    t = tracing.Trace.__new__(tracing.Trace)
    t.lo, t.hi, t.host, t.modules, t.ops = lo, hi, [], {}, {0: ops}
    return tracing.Reading(cell=None, trace=t, counts={"rounds_traced": rounds},
                           peaks=None, chips=1)


def test_flash_attention_kernel_time():
    """Each custom call named after the kernel counts, clones included; a
    fusion that only shares the prefix does not; per traced round, in ms."""
    read = _read()
    E = tracing.Event
    ops = [
        E("%flash_attention.3 = (bf16[2,16,4096,128]) custom-call(bf16[1] %q)", 1.0, 1.5),
        E("%flash_attention = (bf16[2,16,4096,192]) custom-call(bf16[1] %q)", 2.0, 2.125),
        E("%flash_attention.3.clone = (f32[2,16,4096]) custom-call(bf16[1] %q)",
          2.125, 2.25),
        E("%flash_attention_fusion = f32[8] fusion(f32[8] %y)", 3.0, 4.0),
        E("%fusion.2 = f32[8] fusion(f32[8] %y)", 4.0, 5.0),
    ]
    assert read(_reading(ops)) == pytest.approx(1e3 * 0.75 / 2)
    assert read(_reading(ops[3:])) is None


def test_no_training_flash_kernel_in_the_recorded_round():
    """The recorded round trains through XLA's attention: the flash
    kernel's metric reads None there, as it does untraced."""
    read = _read()
    t = tracing.Trace(SCOPED, devices=1)
    assert read(tracing.Reading(None, t, {"rounds_traced": 1}, None, 1)) is None
    assert read(types.SimpleNamespace(trace=None, counts={})) is None
