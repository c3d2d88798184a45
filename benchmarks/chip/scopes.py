"""The program's own names in a device trace: the named scopes on each XLA
op's path, and the named Pallas kernels.

Each XLA op's event metadata in an ``.xplane.pb`` holds its op path, the
``tf_op`` stat ``<path>:<op type>``, where the path lists the
``jax.named_scope`` names the op was traced under, such as
``jit(round_fn)/while/body/closed_call/inner_opt/layout/reshape``.
``jax.profiler.ProfileData`` does not expose event-metadata stats, so
``xla_ops`` reads them from the file with a protobuf wire reader of its own.
A Pallas kernel given ``name=`` is an HLO custom call of that name
(``%fused_nesterov.3 = ... custom-call(...)``), so ``kernel_ms`` finds it in
the event names that ``tracing.Trace`` already keeps.
"""
from __future__ import annotations

import dataclasses
import functools
import re
from typing import Optional

from tracing import CONTAINERS, _DEVICE, clip, opcode, union_length

# the round's named scopes; an op belongs to the innermost of them on its path
SCOPES = ("fwd_bwd", "grad_sync", "inner_opt", "gossip", "layout", "boundary",
          "line6", "lines7_8")


@dataclasses.dataclass(frozen=True)
class Op:
    name: str  # the HLO instruction's text, as ``tracing.Trace.ops`` has it
    start: float  # seconds on the trace's clock
    end: float
    path: str  # the op path, "" where the op has none

    @property
    def dur(self) -> float:
        return self.end - self.start


@functools.lru_cache(maxsize=None)
def scope_of(path: str) -> Optional[str]:
    """The innermost of ``SCOPES`` on an op path, or None.  A component names
    a scope bare (``layout``) or inside the transformations applied under it
    (``vmap(layout)``)."""
    for part in reversed(path.split("/")):
        name = re.sub(r"^(?:\w+\()+|\)+$", "", part)
        if name in SCOPES:
            return name
    return None


def scoped_s(ops, lo: float, hi: float, tokens, transposed=None) -> Optional[float]:
    """Seconds of ``[lo, hi]`` in which one of ``ops`` ran whose innermost
    scope is one of ``tokens``; with ``transposed`` True or False, only the
    ops whose path has, or has not, a ``transpose(`` (the backward pass of a
    ``jax.grad``).  None where no op of the window has a scope: a program
    without them."""
    tokens = (tokens,) if isinstance(tokens, str) else tuple(tokens)
    ops = [o for o in ops if o.end > lo and o.start < hi]
    if not any(scope_of(o.path) for o in ops):
        return None
    keep = [
        o for o in ops
        if scope_of(o.path) in tokens
        and (transposed is None or ("transpose(" in o.path) == transposed)
    ]
    return union_length(clip(keep, lo, hi))


def kernel_ms(run, kernel: str) -> Optional[float]:
    """Device milliseconds per traced round of the Pallas kernel named
    ``kernel`` on device 0 (the ``Reading`` a metric is given); None
    untraced, or where no event bears the name."""
    t, rounds = run.trace, run.counts.get("rounds_traced")
    if t is None or not rounds:
        return None
    # ``%fused_nesterov``, ``%fused_nesterov.3``, ``%fused_nesterov.3.clone``
    named = re.compile(rf"%{re.escape(kernel)}(\.[\w.-]*)? = ")
    evs = [e for e in t.ops.get(0, []) if named.match(e.name)]
    if not evs:
        return None
    return 1e3 * union_length(clip(evs, t.lo, t.hi)) / rounds


def xla_ops(path: str, devices: int) -> dict:
    """The ``XLA Ops`` events of ``/device:TPU:<n>`` (n < ``devices``) with
    their op paths, {n: [Op]}, control-flow containers left out, on the
    clock and in the units of ``tracing.Trace.ops``.  From the ``XSpace``
    protobuf: planes (field 1) hold a name (2), lines (3), event metadata
    (4) and stat metadata (5), both maps of id (1) to a message; a line
    holds a name (2), a start ``timestamp_ns`` (3) and events (4), each a
    metadata id (1), ``offset_ps`` (2) and ``duration_ps`` (3); an event's
    metadata holds an id (1), a name (2) and stats (5), each a stat metadata
    id (1) and a string (5) or a reference to a stat metadata's name (7)."""
    with open(path, "rb") as f:
        buf = f.read()
    out = {}
    for field, plane in _fields(buf, 0, len(buf)):
        if field != 1:
            continue
        parts: dict = {}
        for f2, value in _fields(buf, *plane):
            parts.setdefault(f2, []).append(value)
        m = _DEVICE.match(_str(buf, parts.get(2, [(0, 0)])[0]))
        if not m or int(m.group(1)) >= devices:
            continue
        stat_names = {}
        for entry in parts.get(5, []):
            sm = dict(_fields(buf, *_map_value(buf, entry)))
            stat_names[sm.get(1, 0)] = _str(buf, sm.get(2, (0, 0)))
        tf_op = next((k for k, v in stat_names.items() if v == "tf_op"), None)
        meta = {}
        for entry in parts.get(4, []):
            mid, name, op = 0, "", ""
            for f3, value in _fields(buf, *_map_value(buf, entry)):
                if f3 == 1:
                    mid = value
                elif f3 == 2:
                    name = _str(buf, value)
                elif f3 == 5:
                    stat = dict(_fields(buf, *value))
                    if tf_op is not None and stat.get(1) == tf_op:
                        op = (_str(buf, stat[5]) if 5 in stat
                              else stat_names.get(stat.get(7), ""))
            meta[mid] = (name, op.rpartition(":")[0] if ":" in op else op)
        ops = []
        for line in parts.get(3, []):
            fields = dict(_fields(buf, *line))
            if _str(buf, fields.get(2, (0, 0))) != "XLA Ops":
                continue
            ts = fields.get(3, 0)
            for f3, value in _fields(buf, *line):
                if f3 != 4:
                    continue
                ev = dict(_fields(buf, *value))
                name, op = meta.get(ev.get(1, 0), ("", ""))
                if opcode(name) in CONTAINERS:
                    continue
                # whole nanoseconds, as ``ProfileData`` gives them
                start_ns = ts + ev.get(2, 0) // 1000
                end_ns = start_ns + ev.get(3, 0) // 1000
                ops.append(Op(name, start_ns * 1e-9, end_ns * 1e-9, op))
        out[int(m.group(1))] = ops
    return out


def _varint(buf: bytes, i: int):
    value = shift = 0
    while True:
        c = buf[i]
        i += 1
        value |= (c & 0x7F) << shift
        if c < 0x80:
            return value, i
        shift += 7


def _fields(buf: bytes, i: int, end: int):
    """(field number, value) of the protobuf message in ``buf[i:end]``; a
    length-delimited value is given as its (start, end) in ``buf``."""
    while i < end:
        key, i = _varint(buf, i)
        wire = key & 7
        if wire == 0:
            value, i = _varint(buf, i)
        elif wire == 2:
            n, i = _varint(buf, i)
            value, i = (i, i + n), i + n
        elif wire in (1, 5):  # fixed 64 and 32 bits: not read here
            value, i = None, i + (8 if wire == 1 else 4)
        else:
            raise ValueError(f"protobuf wire type {wire} at byte {i}")
        yield key >> 3, value


def _map_value(buf: bytes, entry) -> tuple:
    """The value (field 2) of a protobuf map entry."""
    return dict(_fields(buf, *entry)).get(2, (0, 0))


def _str(buf: bytes, span) -> str:
    return buf[span[0]:span[1]].decode("utf-8", "replace")
