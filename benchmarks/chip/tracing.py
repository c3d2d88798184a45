"""Reduction of one profiler trace (``.xplane.pb``) to what the per-layer
metrics read, and the ``Reading`` that a metric's ``read(run)`` receives.

The trace is read with ``jax.profiler.ProfileData`` alone.  On a TPU the
planes ``/device:TPU:<n>`` carry a line ``XLA Modules`` (one event per
program execution, named ``jit_<function>(<fingerprint>)``) and a line
``XLA Ops`` (one event per HLO instruction executed, named by its HLO text,
``%name = <shape> <opcode>(...)``).  The plane ``/host:CPU`` carries the
Python threads; the benchmark's own ``jax.profiler.TraceAnnotation`` spans
(``bench:...``) are events there, on the same clock to about a millisecond.
"""
from __future__ import annotations

import dataclasses
import glob
import os
import re
from typing import Any, Optional

WINDOW_SPAN = "bench:window"
_DEVICE = re.compile(r"^/device:TPU:(\d+)$")
# HLO opcodes that move data between chips; the opcode is the word right
# before the operand list's "("
COLLECTIVE = re.compile(
    r"\b(all-reduce|all-reduce-start|all-reduce-done|all-gather|all-gather-start|"
    r"all-gather-done|reduce-scatter|collective-permute|collective-permute-start|"
    r"collective-permute-done|all-to-all)\("
)


@dataclasses.dataclass(frozen=True)
class Event:
    name: str
    start: float  # seconds on the trace's clock
    end: float

    @property
    def dur(self) -> float:
        return self.end - self.start


# control flow whose event spans the operations it runs: not counted as
# work of its own (it would cover the gaps between those operations)
CONTAINERS = ("while", "conditional", "call")


def union_length(intervals) -> float:
    """Total length covered by ``(start, end)`` intervals."""
    total, cur_s, cur_e = 0.0, None, None
    for s, e in sorted(intervals):
        if cur_e is None or s > cur_e:
            if cur_e is not None:
                total += cur_e - cur_s
            cur_s, cur_e = s, e
        else:
            cur_e = max(cur_e, e)
    if cur_e is not None:
        total += cur_e - cur_s
    return total


def merge(intervals) -> list:
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def clip(events, lo: float, hi: float) -> list:
    return [(max(e.start, lo), min(e.end, hi)) for e in events if e.end > lo and e.start < hi]


def opcode(name: str) -> str:
    """The opcode of an HLO instruction event (``%x = <shape> opcode(...)``)."""
    head = name.split(" = ", 1)
    m = re.search(r"\b([a-z][a-z0-9-]*)\(", head[1]) if len(head) == 2 else None
    return m.group(1) if m else ""


def op_label(name: str) -> str:
    """A short label of an HLO instruction event: its name and opcode."""
    head = name.split(" = ", 1)
    if len(head) < 2:
        return name[:80]
    return f"{head[0]} {opcode(name)}".strip()


class Trace:
    """The device and host events of one traced window."""

    def __init__(self, path: str, devices: int):
        from jax.profiler import ProfileData

        pd = ProfileData.from_file(path)
        self.modules: dict[int, list[Event]] = {}
        self.ops: dict[int, list[Event]] = {}
        self.host: list[Event] = []
        for plane in pd.planes:
            m = _DEVICE.match(plane.name)
            if m:
                d = int(m.group(1))
                if d >= devices:
                    continue
                for line in plane.lines:
                    evs = [_event(e) for e in line.events]
                    if line.name == "XLA Modules":
                        self.modules[d] = evs
                    elif line.name == "XLA Ops":
                        self.ops[d] = [e for e in evs if opcode(e.name) not in CONTAINERS]
            elif plane.name == "/host:CPU":
                # the Python thread's spans name what the host did
                for line in plane.lines:
                    if line.name == "python":
                        self.host += [_event(e) for e in line.events]
        spans = [e for e in self.host if e.name == WINDOW_SPAN]
        if spans:
            self.lo, self.hi = spans[0].start, spans[0].end
        else:  # no span: the extent of the device's work
            evs = [e for d in self.ops for e in self.ops[d]]
            self.lo = min((e.start for e in evs), default=0.0)
            self.hi = max((e.end for e in evs), default=0.0)

    @classmethod
    def from_dir(cls, directory: str, devices: int) -> "Trace":
        paths = glob.glob(os.path.join(directory, "**", "*.xplane.pb"), recursive=True)
        if not paths:
            raise FileNotFoundError(f"no .xplane.pb under {directory}")
        return cls(max(paths, key=os.path.getmtime), devices)

    def window_s(self) -> float:
        return self.hi - self.lo

    def busy_s(self) -> float:
        """Seconds in the window in which an operation ran, averaged over
        the devices traced."""
        if not self.ops:
            return 0.0
        return sum(
            union_length(clip(evs, self.lo, self.hi)) for evs in self.ops.values()
        ) / len(self.ops)

    def idle_share(self, device: int = 0) -> Optional[float]:
        evs = self.ops.get(device)
        if not evs or self.window_s() <= 0:
            return None
        return 1.0 - union_length(clip(evs, self.lo, self.hi)) / self.window_s()

    def exposed_collective_s(self, device: int = 0) -> Optional[float]:
        """Seconds of the window in which a collective ran on ``device`` and
        no other operation did; None where the device ran no collective."""
        evs = clip(self.ops.get(device, []), self.lo, self.hi)
        names = [
            e.name for e in self.ops.get(device, []) if e.end > self.lo and e.start < self.hi
        ]
        coll = [iv for iv, n in zip(evs, names) if COLLECTIVE.search(n)]
        if not coll:
            return None
        compute = merge(iv for iv, n in zip(evs, names) if not COLLECTIVE.search(n))
        exposed = 0.0
        for s, e in merge(coll):
            covered = sum(max(0.0, min(e, ce) - max(s, cs)) for cs, ce in compute)
            exposed += (e - s) - covered
        return exposed

    def breakdown(self, device: int = 0, top: int = 10) -> dict:
        """The device operations that took most time in the window, and the
        longest idle gaps, each named by the innermost host span over it."""
        totals: dict[str, float] = {}
        evs = [e for e in self.ops.get(device, []) if e.end > self.lo and e.start < self.hi]
        for e in evs:
            label = op_label(e.name)
            totals[label] = totals.get(label, 0.0) + (min(e.end, self.hi) - max(e.start, self.lo))
        ops = sorted(totals.items(), key=lambda kv: -kv[1])[:top]
        busy = merge(clip(evs, self.lo, self.hi))
        gaps, prev = [], self.lo
        for s, e in busy:
            if s > prev:
                gaps.append((prev, s))
            prev = max(prev, e)
        if self.hi > prev:
            gaps.append((prev, self.hi))
        gaps = sorted(gaps, key=lambda g: g[0] - g[1])[:top]
        idle = [[self._host_at((s + e) / 2), e - s] for s, e in gaps]
        return {"device_ops": [[k, v] for k, v in ops], "idle_gaps": idle}

    def _host_at(self, t: float) -> str:
        """The innermost host span over time ``t``."""
        inner = None
        for e in self.host:
            if e.start <= t <= e.end and e.name != WINDOW_SPAN:
                if inner is None or e.dur < inner.dur:
                    inner = e
        return inner.name if inner is not None else "no Python span"


def _event(e) -> Event:
    return Event(e.name, e.start_ns * 1e-9, (e.start_ns + e.duration_ns) * 1e-9)


@dataclasses.dataclass
class Reading:
    """What a per-layer metric's ``read(run)`` is given: the cell, the
    reduced trace of its traced window (None when the run was not traced),
    the counts the driver kept of the work in that window, the chip's
    published peaks (None off the chip) and the chips used."""

    cell: Any
    trace: Optional[Trace]
    counts: dict
    peaks: Optional[dict]
    chips: int
