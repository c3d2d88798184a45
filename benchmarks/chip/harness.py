"""What every cell of the on-chip benchmark shares: the files found by name,
seeds, compile watching, peaks and the result line.

A cell (one entry of ``workloads`` in ``BENCHMARK.json``) names a
configuration and a traffic mix.  The configuration is
``configs/<file>.json`` (its sizes, as run) with ``configs/<file>.py``
beside it (its plain reference and its operation and byte counts).  The
traffic mix is ``traffic/<traffic>.json``; its ``driver`` key names the
generic code under ``drivers/`` that runs it.  A per-layer metric is
``metrics/<name>.py`` with a ``read(run)`` function.  Nothing here knows a
cell, a configuration, a mix or a metric by name.
"""
from __future__ import annotations

import dataclasses
import hashlib
import importlib.util
import json
import os
import sys
import time
from typing import Any, Optional

HERE = os.path.dirname(os.path.abspath(__file__))
CHECKOUT = os.path.dirname(os.path.dirname(HERE))


class SetupError(RuntimeError):
    """The cell cannot run here (no chip, a file missing, a size that
    disagrees with the program); the run prints no result."""


def load_module(path: str, name: Optional[str] = None):
    """Import a Python file by path (names may hold dots and dashes)."""
    if not os.path.isfile(path):
        raise SetupError(f"missing file {path}")
    name = name or "bench_" + os.path.relpath(path, HERE).replace(os.sep, "_").replace(
        ".", "_"
    ).replace("-", "_")
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[name] = mod
    spec.loader.exec_module(mod)
    return mod


def load_json(path: str) -> Any:
    if not os.path.isfile(path):
        raise SetupError(f"missing file {path}")
    with open(path) as f:
        return json.load(f)


def derive_seed(seed: int, tag: str) -> int:
    """A 31-bit seed for one purpose (``tag``) from the run's ``--seed``,
    which may exceed 32 bits; different tags give independent streams."""
    h = hashlib.blake2b(f"{int(seed)}:{tag}".encode(), digest_size=4).digest()
    return int.from_bytes(h, "little") & 0x7FFFFFFF


@dataclasses.dataclass
class Cell:
    """One cell as ``BENCHMARK.json`` and its files describe it."""

    name: str
    chips: int
    spec: dict  # the whole of BENCHMARK.json
    workload: dict  # this cell's entry
    config: dict  # configs/<name>.json
    config_mod: Any  # configs/<name>.py
    traffic: dict  # traffic/<name>.json
    driver: Any  # drivers/<driver>.py
    root: str  # directory of the benchmark's files

    def end_to_end(self) -> list[dict]:
        return [m for m in self.spec["end_to_end"] if _reports(m, self.name)]

    def per_layer(self) -> list[dict]:
        e2e = {m["name"] for m in self.end_to_end()}
        return [
            m
            for m in self.spec["per_layer"]
            if (self.name in m["workloads"] if "workloads" in m else m["moves"] in e2e)
        ]


def _reports(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def load_cell(name: str, bench_path: str, root: str = HERE) -> Cell:
    """Find cell ``name`` in the benchmark file and load its files."""
    spec = load_json(bench_path)
    cells = {w["name"]: w for w in spec["workloads"]}
    if name not in cells:
        raise SetupError(f"no workload {name!r} in {bench_path}")
    w = cells[name]
    configs = {c["name"]: c for c in spec["configs"]}
    centry = configs[w["config"]]
    # ``file`` is relative to the checkout, where BENCHMARK.json lies
    cfile = os.path.join(os.path.dirname(os.path.abspath(bench_path)), centry["file"])
    traffic = load_json(os.path.join(root, "traffic", w["traffic"] + ".json"))
    return Cell(
        name=name,
        chips=int(w["chips"]),
        spec=spec,
        workload=w,
        config=load_json(cfile),
        config_mod=load_module(os.path.splitext(cfile)[0] + ".py"),
        traffic=traffic,
        driver=load_module(os.path.join(root, "drivers", traffic["driver"] + ".py")),
        root=root,
    )


def load_peaks(device_kind: str, root: str = HERE) -> dict:
    """The published peaks of ``device_kind``; an unknown kind is an error."""
    table = load_json(os.path.join(root, "peaks.json"))
    if device_kind not in table["devices"]:
        raise SetupError(
            f"no peaks for device kind {device_kind!r} in peaks.json; "
            f"known: {sorted(table['devices'])}"
        )
    return table["devices"][device_kind]


class CompileWatch:
    """Compile seconds, compiles and persistent-cache hits and misses, from
    JAX's monitoring events.  ``mark()`` starts counting a window."""

    def __init__(self):
        import jax

        self.seconds = 0.0
        self.compiles = 0
        self.hits = 0
        self.misses = 0
        self._mark = (0, 0)
        jax.monitoring.register_event_listener(self._event)
        jax.monitoring.register_event_duration_secs_listener(self._duration)

    def _event(self, event, **kw):
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.misses += 1

    def _duration(self, event, secs, **kw):
        if event == "/jax/core/compile/backend_compile_duration":
            self.seconds += secs
            self.compiles += 1

    def mark(self) -> None:
        self._mark = (self.compiles, self.hits)

    def since_mark(self) -> int:
        """Programs compiled or loaded from the cache since ``mark()``."""
        return (self.compiles - self._mark[0]) + (self.hits - self._mark[1])

    def line(self) -> str:
        return (
            f"compile {self.seconds:.1f} s in {self.compiles} backend compiles; "
            f"persistent cache {self.hits} hits / {self.misses} misses"
        )


def memory_peak_bytes(devices) -> Optional[int]:
    """Peak bytes in use on the fullest of ``devices`` over the process."""
    peaks = []
    for d in devices:
        stats = d.memory_stats()
        if stats and "peak_bytes_in_use" in stats:
            peaks.append(int(stats["peak_bytes_in_use"]))
    return max(peaks) if peaks else None


def host_peak_rss_gb() -> float:
    """The process's peak resident memory on the host, in GB."""
    import resource

    return resource.getrusage(resource.RUSAGE_SELF).ru_maxrss * 1024 / 1e9


@dataclasses.dataclass
class Check:
    """One number compared with its limit; the run is correct when every
    check's value is at most its limit (a missing value fails)."""

    name: str
    value: Optional[float]
    limit: float

    @property
    def ok(self) -> bool:
        return self.value is not None and self.value == self.value and self.value <= self.limit


@dataclasses.dataclass
class Outcome:
    """What a driver hands back from one run of its cell."""

    attempted: int
    failed: int
    end_to_end: dict  # metric name -> value
    checks: list  # of Check
    memory_peak_bytes: Optional[int]
    counts: dict = dataclasses.field(default_factory=dict)  # for metric readers
    trace: Any = None  # tracing.Trace of the traced window, or None
    notes: list = dataclasses.field(default_factory=list)


class Clock:
    """Seconds since the process began running the benchmark."""

    def __init__(self, start: Optional[float] = None):
        self.start = time.perf_counter() if start is None else start

    def now(self) -> float:
        return time.perf_counter() - self.start


def check_sizes(want: dict, program_config) -> None:
    """The program's model has the configuration file's sizes."""
    got = {k: getattr(program_config, k) for k in want}
    if got != want:
        raise SetupError(f"the program's model has {got}, the configuration file {want}")


def log(msg: str) -> None:
    print(f"[bench] {msg}", file=sys.stderr, flush=True)
