"""The benchmark's core: cells from ``BENCHMARK.json``, the files each cell
names, host-clock spans, the measured window and the result line.

Everything a cell needs is found by name, so a later change adds a cell, a
configuration, a traffic mix or a metric as new files only:

  BENCHMARK.json             the cells, the metrics and their bounds
  configs/<config>.json      sizes, source, cuts (``reduced``, ``assumed``)
  configs/<config>.py        FLOP counts and the plain reference
  traffic/<mix>.json         the driver's name and its parameters
  drivers/<driver>.py        set-up, window loop and ``correct`` of one
                             entry point of the program
  checks/<cell>.json         the limits ``correct`` holds each number to,
                             with the readings they were set from
  metrics/<metric>.py        ``read(run)`` of one metric
  peaks.json                 the chip's peaks, keyed by ``device_kind``
"""
from __future__ import annotations

import importlib.util
import json
import math
import re
import time
from collections import defaultdict
from dataclasses import dataclass, field
from pathlib import Path
from typing import Callable, Dict, List, Optional

HERE = Path(__file__).resolve().parent
REPO = HERE.parent
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")


# --------------------------------------------------------------------------- #
# Files found by name
# --------------------------------------------------------------------------- #

def manifest(root: Path = REPO) -> Dict:
    with open(root / "BENCHMARK.json") as f:
        return json.load(f)


def find_cell(man: Dict, name: str) -> Dict:
    for w in man["workloads"]:
        if w["name"] == name:
            return w
    raise KeyError(f"no cell {name!r} in BENCHMARK.json")


def _checked(name: str) -> str:
    if not NAME.match(name):
        raise ValueError(f"bad name {name!r}")
    return name


def load_json(kind: str, name: str, base: Path = HERE) -> Dict:
    with open(base / kind / f"{_checked(name)}.json") as f:
        return json.load(f)


def load_module(kind: str, name: str, base: Path = HERE):
    path = base / kind / f"{_checked(name)}.py"
    mod_name = f"chipbench_{kind}_{re.sub(r'[^A-Za-z0-9_]', '_', name)}"
    spec = importlib.util.spec_from_file_location(mod_name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def metrics_of(man: Dict, cell: str, traced: bool) -> List[Dict]:
    """The metrics a run of ``cell`` reports: its end-to-end metrics, or with
    tracing its per-layer ones. A metric without ``workloads`` belongs to
    every cell that reports the end-to-end metric it moves."""
    e2e = [m for m in man["end_to_end"]
           if cell in m.get("workloads", [cell])]
    if not traced:
        return e2e
    names = {m["name"] for m in e2e}

    def belongs(m: Dict) -> bool:
        if "workloads" in m:
            return cell in m["workloads"]
        return m["moves"] in names
    return [m for m in man["per_layer"] if belongs(m)]


def peaks(device_kind: str, base: Path = HERE) -> Dict:
    with open(base / "peaks.json") as f:
        table = json.load(f)["devices"]
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r}")
    return table[device_kind]


# --------------------------------------------------------------------------- #
# Host readings
# --------------------------------------------------------------------------- #

def _proc_kb(path: str, key: str) -> int:
    with open(path) as f:
        for line in f:
            if line.startswith(key + ":"):
                return int(line.split()[1]) * 1024
    raise KeyError(key)


def rss_bytes() -> int:
    return _proc_kb("/proc/self/status", "VmRSS")


def mem_total_bytes() -> int:
    return _proc_kb("/proc/meminfo", "MemTotal")


class CompileCounter:
    """Counts XLA backend compiles through JAX's monitoring events."""

    def __init__(self):
        self.n = 0
        self.secs = 0.0

    def __call__(self, event: str, secs: float, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.n += 1
            self.secs += secs


# --------------------------------------------------------------------------- #
# Spans: host-clock self time per layer, mirrored into the profiler trace
# --------------------------------------------------------------------------- #

class Spans:
    """Nested host-clock spans. A span's self time (its length less that of
    the spans opened inside it) is added to its layer while ``recording``.
    Each span is also a ``jax.profiler.TraceAnnotation``, so a trace shows
    what the host was doing while the device sat idle."""

    def __init__(self, annotate: Optional[Callable] = None):
        self.annotate = annotate
        self.recording = False
        self.self_s: Dict[str, float] = defaultdict(float)
        self.calls: Dict[str, int] = defaultdict(int)
        self._stack: List[List[float]] = []

    def wrap(self, layer: str, fn: Callable,
             sync: Optional[Callable] = None) -> Callable:
        """``fn`` inside a span of ``layer``; ``sync(args, result)`` runs
        before the span closes (to wait for work the call left queued)."""
        def spanned(*args, **kw):
            ann = self.annotate(f"chipbench:{layer}") if self.annotate else None
            if ann is not None:
                ann.__enter__()
            t0 = time.perf_counter()
            self._stack.append([t0, 0.0])
            try:
                out = fn(*args, **kw)
                if sync is not None:
                    sync(args, out)
                return out
            finally:
                t1 = time.perf_counter()
                _, child = self._stack.pop()
                if self._stack:
                    self._stack[-1][1] += t1 - t0
                if self.recording:
                    self.self_s[layer] += (t1 - t0) - child
                    self.calls[layer] += 1
                if ann is not None:
                    ann.__exit__(None, None, None)
        return spanned


class Patches:
    """Attribute replacements undone in reverse order on exit."""

    def __init__(self):
        self._undo: List = []

    def set(self, obj, name: str, value) -> None:
        self._undo.append((obj, name, getattr(obj, name)))
        setattr(obj, name, value)

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        while self._undo:
            obj, name, old = self._undo.pop()
            setattr(obj, name, old)


# --------------------------------------------------------------------------- #
# The measured window
# --------------------------------------------------------------------------- #

class Window:
    """Whole rounds: a round starts while less than ``seconds`` have passed
    since the window opened; the window ends when the last of them ends."""

    def __init__(self, seconds: float):
        self.seconds = seconds
        self.t_open: Optional[float] = None
        self.ends: List[float] = []

    def open(self, t: float) -> None:
        self.t_open = t

    def round_ended(self, t: float) -> bool:
        """Record a round's end; True while another round should start."""
        self.ends.append(t)
        return t - self.t_open < self.seconds

    @property
    def rounds(self) -> int:
        return len(self.ends)

    @property
    def window_s(self) -> float:
        return self.ends[-1] - self.t_open

    @property
    def walls(self) -> List[float]:
        starts = [self.t_open] + self.ends[:-1]
        return [b - a for a, b in zip(starts, self.ends)]


# --------------------------------------------------------------------------- #
# What a run hands the metric readers
# --------------------------------------------------------------------------- #

@dataclass
class Run:
    setup_s: float = 0.0
    window: Optional[Window] = None
    chips: int = 1
    peak_hbm_bytes: Optional[int] = None
    spans: Optional[Spans] = None          # traced runs only
    # traced runs: the profiler runs over one round before the window, so
    # device numbers come from that round and host times from the window
    tracing: bool = False                  # the traced round is running
    trace: object = None                   # trace.Trace, traced runs only
    trace_lo: float = 0.0                  # the traced round on the trace's
    trace_hi: float = 0.0                  # clock
    # per kernel: (bytes, ops) of each call the traced round made, from its
    # shapes
    kernel_calls: Dict[str, List] = field(default_factory=dict)
    flops_per_round: float = 0.0
    peaks: Dict = field(default_factory=dict)

    @property
    def rounds(self) -> int:
        return self.window.rounds

    @property
    def round_s(self) -> float:
        return self.window.window_s / self.window.rounds


def finite(x) -> bool:
    return x is not None and isinstance(x, (int, float)) and math.isfinite(x)


def decide(numbers: Dict[str, float], limits: Dict[str, float]):
    """``correct`` and the checks behind it: every number within its limit
    (a number that is not finite fails)."""
    checks = {k: {"value": numbers[k], "limit": limits[k]} for k in limits}
    correct = all(finite(c["value"]) and c["value"] <= c["limit"]
                  for c in checks.values())
    return correct, checks
