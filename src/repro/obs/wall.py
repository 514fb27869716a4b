"""Wall-clock spans and counters of the program (repro.obs).

``repro.obs.tracer`` keeps the simulated timeline: spans on ``env.now``,
which are the WAN simulator's output and measure nothing. This module
measures the host. Where the work happens, the program opens a span by name
(``with wall.span("train.feed"):``) or adds to a counter
(``wall.count("h2d_bytes", n)``). Spans nest, on one thread.

  * Each span adds its self time on ``time.perf_counter`` (its length less
    that of the spans opened inside it) and one call to the totals.
  * Each span is also a ``jax.profiler.TraceAnnotation`` named
    ``unifyfl:<name>``, so a profiler trace holds it on the device trace's
    clock. Keyword arguments (``round=``, ``silo=``) become the annotation's
    metadata; the per-step spans of local training carry none.
  * ``totals()`` is a plain dict over the ``wall`` schema of
    ``repro.obs.metrics`` (``<span>_s`` self seconds, ``<span>_n`` calls,
    ``host_syncs``, ``h2d_bytes``), so two snapshots subtract (``diff``).
    The names are declared there: an undeclared span raises.

The wall is process-wide and off by default. Off, the active object is the
shared ``NULL_WALL``: a span is a shared no-op context and a count does
nothing, with no clock read, no annotation and no dict write.
``ObsConfig.wall`` switches it for a run (``Observability`` adopts its stats
into the run's registry); ``switch`` does so directly.
"""
from __future__ import annotations

from time import perf_counter
from typing import Dict, List

import jax
import numpy as np

from repro.obs.metrics import StatsView

PREFIX = "unifyfl:"   # the annotation name of span ``name`` is PREFIX + name


class _NoSpan:
    __slots__ = ()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return None


_NO_SPAN = _NoSpan()


class NullWall:
    """The wall switched off: every site's whole cost is a call."""

    enabled = False

    def span(self, name: str, **meta):
        return _NO_SPAN

    def count(self, name: str, n: int = 1) -> None:
        pass

    def totals(self) -> Dict[str, float]:
        return {}


NULL_WALL = NullWall()


class _Span:
    __slots__ = ("wall", "name", "meta", "ann", "t0", "inner")

    def __init__(self, wall: "Wall", name: str, meta: Dict):
        self.wall = wall
        self.name = name
        self.meta = meta
        self.inner = 0.0      # seconds of the spans opened inside this one

    def __enter__(self):
        self.ann = jax.profiler.TraceAnnotation(PREFIX + self.name,
                                                **self.meta)
        self.ann.__enter__()
        self.wall._open.append(self)
        self.t0 = perf_counter()
        return self

    def __exit__(self, *exc):
        dt = perf_counter() - self.t0
        opened = self.wall._open
        opened.pop()
        if opened:
            opened[-1].inner += dt
        st = self.wall.stats
        st[self.name + "_s"] += dt - self.inner
        st[self.name + "_n"] += 1
        self.ann.__exit__(*exc)
        return None


class Wall(NullWall):
    enabled = True

    def __init__(self):
        self.stats = StatsView("wall")
        self._open: List[_Span] = []

    def span(self, name: str, **meta) -> _Span:
        if name + "_s" not in self.stats:
            raise KeyError(f"{name!r} is not a declared wall span "
                           f"(repro.obs.metrics.WALL_SPANS)")
        return _Span(self, name, meta)

    def count(self, name: str, n: int = 1) -> None:
        self.stats[name] += n

    def totals(self) -> Dict[str, float]:
        return dict(self.stats)


_active: NullWall = NULL_WALL


def active() -> NullWall:
    """The process's wall: ``NULL_WALL`` while off."""
    return _active


def switch(on: bool) -> NullWall:
    """Switch the process's wall on (with fresh totals) or off."""
    global _active
    _active = Wall() if on else NULL_WALL
    return _active


def span(name: str, **meta):
    return _active.span(name, **meta)


def count(name: str, n: int = 1) -> None:
    _active.count(name, n)


def totals() -> Dict[str, float]:
    return _active.totals()


def diff(before: Dict[str, float], after: Dict[str, float]
         ) -> Dict[str, float]:
    """What was added between two ``totals()`` snapshots."""
    return {k: v - before.get(k, 0) for k, v in after.items()}


def to_host(x, dtype=None) -> np.ndarray:
    """``np.asarray(x, dtype)``. Where ``x`` is a device array the host
    blocks on it and copies it back: one ``host_syncs``."""
    if _active.enabled and isinstance(x, jax.Array):
        _active.count("host_syncs")
    return np.asarray(x, dtype)
