"""Spans and stage counters of the store's host path.

``span(name, **stats)`` times one stage of one upload and records it twice:

* while ``jax.profiler`` is tracing, as a ``TraceAnnotation`` whose keyword
  arguments come back as the event's stats, in the profiler's own trace and
  on the same clock as the device's operations;
* always, in a process-wide counter table, ``stages()``:
  ``{name: {"count", "seconds", "bytes"}}``, which the store server serves
  under ``/stats`` ``server.stages``. The table is never persisted.

A span carries ``key=<repo>/<file>``. A span opened without one takes the
key of the innermost keyed span of its thread or asyncio task (a context
variable, so coroutines that interleave on one event loop keep theirs
apart). ``bytes=`` is the size of what the stage moves; ``span.set()``
gives stats that are known only at the end, ``bytes`` among them.

A wait that starts on one thread and ends on another is no span: ``add()``
counts its seconds, and the span that ends it carries them as a stat.

This module never imports JAX: it traces only where the process has
already loaded it, so the numpy store and the entropy worker processes stay
free of it. With the profiler off a span costs one ``is_enabled`` check, a
context-variable read, two clock reads and a counter update under a lock.
"""

from __future__ import annotations

import contextvars
import sys
import threading
import time
from typing import Dict

__all__ = ["add", "span", "stages"]

_lock = threading.Lock()
_table: Dict[str, list] = {}        # name -> [count, seconds, bytes]
_key: contextvars.ContextVar = contextvars.ContextVar("zllm_span_key",
                                                      default=None)
_annotation = None                  # jax.profiler.TraceAnnotation, once loaded


def add(name: str, seconds: float, nbytes: int = 0) -> None:
    """Count one stage of ``seconds`` that moved ``nbytes``."""
    with _lock:
        row = _table.get(name)
        if row is None:
            row = _table[name] = [0, 0.0, 0]
        row[0] += 1
        row[1] += seconds
        row[2] += nbytes


def stages() -> Dict[str, Dict]:
    """A copy of the counter table."""
    with _lock:
        return {name: {"count": c, "seconds": s, "bytes": b}
                for name, (c, s, b) in _table.items()}


def _tracing():
    """``TraceAnnotation`` while the profiler traces, else None."""
    global _annotation
    if _annotation is None:
        if "jax" not in sys.modules:
            return None
        from jax.profiler import TraceAnnotation
        _annotation = TraceAnnotation
    return _annotation if _annotation.is_enabled() else None


class span:
    """``with span("zllm.stage", key=..., bytes=...) as sp:`` times one
    stage; see the module's docstring."""

    __slots__ = ("name", "stats", "_event", "_token", "_t0")

    def __init__(self, name: str, **stats):
        self.name, self.stats = name, stats

    def __enter__(self) -> "span":
        key = self.stats.get("key")
        if key is None:
            key = _key.get()
            if key is not None:
                self.stats["key"] = key
            self._token = None
        else:
            self._token = _key.set(key)
        annotation = _tracing()
        self._event = None
        if annotation is not None:
            self._event = annotation(self.name, **self.stats)
            self._event.__enter__()
        self._t0 = time.perf_counter()
        return self

    def set(self, **stats) -> None:
        """Stats known only at the end of the stage."""
        self.stats.update(stats)
        if self._event is not None:
            self._event.set_metadata(**stats)

    def __exit__(self, *exc) -> None:
        add(self.name, time.perf_counter() - self._t0,
            int(self.stats.get("bytes", 0)))
        if self._token is not None:
            _key.reset(self._token)
        if self._event is not None:
            self._event.__exit__(*exc)
