"""Observability: spans on the profiler's clock and one counter registry.

Spans.  ``span(name, **meta)`` is a context manager around one step of
the hot path.  Once jax is loaded it is a ``jax.profiler.TraceAnnotation``
(a TraceMe), so the span lands in the same trace as the device's
operations, on the profiler's clock, nested per thread: an idle gap on
the device can be put down to the host step that was running.  The
profiler holds the spans and writes them out with its trace; without a
running profiler a TraceMe is one atomic check.  Where jax is not loaded
(the numpy backend), a span is one shared no-op context.

Names carry one dotted prefix per layer:

  ``ops.``     relational ops (``core/ops.py``): ``ops.join``,
               ``ops.filter_join``, ``ops.group_by`` and their steps
               (``ops.join.hash``, ``ops.group_by.reduce``, ...)
  ``kernel.``  the device-kernel call edge: ``kernel.<kernel>`` around a
               whole call (``core/kdispatch.py``), ``kernel.put`` /
               ``kernel.run`` / ``kernel.fetch`` inside it
               (``kernels/relational.py``)
  ``loader.``  ``loader.decode``: a loader node's zarquet read
  ``sipc.``    ``sipc.read`` / ``sipc.write``: a sandbox's SIPC reads of
               its inputs and write of its output

Counters.  ``add(name, value)`` sums into a lock-protected registry;
``totals(prefix)`` reads the sums and ``reset(prefix)`` clears them — a
reader takes totals before and after what it measures, or resets first.
The program's counters:

  ``kernel.calls.<kernel>.<platform>``  device-kernel calls served
  ``kernel.h2d_bytes`` / ``kernel.d2h_bytes``  bytes of the padded arrays
      copied to the device and back, per call summed
  ``jax.compiles`` / ``jax.compile_s``  backend compiles (a persistent-
      cache hit counts: it runs the backend-compile step), and seconds
      in tracing, lowering and backend compile; counted once the device
      kernels are loaded (``watch_compiles``)

This module imports nothing beyond the standard library.
"""

from __future__ import annotations

import contextlib
import sys
import threading
from typing import Dict

__all__ = ["span", "add", "totals", "reset", "watch_compiles"]

_NOOP = contextlib.nullcontext()
_annotation = None


def span(name: str, **meta):
    """A profiler span named ``name`` (``meta`` becomes its arguments),
    or the shared no-op context while jax is not loaded."""
    global _annotation
    if _annotation is None:
        profiler = getattr(sys.modules.get("jax"), "profiler", None)
        if profiler is None:
            return _NOOP
        _annotation = profiler.TraceAnnotation
    return _annotation(name, **meta)


_lock = threading.Lock()
_counts: Dict[str, float] = {}


def add(name: str, value: float = 1) -> None:
    with _lock:
        _counts[name] = _counts.get(name, 0) + value


def totals(prefix: str = "") -> Dict[str, float]:
    """Every counter whose name starts with ``prefix``, summed since
    its last reset."""
    with _lock:
        return {k: v for k, v in _counts.items() if k.startswith(prefix)}


def reset(prefix: str = "") -> None:
    """Clear every counter whose name starts with ``prefix``."""
    with _lock:
        for k in [k for k in _counts if k.startswith(prefix)]:
            del _counts[k]


#: jax's compile steps: tracing, lowering, and the backend compile
_COMPILE_EVENTS = ("/jax/core/compile/jaxpr_trace_duration",
                   "/jax/core/compile/jaxpr_to_mlir_module_duration",
                   "/jax/core/compile/backend_compile_duration")
_watching = False


def _on_jax_event(event: str, duration: float, **_) -> None:
    if event in _COMPILE_EVENTS:
        with _lock:
            _counts["jax.compile_s"] = _counts.get("jax.compile_s", 0) \
                + duration
            if event == _COMPILE_EVENTS[2]:
                _counts["jax.compiles"] = _counts.get("jax.compiles", 0) + 1


def watch_compiles(monitoring) -> None:
    """Count jax's compiles into ``jax.compiles`` / ``jax.compile_s``;
    ``monitoring`` is ``jax.monitoring``.  Registers once per process."""
    global _watching
    with _lock:
        if _watching:
            return
        _watching = True
    monitoring.register_event_duration_secs_listener(_on_jax_event)
