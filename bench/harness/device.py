"""The chip: where the run may execute, its peaks, and its clocks.

``require_chip`` refuses anything but a TPU holding enough chips with
the relational kernels compiled (not interpreted).  ``peaks`` reads the
published peak table kept beside this file, keyed by ``device_kind``; a
device the table lacks is an error, never a default.  ``CompileClock``
counts JAX's compiles from its monitoring events, so a compile inside
the measured window shows.
"""

from __future__ import annotations

import json
import os
import tempfile
import threading

_PEAKS = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                      "peaks.json")

#: jax's compile spans: tracing, lowering, and the backend compile (which
#: includes a persistent-cache lookup, so a warm cache shrinks it)
_COMPILE_EVENTS = ("/jax/core/compile/jaxpr_trace_duration",
                   "/jax/core/compile/jaxpr_to_mlir_module_duration",
                   "/jax/core/compile/backend_compile_duration")
_BACKEND_COMPILE = _COMPILE_EVENTS[2]


def configure(root: str) -> None:
    """Environment for a run, set before JAX is imported: the relational
    kernels on the device, and JAX's persistent compilation cache at a
    fixed path inside the checkout (the path is part of the cache's key,
    so a moving directory would never hit).  The cache is unbounded: a
    cell compiles a few dozen programs, and a bounded cache's eviction
    bookkeeping failed on the chip's machine, leaving entries unwritten.
    The TPU runtime's logs go under the run's temporary directory."""
    os.environ["ZERROW_KERNEL_BACKEND"] = "pallas"
    os.environ.setdefault("TPU_LOG_DIR", os.path.join(
        tempfile.gettempdir(), "tpu_logs"))
    os.environ["JAX_COMPILATION_CACHE_DIR"] = os.path.join(root, ".jax_cache")
    os.environ["JAX_COMPILATION_CACHE_MAX_SIZE"] = "-1"


class NoChip(RuntimeError):
    """The run cannot measure here: no TPU, too few chips, or kernels
    that would be interpreted."""


def require_chip(chips: int):
    """jax's devices, checked: a TPU first, at least ``chips`` of them,
    and Pallas not forced into interpret mode."""
    import jax
    from repro.kernels.ops import default_interpret
    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        raise NoChip(f"no TPU: jax's first device is {dev.platform!r} "
                     f"({dev.device_kind})")
    if len(devices) < chips:
        raise NoChip(f"the cell needs {chips} chips, jax finds "
                     f"{len(devices)}")
    if os.environ.get("ZERROW_PALLAS_INTERPRET") == "1" or \
            default_interpret():
        raise NoChip("Pallas is in interpret mode")
    return devices[:chips]


def peaks(device_kind: str) -> dict:
    """Published peaks of one chip of this kind."""
    with open(_PEAKS) as fh:
        table = json.load(fh)
    if device_kind not in table:
        raise KeyError(f"no published peaks for device kind "
                       f"{device_kind!r}; known: {sorted(table)}")
    return table[device_kind]


def memory_peak_bytes(devices) -> int:
    """Peak bytes in use on the fullest chip, as the runtime reports it."""
    return max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
               for d in devices)


class CompileClock:
    """Compiles and compile seconds, summed from jax's monitoring
    events (the idea of ``chip_smoke.CompileClock``, counting compiles
    too)."""

    def __init__(self):
        import jax
        self.seconds = 0.0
        self.compiles = 0
        self._lock = threading.Lock()
        jax.monitoring.register_event_duration_secs_listener(self._on_event)

    def _on_event(self, event, duration, **_):
        if event in _COMPILE_EVENTS:
            with self._lock:
                self.seconds += duration
                self.compiles += event == _BACKEND_COMPILE
