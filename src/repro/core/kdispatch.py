"""Kernel backend dispatch: numpy vkernels vs device relational kernels.

``ZERROW_KERNEL_BACKEND`` selects where the relational hot path (key
hashing, the join probe and index gathers, segment reducers) runs.  The
default is ``numpy`` (``core/vkernels.py``); the device backend
(``DEVICE_BACKEND``) routes each kernel call through
``repro.kernels.relational`` — jitted programs on jax's default device
(the TPU on the chip, the CPU under ``JAX_PLATFORMS=cpu``) — **but only
for the arguments the kernel table admits**.

``KERNELS`` is that table: one entry per device kernel an op calls, with
its numpy reference, the op whose cached cones it belongs to, and its
admission rule.  Admission is a bit-identity contract: a kernel enters
the table only once the differential harness
(``tests/test_pallas_relational.py``) proves device bits equal the numpy
reference bits equal a per-row naive reference, across dtypes, nulls,
duplicates and empties.  Arguments a rule refuses are served by the
reference whatever the backend.  The dispatch (``_dispatch``), the
fingerprint deps (``fp_includes``) and the self-check (``self_check``)
are all read off the table.

There is no hidden fallback.  Requesting the device backend where the
device kernels cannot be imported raises, naming the knob and the
cause; an unknown backend name raises; ``self_check()`` re-runs a
compact differential in-process and raises on any kernel whose bits
diverge.  Every call the device kernels serve is counted per kernel and
device platform (``device_calls()``, kept in ``core.obs``'s counter
registry), which is how a run proves its hot path ran on the chip; each
call is also a ``kernel.<name>`` span.

Fingerprint integration: ``fp_includes(op)`` is bound as the relational
ops' ``__fp_includes__`` (``core/fingerprint.py`` calls it at
fingerprint time).  It always returns the op's numpy deps; under the
device backend it adds a backend tag and the live device kernels of
that op.  Flipping the backend or editing a device kernel therefore
invalidates exactly the cached join/group-by cones — switching backends
can never serve a stale cone computed by the other engine.
"""

from __future__ import annotations

import functools
import os
from typing import Callable, Dict, NamedTuple, Optional, Sequence, Tuple

import numpy as np

from . import obs, vkernels

__all__ = [
    "ENV", "DEVICE_BACKEND", "BACKENDS", "KERNELS", "requested_backend",
    "device_requested", "active_backend", "self_check", "device_calls",
    "reset_device_calls",
    "hash_fixed", "combine_hashes", "hash_join_probe",
    "filter_join_gather", "GROUPED_REDUCERS", "fp_includes",
]

#: the environment variable that selects the kernel backend
ENV = "ZERROW_KERNEL_BACKEND"
#: its value for the device kernels of ``repro.kernels.relational``
DEVICE_BACKEND = "pallas"
BACKENDS = ("numpy", DEVICE_BACKEND)


# --------------------------------------------------------------------------
# backend resolution (env-driven, per call — no import-time sniffing)
# --------------------------------------------------------------------------

def requested_backend() -> str:
    """The backend named by ``ZERROW_KERNEL_BACKEND`` (default numpy).
    Read per call so tests and benchmarks can flip it without reloading
    modules; an unknown name raises rather than silently serving the
    default."""
    v = os.environ.get(ENV, "numpy").strip().lower()
    if v not in BACKENDS:
        raise ValueError(f"{ENV}={v!r}: choose one of {BACKENDS}")
    return v


def device_requested() -> bool:
    """Does ``ZERROW_KERNEL_BACKEND`` ask for the device kernels?"""
    return requested_backend() == DEVICE_BACKEND


_device_mod = None
_device_error: Optional[BaseException] = None


def _device():
    """Import ``repro.kernels.relational`` lazily, once; the failure (if
    any) is kept for the error ``active_backend`` raises."""
    global _device_mod, _device_error
    if _device_mod is None and _device_error is None:
        try:
            from repro.kernels import relational
            _device_mod = relational
        except Exception as e:   # ImportError or jax init failure
            _device_error = e
    return _device_mod


def active_backend() -> str:
    """The backend serving calls: the requested one.  Requesting the
    device backend where its kernels cannot be imported raises, naming
    the knob and the cause — never a silent numpy fallback."""
    b = requested_backend()
    if b == DEVICE_BACKEND and _device() is None:
        raise RuntimeError(
            f"{ENV}={b}, but the device kernels "
            f"(repro.kernels.relational) failed to import: "
            f"{_device_error!r}. Repair the jax install, or set "
            f"{ENV}=numpy to run on the numpy vkernels."
        ) from _device_error
    return b


#: counter prefix of the calls served by the device kernels, one counter
#: per kernel and device platform: ``kernel.calls.<kernel>.<platform>``
_CALLS = "kernel.calls."


def device_calls() -> Dict[Tuple[str, str], int]:
    """Calls the device kernels served since the last reset, keyed
    ``(kernel, platform)`` — ``("hash_fixed", "tpu")`` counts hashes
    that ran on the chip."""
    out = {}
    for name, n in obs.totals(_CALLS).items():
        kernel, platform = name[len(_CALLS):].rsplit(".", 1)
        out[(kernel, platform)] = n
    return out


def reset_device_calls() -> None:
    """Clear the kernel-dispatch counters: the calls and the bytes
    copied to and from the device (``kernel.*`` in ``core.obs``)."""
    obs.reset("kernel.")


def _on_device(kernel: str, *args):
    """Serve one call from the device kernel ``kernel`` and count it."""
    with obs.span(f"kernel.{kernel}"):
        out = getattr(_device_mod, kernel)(*args)
    obs.add(f"{_CALLS}{kernel}.{_device_mod.platform()}")
    return out


# --------------------------------------------------------------------------
# the kernel table
# --------------------------------------------------------------------------

def _any_args(*args) -> bool:
    return True


def _integer_values(values, *rest) -> bool:
    # float sums follow the reference's sequential-order contract,
    # accumulation in original row order (np.bincount), and float
    # min/max follow reduceat's left-to-right -0.0/NaN ties: a
    # block-parallel reduction reorders both and changes bits, so
    # floats stay on numpy
    return values.dtype == np.bool_ or np.issubdtype(values.dtype,
                                                     np.integer)


class Kernel(NamedTuple):
    """A device kernel of ``repro.kernels.relational`` (same name)."""
    reference: Callable     #: the numpy reference in ``core/vkernels.py``
    op: str                 #: whose cones it belongs to: join or group_by
    admits: Callable[..., bool] = _any_args   #: arguments it may serve


KERNELS: Dict[str, Kernel] = {
    "hash_fixed": Kernel(vkernels.hash_fixed, "join"),
    "combine_hashes": Kernel(vkernels.combine_hashes, "join"),
    "hash_join_probe": Kernel(vkernels.hash_join_probe, "join"),
    "filter_join_gather": Kernel(vkernels.filter_join_gather, "join"),
    "grouped_count": Kernel(vkernels.grouped_count, "group_by"),
    "grouped_sum": Kernel(vkernels.grouped_sum, "group_by",
                          _integer_values),
    "grouped_min": Kernel(vkernels.grouped_min, "group_by",
                          _integer_values),
    "grouped_max": Kernel(vkernels.grouped_max, "group_by",
                          _integer_values),
}


def _dispatch(name: str, *args):
    """``KERNELS[name]`` on the device when the device backend is active
    and the entry admits ``args``; its numpy reference otherwise."""
    kernel = KERNELS[name]
    if active_backend() == DEVICE_BACKEND and kernel.admits(*args):
        return _on_device(name, *args)
    return kernel.reference(*args)


# signatures mirror vkernels; numpy arrays in and out

def hash_fixed(values: np.ndarray) -> np.ndarray:
    return _dispatch("hash_fixed", values)


def combine_hashes(col_hashes: Sequence[np.ndarray], n: int) -> np.ndarray:
    return _dispatch("combine_hashes", col_hashes, n)


def hash_join_probe(build_hash: np.ndarray, probe_hash: np.ndarray
                    ) -> Tuple[np.ndarray, np.ndarray]:
    return _dispatch("hash_join_probe", build_hash, probe_hash)


def filter_join_gather(sel: np.ndarray, idx: np.ndarray) -> np.ndarray:
    return _dispatch("filter_join_gather", sel, idx)


#: drop-in for ``vkernels.GROUPED_REDUCERS``; the mean composes the float
#: segment sum, so it is the reference's
GROUPED_REDUCERS = {
    **{how: functools.partial(_dispatch, f"grouped_{how}")
       for how in ("count", "sum", "min", "max")},
    "mean": vkernels.grouped_mean,
}


# --------------------------------------------------------------------------
# fingerprint integration: callable __fp_includes__ for the relational ops
# --------------------------------------------------------------------------

#: each op's numpy deps besides its device kernels' references
_OP_DEPS = {
    "join": (vkernels.hash_var, vkernels.bytes_rows_equal),
    "group_by": (vkernels.group_ranges, vkernels.grouped_mean,
                 vkernels.dict_encode_var, vkernels.sort_keys_var),
}


def _backend_tag(name: str) -> None:
    """Inert marker folded into op fingerprints via
    ``functools.partial(_backend_tag, <backend>)`` — the partial's
    argument makes the backends produce distinct cone fingerprints."""


def fp_includes(op: str) -> tuple:
    """Kernel deps of ``op`` (``join``, which ``filter_join`` shares, or
    ``group_by``), evaluated at fingerprint time: the numpy references
    always; plus the backend tag and the live device kernels under the
    device backend (so backend flips and kernel edits invalidate exactly
    that op's cones)."""
    names = [name for name, k in KERNELS.items() if k.op == op]
    deps = tuple(KERNELS[name].reference for name in names) + _OP_DEPS[op]
    if active_backend() == DEVICE_BACKEND:
        deps += (functools.partial(_backend_tag, DEVICE_BACKEND),
                 *(getattr(_device_mod, name) for name in names))
    return deps


# --------------------------------------------------------------------------
# in-process differential: raise on anything whose bits diverge
# --------------------------------------------------------------------------

def _check_inputs(n: int, n_groups: int) -> Dict[str, list]:
    """Seeded adversarial arguments for every kernel of the table: -0.0,
    NaN, 64-bit extremes, duplicates, nulls and ``-1`` miss sentinels;
    the reducers sweep the integer and bool dtypes."""
    rng = np.random.default_rng(0)
    f64 = rng.standard_normal(n)
    f64[rng.random(n) < 0.1] = -0.0
    f64[rng.random(n) < 0.05] = np.nan
    cols = {
        "int32": rng.integers(-50, 50, n).astype(np.int32),
        "int64": rng.integers(-(1 << 62), 1 << 62, n, dtype=np.int64),
        "uint64": rng.integers(0, 1 << 64, n, dtype=np.uint64),
        "float64": f64,
        "bool": rng.random(n) < 0.5,
    }
    valid = rng.random(n) < 0.8
    order, starts = vkernels.group_ranges([rng.integers(0, n_groups, n)])
    sel = np.nonzero(rng.random(n // 2) < 0.5)[0]
    idx = rng.integers(-1, len(sel), n).astype(np.int64)
    hs = [vkernels.hash_fixed(cols[k]) for k in ("int64", "float64",
                                                 "uint64")]
    # many-to-many runs, and the largest and smallest hashes against a
    # padded build that holds the largest too
    top = np.iinfo(np.uint64).max
    bh = rng.integers(0, 400, n // 3).astype(np.uint64)
    bh[:3] = [0, top, top]
    ph = rng.integers(0, 600, n).astype(np.uint64)
    ph[:2] = [0, top]
    ints = [cols[k] for k in ("int32", "int64", "uint64", "bool")]
    return {
        "hash_fixed": [(v,) for v in cols.values()],
        "combine_hashes": [(hs, n)],
        "hash_join_probe": [(bh, ph)],
        "filter_join_gather": [(sel, idx)],
        "grouped_count": [(cols["int64"], order, starts, valid)],
        **{f"grouped_{how}": [(v, order, starts, valid) for v in ints]
           for how in ("sum", "min", "max")},
    }


def _same_bits(got, want) -> bool:
    """Equal values and dtypes, output by output (NaNs equal)."""
    got = got if isinstance(got, tuple) else (got,)
    want = want if isinstance(want, tuple) else (want,)
    return len(got) == len(want) and all(
        g.dtype == w.dtype and np.array_equal(g, w,
                                              equal_nan=g.dtype.kind == "f")
        for g, w in zip(got, want))


def self_check(n: int = 4096, n_groups: int = 97) -> Dict[str, str]:
    """Compact differential over seeded adversarial inputs: every kernel
    of ``KERNELS`` runs device-vs-reference and must match bit for bit
    (values and dtypes).  Any mismatch raises ``RuntimeError`` naming
    every diverging kernel; otherwise returns ``{kernel: "ok"}``.  The
    kernels run on jax's default device, so on the chip this checks the
    compiled TPU programs."""
    if _device() is None:
        raise RuntimeError(
            f"self_check needs the device kernels; import failed with "
            f"{_device_error!r} (see {ENV})") from _device_error
    inputs = _check_inputs(n, n_groups)
    results: Dict[str, str] = {}
    for name, kernel in KERNELS.items():
        device = getattr(_device_mod, name)
        bad = [args for args in inputs[name]
               if not _same_bits(device(*args), kernel.reference(*args))]
        results[name] = "ok" if not bad else (
            f"bit mismatch vs numpy on {len(bad)} of {len(inputs[name])} "
            f"inputs")
    bad = {k: v for k, v in results.items() if v != "ok"}
    if bad:
        raise RuntimeError(f"device kernels lost bit-identity with the "
                           f"numpy reference: {bad}")
    return results
