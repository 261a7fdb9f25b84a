"""Kernel backend dispatch: numpy vkernels vs device relational kernels.

``ZERROW_KERNEL_BACKEND={numpy,pallas}`` selects where the relational
hot path (key hashing, join gathers, segment reducers) runs.  The
default is ``numpy`` (``core/vkernels.py``); ``pallas`` routes each
kernel call through ``repro.kernels.relational`` — jitted programs on
jax's default device (the TPU on the chip, the CPU under
``JAX_PLATFORMS=cpu``) — **but only for kernels the eligibility
registry admits**.  Admission is a bit-identity contract: a kernel
enters ``REGISTRY`` as eligible only once the differential harness
(``tests/test_pallas_relational.py``) proves device-kernel
bits equal the numpy reference bits equal a per-row naive reference,
across dtypes, nulls, duplicates, and empties.  Kernels that *cannot*
meet the contract are documented ineligible with their reason and stay
on numpy regardless of the env knob — the first entries are the float
segment reductions, whose PR 5 contract fixes a sequential accumulation
order (``np.bincount`` original-row-order for sums; left-to-right
``reduceat`` ties for min/max over -0.0/NaN) that a block-parallel
reduction cannot reproduce bit-for-bit.  Position-dependent float
accumulation must never silently change results.

There is no hidden fallback.  Requesting ``pallas`` where the device
kernels cannot be imported raises, naming this knob and the cause; an
unknown backend name raises; ``self_check()`` re-runs a compact
differential in-process and raises on any kernel whose bits diverge.
Every call the device kernels serve is counted per kernel and device
platform (``device_calls()``, kept in ``core.obs``'s counter registry),
which is how a run proves its hot path ran on the chip; each call is
also a ``kernel.<name>`` span.

Fingerprint integration: ``fp_includes_join`` / ``fp_includes_group_by``
are *callables* assigned to the relational ops' ``__fp_includes__``
(``core/fingerprint.py`` calls them at fingerprint time).  They always
return the numpy kernel deps; when the pallas backend is active they
additionally fold in a backend tag plus the live device kernel
functions.  Flipping ``ZERROW_KERNEL_BACKEND`` or editing a device
kernel therefore invalidates exactly the cached join/group-by cones —
switching backends can never serve a stale cone computed by the other
engine.
"""

from __future__ import annotations

import functools
import os
from dataclasses import dataclass
from typing import Dict, Optional, Sequence, Tuple

import numpy as np

from . import obs, vkernels

__all__ = [
    "BACKENDS", "REGISTRY", "Eligibility", "requested_backend",
    "active_backend", "eligible", "self_check", "device_calls",
    "reset_device_calls",
    "hash_fixed", "combine_hashes", "hash_keys", "hash_join_probe",
    "filter_join_gather", "GROUPED_REDUCERS", "fp_includes_join",
    "fp_includes_group_by",
]

BACKENDS = ("numpy", "pallas")

_ENV = "ZERROW_KERNEL_BACKEND"


# --------------------------------------------------------------------------
# backend resolution (env-driven, per call — no import-time sniffing)
# --------------------------------------------------------------------------

def requested_backend() -> str:
    """The backend named by ``ZERROW_KERNEL_BACKEND`` (default numpy).
    Read per call so tests and benchmarks can flip it without reloading
    modules; an unknown name raises rather than silently serving the
    default."""
    v = os.environ.get(_ENV, "numpy").strip().lower()
    if v not in BACKENDS:
        raise ValueError(f"{_ENV}={v!r}: choose one of {BACKENDS}")
    return v


_pallas_mod = None
_pallas_error: Optional[BaseException] = None


def _pallas():
    """Import ``repro.kernels.relational`` lazily, once; the failure (if
    any) is kept for the error ``active_backend`` raises."""
    global _pallas_mod, _pallas_error
    if _pallas_mod is None and _pallas_error is None:
        try:
            from repro.kernels import relational
            _pallas_mod = relational
        except Exception as e:   # ImportError or jax init failure
            _pallas_error = e
    return _pallas_mod


def active_backend() -> str:
    """The backend serving calls: the requested one.  Requesting
    ``pallas`` where the device kernels cannot be imported raises,
    naming the knob and the cause — never a silent numpy fallback."""
    b = requested_backend()
    if b == "pallas" and _pallas() is None:
        raise RuntimeError(
            f"{_ENV}=pallas, but the device kernels "
            f"(repro.kernels.relational) failed to import: "
            f"{_pallas_error!r}. Repair the jax install, or set "
            f"{_ENV}=numpy to run on the numpy vkernels."
        ) from _pallas_error
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
        out = getattr(_pallas_mod, kernel)(*args)
    obs.add(f"{_CALLS}{kernel}.{_pallas_mod.platform()}")
    return out


# --------------------------------------------------------------------------
# eligibility registry: bit-identity admission, documented refusals
# --------------------------------------------------------------------------

@dataclass(frozen=True)
class Eligibility:
    eligible: bool
    reason: str


#: keyed ``kernel`` or ``kernel:dtypeclass`` (``int`` covers bool and all
#: integer widths, ``float`` all floats).  A kernel/dtype pair absent
#: from the registry is NOT admitted — numpy serves it.
REGISTRY: Dict[str, Eligibility] = {
    "hash_fixed": Eligibility(True,
        "splitmix64 over bit patterns: wrapping uint64 multiply and "
        "xor-shift are exact on every backend"),
    "combine_hashes": Eligibility(True,
        "ordered uint64 fold, same per-element exactness as hash_fixed"),
    "hash_keys": Eligibility(True,
        "fused per-column mix + ordered combine over fixed-width key "
        "buffers; var-length (offsets, values) keys route to numpy "
        "structurally (not expressible as a dense block kernel)"),
    "hash_join_probe": Eligibility(True,
        "the build sorted by (hash, row id), a lower-bound search and run "
        "length per probe hash: integer compares and counts only, so the "
        "pairs and their order equal the stable argsort's"),
    "filter_join_gather": Eligibility(True,
        "index gather with -1 sentinel passthrough: no arithmetic"),
    "gather_payload": Eligibility(True,
        "payload-column gather with -1 fill: no arithmetic"),
    "grouped_count": Eligibility(True,
        "integer segment count: exact in any accumulation order"),
    "grouped_sum:int": Eligibility(True,
        "integer segment sum: associative and exact, any block order "
        "reproduces reduceat bits"),
    "grouped_sum:float": Eligibility(False,
        "PR 5 contract: float sums accumulate sequentially in original "
        "row order (np.bincount); block-parallel reduction reorders the "
        "additions and changes low-order bits — position-dependent "
        "accumulation must not silently change results"),
    "grouped_min:int": Eligibility(True,
        "integer extremes are order-free"),
    "grouped_min:float": Eligibility(False,
        "-0.0/+0.0 ties and NaN propagation resolve by reduction order; "
        "the contract is reduceat's left-to-right result"),
    "grouped_max:int": Eligibility(True,
        "integer extremes are order-free"),
    "grouped_max:float": Eligibility(False,
        "-0.0/+0.0 ties and NaN propagation resolve by reduction order; "
        "the contract is reduceat's left-to-right result"),
    "grouped_mean": Eligibility(False,
        "composes the float segment sum, inheriting its sequential-"
        "accumulation contract"),
}

def _dtype_class(dt) -> str:
    dt = np.dtype(dt)
    return "float" if np.issubdtype(dt, np.floating) else "int"


def _registry_key(kernel: str, dtype=None) -> str:
    if dtype is not None and f"{kernel}:{_dtype_class(dtype)}" in REGISTRY:
        return f"{kernel}:{_dtype_class(dtype)}"
    return kernel


def eligible(kernel: str, dtype=None) -> bool:
    """Is this kernel (for this value dtype, if reductions) admitted to
    the device path?  False for documented-ineligible entries and
    unknown kernels alike."""
    e = REGISTRY.get(_registry_key(kernel, dtype))
    return bool(e and e.eligible)


# --------------------------------------------------------------------------
# dispatchers (numpy arrays in and out; signatures mirror vkernels)
# --------------------------------------------------------------------------

def _use_pallas(kernel: str, dtype=None) -> bool:
    return active_backend() == "pallas" and eligible(kernel, dtype)


def hash_fixed(values: np.ndarray) -> np.ndarray:
    if _use_pallas("hash_fixed", values.dtype):
        return _on_device("hash_fixed", values)
    return vkernels.hash_fixed(values)


def combine_hashes(col_hashes: Sequence[np.ndarray], n: int) -> np.ndarray:
    if _use_pallas("combine_hashes"):
        return _on_device("combine_hashes", col_hashes, n)
    return vkernels.combine_hashes(col_hashes, n)


def hash_keys(keys: Sequence, n: int) -> np.ndarray:
    # var-length (offsets, values) keys are structurally numpy-only
    if (_use_pallas("hash_keys")
            and not any(isinstance(k, tuple) for k in keys)):
        return _on_device("hash_keys", keys, n)
    return vkernels.hash_keys(list(keys), n)


def hash_join_probe(build_hash: np.ndarray, probe_hash: np.ndarray
                    ) -> Tuple[np.ndarray, np.ndarray]:
    if _use_pallas("hash_join_probe"):
        return _on_device("hash_join_probe", build_hash, probe_hash)
    return vkernels.hash_join_probe(build_hash, probe_hash)


def filter_join_gather(sel: np.ndarray, idx: np.ndarray) -> np.ndarray:
    if _use_pallas("filter_join_gather"):
        return _on_device("filter_join_gather", sel, idx)
    return vkernels.filter_join_gather(sel, idx)


def gather_payload(values: np.ndarray, idx: np.ndarray,
                   fill=0) -> np.ndarray:
    idx = np.ascontiguousarray(idx, dtype=np.int64)
    if _use_pallas("gather_payload", values.dtype):
        return _on_device("gather_payload", values, idx, fill)
    out = np.full(len(idx), fill, dtype=values.dtype)
    hit = idx >= 0
    out[hit] = values[idx[hit]]
    return out


def _r_count(values, order, starts, valid=None):
    if _use_pallas("grouped_count"):
        return _on_device("grouped_count", values, order, starts, valid)
    return vkernels.grouped_count(values, order, starts, valid)


def _r_sum(values, order, starts, valid=None):
    if _use_pallas("grouped_sum", values.dtype):
        return _on_device("grouped_sum", values, order, starts, valid)
    return vkernels.grouped_sum(values, order, starts, valid)


def _r_min(values, order, starts, valid=None):
    if _use_pallas("grouped_min", values.dtype):
        return _on_device("grouped_min", values, order, starts, valid)
    return vkernels.grouped_min(values, order, starts, valid)


def _r_max(values, order, starts, valid=None):
    if _use_pallas("grouped_max", values.dtype):
        return _on_device("grouped_max", values, order, starts, valid)
    return vkernels.grouped_max(values, order, starts, valid)


def _r_mean(values, order, starts, valid=None):
    # documented ineligible: composes the sequential float sum
    return vkernels.grouped_mean(values, order, starts, valid)


#: drop-in for ``vkernels.GROUPED_REDUCERS`` with per-dtype dispatch
GROUPED_REDUCERS = {
    "count": _r_count, "sum": _r_sum, "min": _r_min, "max": _r_max,
    "mean": _r_mean,
}


# --------------------------------------------------------------------------
# fingerprint integration: callable __fp_includes__ for the relational ops
# --------------------------------------------------------------------------

def _backend_tag(name: str) -> None:
    """Inert marker folded into op fingerprints via
    ``functools.partial(_backend_tag, <backend>)`` — the partial's
    argument makes the backends produce distinct cone fingerprints."""


def _tag():
    return functools.partial(_backend_tag, "pallas")


_JOIN_NUMPY = (vkernels.combine_hashes, vkernels.hash_fixed,
               vkernels.hash_var, vkernels.hash_join_probe,
               vkernels.filter_join_gather, vkernels.bytes_rows_equal)
_GROUP_BY_NUMPY = (vkernels.group_ranges, vkernels.grouped_count,
                   vkernels.grouped_sum, vkernels.grouped_min,
                   vkernels.grouped_max, vkernels.grouped_mean,
                   vkernels.dict_encode_var, vkernels.sort_keys_var)


def fp_includes_join():
    """Kernel deps of the join/filter_join ops, evaluated at fingerprint
    time: numpy always; plus the backend tag and live device kernels
    when the pallas backend is active (so backend flips and kernel edits
    invalidate exactly the join cones)."""
    deps = _JOIN_NUMPY
    if active_backend() == "pallas":
        rel = _pallas_mod
        deps = deps + (_tag(), rel.hash_fixed, rel.combine_hashes,
                       rel.hash_join_probe, rel.filter_join_gather)
    return deps


def fp_includes_group_by():
    """Kernel deps of the group_by op (see ``fp_includes_join``)."""
    deps = _GROUP_BY_NUMPY
    if active_backend() == "pallas":
        rel = _pallas_mod
        deps = deps + (_tag(), rel.grouped_count, rel.grouped_sum,
                       rel.grouped_min, rel.grouped_max)
    return deps


# --------------------------------------------------------------------------
# in-process differential: raise on anything whose bits diverge
# --------------------------------------------------------------------------

def self_check(n: int = 4096, n_groups: int = 97) -> Dict[str, str]:
    """Compact differential over adversarial seeded inputs: every
    *eligible* registry entry runs device-vs-numpy and must match bit
    for bit (values and dtypes).  Any mismatch raises ``RuntimeError``
    naming every diverging registry key; otherwise returns ``{key:
    "ok"}`` for the eligible entries and ``{key: "ineligible: <reason>"}``
    for the documented refusals.  The kernels run on jax's default
    device, so on the chip this checks the compiled TPU programs."""
    if _pallas() is None:
        raise RuntimeError(
            f"self_check needs the device kernels; import failed with "
            f"{_pallas_error!r} (see {_ENV})") from _pallas_error
    rel = _pallas_mod
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
    codes = rng.integers(0, n_groups, n)
    order, starts = vkernels.group_ranges([codes])
    sel = np.nonzero(rng.random(n // 2) < 0.5)[0]
    idx = rng.integers(-1, len(sel), n).astype(np.int64)
    pidx = rng.integers(-1, n, n).astype(np.int64)
    results: Dict[str, str] = {}

    def check(key: str, got, want) -> None:
        gv, gc = got if isinstance(got, tuple) else (got, None)
        wv, wc = want if isinstance(want, tuple) else (want, None)
        same = (gv.dtype == wv.dtype
                and np.array_equal(gv, wv, equal_nan=True)
                and (gc is None or (gc.dtype == wc.dtype
                                    and np.array_equal(gc, wc))))
        if not same:
            results[key] = (f"bit mismatch vs numpy "
                            f"({gv.dtype} vs {wv.dtype})")
        results.setdefault(key, "ok")
    for v in cols.values():
        check("hash_fixed", rel.hash_fixed(v), vkernels.hash_fixed(v))
    hs = [vkernels.hash_fixed(cols["int64"]),
          vkernels.hash_fixed(cols["float64"]),
          vkernels.hash_fixed(cols["uint64"])]
    check("combine_hashes", rel.combine_hashes(hs, n),
          vkernels.combine_hashes(hs, n))
    ks = [cols["int64"], cols["float64"], cols["int32"]]
    check("hash_keys", rel.hash_keys(ks, n), vkernels.hash_keys(ks, n))
    # many-to-many runs, and the largest and smallest hashes against a
    # padded build that holds the largest too
    bh = rng.integers(0, 400, n // 3).astype(np.uint64)
    bh[:3] = [0, np.iinfo(np.uint64).max, np.iinfo(np.uint64).max]
    ph = rng.integers(0, 600, n).astype(np.uint64)
    ph[:2] = [0, np.iinfo(np.uint64).max]
    check("hash_join_probe", rel.hash_join_probe(bh, ph),
          vkernels.hash_join_probe(bh, ph))
    check("filter_join_gather", rel.filter_join_gather(sel, idx),
          vkernels.filter_join_gather(sel, idx))
    check("gather_payload", rel.gather_payload(cols["int64"], pidx, 0),
          np.where(pidx >= 0, cols["int64"][np.where(pidx >= 0, pidx, 0)],
                   0))
    check("grouped_count",
          rel.grouped_count(cols["int64"], order, starts, valid),
          vkernels.grouped_count(cols["int64"], order, starts, valid))
    for name in ("int32", "int64", "uint64", "bool"):
        v = cols[name]
        check("grouped_sum:int", rel.grouped_sum(v, order, starts, valid),
              vkernels.grouped_sum(v, order, starts, valid))
        check("grouped_min:int", rel.grouped_min(v, order, starts, valid),
              vkernels.grouped_min(v, order, starts, valid))
        check("grouped_max:int", rel.grouped_max(v, order, starts, valid),
              vkernels.grouped_max(v, order, starts, valid))
    bad = {k: v for k, v in results.items() if v != "ok"}
    if bad:
        raise RuntimeError(f"device kernels lost bit-identity with the "
                           f"numpy reference: {bad}")
    for key, e in REGISTRY.items():
        if not e.eligible:
            results[key] = f"ineligible: {e.reason}"
    return results
