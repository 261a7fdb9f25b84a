"""Public wrappers for the Pallas kernels.

``interpret`` resolves *per call* (not at trace time):
``ZERROW_PALLAS_INTERPRET=0/1`` is the explicit, tested override;
without it, kernels interpret only on the CPU backend.
The wrappers here are plain functions that resolve the mode and pass it
as a static argument into the jitted implementations, so flipping the
env var between calls takes effect immediately (a mode baked into a jit
trace would be stale).  Every wrapper has a pure-jnp oracle in ref.py;
tests sweep shapes/dtypes and assert allclose against it.

The gather wrappers additionally validate on the host: out-of-range
indices/codes raise ``IndexError`` (a gather must never silently wrap
or clamp — that is how a wrong row ships), and zero-row inputs return
empty outputs without launching a kernel.
"""

from __future__ import annotations

import functools
import os

try:
    import jax
    import jax.numpy as jnp
except ImportError as e:                                # pragma: no cover
    raise ImportError(
        "repro.kernels requires jax (with Pallas), which is not "
        "importable here."
    ) from e

import numpy as np

from .flash_attention import flash_attention as _flash
from .rglru_scan import rglru_pallas as _rglru
from .take_gather import dict_decode as _dict_decode
from .take_gather import take_rows as _take_rows
from .wkv6 import wkv6_pallas as _wkv6

_INTERPRET_ENV = "ZERROW_PALLAS_INTERPRET"


def on_tpu() -> bool:
    """True when jax's default backend is a real TPU."""
    return jax.default_backend() == "tpu"


def default_interpret() -> bool:
    """Should Pallas kernels run in interpret mode?  The explicit env
    override ``ZERROW_PALLAS_INTERPRET=1`` (force interpret) / ``0``
    (force compiled) wins and is read per call, so tests and CI lanes
    can flip it without reimporting; any other value raises.  Without
    the override: interpret only on the CPU backend, which cannot run a
    compiled Pallas kernel — any accelerator compiles, and a kernel it
    refuses fails loudly instead of falling back to the interpreter."""
    v = os.environ.get(_INTERPRET_ENV)
    if v:
        if v not in ("0", "1"):
            raise ValueError(
                f"{_INTERPRET_ENV}={v!r}: use '1' (force interpret "
                "mode) or '0' (force compiled)")
        return v == "1"
    return jax.default_backend() == "cpu"


@functools.partial(jax.jit, static_argnames=("causal", "window", "bq",
                                             "bk", "interpret"))
def _flash_jit(q, k, v, *, causal, window, bq, bk, interpret):
    return _flash(q, k, v, causal=causal, window=window, bq=bq, bk=bk,
                  interpret=interpret)


def flash_attention(q, k, v, *, causal: bool = True, window: int = 0,
                    bq: int = 128, bk: int = 128):
    return _flash_jit(q, k, v, causal=causal, window=window, bq=bq,
                      bk=bk, interpret=default_interpret())


@functools.partial(jax.jit, static_argnames=("chunk", "bw", "interpret"))
def _rglru_jit(a, b, h0, *, chunk, bw, interpret):
    return _rglru(a, b, h0, chunk=chunk, bw=bw, interpret=interpret)


def rglru_scan(a, b, h0=None, *, chunk: int = 256, bw: int = 512):
    return _rglru_jit(a, b, h0, chunk=chunk, bw=bw,
                      interpret=default_interpret())


@functools.partial(jax.jit, static_argnames=("chunk", "interpret"))
def _wkv6_jit(r, k, v, w, u, state, *, chunk, interpret):
    return _wkv6(r, k, v, w, u, state, chunk=chunk, interpret=interpret)


def wkv6(r, k, v, w, u, state=None, *, chunk: int = 16):
    return _wkv6_jit(r, k, v, w, u, state, chunk=chunk,
                     interpret=default_interpret())


def _check_gather_domain(what: str, idx, n_rows: int) -> None:
    """Host-side gather validation: a Pallas index map silently wraps or
    clamps out-of-range block indices, so reject them *before* launch."""
    idx = np.asarray(idx)
    if len(idx) == 0:
        return
    lo, hi = int(idx.min()), int(idx.max())
    if lo < 0 or hi >= n_rows:
        bad = lo if lo < 0 else hi
        raise IndexError(
            f"{what}: index {bad} out of range for {n_rows} rows")


@functools.partial(jax.jit, static_argnames=("interpret",))
def _take_rows_jit(values, indices, *, interpret):
    return _take_rows(values, indices, interpret=interpret)


def take_rows(values, indices):
    """out[i] = values[indices[i]] — (R, W) x (M,) -> (M, W); empty
    index arrays return an empty gather, out-of-range indices raise."""
    _check_gather_domain("take_rows", indices, values.shape[0])
    if np.asarray(indices).shape[0] == 0:
        return jnp.empty((0, values.shape[1]), dtype=values.dtype)
    return _take_rows_jit(values, indices,
                          interpret=default_interpret())


@functools.partial(jax.jit, static_argnames=("bm", "interpret"))
def _dict_decode_jit(codes, dictionary, *, bm, interpret):
    return _dict_decode(codes, dictionary, bm=bm, interpret=interpret)


def dict_decode(codes, dictionary, *, bm: int = 256):
    """out[i] = dictionary[codes[i]] — (M,) x (R, W) -> (M, W).  Codes
    are validated on the host (out-of-range raises ``IndexError``; the
    one-hot matmul would silently decode garbage otherwise), zero codes
    return an empty decode, and M is padded up to the block size so any
    length works (the kernel requires bm | M; the pad rows decode row 0
    and are sliced off)."""
    _check_gather_domain("dict_decode", codes, dictionary.shape[0])
    M = np.asarray(codes).shape[0]
    if M == 0:
        return jnp.empty((0, dictionary.shape[1]),
                         dtype=dictionary.dtype)
    bm = min(bm, M)
    pad = -M % bm
    if pad:
        codes = jnp.concatenate(
            [jnp.asarray(codes),
             jnp.zeros(pad, dtype=jnp.asarray(codes).dtype)])
    out = _dict_decode_jit(codes, dictionary, bm=bm,
                           interpret=default_interpret())
    return out[:M] if pad else out
