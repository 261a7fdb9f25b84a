"""Device-resident relational kernels: the eligible ``core/vkernels.py``
hot paths as jitted XLA programs on jax's default device.

The device backend of ``core/kdispatch.py`` routes the join/group-by
hot path here, so it runs on the accelerator instead of host numpy.
Every kernel is held to a **bit-identity** contract against its numpy
reference — admission is decided by the differential harness in
``tests/test_pallas_relational.py`` and recorded in
``core.kdispatch.KERNELS``; anything that cannot reproduce the numpy
bits exactly (float segment sums: PR 5's sequential-accumulation
contract) stays on numpy *by the table's admission rule*, never
silently.

Why XLA and not Pallas: every kernel here works in 64-bit lanes
(splitmix64 over uint64, int64 gather indices, int64 accumulators), and
Mosaic, the TPU's Pallas compiler, has no tiling for 64-bit block
dtypes; the gathers also read data-dependent rows from a source far
larger than VMEM.  XLA emulates 64-bit integers on the TPU and gathers
from HBM, so the jitted forms below compile for the chip
(``tests/test_tpu_compile.py``) and run there.

Each kernel has two parts:

  * a *core* (``_hash_core``, ``_combine_core``, ``_gather_core``,
    ``_probe_count_core``, ``_probe_expand_core``, ``_segreduce_core``):
    a jittable function of arrays and static options, which the compile
    tests lower from shapes alone;
  * a numpy *edge* (the public functions, signatures mirroring
    ``core/vkernels.py``): it views the inputs' raw bits, pads them to a
    bucketed length (``_bucket``) so a new row count rarely means a new
    compile, runs the jitted core under scoped 64-bit mode, and returns
    numpy arrays.  The call (``_call``) is three blocked steps, each a
    span (``core.obs``): ``kernel.put`` copies the padded arguments to
    the device, ``kernel.run`` runs the program, ``kernel.fetch`` copies
    the outputs back; the bytes that cross are counted in
    ``kernel.h2d_bytes`` and ``kernel.d2h_bytes``.  The join probe's
    edge alone runs two programs and leaves the first one's outputs on
    the device for the second (``_run`` without ``_fetch``).

Ported kernels:

  ``hash_fixed(v)``        splitmix64 over the bit patterns of a
      fixed-width array (float -0.0 canonicalized, as numpy does).
  ``combine_hashes(hs,n)`` order-sensitive fold of per-column uint64
      hashes into one row hash (the representation-free combiner).
  ``filter_join_gather(sel, idx)``  compose a filter selection with join
      gather indices, ``-1`` miss sentinels preserved.
  ``hash_join_probe(build_hash, probe_hash)``  equal-hash candidate
      pairs, probe-major with build rows ascending, in two stages:
      ``_probe_count_core`` sorts the build by (hash, row id), finds
      each probe's lower bound (``_lower_bound``: one broadcast compare,
      then a gathered row of 64 pivots for each six further bits) and
      its run length, and returns the pair count, the one value fetched;
      ``_probe_expand_core``, sized by ``_bucket(count)``, writes the
      pairs.
  ``grouped_count / grouped_sum / grouped_min / grouped_max(values,
  order, starts, valid=None)``  segment reducers over ``group_ranges``
      boundaries, with no segment ids and no search: rows are gathered
      into group order, so each group is a contiguous run ending where
      the next starts.  A sum or a count is the difference of an
      exclusive prefix sum at the group's two ends, with no scatter —
      exact in wrapping 64-bit arithmetic, so bit-identical to
      ``reduceat`` even where the prefix itself overflows; min/max is a
      scan segmented by head flags set at the starts, read at each
      group's last row.  Integer/bool reductions hold bit-identity;
      float sum/min/max are order- or tie-sensitive and the kernel
      table refuses them.

The cores are plain module functions, jitted at the call (jax reuses the
compiled program for the same function, options and shapes) rather than
held as module-level jit objects, so ``core.fingerprint``'s direct-
global scan sees their code: editing a kernel here invalidates every
cached join/group-by cone computed under the device backend.
"""

from __future__ import annotations

from typing import Sequence, Tuple

import numpy as np

import jax
import jax.numpy as jnp
from jax import lax

from repro.compat import enable_x64
from repro.core import obs, vkernels

__all__ = [
    "hash_fixed", "combine_hashes", "filter_join_gather", "hash_join_probe",
    "grouped_count", "grouped_sum", "grouped_min", "grouped_max",
    "platform",
]

_GOLDEN = np.uint64(0x9E3779B97F4A7C15)

#: smallest padded length: tiny inputs share one compiled shape
_MIN_ROWS = 1024

obs.watch_compiles(jax.monitoring)


def platform() -> str:
    """Platform of the device the kernels run on (jax's default)."""
    return jax.devices()[0].platform


# --------------------------------------------------------------------------
# the numpy edge: bucketed padding and the jitted call
# --------------------------------------------------------------------------

def _bucket(n: int) -> int:
    """Padded length for ``n`` rows: at least ``_MIN_ROWS``, otherwise
    ``n`` rounded up to its three leading bits — padding wastes at most
    an eighth, and row counts share a few compiled shapes."""
    n = max(n, _MIN_ROWS)
    q = 1 << (n.bit_length() - 3)
    return -(-n // q) * q


def _pad(a: np.ndarray, size: int, fill) -> np.ndarray:
    """``a`` extended with ``fill`` to ``size`` rows."""
    if len(a) == size:
        return a
    out = np.full(size, fill, dtype=a.dtype)
    out[:len(a)] = a
    return out


def _nbytes(tree) -> int:
    """Bytes of the host arrays in ``tree`` (device arrays do not cross)."""
    return sum(x.nbytes for x in jax.tree.leaves(tree)
               if not isinstance(x, jax.Array))


def _run(core, args, static):
    """``jax.jit(core)(*args, **static)`` on the default device under
    64-bit mode, outputs left there.  Host arguments are copied in;
    device arguments (an earlier ``_run``'s outputs) stay where they
    are.  The copy in and the run are each waited for, so each span
    holds its own step's time."""
    with enable_x64():
        obs.add("kernel.h2d_bytes", _nbytes(args))
        with obs.span("kernel.put"):
            args = jax.block_until_ready(jax.device_put(args))
        with obs.span("kernel.run"):
            return jax.block_until_ready(
                jax.jit(core, static_argnames=tuple(static))(*args,
                                                             **static))


def _fetch(out):
    """Device outputs copied back as numpy arrays."""
    with obs.span("kernel.fetch"):
        out = jax.tree.map(np.asarray, out)
    obs.add("kernel.d2h_bytes", _nbytes(out))
    return out


def _call(core, args, static):
    """``_run``, then every output copied back."""
    return _fetch(_run(core, args, static))


def _bits(values: np.ndarray) -> Tuple[np.ndarray, int]:
    """(unsigned view of the raw bits, the float sign-bit pattern or 0):
    the representation half of ``vkernels.hash_fixed``'s prep — the
    -0.0 canonicalization and the widening run in ``_hash_core``."""
    values = np.ascontiguousarray(values)
    w = values.dtype.itemsize
    sign = 1 << (8 * w - 1) if np.issubdtype(values.dtype, np.floating) \
        else 0
    return values.view(f"u{w}"), sign


# --------------------------------------------------------------------------
# splitmix64: hash_fixed / combine_hashes
# --------------------------------------------------------------------------

def _mix64(h):
    """splitmix64 finalizer over a uint64 array — the same constants and
    operation order as ``vkernels._mix64`` (wrapping uint64 multiply and
    xor-shift are exact on any backend, so bits agree)."""
    h = h ^ (h >> jnp.uint64(30))
    h = h * jnp.uint64(0xBF58476D1CE4E5B9)
    h = h ^ (h >> jnp.uint64(27))
    h = h * jnp.uint64(0x94D049BB133111EB)
    return h ^ (h >> jnp.uint64(31))


def _hash_core(bits, *, sign: int):
    """splitmix64 of each element's bits.  ``sign`` is the float
    sign-bit pattern (the bits of -0.0, which hash as +0.0) or 0 for
    integer bits; narrower bits zero-extend to uint64."""
    if sign:
        bits = jnp.where(bits == bits.dtype.type(sign),
                         jnp.zeros_like(bits), bits)
    return _mix64(bits.astype(jnp.uint64) ^ jnp.uint64(_GOLDEN))


def _combine_core(hashes):
    """Ordered fold of a tuple of uint64 column hashes into row hashes."""
    h = jnp.full(hashes[0].shape, _GOLDEN, jnp.uint64)
    for hk in hashes:
        h = _mix64(h * jnp.uint64(_GOLDEN) ^ hk)
    return h


def hash_fixed(values: np.ndarray) -> np.ndarray:
    """uint64 splitmix64 hash per element of a fixed-width array —
    bit-identical to ``vkernels.hash_fixed``."""
    bits, sign = _bits(values)
    n = len(bits)
    if n == 0:
        return np.empty(0, dtype=np.uint64)
    return _call(_hash_core, (_pad(bits, _bucket(n), 0),),
                 {"sign": sign})[:n]


def combine_hashes(col_hashes: Sequence[np.ndarray], n: int) -> np.ndarray:
    """Fold per-column uint64 hash arrays into one row hash —
    bit-identical to ``vkernels.combine_hashes`` (order-sensitive)."""
    if not col_hashes or n == 0:
        return vkernels.combine_hashes(col_hashes, n)
    size = _bucket(n)
    hs = tuple(_pad(np.asarray(h, dtype=np.uint64), size, 0)
               for h in col_hashes)
    return _call(_combine_core, (hs,), {})[:n]


# --------------------------------------------------------------------------
# gather: filter->join index composition
# --------------------------------------------------------------------------

def _gather_core(src, idx, fill):
    """``out[i] = src[idx[i]]``, with ``fill`` where ``idx[i] < 0``."""
    hit = idx >= 0
    g = jnp.take(src, jnp.where(hit, idx, 0), axis=0, mode="clip")
    return jnp.where(hit, g, fill)


def filter_join_gather(sel: np.ndarray, idx: np.ndarray) -> np.ndarray:
    """Compose a filter's row selection with a join's gather indices,
    ``-1`` left-join miss sentinels preserved — bit-identical to
    ``vkernels.filter_join_gather``."""
    sel = np.asarray(sel, dtype=np.int64)
    m = len(idx)
    if m == 0:
        return np.empty(0, dtype=np.int64)
    if len(sel) == 0:
        # every index must be a -1 miss sentinel; nothing to gather
        return np.full(m, -1, dtype=np.int64)
    return _call(_gather_core,
                 (_pad(sel, _bucket(len(sel)), 0),
                  _pad(np.asarray(idx, dtype=np.int64), _bucket(m), -1),
                  np.asarray(-1, dtype=np.int64)), {})[:m]


# --------------------------------------------------------------------------
# segment reducers over group_ranges boundaries
# --------------------------------------------------------------------------

def _scan(combine, xs, identity):
    """Inclusive scan of the row-aligned arrays ``xs`` under
    ``combine(earlier, later)``, by doubling: pass k combines every row
    with the partial result ``2**k`` rows before it (``identity`` before
    row 0), so ``ceil(log2(rows))`` elementwise passes and no loop on the
    device.  ``lax.associative_scan`` and ``jnp.cumsum`` do less work,
    but the TPU compiler's time grows with their length (about two
    minutes for 2**20 int64 rows, compiling for a v5e on an 8-core x86
    host); these shifted passes compile in seconds at any length."""
    rows, k = xs[0].shape[0], 1
    while k < rows:
        before = tuple(lax.pad(x[:-k], jnp.asarray(f, x.dtype), [(k, 0, 0)])
                       for x, f in zip(xs, identity))
        xs = combine(before, xs)
        k *= 2
    return xs


def _take(x, idx):
    """``x[idx]`` for an integer or bool ``x``, gathered as 64-bit
    rows of two 32-bit lanes: the TPU gathers those about twice as fast
    as single 32-bit or smaller elements and five times as fast as
    single 64-bit ones (28.5 ms, 47–62 ms and 139 ms for 6.29M rows on a
    v5e).  Narrower values widen first and narrow back, exactly."""
    wide = jnp.uint64 if x.dtype == jnp.uint64 else jnp.int64
    lanes = lax.bitcast_convert_type(x.astype(wide), jnp.uint32)
    return lax.bitcast_convert_type(lanes[idx], wide).astype(x.dtype)


def _prefix_diff(x, starts, ends):
    """``x[starts[g]:ends[g]].sum()`` for every group g, as the
    difference of the exclusive prefix sum at the two ends.  Integer
    addition wraps modulo 2**64, and the difference of two wrapped
    prefixes is the group's own total modulo 2**64: the same bits as a
    row-by-row wrapping sum, however far the prefix overflows."""
    (cs,) = _scan(lambda a, b: (a[0] + b[0],), (x,), (0,))
    cs = jnp.concatenate([jnp.zeros(1, x.dtype), cs])
    return _take(cs, ends) - _take(cs, starts)


def _segreduce_core(values, order, starts, valid, n, *, op: str):
    """(reduced, counts) per group.  ``order``/``starts`` are
    ``vkernels.group_ranges`` output and ``values``/``valid`` are in
    original row order, all padded: ``values[order]`` lays each group
    out as the run ``starts[g]:ends[g]``, ``ends`` being the next
    group's start and ``n`` for the last.  Padding groups start at
    ``n``, so they end there too and are empty; sorted positions
    ``>= n`` are padding and lie past every end.  ``valid=None`` means
    no nulls; ``values`` is unused (None) for a count.

    sum and count are prefix differences (``_prefix_diff``): exact in
    wrapping arithmetic, with no data-dependent loop and no scatter.
    min/max is a scan over (group head, value) that restarts at each
    head, read at each group's last row; nulls enter as the dtype's
    extreme, so all-null groups reduce to it, as ``reduceat`` over
    sentinel-filled rows does in numpy."""
    rows = order.shape[0]
    ends = jnp.concatenate([starts[1:], n[None]])
    w = None if valid is None else _take(valid, order)
    counts = ends - starts if w is None else _prefix_diff(
        w.astype(jnp.int64), starts, ends)
    if op == "count":
        return counts, counts
    if op == "sum":
        v = _take(values.astype(jnp.uint64 if values.dtype == jnp.uint64
                                else jnp.int64), order)
        if w is not None:
            v = jnp.where(w, v, jnp.zeros_like(v))
        return _prefix_diff(v, starts, ends), counts
    v = _take(values, order)
    info = jnp.iinfo(v.dtype)
    extreme = info.max if op == "min" else info.min
    if w is not None:
        v = jnp.where(w, v, jnp.full_like(v, extreme))
    pick = jnp.minimum if op == "min" else jnp.maximum
    head = jnp.zeros(rows, jnp.bool_).at[starts].set(
        True, mode="drop", indices_are_sorted=True)

    def combine(a, b):
        (ha, va), (hb, vb) = a, b
        return ha | hb, jnp.where(hb, vb, pick(va, vb))

    _, run = _scan(combine, (head, v), (False, extreme))
    return _take(run, ends - 1), counts


def _segreduce(op: str, values, order: np.ndarray, starts: np.ndarray,
               valid) -> Tuple[np.ndarray, np.ndarray]:
    """The reducers' shared edge: pad to bucketed row and group counts,
    run ``_segreduce_core``, slice the real groups."""
    n, g = len(order), len(starts)
    rows, groups = _bucket(n), _bucket(g)
    vals, counts = _call(_segreduce_core, (
        None if values is None else _pad(values, rows, 0),
        _pad(np.asarray(order, dtype=np.int64), rows, 0),
        _pad(np.asarray(starts, dtype=np.int64), groups, n),
        None if valid is None else _pad(np.asarray(valid, dtype=bool),
                                        rows, False),
        np.int64(n)), {"op": op})
    return vals[:g], counts[:g]


def grouped_count(values: np.ndarray, order: np.ndarray,
                  starts: np.ndarray, valid=None
                  ) -> Tuple[np.ndarray, np.ndarray]:
    """Per-group count of non-null rows — bit-identical to
    ``vkernels.grouped_count`` (``values`` ignored, uniform reducer
    signature)."""
    if len(starts) == 0:
        counts = np.empty(0, np.int64)
        return counts, counts
    _, counts = _segreduce("count", None, order, starts, valid)
    return counts, counts


def grouped_sum(values: np.ndarray, order: np.ndarray,
                starts: np.ndarray, valid=None
                ) -> Tuple[np.ndarray, np.ndarray]:
    """Per-group sum over non-null rows, integer/bool inputs only —
    a prefix-sum difference at the group's ends, exact in wrapping
    int64/uint64 arithmetic, so it holds bit-identity with
    ``vkernels.grouped_sum``'s ``reduceat`` even past overflow.  Float
    inputs are rejected: PR 5's contract accumulates float sums
    sequentially in original row order (``np.bincount``), which a
    parallel reduction cannot reproduce bit-for-bit — the kernel table's
    admission rule keeps them on numpy."""
    if not (values.dtype == np.bool_
            or np.issubdtype(values.dtype, np.integer)):
        raise TypeError(
            f"grouped_sum (device) is integer/bool only; {values.dtype} "
            "sums are order-sensitive and stay on numpy")
    if len(starts) == 0:
        acc = np.uint64 if values.dtype == np.uint64 else np.int64
        return np.empty(0, acc), np.empty(0, np.int64)
    return _segreduce("sum", _int_view(values), order, starts, valid)


def _int_view(values: np.ndarray) -> np.ndarray:
    """Bools as uint8 (vkernels reduces them so), integers unchanged."""
    return values.view(np.uint8) if values.dtype == np.bool_ else values


def _grouped_extreme(op, values, order, starts, valid):
    if np.issubdtype(values.dtype, np.floating):
        raise TypeError(
            f"grouped_{op} (device) is integer/bool only; float "
            "-0.0/NaN tie-breaking is order-sensitive and stays on "
            "numpy")
    v = _int_view(values)
    if len(starts) == 0:
        return np.empty(0, v.dtype), np.empty(0, np.int64)
    return _segreduce(op, v, order, starts, valid)


def grouped_min(values, order, starts, valid=None):
    """Per-group min over non-null rows (integer/bool) — bit-identical
    to ``vkernels.grouped_min`` including the all-null sentinel."""
    return _grouped_extreme("min", values, order, starts, valid)


def grouped_max(values, order, starts, valid=None):
    """Per-group max over non-null rows (integer/bool) — bit-identical
    to ``vkernels.grouped_max`` including the all-null sentinel."""
    return _grouped_extreme("max", values, order, starts, valid)


# --------------------------------------------------------------------------
# join probe: the build side sorted on the device, every probe searched
# --------------------------------------------------------------------------

#: keys a row level of ``_lower_bound`` compares at once: 64 uint64
#: keys are one gathered row of 128 32-bit lanes
_FAN = 64
#: bits of a lower bound that its first level resolves with no gather,
#: by one broadcast compare against ``2**_TOP_BITS - 1`` pivots at most
_TOP_BITS = 10


def _halves(x):
    """(high, low) uint32 halves of a 64-bit array, not negative if
    signed: compared high first, they order as ``x`` does."""
    x = x.astype(jnp.uint64)
    return (x >> jnp.uint64(32)).astype(jnp.uint32), x.astype(jnp.uint32)


def _less(ah, al, qh, ql):
    return (ah < qh) | ((ah == qh) & (al < ql))


def _count(flags):
    """True flags per row of a 2-D bool array, as int64 (summed in 32
    bits: 64-bit arithmetic over a 2-D array is slow to compile)."""
    return flags.sum(1, dtype=jnp.int32).astype(jnp.int64)


def _lower_bound(a, n, q):
    """``(lo, hit)`` per query ``q[i]``: ``lo`` the number of ``a[:n]``
    below it, ``hit`` whether ``a[lo] == q[i]`` with ``lo < n``.
    ``a[:n]`` is sorted ascending; ``a`` and ``q`` are 64-bit, not
    negative if signed, and ``len(a) >= 1024``.

    Binary lifting with its steps grouped, and no ``while``: the first
    level resolves up to ``_TOP_BITS`` bits of ``lo`` by one broadcast
    compare against evenly spaced pivots; each further level gathers the
    ``_FAN`` pivots of the query's window as one row of 128 32-bit
    lanes, their high halves then their low halves, and counts those
    below the query, resolving six more bits.  All 2-D work is on 32-bit
    halves: 64-bit arithmetic over a 2-D array, or a gathered row
    reshaped into (key, half) pairs, takes the TPU compiler over a
    minute (compiling for a v5e on an 8-core x86 host).  The gathered rows are the program's largest buffer, 512
    bytes a query.  The last level's row holds ``a[lo]``, which gives
    ``hit``."""
    rows = a.shape[0]
    k = rows.bit_length()                  # 2**k > rows >= n: lo < 2**k
    bits = _FAN.bit_length() - 1
    width = 1 << bits * max(1, -(-(k - _TOP_BITS) // bits))
    ah, al = _halves(jnp.concatenate([a, jnp.zeros((1 << k) - rows,
                                                   a.dtype)]))
    qh, ql = _halves(q)
    top = (1 << k) // width
    ph, pl = (x.reshape(top, width)[:top - 1, width - 1] for x in (ah, al))
    real = jnp.arange(1, top, dtype=jnp.int64) * width <= n
    lo = _count(_less(ph, pl, qh[:, None], ql[:, None]) & real) * width
    c = lax.iota(jnp.int32, _FAN) + 1
    while True:
        # pivot c (from 1) of the window at lo: a[lo + c * step - 1]
        step = width // _FAN
        table = jnp.concatenate([x.reshape(-1, _FAN, step)[:, :, step - 1]
                                 for x in (ah, al)], 1)
        row = table[(lo // width).astype(jnp.int32)]
        rh, rl = row[:, :_FAN], row[:, _FAN:]
        real = c <= jnp.clip((n - lo) // step, 0, _FAN).astype(
            jnp.int32)[:, None]
        lo = lo + _count(_less(rh, rl, qh[:, None], ql[:, None])
                         & real) * step
        if step == 1:
            # a[lo], when it is real, lies in this row
            hit = ((rh == qh[:, None]) & (rl == ql[:, None]) & real).any(1)
            return lo, hit
        width = step


def _probe_count_core(build, probe, nb, npr):
    """Stage 1 of the probe: ``(total, ends, lo, order)``.  ``build`` and
    ``probe`` are padded uint64 hashes with real lengths ``nb`` and
    ``npr``.  The build sorts with its row ids, real rows first (padding
    takes the largest key and sorts after every real row, so the search,
    bounded by ``nb``, never reaches it whatever its value).  ``lo`` is
    each probe's lower bound in the sorted build, ``ends`` the inclusive
    prefix sum of each probe's equal-hash run length (0 for a miss and
    for a padding probe), ``total`` its last element: the pair count."""
    rows = build.shape[0]
    ids = lax.iota(jnp.int64, rows)
    keys, order = lax.sort(
        (jnp.where(ids < nb, build,
                   jnp.full_like(build, jnp.iinfo(build.dtype).max)), ids),
        num_keys=1, is_stable=True)
    # each sorted position's distance to the end of its equal-hash run:
    # a min-scan from the right over the runs' last positions
    last = (ids >= nb - 1) | (keys != jnp.roll(keys, -1))
    (end,) = _scan(lambda a, b: (jnp.minimum(a[0], b[0]),),
                   (jnp.where(last, ids, rows)[::-1],), (rows,))
    run = end[::-1] - ids + 1
    lo, hit = _lower_bound(keys, nb, probe)
    hit &= lax.iota(jnp.int64, probe.shape[0]) < npr
    (ends,) = _scan(lambda a, b: (a[0] + b[0],),
                    (jnp.where(hit, _take(run, jnp.minimum(lo, rows - 1)),
                               0),), (0,))
    return ends[-1], ends, lo, order


def _probe_expand_core(ends, lo, order, *, size: int):
    """Stage 2: the pairs ``k < size``.  Pair k belongs to the probe
    whose run of ``ends`` covers it, found by ``_lower_bound``, and
    takes the build row ``order[lo + k - start]``.  Rows past the pair
    count are sliced off by the edge."""
    probes = ends.shape[0]
    k = lax.iota(jnp.int64, size)
    p = jnp.minimum(_lower_bound(ends, probes, k + 1)[0], probes - 1)
    start = jnp.where(p > 0, _take(ends, jnp.maximum(p - 1, 0)), 0)
    pos = _take(lo, p) + k - start
    return p, _take(order, jnp.minimum(pos, order.shape[0] - 1))


def hash_join_probe(build_hash: np.ndarray, probe_hash: np.ndarray
                    ) -> Tuple[np.ndarray, np.ndarray]:
    """Hash-equality candidate pairs ``(probe_idx, build_idx)``, int64,
    probe-major with build indices ascending within each probe row —
    bit-identical to ``vkernels.hash_join_probe``, duplicates and 64-bit
    collisions included.  Two programs: ``_probe_count_core`` sorts the
    build and searches every probe, and only its pair count comes back;
    ``_probe_expand_core``, sized by that count, writes the pairs.  The
    first program's per-probe arrays stay on the device between them."""
    nb, npr = len(build_hash), len(probe_hash)
    if nb == 0 or npr == 0:
        return np.empty(0, np.int64), np.empty(0, np.int64)
    total, *kept = _run(_probe_count_core, (
        _pad(np.asarray(build_hash, dtype=np.uint64), _bucket(nb), 0),
        _pad(np.asarray(probe_hash, dtype=np.uint64), _bucket(npr), 0),
        np.int64(nb), np.int64(npr)), {})
    total = int(_fetch(total))
    if total == 0:
        return np.empty(0, np.int64), np.empty(0, np.int64)
    pi, bi = _call(_probe_expand_core, tuple(kept),
                   {"size": _bucket(total)})
    return pi[:total], bi[:total]
