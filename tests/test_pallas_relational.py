"""Admission harness for the device relational kernels.

Admission to the device backend's kernel table (``kdispatch.KERNELS``)
is decided here: every kernel in ``repro.kernels.relational`` must be
**bit-identical** (values AND dtypes) to its ``core/vkernels.py`` numpy
reference, which must itself match a per-row naive Python reference —
across dtype mixes (int32/int64/uint64/float64 including -0.0 and NaN),
null fractions, duplicate-heavy keys, zero-row and empty(all-null)-group
inputs.  Every kernel of the table is dispatched under both backends;
the arguments its admission rule refuses (the order-sensitive float
reductions) are asserted to *stay* on the numpy path with the device
backend active, ``self_check`` is proven to raise on a kernel whose bits
regress, and a backend that cannot load raises instead of serving
numpy.  The fingerprint section pins the cache contract: flipping the
backend or editing a device kernel invalidates exactly the
join/group-by cones (manifest adoption counts asserted, mirroring the
relational ops' ``__fp_includes__`` tests).

The kernels run on jax's default device: the CPU under
``JAX_PLATFORMS=cpu`` here, the TPU on the chip.  The seeded-numpy sweeps
always run, and a hypothesis property (stress lane) widens the input
space when hypothesis is installed.
"""
import functools
import os

import numpy as np
import pytest

pytest.importorskip("jax", reason="pallas kernels need jax")

from repro.core import (BufferStore, DAG, Executor, NodeSpec, RMConfig,
                        ResourceManager, SipcReader, code_fingerprint)
from repro.core import fingerprint, kdispatch as kd, ops, vkernels, zarquet
from repro.core.arrow import Column, Table, pack_validity
from repro.kernels import relational as rel

try:
    from hypothesis import given, settings, strategies as st
    HAVE_HYPOTHESIS = True
except ImportError:
    HAVE_HYPOTHESIS = False

_M64 = (1 << 64) - 1


@pytest.fixture
def pallas_backend(monkeypatch):
    monkeypatch.setenv(kd.ENV, kd.DEVICE_BACKEND)


# ---------------------------------------------------------------------------
# per-row naive references (pure Python ints — no numpy accumulation)
# ---------------------------------------------------------------------------

def _py_mix64(h: int) -> int:
    h ^= h >> 30
    h = (h * 0xBF58476D1CE4E5B9) & _M64
    h ^= h >> 27
    h = (h * 0x94D049BB133111EB) & _M64
    return h ^ (h >> 31)


def _py_bits(v, dtype) -> int:
    if np.issubdtype(dtype, np.floating):
        if v == 0:
            v = abs(v) * 0            # -0.0 canonicalizes to +0.0
        return int(np.asarray(v, dtype).view(f"u{np.dtype(dtype).itemsize}"))
    return int(np.asarray(v, dtype).view(f"u{np.dtype(dtype).itemsize}"))


def py_hash_fixed(values: np.ndarray) -> np.ndarray:
    g = 0x9E3779B97F4A7C15
    return np.array([_py_mix64(_py_bits(v, values.dtype) ^ g)
                     for v in values], dtype=np.uint64)


def py_combine(col_hashes, n) -> np.ndarray:
    g = 0x9E3779B97F4A7C15
    out = []
    for i in range(n):
        h = g
        for col in col_hashes:
            h = _py_mix64((h * g ^ int(col[i])) & _M64)
        out.append(h)
    return np.array(out, dtype=np.uint64) if n else np.empty(0, np.uint64)


def py_segreduce(op, values, order, starts, valid):
    """Per-group loop reference for the integer reducers; returns
    (vals, counts) in the reducer's output dtype contract."""
    n = len(order)
    ends = list(starts[1:]) + [n]
    vals, counts = [], []
    v = values.astype(np.uint8) if values.dtype == np.bool_ else values
    acc_t = np.uint64 if v.dtype == np.uint64 else np.int64
    for s, e in zip(starts, ends):
        rows = [order[i] for i in range(s, e)
                if valid is None or valid[order[i]]]
        counts.append(len(rows))
        if op == "count":
            vals.append(len(rows))
        elif op == "sum":
            t = 0
            for r in rows:
                t = int(np.asarray(t, acc_t) + np.asarray(v[r], acc_t)
                        .astype(acc_t))  # wrap like the kernel
            vals.append(np.asarray(t % (1 << 64) if acc_t == np.uint64
                                   else t, acc_t))
        elif op == "min":
            vals.append(min((v[r] for r in rows),
                            default=vkernels._dtype_max(v.dtype)))
        else:
            vals.append(max((v[r] for r in rows),
                            default=vkernels._dtype_min(v.dtype)))
    out_t = np.int64 if op == "count" else (
        acc_t if op == "sum" else v.dtype)
    return (np.array(vals, dtype=out_t) if vals else np.empty(0, out_t),
            np.array(counts, dtype=np.int64))


def assert_bits(got, want, msg=""):
    gv, gc = got if isinstance(got, tuple) else (got, None)
    wv, wc = want if isinstance(want, tuple) else (want, None)
    assert gv.dtype == wv.dtype, f"{msg}: dtype {gv.dtype} != {wv.dtype}"
    assert np.array_equal(gv, wv, equal_nan=gv.dtype.kind == "f"), \
        f"{msg}: values diverge"
    if gc is not None:
        assert gc.dtype == wc.dtype and np.array_equal(gc, wc), \
            f"{msg}: counts diverge"


# ---------------------------------------------------------------------------
# hashing: device kernel == numpy == per-row python
# ---------------------------------------------------------------------------

_HASH_ARRAYS = {
    "int32": np.array([0, -1, 5, 2**31 - 1, -2**31], np.int32),
    "int64": np.array([0, -1, 2**62, -2**62, 7], np.int64),
    "uint64": np.array([0, 1, 2**63, 2**64 - 1], np.uint64),
    "float64": np.array([0.0, -0.0, 1.5, -1.5, np.nan, np.inf,
                         -np.inf, 1e-300], np.float64),
    "float32": np.array([0.0, -0.0, 2.5, np.nan], np.float32),
    "empty": np.empty(0, np.int64),
}


@pytest.mark.parametrize("name", sorted(_HASH_ARRAYS))
def test_hash_fixed_triple_identical(name):
    v = _HASH_ARRAYS[name]
    ref = vkernels.hash_fixed(v)
    assert_bits(rel.hash_fixed(v), ref, f"pallas hash_fixed[{name}]")
    assert_bits(ref, py_hash_fixed(v), f"numpy hash_fixed[{name}]")


def test_hash_fixed_large_blocked():
    # spans multiple kernel blocks, with -0.0/NaN sprinkled in
    rng = np.random.default_rng(0)
    v = rng.standard_normal(5001)
    v[rng.random(5001) < 0.1] = -0.0
    v[rng.random(5001) < 0.05] = np.nan
    assert_bits(rel.hash_fixed(v), vkernels.hash_fixed(v))


def test_combine_hashes_triple_identical():
    rng = np.random.default_rng(1)
    n = 700
    hs = [rng.integers(0, 1 << 64, n, dtype=np.uint64) for _ in range(3)]
    ref = vkernels.combine_hashes(hs, n)
    assert_bits(rel.combine_hashes(hs, n), ref)
    assert_bits(ref, py_combine(hs, n))
    # order-sensitive: reversing the columns must change the bits
    assert not np.array_equal(ref, rel.combine_hashes(hs[::-1], n))
    # zero-column and zero-row edges match numpy exactly
    assert_bits(rel.combine_hashes([], 4), vkernels.combine_hashes([], 4))
    zero = [np.empty(0, np.uint64)] * 3
    assert_bits(rel.combine_hashes(zero, 0),
                vkernels.combine_hashes(zero, 0))


# ---------------------------------------------------------------------------
# gather
# ---------------------------------------------------------------------------

def test_filter_join_gather_triple_identical():
    rng = np.random.default_rng(3)
    sel = np.nonzero(rng.random(400) < 0.5)[0]
    idx = rng.integers(-1, len(sel), 900).astype(np.int64)
    ref = vkernels.filter_join_gather(sel, idx)
    assert_bits(rel.filter_join_gather(sel, idx), ref)
    naive = np.array([-1 if i < 0 else sel[i] for i in idx], np.int64)
    assert_bits(ref, naive)
    # no-sentinel fast path and empty edges
    pos = np.abs(idx[idx >= 0])
    assert_bits(rel.filter_join_gather(sel, pos),
                vkernels.filter_join_gather(sel, pos))
    empty = np.empty(0, np.int64)
    assert_bits(rel.filter_join_gather(sel, empty),
                vkernels.filter_join_gather(sel, empty))
    all_miss = np.full(5, -1, np.int64)
    assert_bits(rel.filter_join_gather(empty, all_miss),
                vkernels.filter_join_gather(empty, all_miss))


# ---------------------------------------------------------------------------
# join probe: device kernel == numpy == nested loop
# ---------------------------------------------------------------------------

def py_hash_join_probe(build, probe):
    """Every (probe row, build row) pair with equal hashes, probe-major
    and build rows ascending: one scan of the whole build per probe row."""
    pairs = [(i, j) for i, h in enumerate(probe)
             for j in np.nonzero(build == h)[0]]
    out = np.array(pairs, np.int64).reshape(-1, 2)
    return out[:, 0].copy(), out[:, 1].copy()


def _probe_case(rng, case):
    """(build hashes, probe hashes) for a probe case: ``<nb>x<np>`` is
    random hashes over a few hundred values (many-to-many runs) at those
    lengths, the ``_bucket`` edges among them."""
    top = np.uint64(_M64)
    if case == "empty_build":
        return np.empty(0, np.uint64), rng.integers(0, 9, 50, np.uint64)
    if case == "empty_probe":
        return rng.integers(0, 9, 50, np.uint64), np.empty(0, np.uint64)
    if case == "no_match":
        return (rng.integers(0, 1000, 700, np.uint64) * np.uint64(2),
                rng.integers(0, 1000, 900, np.uint64) * np.uint64(2)
                + np.uint64(1))
    if case == "duplicates":
        return (rng.integers(0, 5, 600, np.uint64),
                rng.integers(0, 7, 400, np.uint64))
    if case == "extremes_absent":
        # the probe holds 0 and 2**64-1; the padded build holds neither,
        # so no pair may come from its padding
        return (rng.integers(1, 1000, 1100, np.uint64),
                np.array([0, top, 5, 0, top], np.uint64))
    if case == "extremes_present":
        b = rng.integers(0, 50, 1100, np.uint64)
        b[[3, 700, 1099]] = [top, 0, top]
        p = rng.integers(0, 50, 1500, np.uint64)
        p[[0, 1, 1499]] = [top, 0, top]
        return b, p
    if case == "build_larger":
        return (rng.integers(0, 300, 5000, np.uint64),
                rng.integers(0, 400, 300, np.uint64))
    nb, npr = map(int, case.split("x"))
    return (rng.integers(0, 300, nb, np.uint64),
            rng.integers(0, 300, npr, np.uint64))


_PROBE_CASES = ["empty_build", "empty_probe", "no_match", "duplicates",
                "extremes_absent", "extremes_present", "build_larger",
                "1023x1024", "1024x1025", "1025x1280", "1280x1281",
                "1281x1023"]


@pytest.mark.parametrize("case", _PROBE_CASES)
def test_hash_join_probe_triple_identical(case):
    rng = np.random.default_rng(sum(map(ord, case)))
    build, probe = _probe_case(rng, case)
    ref = vkernels.hash_join_probe(build, probe)
    assert_bits(rel.hash_join_probe(build, probe), ref,
                f"device hash_join_probe[{case}]")
    assert_bits(ref, py_hash_join_probe(build, probe),
                f"numpy hash_join_probe[{case}]")


def test_hash_join_probe_searches_past_one_row_level():
    """Builds and probe prefixes of 2**17 rows and more take two row
    levels of the search, and duplicate runs cross the rows' edges."""
    rng = np.random.default_rng(13)
    for nb, npr, card in ((70_000, 3_000, 40_000), (2_000, 140_000, 1_500),
                          (66_000, 70_000, 300)):
        build = rng.integers(0, card, nb, np.uint64)
        probe = rng.integers(0, card, npr, np.uint64)
        assert_bits(rel.hash_join_probe(build, probe),
                    vkernels.hash_join_probe(build, probe), f"{nb}x{npr}")


# ---------------------------------------------------------------------------
# segment reducers: dtype sweep x null fraction x group shape
# ---------------------------------------------------------------------------

def _values(rng, n, dtype):
    if dtype == "bool":
        return rng.random(n) < 0.5
    if dtype == "uint64":
        return rng.integers(0, 1 << 64, n, dtype=np.uint64)
    return rng.integers(-100, 100, n).astype(dtype)


def _near_top(rng, n, dtype):
    """Values a little inside the dtype's reach: ±2**62 for int64, 2**63
    for uint64, ±2**31 for int32 (two of them fit every accumulator)."""
    if dtype == "bool":
        return rng.random(n) < 0.5
    off = rng.integers(0, 1000, n)
    if dtype == "uint64":
        return np.uint64(1 << 63) - off.astype(np.uint64)
    top = (1 << 62) if dtype == "int64" else (1 << 31) - 1
    return ((top - off) * rng.choice([-1, 1], n)).astype(dtype)


def _reducer_case(rng, shape, dtype):
    """(codes, values) for a group shape:

    * ``spread`` / ``dup_heavy`` / ``empty``: random codes over 200 / 3
      keys, or no rows;
    * ``prefix_overflow``: two rows a group, each near the dtype's top,
      so the running prefix over the sorted rows wraps many times while
      no group's own total does;
    * ``group_wraps``: small values, but one group's rows all near the
      top, so that group's 64-bit total wraps;
    * ``bucket_groups``: exactly ``_bucket(1024)`` groups, so the device
      edge pads no group and the last group ends at the last row;
    * ``one_group`` / ``singletons``: all rows in one group; one row a
      group;
    * ``null_groups``: random codes; the caller nulls every row of some
      groups."""
    n = 0 if shape == "empty" else 1500
    if shape == "prefix_overflow":
        return rng.permutation(n) // 2, _near_top(rng, n, dtype)
    v = _values(rng, n, dtype)
    if shape == "group_wraps":
        codes = rng.integers(0, 200, n)
        top = _near_top(rng, n, dtype)
        hot = codes == codes[0]
        v[hot] = np.abs(top[hot]) if dtype != "bool" else True
        return codes, v
    if shape == "bucket_groups":
        g = rel._bucket(1024)
        assert g == 1024
        return np.concatenate([np.arange(g), rng.integers(0, g, n - g)]), v
    if shape == "one_group":
        return np.zeros(n, np.int64), v
    if shape == "singletons":
        return rng.permutation(n), v
    return rng.integers(0, 3 if shape == "dup_heavy" else 200, n), v


_REDUCER_SHAPES = ["spread", "dup_heavy", "empty", "prefix_overflow",
                   "group_wraps", "bucket_groups", "one_group", "singletons",
                   "null_groups"]


@pytest.mark.parametrize("dtype", ["int32", "int64", "uint64", "bool"])
@pytest.mark.parametrize("null_frac", [0.0, 0.3, 1.0])
@pytest.mark.parametrize("shape", _REDUCER_SHAPES)
def test_grouped_reducers_triple_identical(dtype, null_frac, shape):
    rng = np.random.default_rng(hash((dtype, null_frac, shape)) % 2**32)
    codes, v = _reducer_case(rng, shape, dtype)
    n = len(codes)
    order, starts = vkernels.group_ranges([codes])
    valid = None if null_frac == 0.0 else rng.random(n) >= null_frac
    if shape == "null_groups":
        valid = (np.ones(n, bool) if valid is None else valid) \
            & (codes % 5 != 0)
    for op, pf, nf in (("count", rel.grouped_count, vkernels.grouped_count),
                       ("sum", rel.grouped_sum, vkernels.grouped_sum),
                       ("min", rel.grouped_min, vkernels.grouped_min),
                       ("max", rel.grouped_max, vkernels.grouped_max)):
        ref = nf(v, order, starts, valid)
        assert_bits(pf(v, order, starts, valid), ref,
                    f"pallas grouped_{op}[{dtype},{null_frac},{shape}]")
        assert_bits(ref, py_segreduce(op, v, order, starts, valid),
                    f"numpy grouped_{op}[{dtype},{null_frac},{shape}]")


def test_grouped_sum_uint64_wraps_like_numpy():
    # a group total past 2**64 wraps mod 2**64 on both backends
    v = np.array([2**63, 2**63, 5], np.uint64)
    order, starts = vkernels.group_ranges([np.zeros(3, np.int64)])
    assert_bits(rel.grouped_sum(v, order, starts),
                vkernels.grouped_sum(v, order, starts))


def test_grouped_reducers_many_groups_blocked():
    # group count spans multiple output blocks of the kernel grid
    rng = np.random.default_rng(11)
    n, card = 4000, 1300
    codes = rng.integers(0, card, n)
    order, starts = vkernels.group_ranges([codes])
    v = rng.integers(-1000, 1000, n).astype(np.int64)
    valid = rng.random(n) >= 0.2
    for pf, nf in ((rel.grouped_sum, vkernels.grouped_sum),
                   (rel.grouped_min, vkernels.grouped_min),
                   (rel.grouped_max, vkernels.grouped_max),
                   (rel.grouped_count, vkernels.grouped_count)):
        assert_bits(pf(v, order, starts, valid),
                    nf(v, order, starts, valid))


# ---------------------------------------------------------------------------
# the kernel table: dispatch under both backends, refusals stay on numpy
# ---------------------------------------------------------------------------

def _dispatch_args(name, dtype):
    """Seeded arguments for one kernel of the table: ``-1`` sentinels,
    duplicate probe runs, nulls; float reducer values with -0.0 and
    NaN."""
    rng = np.random.default_rng(sum(map(ord, name + dtype)))
    n = 1500
    if name == "hash_fixed":
        return (rng.integers(-(1 << 62), 1 << 62, n, dtype=np.int64),)
    if name == "combine_hashes":
        return [rng.integers(0, 1 << 64, n, dtype=np.uint64)
                for _ in range(3)], n
    if name == "hash_join_probe":
        return _probe_case(rng, "duplicates")
    if name == "filter_join_gather":
        sel = np.nonzero(rng.random(n) < 0.5)[0]
        return sel, rng.integers(-1, len(sel), n).astype(np.int64)
    order, starts = vkernels.group_ranges([rng.integers(0, 50, n)])
    if dtype == "float64":
        v = rng.standard_normal(n) * 10.0 ** rng.integers(-12, 12, n)
        v[rng.random(n) < 0.1] = -0.0
        v[rng.random(n) < 0.05] = np.nan
    else:
        v = rng.integers(-100, 100, n).astype(dtype)
    return v, order, starts, rng.random(n) >= 0.2


#: every kernel of the table on input it admits, and the reducers'
#: float input, which the table refuses
_DISPATCH_CASES = [(name, "int64") for name in kd.KERNELS] + [
    (f"grouped_{how}", "float64") for how in ("sum", "min", "max")]


@pytest.mark.parametrize("name,dtype", _DISPATCH_CASES,
                         ids=[f"{n}:{d}" for n, d in _DISPATCH_CASES])
def test_dispatch_serves_reference_bits_on_both_backends(monkeypatch, name,
                                                         dtype):
    """Under either backend a kernel returns its numpy reference's bits;
    only the device backend serves it on the device, as one counted
    call, and only for input its entry admits."""
    args = _dispatch_args(name, dtype)
    want = getattr(vkernels, name)(*args)
    fn = kd.GROUPED_REDUCERS[name[len("grouped_"):]] \
        if name.startswith("grouped_") else getattr(kd, name)
    for backend in ("numpy", kd.DEVICE_BACKEND):
        monkeypatch.setenv(kd.ENV, backend)
        kd.reset_device_calls()
        assert_bits(fn(*args), want, f"{backend} {name}[{dtype}]")
        served = backend == kd.DEVICE_BACKEND and dtype != "float64"
        assert kd.device_calls() == (
            {(name, rel.platform()): 1} if served else {}), backend


def test_float_reducers_are_registry_ineligible():
    """The table's one admission rule: sum, min and max reduce integer
    and bool values only; the mean is never a device kernel."""
    order, starts = vkernels.group_ranges([np.zeros(4, np.int64)])
    for name in ("grouped_sum", "grouped_min", "grouped_max"):
        admits = kd.KERNELS[name].admits
        for dt in (np.float64, np.float32):
            assert not admits(np.zeros(4, dt), order, starts, None), name
        for dt in (np.int32, np.int64, np.uint64, np.bool_):
            assert admits(np.zeros(4, dt), order, starts, None), name
    assert kd.KERNELS["grouped_count"].admits(np.zeros(4), order, starts)
    assert kd.KERNELS["hash_fixed"].admits(np.zeros(4))
    assert "grouped_mean" not in kd.KERNELS
    assert kd.GROUPED_REDUCERS["mean"] is vkernels.grouped_mean


def test_float_sum_dispatch_stays_numpy(pallas_backend):
    """With the device backend active, a float grouped_sum must serve
    the numpy bits — the sequential np.bincount accumulation — and the
    device kernel must refuse float input outright (it cannot honor the
    order contract)."""
    rng = np.random.default_rng(5)
    n = 2000
    # adversarial magnitudes: parallel reordering WOULD change the bits
    v = rng.standard_normal(n) * 10.0 ** rng.integers(-12, 12, n)
    codes = rng.integers(0, 7, n)
    order, starts = vkernels.group_ranges([codes])
    valid = rng.random(n) >= 0.2
    want = vkernels.grouped_sum(v, order, starts, valid)
    assert_bits(kd.GROUPED_REDUCERS["sum"](v, order, starts, valid), want)
    assert_bits(kd.GROUPED_REDUCERS["mean"](v, order, starts, valid),
                vkernels.grouped_mean(v, order, starts, valid))
    with pytest.raises(TypeError):
        rel.grouped_sum(v, order, starts, valid)
    with pytest.raises(TypeError):
        rel.grouped_min(v, order, starts, valid)
    with pytest.raises(TypeError):
        rel.grouped_max(v, order, starts, valid)


def test_self_check_admits_current_kernels(pallas_backend):
    res = kd.self_check()
    assert res == {name: "ok" for name in kd.KERNELS}, res


def test_self_check_raises_on_bit_regressions(pallas_backend, monkeypatch):
    """A kernel whose differential fails is not admitted: corrupt the
    device grouped_sum, prove self_check raises naming it (no demotion,
    no quiet numpy fallback), and that the edit changes the group_by
    cone fingerprint."""
    fp_before = code_fingerprint(ops.group_by)

    def bad_grouped_sum(values, order, starts, valid=None):
        vals, counts = vkernels.grouped_sum(values, order, starts, valid)
        return vals + vals.dtype.type(1), counts       # off by one

    monkeypatch.setattr(rel, "grouped_sum", bad_grouped_sum)
    assert code_fingerprint(ops.group_by) != fp_before, \
        "kernel edit must change the cone"
    with pytest.raises(RuntimeError, match="grouped_sum"):
        kd.self_check()
    assert kd.KERNELS["grouped_sum"].admits(np.arange(3)), \
        "the table is static: a failed check raises, it never demotes"


def test_unavailable_pallas_raises_naming_the_knob(monkeypatch):
    monkeypatch.setenv(kd.ENV, kd.DEVICE_BACKEND)
    monkeypatch.setattr(kd, "_device_mod", None)
    monkeypatch.setattr(kd, "_device_error",
                        ImportError("jax not installed"))
    v = np.arange(10, dtype=np.int64)
    with pytest.raises(RuntimeError,
                       match="ZERROW_KERNEL_BACKEND.*jax not installed"):
        kd.active_backend()
    with pytest.raises(RuntimeError, match="ZERROW_KERNEL_BACKEND"):
        kd.hash_fixed(v)                # every call, not just the first
    monkeypatch.setenv("ZERROW_KERNEL_BACKEND", "numpy")
    assert kd.active_backend() == "numpy"
    assert_bits(kd.hash_fixed(v), vkernels.hash_fixed(v))


def test_device_calls_counted_per_kernel_and_platform(pallas_backend):
    rng = np.random.default_rng(9)
    v = rng.integers(-50, 50, 300).astype(np.int64)
    order, starts = vkernels.group_ranges([rng.integers(0, 7, 300)])
    kd.reset_device_calls()
    kd.hash_fixed(v)
    kd.hash_fixed(v)
    kd.GROUPED_REDUCERS["sum"](v, order, starts)
    kd.GROUPED_REDUCERS["sum"](v.astype(np.float64), order, starts)
    h = vkernels.hash_fixed(v)
    kd.hash_join_probe(h[:100], h)
    plat = rel.platform()
    assert kd.device_calls() == {("hash_fixed", plat): 2,
                                 ("grouped_sum", plat): 1,
                                 ("hash_join_probe", plat): 1}, \
        "the table refuses the float sum, which must not count"
    kd.reset_device_calls()
    assert kd.device_calls() == {}


def test_process_executor_refuses_pallas_backend(tmp_path, monkeypatch):
    """One process owns the chip: Flight workers never run the device
    kernels, so the process executor refuses the backend outright."""
    from repro.core import ProcessWorkerExecutor
    store = BufferStore(backing="file", data_dir=str(tmp_path / "store"))
    try:
        rm = ResourceManager(store, RMConfig())
        monkeypatch.setenv(kd.ENV, kd.DEVICE_BACKEND)
        with pytest.raises(ValueError, match="ZERROW_KERNEL_BACKEND"):
            ProcessWorkerExecutor(store, rm, workers=1)
        # flipping the knob after construction is refused before any
        # worker is spawned
        monkeypatch.setenv("ZERROW_KERNEL_BACKEND", "numpy")
        ex = ProcessWorkerExecutor(store, rm, workers=1)
        monkeypatch.setenv(kd.ENV, kd.DEVICE_BACKEND)
        with pytest.raises(ValueError, match="thread executor"):
            ex._ensure_pool()
        assert ex._pool is None
        ex.close()
    finally:
        store.close()


def test_unknown_backend_name_raises(monkeypatch):
    monkeypatch.setenv("ZERROW_KERNEL_BACKEND", "cuda")
    with pytest.raises(ValueError, match="ZERROW_KERNEL_BACKEND"):
        kd.requested_backend()


# ---------------------------------------------------------------------------
# fingerprint pinning: backend flips + kernel edits invalidate the cones
# ---------------------------------------------------------------------------

def test_backend_flip_changes_only_relational_fingerprints(monkeypatch):
    relational_ops = (ops.join, ops.group_by, ops.filter_join,
                      ops.join_node, ops.group_by_node,
                      ops.filter_join_node)
    unrelated = (ops.filter_rows, ops.select_columns, ops.sort_by,
                 ops.add_column)
    monkeypatch.setenv("ZERROW_KERNEL_BACKEND", "numpy")
    fp_np = [code_fingerprint(f) for f in relational_ops + unrelated]
    assert all(fp_np)
    monkeypatch.setenv(kd.ENV, kd.DEVICE_BACKEND)
    fp_pl = [code_fingerprint(f) for f in relational_ops + unrelated]
    assert all(fp_pl)
    k = len(relational_ops)
    assert all(a != b for a, b in zip(fp_np[:k], fp_pl[:k])), \
        "backend flip did not invalidate a relational op"
    assert fp_np[k:] == fp_pl[k:], \
        "backend flip leaked into non-relational ops"


def test_pallas_kernel_edit_invalidates_join_cone(monkeypatch):
    monkeypatch.setenv(kd.ENV, kd.DEVICE_BACKEND)
    fp1 = code_fingerprint(ops.join)
    fj1 = code_fingerprint(ops.filter_join)
    gb1 = code_fingerprint(ops.group_by)

    def other_hash_fixed(values):
        return vkernels.hash_fixed(values)

    monkeypatch.setattr(rel, "hash_fixed", other_hash_fixed)
    fp2 = code_fingerprint(ops.join)
    fj2 = code_fingerprint(ops.filter_join)
    assert fp2 != fp1 and fj2 != fj1, \
        "editing a pallas hash kernel must invalidate join cones"
    assert code_fingerprint(ops.group_by) == gb1, \
        "the hash kernel is not a group_by dependency"

    def other_probe(build_hash, probe_hash):
        return vkernels.hash_join_probe(build_hash, probe_hash)

    monkeypatch.setattr(rel, "hash_join_probe", other_probe)
    assert code_fingerprint(ops.join) != fp2, \
        "editing the device probe must invalidate join cones"
    assert code_fingerprint(ops.filter_join) != fj2
    assert code_fingerprint(ops.group_by) == gb1, \
        "the probe is not a group_by dependency"


def _select_amount(tables):
    return ops.select_columns(tables[0], ["amount"])


def test_backend_flip_recomputes_exactly_join_groupby_cone(
        tmp_path, monkeypatch):
    """Manifest adoption across a backend flip: sources and the
    non-relational node adopt from the cache, the join + group_by cone
    re-executes — and both backends' outputs are bit-identical, so the
    recompute converges back to the same aggregates."""
    data = str(tmp_path / "data")
    cache_root = str(tmp_path / "cache")
    os.makedirs(data)
    rng = np.random.default_rng(7)
    zarquet.write_table(os.path.join(data, "cust.zq"), Table.from_pydict({
        "cust": np.arange(64, dtype=np.int64),
        "country": [f"c{i % 5}" for i in range(64)]}))
    zarquet.write_table(os.path.join(data, "orders.zq"),
                        Table.from_pydict({
        "cust": rng.integers(0, 72, 400).astype(np.int64),
        "amount": rng.integers(0, 10_000, 400).astype(np.int64)}))

    def dag():
        est = 1 << 22
        return DAG([
            NodeSpec("orders", source=os.path.join(data, "orders.zq"),
                     est_mem=est),
            NodeSpec("cust", source=os.path.join(data, "cust.zq"),
                     est_mem=est, dict_columns=("country",)),
            NodeSpec("amounts", fn=_select_amount, deps=["orders"],
                     est_mem=est, keep_output=True),
            NodeSpec("join", fn=functools.partial(
                ops.join_node, on="cust", how="left"),
                deps=["orders", "cust"], est_mem=est),
            NodeSpec("agg", fn=functools.partial(
                ops.group_by_node, keys="country",
                aggs={"total": ("amount", "sum"),
                      "hi": ("amount", "max"),
                      "n": ("amount", "count")}),
                deps=["join"], est_mem=est, keep_output=True),
        ], name="star")

    def run(backend):
        monkeypatch.setenv("ZERROW_KERNEL_BACKEND", backend)
        fingerprint.reset_caches()
        store = BufferStore(backing="file", root=cache_root,
                            swap_dir=os.path.join(data, "swap"))
        rm = ResourceManager(store, RMConfig(policy="adaptive",
                                             cache_root=cache_root))
        ex = Executor(store, rm)
        d = dag()
        ex.run([d])
        out = SipcReader(store).read_table(
            d.nodes["agg"].output).to_pydict()
        counts = (ex.node_runs, ex.cache_hits,
                  {n: s.status for n, s in d.nodes.items()})
        store.close()
        return out, counts

    out1, (runs1, hits1, _) = run("numpy")
    assert (runs1, hits1) == (5, 0)
    out2, (runs2, hits2, _) = run("numpy")         # warm, same backend
    assert out2 == out1
    assert (runs2, hits2) == (0, 5)
    out3, (runs3, hits3, states) = run(kd.DEVICE_BACKEND)   # flip
    assert out3 == out1, "pallas cone diverged from the numpy bits"
    assert (runs3, hits3) == (2, 3), \
        f"backend flip should re-run exactly join+agg, got {states}"
    assert states["orders"] == states["cust"] == states["amounts"] \
        == "cached"
    out4, (runs4, hits4, _) = run("numpy")         # old manifests live on
    assert out4 == out1
    assert (runs4, hits4) == (0, 5)


# ---------------------------------------------------------------------------
# end-to-end: join+group_by tables bit-identical across backends
# ---------------------------------------------------------------------------

def test_join_group_by_tables_bit_identical_across_backends(monkeypatch):
    rng = np.random.default_rng(8)
    n, m = 3000, 150
    lvalid = pack_validity(rng.random(n) >= 0.1)
    left = Table.from_pydict({
        "cust": Column.primitive(
            rng.integers(0, m + 20, n).astype(np.int64), lvalid),
        "amount": rng.integers(-500, 500, n).astype(np.int64),
        "f": np.where(rng.random(n) < 0.1, -0.0, rng.standard_normal(n)),
        "flag": rng.random(n) < 0.5})
    right = Table.from_pydict({
        "cust": np.arange(m, dtype=np.int64),
        "country": [f"c{i % 7}" for i in range(m)]})
    aggs = {"total": ("amount", "sum"), "lo": ("amount", "min"),
            "hi": ("amount", "max"), "n": ("amount", "count"),
            "fmean": ("f", "mean"), "fsum": ("f", "sum"),
            "any": ("flag", "max")}

    outs, raw = {}, {}
    for backend in ("numpy", kd.DEVICE_BACKEND):
        monkeypatch.setenv(kd.ENV, backend)
        j = ops.join(left, right, on="cust", how="left")
        g = ops.group_by(j, "country", aggs)
        outs[backend] = g.to_pydict()
        raw[backend] = {
            f.name: c._logical()
            for f, c in zip(g.batches[0].schema.fields,
                            g.batches[0].columns)
            if c.type.is_primitive}
    assert outs["numpy"] == outs[kd.DEVICE_BACKEND]
    for name, va in raw["numpy"].items():        # raw buffer bits too
        vb = raw[kd.DEVICE_BACKEND][name]
        assert va.dtype == vb.dtype, name
        assert np.array_equal(va, vb, equal_nan=va.dtype.kind == "f"), \
            name


# ---------------------------------------------------------------------------
# hypothesis stress lane: widen the differential's input space
# ---------------------------------------------------------------------------

if HAVE_HYPOTHESIS:
    @pytest.mark.stress
    @settings(max_examples=int(os.environ.get("ZERROW_HYP_EXAMPLES",
                                              "25")), deadline=None)
    @given(st.integers(0, 2**31 - 1),
           st.sampled_from(["int32", "int64", "uint64", "bool"]),
           st.integers(0, 60), st.floats(0.0, 1.0), st.integers(1, 9))
    def test_grouped_reducers_match_hypothesis(seed, dtype, n, null_frac,
                                               card):
        rng = np.random.default_rng(seed)
        codes = rng.integers(0, card, n)
        order, starts = vkernels.group_ranges([codes]) if n else \
            (np.empty(0, np.int64), np.empty(0, np.int64))
        v = _values(rng, n, dtype)
        valid = None if null_frac == 0 else rng.random(n) >= null_frac
        for pf, nf in ((rel.grouped_count, vkernels.grouped_count),
                       (rel.grouped_sum, vkernels.grouped_sum),
                       (rel.grouped_min, vkernels.grouped_min),
                       (rel.grouped_max, vkernels.grouped_max)):
            assert_bits(pf(v, order, starts, valid),
                        nf(v, order, starts, valid))

    @pytest.mark.stress
    @settings(max_examples=int(os.environ.get("ZERROW_HYP_EXAMPLES",
                                              "25")), deadline=None)
    @given(st.integers(0, 2**31 - 1),
           st.sampled_from(["int32", "int64", "uint64", "float64"]),
           st.integers(0, 80))
    def test_hash_and_gather_match_hypothesis(seed, dtype, n):
        rng = np.random.default_rng(seed)
        v = _values(rng, n, dtype) if dtype != "float64" else \
            np.where(rng.random(n) < 0.2, -0.0, rng.standard_normal(n))
        assert_bits(rel.hash_fixed(v), vkernels.hash_fixed(v))
        sel = np.nonzero(rng.random(n) < 0.5)[0]
        idx = rng.integers(-1, max(len(sel), 1), n).astype(np.int64) \
            if len(sel) else np.full(n, -1, np.int64)
        assert_bits(rel.filter_join_gather(sel, idx),
                    vkernels.filter_join_gather(sel, idx))

    @pytest.mark.stress
    @settings(max_examples=int(os.environ.get("ZERROW_HYP_EXAMPLES",
                                              "25")), deadline=None)
    @given(st.integers(0, 2**31 - 1), st.integers(0, 1300),
           st.integers(0, 1300), st.integers(1, 2000))
    def test_hash_join_probe_matches_hypothesis(seed, nb, npr, card):
        rng = np.random.default_rng(seed)
        top = np.uint64(_M64)
        build = rng.integers(0, card, nb, np.uint64)
        probe = rng.integers(0, card, npr, np.uint64)
        # the extremes, sometimes: a real 0 or 2**64-1 beside the padding
        build[rng.random(nb) < 0.02] = top
        probe[rng.random(npr) < 0.02] = top
        want = vkernels.hash_join_probe(build, probe)
        assert_bits(rel.hash_join_probe(build, probe), want)
        assert_bits(want, py_hash_join_probe(build, probe))
