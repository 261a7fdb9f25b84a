"""The relational device kernels compile for a TPU v5e at real sizes.

Each kernel core of ``repro.kernels.relational`` — everything the star
join + group-by runs on the device — is lowered from shapes alone and
compiled by the TPU compiler for a *described* v5e (no chip attached):
2^23 rows, a 2^20-row gather source and 1024 groups, the sizes
``chip_smoke.py`` drives on the chip; the join probe searches 2^23
probe hashes in an 8,192-row build and expands 2^20 pairs.  What the chip's compiler refuses
(an unsupported 64-bit op, a program that does not fit in HBM) fails
here at no chip time.

The topology is described inside a module fixture, never at import:
only one process at a time may load the TPU library, so a test worker
that is not running this file must not touch it.
"""
import os

import pytest

jax = pytest.importorskip("jax")
import jax.numpy as jnp  # noqa: E402

from repro.compat import enable_x64  # noqa: E402
from repro.kernels import relational as rel  # noqa: E402

ROWS = 1 << 23
SOURCE_ROWS = 1 << 20
GROUPS = 1024
BUILD_ROWS = 1 << 13            # a filtered dimension: SSB's part side
PAIRS = 1 << 20
HBM_BYTES = 16 * 10**9          # one v5e chip


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:   # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    from jax.sharding import SingleDeviceSharding
    # a compile for a described chip is written to the persistent cache
    # but cannot be read back without one: keep the cache out of it
    enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", enabled)


def _spec(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _cases(s):
    """kernel -> (core, argument shapes, static options), at the padded
    lengths the numpy edge hands the core."""
    u64, i64 = jnp.uint64, jnp.int64
    rows = _spec((rel._bucket(ROWS),), u64, s)
    idx = _spec((rel._bucket(ROWS),), i64, s)
    src = _spec((rel._bucket(SOURCE_ROWS),), i64, s)
    fill = _spec((), i64, s)
    order = _spec((rel._bucket(ROWS),), i64, s)
    starts = _spec((rel._bucket(GROUPS),), i64, s)
    valid = _spec((rel._bucket(ROWS),), jnp.bool_, s)
    n = _spec((), i64, s)
    build = _spec((rel._bucket(BUILD_ROWS),), u64, s)
    probe = {
        "hash_join_probe:count": (rel._probe_count_core,
                                  (build, rows, n, n), {}),
        "hash_join_probe:expand": (rel._probe_expand_core,
                                   (idx, idx, _spec((rel._bucket(BUILD_ROWS),),
                                                    i64, s)),
                                   {"size": rel._bucket(PAIRS)}),
    }
    seg = {f"grouped_{op}": (rel._segreduce_core,
                             (None if op == "count" else idx, order,
                              starts, valid, n), {"op": op})
           for op in ("count", "sum", "min", "max")}
    return {
        "hash_fixed": (rel._hash_core, (rows,), {"sign": 0}),
        "hash_fixed:float": (rel._hash_core, (rows,), {"sign": 1 << 63}),
        "combine_hashes": (rel._combine_core, ((rows, rows),), {}),
        "filter_join_gather": (rel._gather_core, (src, idx, fill), {}),
        **probe,
        **seg,
    }


KERNELS = ("hash_fixed", "hash_fixed:float", "combine_hashes",
           "hash_join_probe:count", "hash_join_probe:expand",
           "filter_join_gather", "grouped_count", "grouped_sum",
           "grouped_min", "grouped_max")


@pytest.mark.parametrize("kernel", KERNELS)
def test_kernel_compiles_for_v5e(one_chip, kernel):
    with enable_x64():
        core, args, static = _cases(one_chip)[kernel]
        compiled = jax.jit(core, static_argnames=tuple(static)) \
            .lower(*args, **static).compile()
    mem = compiled.memory_analysis()
    used = (mem.argument_size_in_bytes + mem.output_size_in_bytes
            + mem.temp_size_in_bytes)
    assert used < HBM_BYTES, f"{kernel}: {used} bytes do not fit one v5e"


def test_segment_reducer_without_nulls_compiles_for_v5e(one_chip):
    """``valid=None`` (a column without nulls) is its own program."""
    s = one_chip
    with enable_x64():
        jax.jit(rel._segreduce_core, static_argnames=("op",)).lower(
            _spec((ROWS,), jnp.int64, s), _spec((ROWS,), jnp.int64, s),
            _spec((GROUPS,), jnp.int64, s), None, _spec((), jnp.int64, s),
            op="sum").compile()


@pytest.mark.parametrize("with_valid", [False, True])
@pytest.mark.parametrize("op", ["count", "sum", "min", "max"])
def test_segment_reducer_lowers_without_loop_or_scatter(one_chip, op,
                                                        with_valid):
    """Groups are contiguous runs, so no reducer searches for segment
    ids (a ``while``), and sum and count are prefix differences with no
    scatter; min/max keep one, of the group-head flags."""
    s = one_chip
    with enable_x64():
        hlo = jax.jit(rel._segreduce_core, static_argnames=("op",)).lower(
            None if op == "count" else _spec((ROWS,), jnp.int64, s),
            _spec((ROWS,), jnp.int64, s), _spec((GROUPS,), jnp.int64, s),
            _spec((ROWS,), jnp.bool_, s) if with_valid else None,
            _spec((), jnp.int64, s), op=op).as_text()
    assert "stablehlo.while" not in hlo
    if op in ("count", "sum"):
        assert "stablehlo.scatter" not in hlo


@pytest.mark.parametrize("stage", ["count", "expand"])
def test_join_probe_lowers_without_loop(one_chip, stage):
    """The probe's searches are static levels and its prefix sums
    doubling passes: no ``while`` (the segment reducer's old ``while``
    search of 64-bit gathers took 5.5 s a call on a v5e)."""
    with enable_x64():
        core, args, static = _cases(one_chip)[f"hash_join_probe:{stage}"]
        hlo = jax.jit(core, static_argnames=tuple(static)) \
            .lower(*args, **static).as_text()
    assert "stablehlo.while" not in hlo


def test_cases_cover_every_registered_device_kernel():
    from repro.core import kdispatch
    assert set(kdispatch.KERNELS) == {k.split(":")[0] for k in KERNELS}
    # the real sizes are their own buckets: no padding at the smoke's size
    assert all(rel._bucket(n) == n
               for n in (ROWS, SOURCE_ROWS, GROUPS, BUILD_ROWS, PAIRS))
