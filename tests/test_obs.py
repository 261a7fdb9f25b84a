"""``core.obs``: spans that cost nothing without jax or a profiler, one
thread-safe counter registry, the byte counts of the device-kernel call
edge, and the process-mode chain clock.

The instrumentation must not change what the program computes: a join
and group-by plan on the device backend gives the same bits and the
same node fingerprints with the profiler running and without it, and
the put / run / fetch split of a kernel call runs the same compiled
program as a plain jitted call."""

import os
import subprocess
import sys
import threading
import time

import numpy as np
import pytest

from repro.core import (BufferStore, DAG, Executor, NodeSpec,
                        ProcessWorkerExecutor, RMConfig, ResourceManager,
                        fingerprint, kdispatch as kd, obs, ops, vkernels,
                        zarquet)
from repro.core.arrow import Column, Table
from repro.core.plan import col, compile_plans, scan

SRC = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "src")


def test_span_is_a_shared_noop_without_jax():
    code = ("import sys; import repro.core; from repro.core import obs, ops\n"
            "assert 'jax' not in sys.modules\n"
            "a, b = obs.span('ops.join', rows=3), obs.span('kernel.put')\n"
            "assert a is b\n"
            "with a:\n"
            "    with b:\n"
            "        pass\n"
            "print('ok')\n")
    env = dict(os.environ, PYTHONPATH=SRC, ZERROW_KERNEL_BACKEND="numpy")
    out = subprocess.run([sys.executable, "-c", code], env=env,
                         capture_output=True, text=True, timeout=120)
    assert out.returncode == 0, out.stderr
    assert out.stdout.strip() == "ok"


def test_counters_add_read_and_reset_by_prefix():
    obs.reset("test.")
    obs.add("test.a")
    obs.add("test.a", 2)
    obs.add("test.b.x", 0.5)
    assert obs.totals("test.") == {"test.a": 3, "test.b.x": 0.5}
    assert obs.totals("test.b.") == {"test.b.x": 0.5}
    obs.reset("test.b.")
    assert obs.totals("test.") == {"test.a": 3}
    obs.reset("test.")
    assert obs.totals("test.") == {}


def test_counters_are_thread_safe():
    obs.reset("test.")
    n_threads = 4 * (os.cpu_count() or 1)
    threads = [threading.Thread(
        target=lambda: [obs.add("test.n") for _ in range(5000)])
        for _ in range(n_threads)]
    interval = sys.getswitchinterval()
    sys.setswitchinterval(1e-6)
    try:
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=60)
    finally:
        sys.setswitchinterval(interval)
    assert not any(t.is_alive() for t in threads)
    assert obs.totals("test.") == {"test.n": n_threads * 5000}
    obs.reset("test.")


# ---------------------------------------------------------------------------
# the device-kernel call edge (jax's CPU device stands in for the chip)
# ---------------------------------------------------------------------------

jax = pytest.importorskip("jax")
from repro.kernels import relational as rel  # noqa: E402


@pytest.fixture
def pallas_backend(monkeypatch):
    monkeypatch.setenv(kd.ENV, kd.DEVICE_BACKEND)


def _moved(fn, *args):
    obs.reset("kernel.")
    out = fn(*args)
    t = obs.totals("kernel.")
    obs.reset("kernel.")
    return out, t["kernel.h2d_bytes"], t["kernel.d2h_bytes"]


@pytest.mark.parametrize("dtype,n", [(np.int32, 3000), (np.int64, 1024),
                                     (np.float64, 70001)])
def test_hash_fixed_counts_padded_bytes(dtype, n):
    v = np.arange(n).astype(dtype)
    out, h2d, d2h = _moved(rel.hash_fixed, v)
    size = rel._bucket(n)
    assert h2d == size * np.dtype(dtype).itemsize
    assert d2h == size * 8
    assert out.tobytes() == vkernels.hash_fixed(v).tobytes()


@pytest.mark.parametrize("with_valid", [False, True])
def test_segreduce_counts_padded_bytes(with_valid):
    rng = np.random.default_rng(4)
    n = 5000
    v = rng.integers(-9, 9, n)
    order, starts = vkernels.group_ranges([rng.integers(0, 300, n)])
    valid = rng.random(n) < 0.7 if with_valid else None
    (vals, counts), h2d, d2h = _moved(rel.grouped_sum, v, order, starts,
                                      valid)
    rows, groups = rel._bucket(n), rel._bucket(len(starts))
    # values, order, starts, the validity mask, and the row count n
    assert h2d == rows * 8 + rows * 8 + groups * 8 \
        + (rows if with_valid else 0) + 8
    assert d2h == groups * 8 + groups * 8          # sums and counts
    want = vkernels.grouped_sum(v, order, starts, valid)
    assert vals.tobytes() == want[0].tobytes()
    assert counts.tobytes() == want[1].tobytes()


def test_call_split_runs_the_same_compiled_program():
    """After the split call has compiled a shape, a plain jitted call on
    the numpy arguments (the call before the split) compiles nothing."""
    from repro.compat import enable_x64
    v = np.arange(6007, dtype=np.int64)
    split = rel.hash_fixed(v)
    before = obs.totals("jax.").get("jax.compiles", 0)
    bits, sign = rel._bits(v)
    with enable_x64():
        core = jax.jit(rel._hash_core, static_argnames=("sign",))
        plain = np.asarray(core(rel._pad(bits, rel._bucket(len(v)), 0),
                                sign=sign))[:len(v)]
    assert obs.totals("jax.").get("jax.compiles", 0) == before
    assert plain.tobytes() == split.tobytes()


def test_compiles_are_counted():
    before = obs.totals("jax.")
    jax.jit(lambda x: x * 3 + 1)(np.arange(5))        # a new function
    after = obs.totals("jax.")
    assert after["jax.compiles"] >= before.get("jax.compiles", 0) + 1
    assert after["jax.compile_s"] > before.get("jax.compile_s", 0)


def test_device_calls_contract_and_reset(pallas_backend):
    kd.reset_device_calls()
    v = np.arange(100, dtype=np.int64)
    kd.hash_fixed(v)
    kd.filter_join_gather(v, np.arange(-1, 9))
    plat = rel.platform()
    assert kd.device_calls() == {("hash_fixed", plat): 1,
                                 ("filter_join_gather", plat): 1}
    assert obs.totals("kernel.")["kernel.h2d_bytes"] > 0
    kd.reset_device_calls()
    assert kd.device_calls() == {}
    assert obs.totals("kernel.") == {}


# ---------------------------------------------------------------------------
# a plan under the profiler: same bits, same fingerprints
# ---------------------------------------------------------------------------

def _star(tmp_path):
    rng = np.random.default_rng(11)
    orders = Table.from_pydict({
        "cust": rng.integers(0, 600, 20000, dtype=np.int64),
        "amount": rng.integers(0, 1000, 20000, dtype=np.int64)})
    names = Column.from_strings([f"c{i}" for i in range(12)])
    customers = Table.from_pydict({
        "cust": np.arange(500, dtype=np.int64),
        "country": Column.dictionary_encoded(rng.integers(0, 12, 500),
                                             names)})
    paths = (str(tmp_path / "orders.zq"), str(tmp_path / "customers.zq"))
    zarquet.write_table(paths[0], orders)
    zarquet.write_table(paths[1], customers)
    return paths


def _run_plan(paths, swap):
    plan = (scan(paths[0]).filter(col("amount") > 100)
            .join(scan(paths[1], dict_columns=("country",)), on="cust")
            .group_by("country", {"total": ("amount", "sum"),
                                  "n": ("amount", "count")}))
    store = BufferStore(swap_dir=swap)
    try:
        ex = Executor(store, ResourceManager(store, RMConfig()))
        cp = compile_plans({"agg": plan}, name="star")
        fps = fingerprint.fingerprint_dag(cp.dag)
        ex.run([cp.dag])
        b = cp.read(store, "agg").combine().batches[0]
        bits = [(c.values.tobytes(), None if c.validity is None
                 else c.validity.tobytes()) for c in b.columns]
        ex.close()
    finally:
        store.close()
    return fps, bits


def test_plan_same_bits_and_fingerprints_under_profiler(tmp_path,
                                                        pallas_backend):
    paths = _star(tmp_path)
    fps, bits = _run_plan(paths, str(tmp_path / "swap0"))
    jax.profiler.start_trace(str(tmp_path / "profile"))
    try:
        kd.reset_device_calls()
        traced_fps, traced_bits = _run_plan(paths, str(tmp_path / "swap1"))
    finally:
        jax.profiler.stop_trace()
    assert kd.device_calls()[("filter_join_gather", rel.platform())] > 0
    assert all(fp is not None for fp in fps.values())
    assert traced_fps == fps
    assert traced_bits == bits


# ---------------------------------------------------------------------------
# process mode: each step of a shipped chain keeps its own time
# ---------------------------------------------------------------------------

def slow_op(tables):
    time.sleep(0.4)
    return tables[0]


def fast_op(tables):
    return ops.select_columns(tables[0], tables[0].schema.names())


def test_chain_steps_keep_their_own_latency(tmp_path):
    src = str(tmp_path / "t.zq")
    zarquet.write_table(src, Table.from_pydict(
        {"k": np.arange(4096, dtype=np.int64)}))
    store = BufferStore(backing="file", data_dir=str(tmp_path / "store"))
    rm = ResourceManager(store, RMConfig(workers=1, workers_mode="process"))
    ex = ProcessWorkerExecutor(store, rm, workers=1)
    dag = DAG([NodeSpec("load", source=src, est_mem=1 << 20),
               NodeSpec("slow", fn=slow_op, deps=["load"], est_mem=1 << 20),
               NodeSpec("fast", fn=fast_op, deps=["slow"], est_mem=1 << 20,
                        keep_output=True)], name="chain")
    try:
        ex.run([dag])
        assert ex.chains_shipped == 1 and ex.chain_nodes_shipped == 3
        slow, fast = dag.nodes["slow"], dag.nodes["fast"]
        assert slow.exec_latency >= 0.4
        assert fast.exec_latency < slow.exec_latency - 0.3
    finally:
        ex.close()
        store.close()
