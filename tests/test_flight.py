"""Flight data plane: file-backed store, SIPC wire protocol, process
workers.

Covers the three zero-copy claims the subsystem makes:
  * the wire roundtrip moves references, never data (copied_bytes == 0);
  * a *real second process* maps the same physical store file;
  * ``workers_mode='process'`` produces bit-identical DAG outputs to the
    sequential executor while only tiny control frames cross the socket.
"""

import functools
import os
import signal
import subprocess
import sys
import tempfile

import numpy as np
import pytest

from repro.core import (BufferStore, DAG, Executor, FlightClient,
                        FlightServer, FlightWorkerError, KernelZero,
                        NodeSpec, ProcessWorkerExecutor, RMConfig,
                        ResourceManager, Sandbox, SipcReader, Table,
                        decode_message, encode_message, make_executor)
from repro.core import ops, zarquet


# ---------------------------------------------------------------------------
# module-level node fns: must be picklable for the process executor
# ---------------------------------------------------------------------------

def dict_encode_op(tables):
    return ops.dict_encode(tables[0], ["s0"])


def filter_even_op(tables):
    t = tables[0]
    mask = np.arange(t.num_rows) % 2 == 0
    return ops.filter_rows(t, mask)


def upper_op(tables):
    return ops.upper(tables[0], "s0")


def crash_once_op(tables, marker):
    """SIGKILL the hosting worker process the first time it runs; the
    marker file makes the retry succeed."""
    if not os.path.exists(marker):
        open(marker, "w").close()
        os.kill(os.getpid(), signal.SIGKILL)
    return tables[0]


def crash_always_op(tables):
    os.kill(os.getpid(), signal.SIGKILL)


def _make_table(rows=1200):
    rng = np.random.default_rng(7)
    return Table.from_pydict({
        "a": rng.integers(0, 1 << 30, size=rows).astype(np.int64),
        "s": ["alpha", "beta", "gamma", "delta"] * (rows // 4),
    })


def _file_store(tmp_path, name="store"):
    return BufferStore(backing="file",
                       data_dir=os.path.join(str(tmp_path), name))


# ---------------------------------------------------------------------------
# wire roundtrip
# ---------------------------------------------------------------------------

def test_wire_roundtrip_zero_copy(tmp_path):
    store = _file_store(tmp_path)
    sb = Sandbox(store, KernelZero(store), "w", mode="zero")
    t = _make_table()
    msg = sb.write_output(t, label="t")

    c_before = store.copied_bytes
    frame = encode_message(msg, store)
    # the frame is tiny: references only, no data bytes
    assert len(frame) < 1024
    assert len(frame) < t.nbytes // 10

    reader_store = _file_store(tmp_path, "reader")
    msg2 = decode_message(frame, reader_store)
    t2 = SipcReader(reader_store).read_table(msg2)
    assert t2.equals(t)
    # zero data-byte copies on either side of the hop
    assert store.copied_bytes == c_before
    assert reader_store.copied_bytes == 0
    # all bytes were adopted (mapped), none reshared on first decode
    assert msg2.new_bytes > 0
    assert msg2.reshared_bytes == 0
    store.close()
    reader_store.close()


def test_wire_decode_into_producer_store_is_all_reshared(tmp_path):
    """Decoding a frame back into the store that owns the files must
    resolve every reference to the existing files: zero new bytes."""
    store = _file_store(tmp_path)
    sb = Sandbox(store, KernelZero(store), "w", mode="zero")
    msg = sb.write_output(_make_table(), label="t")
    frame = encode_message(msg, store)
    files_before = store.stats.files_created
    msg2 = decode_message(frame, store)
    assert msg2.new_bytes == 0
    assert msg2.reshared_bytes > 0
    assert store.stats.files_created == files_before
    store.close()


def test_wire_export_materializes_direct_swap_extents(tmp_path):
    """An anon region swapped out before deanon lands in the store as a
    direct-swap entry whose backing-file region is only reserved; the
    wire encoder must land the bytes in the backing file before naming
    the path, or readers would map a sparse hole of zeros."""
    from repro.core import AnonRegion

    store = _file_store(tmp_path)
    kz = KernelZero(store)
    cg = store.new_cgroup("sb")
    payload = (np.arange(4096 * 4) % 251).astype(np.uint8)
    region = AnonRegion(payload.copy(), cg)
    region.swap_out(store)                  # pre-deanon swap (the Fig 4
    f = kz.new_file(cg, "ds")               # direct-swap scenario)
    kz.deanon(f, region, direct_swap=True)
    ext = f.extents[0]
    assert not ext.resident and not ext.file_backed

    sb_msg_store = _file_store(tmp_path, "reader")
    # build a minimal message referencing the direct-swap file
    from repro.core import BufRef, SipcMessage
    from repro.core.arrow import UINT8
    from repro.core.sipc import BatchRefs, ColumnRefs
    msg = SipcMessage(b"[]", [BatchRefs(len(payload), [ColumnRefs(
        UINT8, len(payload), None, None,
        BufRef(f.file_id, 0, len(payload)))])])
    frame = encode_message(msg, store)
    got = decode_message(frame, sb_msg_store)
    ref = got.batches[0].columns[0].values
    arr = sb_msg_store.get(ref.file_id).read(ref.offset, ref.length)
    np.testing.assert_array_equal(np.asarray(arr), payload)
    store.close()
    sb_msg_store.close()


def test_wire_dictionary_column_roundtrip(tmp_path):
    store = _file_store(tmp_path)
    sb = Sandbox(store, KernelZero(store), "w", mode="zero")
    t = ops.dict_encode(_make_table(), ["s"])
    msg = sb.write_output(t, label="t")
    reader_store = _file_store(tmp_path, "reader")
    t2 = SipcReader(reader_store).read_table(
        decode_message(encode_message(msg, store), reader_store))
    assert t2.equals(t)
    assert reader_store.copied_bytes == 0
    store.close()
    reader_store.close()


# ---------------------------------------------------------------------------
# a real second process maps the same store file
# ---------------------------------------------------------------------------

_CHILD_SNIPPET = r"""
import sys
import numpy as np
from repro.core import BufferStore, SipcReader
from repro.core.flight import decode_message

frame = bytes.fromhex(sys.stdin.read().strip())
store = BufferStore(backing="file")
msg = decode_message(frame, store)
t = SipcReader(store).read_table(msg)
col = t.combine().batches[0].column("a")
print("SUM", int(col.values.sum()))
print("COPIED", store.copied_bytes)
store.close()
"""


def test_two_processes_map_same_store_file(tmp_path):
    store = _file_store(tmp_path)
    sb = Sandbox(store, KernelZero(store), "w", mode="zero")
    t = _make_table()
    msg = sb.write_output(t, label="t")
    frame = encode_message(msg, store)

    env = dict(os.environ, PYTHONPATH=os.path.join(
        os.path.dirname(__file__), "..", "src"))
    out = subprocess.run([sys.executable, "-c", _CHILD_SNIPPET],
                         input=frame.hex(), capture_output=True,
                         text=True, timeout=120, env=env)
    assert out.returncode == 0, out.stderr[-2000:]
    expect = int(t.combine().batches[0].column("a").values.sum())
    assert f"SUM {expect}" in out.stdout
    assert "COPIED 0" in out.stdout       # the child copied no data bytes
    store.close()


def test_child_sees_same_physical_bytes(tmp_path):
    """Byte-level check: a subprocess mmaps the backing file directly (no
    repro imports beyond numpy) and checksums the same extent."""
    store = _file_store(tmp_path)
    kz = KernelZero(store)
    cg = store.new_cgroup("t")
    f = kz.new_file(cg, "payload")
    payload = np.arange(4096 * 3, dtype=np.uint8)
    kz.deanon(f, payload)
    path = store.backing_path(f.file_id)
    code = ("import numpy as np, sys;"
            f"mm = np.memmap({path!r}, dtype=np.uint8, mode='r');"
            "print(int(mm.sum()))")
    out = subprocess.run([sys.executable, "-c", code],
                         capture_output=True, text=True, timeout=60)
    assert out.returncode == 0, out.stderr
    assert int(out.stdout.strip()) == int(payload.sum())
    store.close()


# ---------------------------------------------------------------------------
# process executor ≡ sequential executor
# ---------------------------------------------------------------------------

def _write_shards(tmpdir, n=3):
    paths = []
    for i in range(n):
        t = zarquet.gen_str_table(1, 1 << 16, str_len=32, repeats=4, seed=i)
        p = os.path.join(tmpdir, f"s{i}.zq")
        zarquet.write_table(p, t)
        paths.append(p)
    return paths


def _build_dags(paths):
    return [DAG([
        NodeSpec("load", source=p, est_mem=1 << 22),
        NodeSpec("enc", fn=dict_encode_op, deps=["load"], est_mem=1 << 22),
        NodeSpec("filt", fn=filter_even_op, deps=["enc"], est_mem=1 << 22,
                 keep_output=True),
    ], name=f"job{i}") for i, p in enumerate(paths)]


def test_process_mode_matches_sequential(tmp_path):
    paths = _write_shards(str(tmp_path))

    # sequential reference (seed semantics: RAM store, workers=1)
    ram = BufferStore()
    rm1 = ResourceManager(ram, RMConfig())
    dags1 = _build_dags(paths)
    Executor(ram, rm1).run(dags1)
    refs = [SipcReader(ram).read_table(d.nodes["filt"].output)
            for d in dags1]

    fstore = _file_store(tmp_path)
    rm2 = ResourceManager(fstore, RMConfig(workers=2,
                                           workers_mode="process"))
    ex = make_executor(fstore, rm2)
    assert isinstance(ex, ProcessWorkerExecutor)
    dags2 = _build_dags(paths)
    ex.run(dags2)
    try:
        outs = [SipcReader(fstore).read_table(d.nodes["filt"].output)
                for d in dags2]
        for got, want in zip(outs, refs):
            assert got.equals(want)
        # no node fell back to in-parent execution ...
        assert ex.fallback_inline == 0
        # ... and the socket carried references, not data: orders of
        # magnitude below the data the pipeline processed
        data_bytes = sum(t.nbytes for t in refs)
        assert ex.socket_bytes < max(data_bytes // 10, 1)
        assert fstore.copied_bytes == 0
    finally:
        ex.close()
        fstore.close()
        ram.close()


def test_process_mode_unpicklable_fn_falls_back_inline(tmp_path):
    paths = _write_shards(str(tmp_path), n=1)
    fstore = _file_store(tmp_path)
    rm = ResourceManager(fstore, RMConfig(workers=2,
                                          workers_mode="process"))
    ex = ProcessWorkerExecutor(fstore, rm, workers=2)

    captured = []                       # closure -> unpicklable

    def local_op(tables):
        captured.append(tables[0].num_rows)
        return tables[0]

    dag = DAG([
        NodeSpec("load", source=paths[0], est_mem=1 << 22),
        NodeSpec("op", fn=local_op, deps=["load"], est_mem=1 << 22,
                 keep_output=True),
    ], name="fb")
    ex.run([dag])
    try:
        assert ex.fallback_inline == 1
        assert captured and captured[0] > 0
        t = SipcReader(fstore).read_table(dag.nodes["op"].output)
        assert t.num_rows == captured[0]
    finally:
        ex.close()
        fstore.close()


def _worker_jax_platforms(tables):
    return Table.from_pydict(
        {"jax_platforms": [os.environ.get("JAX_PLATFORMS", "<unset>")]})


def test_workers_spawn_on_the_host_only(tmp_path, monkeypatch):
    """Flight workers are spawned with JAX_PLATFORMS=cpu, so none can
    take a chip the parent holds; the parent's own value is restored."""
    monkeypatch.setenv("JAX_PLATFORMS", "tpu")
    paths = _write_shards(str(tmp_path), n=1)
    fstore = _file_store(tmp_path)
    rm = ResourceManager(fstore, RMConfig(workers=1,
                                          workers_mode="process"))
    ex = ProcessWorkerExecutor(fstore, rm, workers=1)
    dag = DAG([
        NodeSpec("load", source=paths[0], est_mem=1 << 22),
        NodeSpec("env", fn=_worker_jax_platforms, deps=["load"],
                 est_mem=1 << 22, keep_output=True),
    ], name="env")
    try:
        ex.run([dag])
        assert ex.fallback_inline == 0
        t = SipcReader(fstore).read_table(dag.nodes["env"].output)
        assert t.to_pydict() == {"jax_platforms": ["cpu"]}
        assert os.environ["JAX_PLATFORMS"] == "tpu"
    finally:
        ex.close()
        fstore.close()


def test_process_mode_decache_shares_loads(tmp_path):
    """Two DAGs over the same source: the load runs once, in a worker."""
    paths = _write_shards(str(tmp_path), n=1)
    fstore = _file_store(tmp_path)
    rm = ResourceManager(fstore, RMConfig(workers=2,
                                          workers_mode="process"))
    ex = ProcessWorkerExecutor(fstore, rm, workers=2)
    dags = [DAG([
        NodeSpec("load", source=paths[0], est_mem=1 << 22),
        NodeSpec("up", fn=upper_op, deps=["load"], est_mem=1 << 22,
                 keep_output=True),
    ], name=f"d{i}") for i in range(2)]
    ex.run(dags)
    try:
        assert ex.load_runs == 1
        t0 = SipcReader(fstore).read_table(dags[0].nodes["up"].output)
        t1 = SipcReader(fstore).read_table(dags[1].nodes["up"].output)
        assert t0.equals(t1)
    finally:
        ex.close()
        fstore.close()


# ---------------------------------------------------------------------------
# worker-crash fault injection
# ---------------------------------------------------------------------------

def test_worker_crash_mid_node_is_retried(tmp_path):
    """A worker SIGKILLed mid-node loses nothing: the request (references
    only, side-effect free) is replayed on a surviving worker, the node
    completes, and the RM's admission reservations fully drain."""
    paths = _write_shards(str(tmp_path), n=1)
    fstore = _file_store(tmp_path)
    rm = ResourceManager(fstore, RMConfig(workers=2,
                                          workers_mode="process"))
    ex = ProcessWorkerExecutor(fstore, rm, workers=2)
    marker = os.path.join(str(tmp_path), "crashed-once")
    dag = DAG([
        NodeSpec("load", source=paths[0], est_mem=1 << 22),
        NodeSpec("op", fn=functools.partial(crash_once_op, marker=marker),
                 deps=["load"], est_mem=1 << 22, keep_output=True),
    ], name="crash")
    try:
        ex.run([dag])
        assert os.path.exists(marker)          # it really died once
        assert ex.worker_retries == 1
        assert ex._pool.live_workers == 1      # the victim stayed retired
        assert dag.all_done()
        assert rm.admission.reserved == 0      # reservations released
        assert ex._inflight == {}
        t = SipcReader(fstore).read_table(dag.nodes["op"].output)
        assert t.num_rows > 0
    finally:
        ex.close()
        fstore.close()


def test_poison_op_quarantined_pool_survives(tmp_path):
    """An op that SIGKILLs its worker on every attempt exhausts its node
    retries and poisons *its own DAG* — run() returns (no raise), the
    DAG lands in the typed 'poisoned' outcome, every RM reservation
    drains, the offending fn is quarantined, and the pool heals back to
    at least one live worker so later DAGs still run."""
    paths = _write_shards(str(tmp_path), n=1)
    fstore = _file_store(tmp_path)
    rm = ResourceManager(fstore, RMConfig(workers=1, workers_mode="process",
                                          max_node_retries=2,
                                          retry_backoff_s=0.01))
    ex = ProcessWorkerExecutor(fstore, rm, workers=1)
    dag = DAG([
        NodeSpec("load", source=paths[0], est_mem=1 << 22),
        NodeSpec("op", fn=crash_always_op, deps=["load"], est_mem=1 << 22),
    ], name="doomed")
    try:
        ex.run([dag])                       # returns; does NOT raise
        assert dag.outcome == "poisoned"
        assert dag.cancelled
        assert rm.serve_stats["poisoned"] == 1
        assert rm.quarantined                # the killer fn is blacklisted
        assert rm.admission.reserved == 0
        assert ex._inflight == {}
        assert ex._pool.respawns >= 1        # replacements were spawned
        assert ex._pool.live_workers >= 1    # ...and the pool healed

        # the pool is still usable: an innocent DAG completes normally
        dag2 = DAG([
            NodeSpec("load", source=paths[0], est_mem=1 << 22),
            NodeSpec("op", fn=filter_even_op, deps=["load"], est_mem=1 << 22,
                     keep_output=True),
        ], name="innocent")
        ex.run([dag2])
        assert dag2.all_done() and dag2.outcome == "completed"
        t = SipcReader(fstore).read_table(dag2.nodes["op"].output)
        assert t.num_rows > 0
    finally:
        ex.close()
        fstore.close()


# ---------------------------------------------------------------------------
# pipeline integration
# ---------------------------------------------------------------------------

def test_pipeline_process_mode_equals_thread_mode(tmp_path):
    from repro.data.pipeline import (PipelineConfig, ZerrowDataPipeline,
                                     make_text_shards)
    shards = make_text_shards(os.path.join(str(tmp_path), "corpus"),
                              n_shards=2, rows_per_shard=400)
    batches = {}
    for mode in ("thread", "process"):
        pipe = ZerrowDataPipeline(shards, PipelineConfig(
            batch=4, seq_len=64, workers=2, workers_mode=mode))
        batches[mode] = [b["tokens"].copy()
                         for _, b in zip(range(4), pipe.batches(epochs=1))]
        pipe.close()
    assert len(batches["thread"]) == len(batches["process"])
    for a, b in zip(batches["thread"], batches["process"]):
        np.testing.assert_array_equal(a, b)


# ---------------------------------------------------------------------------
# flight server / client
# ---------------------------------------------------------------------------

def test_flight_server_client_roundtrip(tmp_path):
    server = FlightServer(store=_file_store(tmp_path, "server"))
    producer = _file_store(tmp_path, "producer")
    sb = Sandbox(producer, KernelZero(producer), "p", mode="zero")
    t = _make_table()
    pc = FlightClient(server.sock_path, store=producer)
    pc.put("tbl", sb.write_output(t, label="t"))

    consumer = FlightClient(server.sock_path,
                            store=_file_store(tmp_path, "consumer"))
    assert consumer.list() == ["tbl"]
    msg = consumer.get("tbl")
    t2 = SipcReader(consumer.store).read_table(msg)
    assert t2.equals(t)
    assert consumer.store.copied_bytes == 0
    # the exchange moved only control frames
    assert consumer.wire_bytes < t.nbytes
    stats = consumer.stats()
    assert stats["requests"] >= 3
    consumer.drop("tbl")
    assert consumer.list() == []
    consumer.store.close()
    consumer.close()
    pc.close()
    producer.close()
    server.close()
    server.store.close()
