"""The trace reduction: busy/idle union, device time per program, and
idle gaps named by the host span around them — on a hand-made trace,
and on a small trace recorded on a TPU v5e (``bench/testdata``, made by
``bench/record_trace.py``) against the numbers written beside it."""

import gzip
import json
import os

import pytest

from harness import trace

TESTDATA = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "testdata")


def test_union_merges_overlaps():
    assert trace.union([(5, 7), (0, 2), (1, 3), (7, 9), (10, 11)]) == \
        [(0, 3), (5, 9), (10, 11)]


def test_program_function():
    assert trace.program_function("jit__hash_core(12)") == "_hash_core"
    assert trace.program_function("jit__segreduce_core") == \
        "_segreduce_core"
    assert trace.program_function("jit__gather_core.3") == "_gather_core"
    assert trace.program_function("copy-start") == "copy-start"


def test_reduce_hand_made():
    s = 1_000_000_000                      # ns per second
    compact = {
        "window": [0, 10 * s],
        "devices": {"/device:TPU:0": {
            "programs": [["jit__hash_core(1)", 1 * s, int(1.5 * s)],
                         ["jit__segreduce_core(2)", 6 * s, 2 * s],
                         ["jit__gather_core(3)", int(9.5 * s), 1 * s]],
            "ops": [["%fusion", "jit__hash_core(1)", 1 * s, 1 * s],
                    ["%fusion.1", "jit__hash_core(1)", int(1.5 * s), 1 * s],
                    ["%scatter", "jit__segreduce_core(2)", 6 * s, 2 * s],
                    ["%late", "jit__gather_core(3)", int(9.5 * s), 1 * s]]}},
        "host": [["query Q1", 0, 9 * s], ["op:join", 0, 3 * s],
                 ["op:group_by", 4 * s, 5 * s]]}
    r = trace.reduce(compact)
    assert r["window_s"] == 10
    assert r["busy_s"] == pytest.approx(1.5 + 2 + 0.5)
    assert r["program_s"] == pytest.approx(
        {"_hash_core": 1.5, "_segreduce_core": 2.0, "_gather_core": 0.5})
    gaps = dict(map(tuple, r["idle_gaps"]))
    # [0,1) op:join, [2.5,6) mid 4.25 op:group_by, [8,9.5) op:group_by
    assert gaps == pytest.approx({"op:join": 1.0, "op:group_by": 5.0})
    assert r["device_ops"][0][1] == 2.0


def test_gap_outside_ops_and_queries():
    s = 1_000_000_000
    compact = {"window": [0, 4 * s],
               "devices": {"/device:TPU:0": {
                   "programs": [["jit_f", s, s]], "ops": []}},
               "host": [["query Q", 2 * s, 2 * s]]}
    gaps = dict(map(tuple, trace.reduce(compact)["idle_gaps"]))
    assert gaps == pytest.approx({"between queries": 1.0,
                                  "loaders and executor": 2.0})


def _recorded():
    path = os.path.join(TESTDATA, "trace_small.json.gz")
    if not os.path.exists(path):
        pytest.fail(f"missing recorded trace {path}")
    with gzip.open(path, "rt") as fh:
        return json.load(fh)


def test_extract_recorded_chip_trace(tmp_path):
    """``extract`` of the raw recording gives the committed compact form."""
    raw = tmp_path / "trace.xplane.pb"
    with gzip.open(os.path.join(TESTDATA, "trace_small.xplane.pb.gz")) as fh:
        raw.write_bytes(fh.read())
    assert trace.extract(str(raw)) == _recorded()


def test_recorded_chip_trace():
    compact = _recorded()
    with open(os.path.join(TESTDATA, "trace_small.expected.json")) as fh:
        expected = json.load(fh)
    got = trace.reduce(compact)
    assert got["window_s"] == pytest.approx(expected["window_s"])
    assert got["busy_s"] == pytest.approx(expected["busy_s"])
    assert got["program_s"] == pytest.approx(expected["program_s"])
    assert got["idle_gaps"] == expected["idle_gaps"]
    assert 0 < got["busy_s"] < got["window_s"]
    assert {"_hash_core", "_combine_core", "_gather_core",
            "_segreduce_core"} <= set(got["program_s"])
