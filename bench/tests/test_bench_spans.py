"""The program's spans in a trace (``harness.spans``), the span report's
summary (``span_report.summarize``) and the reader of
``kdispatch.transfer_bytes_per_row`` — on hand-made input, and on a
trace recorded here on jax's CPU device."""

import os

import numpy as np
import pytest

import span_report
from harness import cellrun, program, spans, spec, trace
from repro.core import obs

S = 1_000_000_000                          # ns per second


def _compact(n_chips=1):
    plane = {"programs": [["jit__hash_core(1)", int(1.6 * S),
                           int(0.8 * S)]],
             "ops": [["%fusion", "jit__gather_core(2)", 7 * S,
                      int(0.5 * S)],
                     ["%late", "jit__gather_core(2)", int(9.8 * S),
                      int(0.7 * S)]]}
    return {"window": [0, 10 * S],
            "devices": {f"/device:TPU:{i}": plane for i in range(n_chips)},
            "host": []}


#: thread 0: a join with a kernel call (put, run, fetch) and a probe,
#: then a group-by running past the window's end; thread 1: a load and
#: its SIPC write, the load opening while the join is still open
SPANS = [["ops.join", 0, 0, 6 * S],
         ["kernel.hash_fixed", 0, 1 * S, 2 * S],
         ["kernel.put", 0, 1 * S, int(0.5 * S)],
         ["kernel.run", 0, int(1.5 * S), 1 * S],
         ["kernel.fetch", 0, int(2.5 * S), int(0.5 * S)],
         ["ops.join.probe", 0, 4 * S, 1 * S],
         ["ops.group_by", 0, int(9.5 * S), int(1.5 * S)],
         ["loader.decode", 1, int(5.5 * S), int(2.5 * S)],
         ["sipc.write", 1, 8 * S, 1 * S]]


def test_self_seconds_two_threads():
    got = spans.reduce(_compact(), SPANS)["span_self_s"]
    assert got == pytest.approx({
        "ops.join": 3.0, "kernel.put": 0.5, "kernel.run": 1.0,
        "kernel.fetch": 0.5, "ops.join.probe": 1.0, "ops.group_by": 0.5,
        "loader.decode": 2.5, "sipc.write": 1.0})
    assert "kernel.hash_fixed" not in got      # its children cover it


@pytest.mark.parametrize("n_chips", [1, 2])
def test_idle_split_by_overlap_over_innermost_span(n_chips):
    r = spans.reduce(_compact(n_chips), SPANS)
    # idle: [0, 1.6), [2.4, 7), [7.5, 9.8); at [5.5, 6) the load, opened
    # last, holds the instant; [9, 9.5) has no span open
    assert dict(map(tuple, r["idle_by_span"])) == pytest.approx({
        "ops.join": 2.5, "kernel.put": 0.5, "kernel.run": 0.2,
        "kernel.fetch": 0.5, "ops.join.probe": 1.0, "loader.decode": 2.0,
        "sipc.write": 1.0, "ops.group_by": 0.3, spans.OUTSIDE: 0.5})
    assert r["idle_s"] == pytest.approx(8.5)
    assert r["idle_by_span"][0][0] == "ops.join"
    # the idle time is the one trace.reduce finds
    t = trace.reduce(_compact(n_chips))
    assert r["idle_s"] == pytest.approx(t["window_s"] - t["busy_s"])


def test_no_program_spans():
    r = spans.reduce(_compact(), [])
    assert r["span_self_s"] == {}
    assert r["idle_by_span"] == [[spans.OUTSIDE, pytest.approx(8.5)]]
    assert spans.seconds_per_query({}, "ops.", 4) is None


def test_seconds_per_query():
    selfs = spans.reduce(_compact(), SPANS)["span_self_s"]
    assert spans.seconds_per_query(selfs, "ops.", 2) == pytest.approx(2.25)
    assert spans.seconds_per_query(selfs, "kernel.", 2) == \
        pytest.approx(1.0)
    assert spans.seconds_per_query(selfs, "ops.", 0) is None


def test_summary_of_a_run():
    lines = ["set-up 30.000 s: ...",
             "window: 5 queries issued, 4 completed inside 51.0 s, last at "
             "48.500 s, 24818224 rows scanned",
             "compiles in the window: 0 (expected 0)"]
    report = span_report.summarize({"attempted": 2}, lines, _compact(),
                                   SPANS)
    assert report["traced_rows_per_s"] == pytest.approx(24818224 / 48.5)
    assert report["traced_completed"] == 4
    assert report["window_compiles"] == 0
    assert report["ops.host_s_per_query"] == pytest.approx(2.25)
    assert report["kdispatch.call_s_per_query"] == pytest.approx(1.0)
    assert report["idle_in_spans_pct"] == pytest.approx(100 * 8 / 8.5)
    assert report["idle_gaps"] == trace.reduce(_compact())["idle_gaps"]


def test_transfer_bytes_reader():
    read = spec.reader("kdispatch.transfer_bytes_per_row").read
    record = cellrun.RunRecord(queries=[{"name": "a", "rows_scanned": 60},
                                        {"name": "b", "rows_scanned": 40}])
    obs.reset("kernel.")
    assert read(record) is None                 # no device call counted
    obs.add("kernel.h2d_bytes", 600)
    obs.add("kernel.d2h_bytes", 400)
    obs.add("kernel.calls.hash_fixed.tpu")
    assert read(record) == 10.0
    assert read(cellrun.RunRecord()) is None    # no rows
    obs.reset("kernel.")


def test_spans_nest_in_a_recorded_trace(tmp_path, monkeypatch):
    """On jax's CPU device: the harness's query span holds ``ops.join``,
    which holds ``kernel.hash_fixed``, which holds ``kernel.put``, all
    on one thread."""
    jax = pytest.importorskip("jax")
    from repro.core.plan import scan
    monkeypatch.setenv("ZERROW_KERNEL_BACKEND", "pallas")
    rng = np.random.default_rng(5)
    paths = program.write_tables({
        "fact": {"k": rng.integers(0, 300, 5000).astype(np.int32),
                 "v": rng.integers(0, 100, 5000).astype(np.int64)},
        "dim": {"k": np.arange(250, dtype=np.int32),
                "g": rng.integers(0, 7, 250).astype(np.int64)}},
        str(tmp_path))
    plan = scan(paths["fact"]).join(scan(paths["dim"]), on="k") \
        .group_by("g", {"s": ("v", "sum")})
    swap = str(tmp_path / "swap")
    program.run_query(plan, "Q", swap)       # compile outside the trace
    logdir = str(tmp_path / "profile")
    jax.profiler.start_trace(logdir)
    try:
        with jax.profiler.TraceAnnotation(trace.WINDOW):
            with jax.profiler.TraceAnnotation("query Q"):
                program.run_query(plan, "Q", swap, op_spans=True)
    finally:
        jax.profiler.stop_trace()
    path = [os.path.join(d, f) for d, _, fs in os.walk(logdir)
            for f in fs if f.endswith(".xplane.pb")][0]
    compact = trace.extract(path)
    found = spans.extract(path)

    def inside(inner, outer):
        return outer[2] <= inner[2] and \
            inner[2] + inner[3] <= outer[2] + outer[3]

    query = next(h for h in compact["host"] if h[0] == "query Q")
    joins = [s for s in found if s[0] == "ops.join"]
    assert joins
    nested = [(j, h, p) for j in joins
              for h in found if h[0] == "kernel.hash_fixed"
              and h[1] == j[1] and inside(h, j)
              for p in found if p[0] == "kernel.put"
              and p[1] == j[1] and inside(p, h)]
    assert nested
    j = nested[0][0]
    assert query[1] <= j[2] and j[2] + j[3] <= query[1] + query[2]
    names = {s[0] for s in found}
    assert {"ops.join.hash", "ops.join.probe", "ops.join.gather_idx",
            "ops.join.confirm", "ops.join.output", "ops.group_by",
            "ops.group_by.codes", "ops.group_by.ranges", "ops.group_by.keys",
            "ops.group_by.reduce", "kernel.run", "kernel.fetch",
            "kernel.grouped_sum", "loader.decode", "sipc.read",
            "sipc.write"} <= names
    r = spans.reduce(compact, found)
    assert r["span_self_s"]["ops.join"] > 0
