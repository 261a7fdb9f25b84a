"""BENCHMARK.json: every cell, configuration, traffic mix and metric is
found by name, and every name, unit and line keeps to its limits."""

import json
import os

import pytest

from harness import cellrun, queryspec, roofline, spec

BENCH = json.load(open(os.path.join(spec.ROOT, "BENCHMARK.json")))
CELLS = [w["name"] for w in BENCH["workloads"]]
METRICS = BENCH["end_to_end"] + BENCH["per_layer"]


def test_top_level_keys():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert BENCH["paths"] == ["bench"]
    assert BENCH["command"][1] == "bench/run.py"
    assert 1 <= BENCH["run_seconds"] <= 51
    # a full check of 24 cells fits its 43200 s
    runs = 2 + 14 * 24
    assert runs * (BENCH["run_seconds"] + 60) + 24 * 2 * 90 + 1200 <= 43200


@pytest.mark.parametrize("entry", BENCH["configs"] + BENCH["workloads"]
                         + METRICS, ids=lambda e: e["name"])
def test_names_and_lines(entry):
    assert spec.NAME.match(entry["name"])
    for key in ("why", "layer", "source"):
        if key in entry:
            assert 1 <= len(entry[key]) <= 200
            assert "\n" not in entry[key] and "\t" not in entry[key]
    if "unit" in entry:
        assert spec.UNIT.match(entry["unit"])
        assert entry["better"] in ("lower", "higher")


@pytest.mark.parametrize("cell", CELLS)
def test_cell_finds_its_files(cell):
    c = spec.cell(BENCH, cell)
    assert c["chips"] == 1
    config = spec.config(BENCH, c)
    assert spec.NAME.match(c["traffic"]) and spec.NAME.match(c["config"])
    entry = next(e for e in BENCH["configs"] if e["name"] == c["config"])
    assert entry["file"].startswith("bench/configs/")
    assert set(entry["reduced"]) == set(config["reduced"])
    assert all(k in config for k in entry["reduced"])
    assert config["assumed"] and config["source"]
    assert hasattr(spec.generator(config), "generate")
    spec.path_kernels(spec.traffic(c))
    for q in spec.traffic(c)["queries"]:
        queryspec.validate(q["plan"])
        assert set(queryspec.scanned_tables(q["plan"])) <= \
            set(config["tables"])
    names = {m["name"] for m in spec.end_to_end(BENCH, cell)}
    assert {"setup_s", "rows_per_s"} <= names
    assert spec.per_layer(BENCH, cell)


@pytest.mark.parametrize("metric", BENCH["per_layer"],
                         ids=lambda m: m["name"])
def test_per_layer_metric_reader(metric):
    assert metric["moves"] in {m["name"] for m in BENCH["end_to_end"]}
    assert set(metric["workloads"]) <= set(CELLS)
    assert metric["source"] in ("device_trace", "program_span",
                                "program_counter", "host_clock")
    module = spec.reader(metric["name"])
    assert module.read(cellrun.RunRecord()) is None   # nothing to read


def test_end_to_end_bounds():
    by = {m["name"]: m for m in BENCH["end_to_end"]}
    assert by["setup_s"]["bound"] == 0.25
    for m in BENCH["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25
        assert m["source"] in ("host_clock", "device_trace")


#: self seconds of a traced window's program spans: an op, the kernel
#: call inside it and a load (which neither span metric reads)
SPAN_SELF_S = {"ops.join": 0.5, "ops.join.probe": 0.25, "kernel.put": 0.125,
               "kernel.fetch": 0.375, "loader.decode": 2.0}


@pytest.mark.parametrize("span_self_s", [None, {}, SPAN_SELF_S],
                         ids=["untraced", "no-spans", "spans"])
def test_readers_on_a_record(span_self_s):
    record = cellrun.RunRecord(
        span_self_s=span_self_s,
        peaks={"hbm_bytes_per_s": 1e9},
        trace={"busy_s": 0.5, "window_s": 2.0,
               "program_s": {"_hash_core": 0.01, "_segreduce_core": 0.02}},
        queries=[{"name": "q", "rows_scanned": 100, "load_s": 0.25,
                  "bytes_copied": 300,
                  "ops": [{"op": "join", "left_rows": 1000,
                           "right_rows": 10, "key_bytes": 4,
                           "matches": 100},
                          {"op": "group_by", "rows": 100, "groups": 5,
                           "value_bytes": [8]}]}])
    read = {m["name"]: spec.reader(m["name"]).read(record)
            for m in BENCH["per_layer"]}
    assert read["device.idle_pct"] == pytest.approx(75.0)
    assert read["kernels.join_roofline"] == pytest.approx(
        100 * (1010 * 4 + 1600) / 1e9 / 0.01)
    assert read["kernels.reduce_roofline"] == pytest.approx(
        100 * (100 * 16 + 40) / 1e9 / 0.02)
    assert read["data_plane.copied_bytes_per_row"] == 3.0
    assert read["loaders.load_s_per_query"] == 0.25
    if span_self_s:
        assert read["ops.host_s_per_query"] == 0.75
        assert read["kdispatch.call_s_per_query"] == 0.5
    else:
        assert read["ops.host_s_per_query"] is None
        assert read["kdispatch.call_s_per_query"] is None


@pytest.mark.parametrize("misses", [0, 7])
def test_join_bytes_count_misses(misses):
    """A left join writes one (left, -1) index pair per miss; an entry
    without ``misses`` (an inner join) needs the bytes it always did."""
    op = {"op": "join", "left_rows": 1000, "right_rows": 10,
          "key_bytes": 4, "matches": 100}
    base = roofline.join_bytes(op)
    assert base == 1010 * 4 + 100 * 16
    assert roofline.join_bytes(dict(op, misses=misses)) == base + misses * 16
