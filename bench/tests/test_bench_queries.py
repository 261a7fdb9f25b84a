"""Every query of the mixes, through ``compile_plans`` and a thread
``Executor`` with the device kernels (jax's CPU backend here), matches
the plain reference at a tiny scale; so do TPC-H Q13's left join and
counts, which no mix runs yet; and the reference's left joins and nulls
on a frame made by hand."""

import copy

import numpy as np
import pytest

from harness import program, queryspec, reference, spec
from repro.core import ops

BENCH = spec.load_benchmark()


def tables_for(cell: str, tmp_path, seed: int = 2 ** 31 + 3, **rows):
    c = spec.cell(BENCH, cell)
    config = copy.deepcopy(spec.config(BENCH, c))
    for table, n in rows.items():
        config["tables"][table]["rows"] = n
    tables = spec.generator(config).generate(config, spec.rng(seed))
    paths = program.write_tables(tables, str(tmp_path))
    dict_columns = {t: tuple(s.get("dict_columns", ()))
                    for t, s in config["tables"].items()}
    return tables, paths, dict_columns, spec.traffic(c)["queries"]


def check(query: dict, tables, paths, dict_columns, tmp_path) -> int:
    plan = program.build_plan(query["plan"], paths, dict_columns)
    got = program.run_query(plan, query["name"], str(tmp_path / "swap"))
    want, stats = reference.run(query["plan"], tables)
    assert reference.wrong_rows(want, got.rows) == 0
    assert got.load_s > 0 and stats
    return len(want)


@pytest.fixture(scope="module")
def ssb_data(tmp_path_factory):
    tmp = tmp_path_factory.mktemp("ssb")
    return tmp, tables_for("ssb_sf1.q2q3", tmp, lineorder=40000,
                           part=4000, customer=3000)


@pytest.mark.parametrize("i", range(7), ids=lambda i: f"ssb-q{i}")
def test_ssb_query_matches_reference(i, ssb_data, monkeypatch):
    monkeypatch.setenv("ZERROW_KERNEL_BACKEND", "pallas")
    tmp, (tables, paths, dict_columns, queries) = ssb_data
    n = check(queries[i], tables, paths, dict_columns, tmp)
    if queries[i]["name"] in ("Q2.1", "Q3.1", "Q3.2"):
        assert n > 0


@pytest.mark.parametrize("threshold", [30000, 20000])
def test_q18_matches_reference(threshold, tmp_path, monkeypatch):
    """Q18 as the mix runs it and, to have rows at this scale, with its
    subquery's threshold lowered."""
    monkeypatch.setenv("ZERROW_KERNEL_BACKEND", "pallas")
    tables, paths, dict_columns, queries = tables_for(
        "tpch_sf1.q18", tmp_path, orders=20000, customer=2000)
    query = copy.deepcopy(queries[0])
    sub = query["plan"]["steps"][0]["join"]["steps"][0]["join"]
    sub["steps"][1]["filter"][2] = threshold
    n = check(query, tables, paths, dict_columns, tmp_path)
    if threshold < 30000:
        assert n > 0


#: TPC-H Q13's outer join and its two counts, the comment predicate
#: (``o_comment not like``) left out: no traffic runs this yet.
Q13_PLAN = {"scan": "customer", "steps": [
    {"join": {"scan": "orders"}, "on": "custkey", "how": "left"},
    {"group_by": ["custkey"], "aggs": {"c_count": ["orderkey", "count"]}},
    {"group_by": ["c_count"], "aggs": {"custdist": ["custkey", "count"]}}]}


@pytest.fixture
def q13_data(tmp_path, monkeypatch):
    monkeypatch.setenv("ZERROW_KERNEL_BACKEND", "pallas")
    tables, paths, dict_columns, _ = tables_for(
        "tpch_sf1.q18", tmp_path, orders=20000, customer=2000)
    return tables, paths, dict_columns


def test_q13_matches_reference(q13_data, tmp_path):
    """The program's left join and null-aware counts match the
    reference: the customers with no orders (custkey mod 3 = 0) come
    out as the c_count 0 group."""
    tables, paths, dict_columns = q13_data
    check({"name": "Q13", "plan": Q13_PLAN}, tables, paths, dict_columns,
          tmp_path)
    want, stats = reference.run(Q13_PLAN, tables)
    assert stats[0]["misses"] > 0
    assert (0, stats[0]["misses"]) in want


def test_left_join_as_inner_is_not_correct(q13_data, tmp_path,
                                           monkeypatch):
    """The comparison catches a left join run as an inner one."""
    real = ops.join

    def inner(left, right, on, how="inner", suffix="_right"):
        return real(left, right, on, how="inner", suffix=suffix)

    monkeypatch.setattr(ops, "join", inner)
    tables, paths, dict_columns = q13_data
    plan = program.build_plan(Q13_PLAN, paths, dict_columns)
    got = program.run_query(plan, "Q13", str(tmp_path / "swap"))
    want, _ = reference.run(Q13_PLAN, tables)
    assert reference.wrong_rows(want, got.rows) > 0


HAND = {"L": {"k": np.array([1, 2, 3, 4]), "a": np.array([10, 20, 30, 40])},
        "R": {"k": np.array([1, 1, 3, 5]), "v": np.array([5, 6, 7, 8])}}


def joined(how, *steps):
    return {"scan": "L", "steps": [{"join": {"scan": "R"}, "on": "k",
                                    "how": how}, *steps]}


@pytest.mark.parametrize("plan, want", [
    (joined("inner"), [(1, 10, 5), (1, 10, 6), (3, 30, 7)]),
    (joined("left"), [(1, 10, 5), (1, 10, 6), (2, 20, None), (3, 30, 7),
                      (4, 40, None)]),
    (joined("left", {"group_by": ["k"], "aggs": {
        "n": ["v", "count"], "rows": ["a", "count"], "s": ["v", "sum"],
        "lo": ["v", "min"], "hi": ["v", "max"]}}),
     [(1, 2, 2, 11, 5, 6), (2, 0, 1, None, None, None), (3, 1, 1, 7, 7, 7),
      (4, 0, 1, None, None, None)]),
], ids=["inner", "left-miss", "aggs-skip-nulls"])
def test_reference_left_join_and_nulls(plan, want):
    queryspec.validate(plan)
    got, stats = reference.run(plan, HAND)
    assert sorted(got, key=repr) == sorted(want, key=repr)
    assert stats[0]["matches"] == 3
    assert stats[0]["misses"] == (2 if plan["steps"][0]["how"] == "left"
                                  else 0)


@pytest.mark.parametrize("step, use", [
    ({"group_by": ["v"], "aggs": {"n": ["k", "count"]}}, "a group-by"),
    ({"filter": ["<", "v", 7]}, "a filter"),
    ({"filter": ["!=", "v", 7]}, "a filter"),
    ({"join": {"scan": "R"}, "on": "v"}, "a join"),
], ids=["group-key", "filter", "filter-ne", "join-key"])
def test_reference_refuses_nullable_keys(step, use):
    """No traffic keys, groups or filters on a column a left join made
    nullable, so the reference has no semantics for it and says so."""
    with pytest.raises(ValueError, match=f"{use} on 'v'"):
        reference.run(joined("left", step), HAND)


@pytest.mark.parametrize("control, want", [
    (False, [(1, 2), (2, 0), (3, 1), (4, 0)]),
    (True, [(1, 2), (2, 1), (3, 1), (4, 1)])])
def test_control_counts_nulls(control, want):
    """The control's count counts rows, nulls included."""
    plan = joined("left", {"group_by": ["k"], "aggs": {"n": ["v", "count"]}})
    got, _ = reference.run(plan, HAND, control=control)
    assert sorted(got) == want


@pytest.mark.parametrize("how", ["outer", "right", "full", None])
def test_validate_rejects_join_kind(how):
    with pytest.raises(ValueError, match="join how"):
        queryspec.validate(joined(how))
