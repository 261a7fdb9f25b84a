"""Every query of both mixes, through ``compile_plans`` and a thread
``Executor`` with the device kernels (jax's CPU backend here), matches
the plain reference at a tiny scale."""

import copy

import pytest

from harness import program, reference, spec

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
