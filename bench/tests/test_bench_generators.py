"""The generators keep their sources' invariants, at a tiny scale."""

import copy

import numpy as np
import pytest

from generators import ssb, tpch
from harness import spec

BENCH = spec.load_benchmark()


def config(name: str, **rows) -> dict:
    c = copy.deepcopy(spec.config(BENCH, {"config": name}))
    for table, n in rows.items():
        c["tables"][table]["rows"] = n
    return c


SSB = config("ssb_sf1", lineorder=20000, part=20000, supplier=500,
             customer=3000)
TPCH = config("tpch_sf1", orders=8000, customer=3000)


def test_ssb_tables():
    t = ssb.generate(SSB, spec.rng(2 ** 31 + 17))
    for name, cols in t.items():
        assert set(cols) == set(SSB["tables"][name]["columns"])
        for col, kind in SSB["tables"][name]["columns"].items():
            if kind == "utf8":
                assert cols[col].dtype.kind == "S"
            else:
                assert cols[col].dtype == np.dtype(kind)
            assert len(cols[col]) == SSB["tables"][name]["rows"]
    part, lo = t["part"], t["lineorder"]
    assert len(set(part["p_category"])) == 25
    _, per_brand = np.unique(part["p_brand1"], return_counts=True)
    assert len(per_brand) == 1000 and set(per_brand) == {20}
    assert all(b.startswith(c) for b, c in
               zip(part["p_brand1"], part["p_category"]))
    for side, prefix in (("supplier", "s"), ("customer", "c")):
        g = t[side]
        assert len(set(g[f"{prefix}_region"])) == 5
        assert len(set(g[f"{prefix}_nation"])) == 25
        _, per_city = np.unique(g[f"{prefix}_city"], return_counts=True)
        assert len(per_city) == 250
        assert set(per_city) == {len(g[f"{prefix}_city"]) // 250}
        for city, nation, region in zip(g[f"{prefix}_city"],
                                        g[f"{prefix}_nation"],
                                        g[f"{prefix}_region"]):
            assert city[:9] == nation[:9].ljust(9)
            assert nation.decode() in ssb.REGIONS[region.decode()]
    d = t["date"]
    assert len(d["datekey"]) == 2556 and d["datekey"][0] == 19920101
    assert set(d["d_year"]) == set(range(1992, 1999))
    assert b"Dec1997" in set(d["d_yearmonth"])
    assert set(lo["datekey"]) <= set(d["datekey"][:ssb.ORDER_DAYS])
    for key, dim in (("partkey", "part"), ("suppkey", "supplier"),
                     ("custkey", "customer")):
        assert lo[key].min() >= 1 and lo[key].max() <= len(t[dim][key])
    price = ssb.retail_price_cents(lo["partkey"])
    assert (lo["lo_revenue"] <= 50 * price).all()
    assert (lo["lo_revenue"] >= price * 90 // 100).all()


def test_tpch_tables():
    t = tpch.generate(TPCH, spec.rng(5))
    orders, li, cust = t["orders"], t["lineitem"], t["customer"]
    assert len(orders["orderkey"]) == 8000 and len(cust["custkey"]) == 3000
    k = orders["orderkey"]
    assert ((k - 1) % 32 < 8).all() and k[0] == 1 and k[8] == 33
    assert (orders["custkey"] % 3 != 0).all()
    assert orders["custkey"].max() <= 3000
    _, lines = np.unique(li["orderkey"], return_counts=True)
    assert lines.min() >= 1 and lines.max() <= 7 and len(lines) == 8000
    assert 3.5 < len(li["orderkey"]) / 8000 < 4.5
    q = li["l_quantity"]
    assert (q % 100 == 0).all() and q.min() >= 100 and q.max() <= 5000
    assert cust["c_name"][0] == b"Customer#000000001"
    assert (orders["o_totalprice"] > 0).all()
    assert orders["o_orderdate"].min() >= tpch.EPOCH_1992
    assert orders["o_orderdate"].max() <= tpch.EPOCH_1998_END - 151


@pytest.mark.parametrize("gen,cfg", [(ssb, SSB), (tpch, TPCH)],
                         ids=["ssb", "tpch"])
def test_same_seed_same_tables(gen, cfg):
    a = gen.generate(cfg, spec.rng(2 ** 33))
    b = gen.generate(cfg, spec.rng(2 ** 33))
    c = gen.generate(cfg, spec.rng(2 ** 33 + 1))
    for table in a:
        for col in a[table]:
            assert np.array_equal(a[table][col], b[table][col])
    assert any(not np.array_equal(a[t][k], c[t][k])
               for t in a for k in a[t] if len(a[t][k]) == len(c[t][k]))
