"""Star Schema Benchmark tables (O'Neil et al., TPCTC 2009), generated
from a seed with dbgen's value distributions.

``generate(config, rng)`` returns ``{table: {column: ndarray}}`` with
the row counts of ``config["tables"]``.  Integers are numpy integer
arrays of the configured width, text is a numpy bytes (``S``) array.
Only the columns of ``config["tables"]`` are made; what each value
follows is listed under ``assumed`` in the configuration file.
"""

from __future__ import annotations

import numpy as np

#: the 25 TPC-H nations, five to each of the five regions
REGIONS = {
    "AFRICA": ["ALGERIA", "ETHIOPIA", "KENYA", "MOROCCO", "MOZAMBIQUE"],
    "AMERICA": ["ARGENTINA", "BRAZIL", "CANADA", "PERU", "UNITED STATES"],
    "ASIA": ["INDIA", "INDONESIA", "JAPAN", "CHINA", "VIETNAM"],
    "EUROPE": ["FRANCE", "GERMANY", "ROMANIA", "RUSSIA", "UNITED KINGDOM"],
    "MIDDLE EAST": ["EGYPT", "IRAN", "IRAQ", "JORDAN", "SAUDI ARABIA"],
}
NATIONS = [n for ns in REGIONS.values() for n in ns]
NATION_REGION = [r for r, ns in REGIONS.items() for _ in ns]

#: SSB's date table spans 1992-01-01 .. 1998-12-30: 2556 days
FIRST_DAY = np.datetime64("1992-01-01")
N_DAYS = 2556
#: TPC-H draws order dates up to ENDDATE - 151 days
ORDER_DAYS = N_DAYS - 151

MONTHS = ["Jan", "Feb", "Mar", "Apr", "May", "Jun", "Jul", "Aug", "Sep",
          "Oct", "Nov", "Dec"]


def retail_price_cents(partkey: np.ndarray) -> np.ndarray:
    """TPC-H clause 4.2.3 p_retailprice, in cents."""
    pk = partkey.astype(np.int64)
    return 90000 + (pk // 10) % 20001 + 100 * (pk % 1000)


def _balanced(rng, n: int, k: int) -> np.ndarray:
    """``n`` draws of ``0 .. k-1``, each value ``n / k`` times (within
    one), in a random order: uniform like dbgen's draws, but with the
    same count of every value on every seed, so a seed changes which
    rows a filter keeps and not how many."""
    return rng.permutation(np.arange(n) % k)


def _geography(rng, n: int, prefix: str) -> dict:
    """city / nation / region columns for ``n`` suppliers or customers."""
    city = _balanced(rng, n, len(NATIONS) * 10)
    nation, digit = city % len(NATIONS), city // len(NATIONS)
    cities = np.array([f"{name[:9]:<9}{d}" for name in NATIONS
                       for d in range(10)], dtype="S")
    return {
        f"{prefix}_city": cities[nation * 10 + digit],
        f"{prefix}_nation": np.array(NATIONS, dtype="S")[nation],
        f"{prefix}_region": np.array(NATION_REGION, dtype="S")[nation],
    }


def _dates() -> dict:
    days = FIRST_DAY + np.arange(N_DAYS)
    year = days.astype("datetime64[Y]").astype(np.int64) + 1970
    month = days.astype("datetime64[M]").astype(np.int64) % 12
    dom = (days - days.astype("datetime64[M]")).astype(np.int64) + 1
    ym = np.array([f"{MONTHS[m]}{y}" for m, y in zip(month, year)], dtype="S")
    return {"datekey": (year * 10000 + (month + 1) * 100 + dom)
            .astype(np.int32),
            "d_year": year.astype(np.int32), "d_yearmonth": ym}


def generate(config: dict, rng: np.random.Generator) -> dict:
    rows = {t: spec["rows"] for t, spec in config["tables"].items()}
    if rows["date"] != N_DAYS:
        raise ValueError(f"SSB's date table has {N_DAYS} rows, not "
                         f"{rows['date']}")
    n_part, n_supp, n_cust = rows["part"], rows["supplier"], rows["customer"]

    combo = _balanced(rng, n_part, 1000)       # category x brand
    mfgr, cat, brand = combo % 5 + 1, combo // 5 % 5 + 1, combo // 25 + 1
    categories = np.array([f"MFGR#{m}{c}" for m in range(1, 6)
                           for c in range(1, 6)], dtype="S")
    brands = np.array([f"MFGR#{m}{c}{b}" for m in range(1, 6)
                       for c in range(1, 6) for b in range(1, 41)], dtype="S")
    part = {"partkey": np.arange(1, n_part + 1, dtype=np.int32),
            "p_category": categories[(mfgr - 1) * 5 + cat - 1],
            "p_brand1": brands[((mfgr - 1) * 5 + cat - 1) * 40 + brand - 1]}

    supplier = {"suppkey": np.arange(1, n_supp + 1, dtype=np.int32),
                **_geography(rng, n_supp, "s")}
    customer = {"custkey": np.arange(1, n_cust + 1, dtype=np.int32),
                **_geography(rng, n_cust, "c")}
    date = _dates()

    n = rows["lineorder"]
    partkey = rng.integers(1, n_part + 1, n, dtype=np.int32)
    quantity = rng.integers(1, 51, n, dtype=np.int64)
    discount = rng.integers(0, 11, n, dtype=np.int64)
    revenue = quantity * retail_price_cents(partkey) * (100 - discount) // 100
    lineorder = {
        "datekey": date["datekey"][rng.integers(0, ORDER_DAYS, n)],
        "partkey": partkey,
        "suppkey": rng.integers(1, n_supp + 1, n, dtype=np.int32),
        "custkey": rng.integers(1, n_cust + 1, n, dtype=np.int32),
        "lo_revenue": revenue.astype(np.int32),
    }
    return {"lineorder": lineorder, "part": part, "supplier": supplier,
            "customer": customer, "date": date}
