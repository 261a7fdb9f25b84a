"""TPC-H customer, orders and lineitem (spec clause 4.2.3), generated
from a seed with dbgen's key layout and value ranges.

``generate(config, rng)`` returns ``{table: {column: ndarray}}``: the
customer and orders row counts of ``config["tables"]``, and 1-7
lineitems per order.  Only the columns Q18 reads are made; what each
value follows is listed under ``assumed`` in the configuration file.
"""

from __future__ import annotations

import numpy as np

from generators.ssb import retail_price_cents

EPOCH_1992 = int(np.datetime64("1992-01-01").astype(np.int64))
EPOCH_1998_END = int(np.datetime64("1998-12-31").astype(np.int64))
N_PARTS_SF1 = 200000


def sparse_orderkeys(n: int) -> np.ndarray:
    """dbgen's order keys: 8 used in every 32, starting at 1."""
    i = np.arange(n, dtype=np.int64)
    return (i // 8) * 32 + i % 8 + 1


def generate(config: dict, rng: np.random.Generator) -> dict:
    tables = config["tables"]
    n_cust, n_ord = tables["customer"]["rows"], tables["orders"]["rows"]
    lo, hi = tables["lineitem"]["rows_per_order"]

    custkey = np.arange(1, n_cust + 1, dtype=np.int64)
    customer = {"custkey": custkey,
                "c_name": np.char.add(b"Customer#", np.char.zfill(
                    custkey.astype("S9"), 9))}

    orderkey = sparse_orderkeys(n_ord)
    active = custkey[custkey % 3 != 0]
    lines = rng.integers(lo, hi + 1, n_ord)
    n_line = int(lines.sum())
    line_order = np.repeat(np.arange(n_ord), lines)
    quantity = rng.integers(1, 51, n_line, dtype=np.int64) * 100
    price = retail_price_cents(rng.integers(1, N_PARTS_SF1 + 1, n_line))
    discount = rng.integers(0, 11, n_line, dtype=np.int64)
    tax = rng.integers(0, 9, n_line, dtype=np.int64)
    # quantity in hundredths times price in cents is 1/100 cent; the
    # (100 + tax) and (100 - discount) percentages add two more 1/100s
    line_total = quantity * price * (100 + tax) * (100 - discount) \
        // 1_000_000
    first_line = np.concatenate([[0], np.cumsum(lines)[:-1]])
    orders = {
        "orderkey": orderkey,
        "custkey": active[rng.integers(0, len(active), n_ord)],
        "o_orderdate": rng.integers(EPOCH_1992, EPOCH_1998_END - 151 + 1,
                                    n_ord).astype(np.int32),
        "o_totalprice": np.add.reduceat(line_total, first_line),
    }
    lineitem = {"orderkey": orderkey[line_order], "l_quantity": quantity}
    return {"customer": customer, "orders": orders, "lineitem": lineitem}
