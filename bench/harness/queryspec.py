"""The query language of the traffic files, and what a query costs.

A query is JSON data, so that a traffic mix is a data file:

    plan      := {"scan": <table>, "steps": [step, ...]}       (steps optional)
    step      := {"filter": pred}
               | {"join": plan, "on": <column>, "how": <join>}   (how optional)
               | {"group_by": [<key>, ...], "aggs": {<out>: [<column>, <agg>]}}
    join      := "inner" (the default) | "left"          (left outer join)
    agg       := "sum" | "min" | "max" | "count"
    pred      := ["and", pred, pred, ...] | ["or", pred, pred, ...]
               | [<op>, <column>, <literal>]      op: == != < <= > >=
               | ["in", <column>, [<literal>, ...]]   (an OR of equalities)
               | ["between", <column>, <lo>, <hi>]    (inclusive)
               | ["like", <column>, <pattern>]      (SQL LIKE, % only)
               | ["not like", <column>, <pattern>]

A join's right side is a plan of its own (a filtered dimension, or a
subquery such as Q18's).  Both sides name the key alike.  A left join
keeps a left row that matches nothing once, its right columns null.
Aggregates follow SQL: nulls are left out of every one, so ``count`` is
``count(column)``, the column's non-null values (its row count where it
has no nulls), and a ``sum``, ``min`` or ``max`` over no value is null.

``like`` is SQL LIKE on a utf8 column, case- and byte-exact, with ``%``
(any run of bytes, the empty one too) as its only wildcard; ``not like``
is its complement.  ``_`` and ``\\`` escapes are refused: TPC-H's LIKEs
(Q2, Q9, Q13, Q14, Q16, Q20) use ``%`` alone.

The program (``harness.program``) and the plain reference
(``harness.reference``) each interpret this tree on their own; this
module only walks it.
"""

from __future__ import annotations

from typing import Dict, Iterator, List

COMPARISONS = ("==", "!=", "<", "<=", ">", ">=")
LIKES = ("like", "not like")
AGGREGATES = ("sum", "min", "max", "count")
JOINS = ("inner", "left")


def scanned_tables(plan: dict) -> List[str]:
    """Every table the plan scans, each once, in first-scan order."""
    out: List[str] = []

    def walk(p: dict) -> None:
        if p["scan"] not in out:
            out.append(p["scan"])
        for step in p.get("steps", ()):
            if "join" in step:
                walk(step["join"])
    walk(plan)
    return out


def steps(plan: dict) -> Iterator[dict]:
    """Every step of the plan and its subplans, depth first."""
    for step in plan.get("steps", ()):
        if "join" in step:
            yield from steps(step["join"])
        yield step


def aggregates_used(plan: dict) -> List[str]:
    """The aggregate functions (``sum``, ...) the plan's group-bys use."""
    return sorted({how for s in steps(plan) if "group_by" in s
                   for _, how in s["aggs"].values()})


def rows_scanned(plan: dict, table_rows: Dict[str, int]) -> int:
    """The rows a query scans: each base table's rows, once per table."""
    return sum(table_rows[t] for t in scanned_tables(plan))


def validate(plan: dict) -> None:
    """Raise ``ValueError`` on anything the interpreters do not know."""
    if set(plan) - {"scan", "steps"} or "scan" not in plan:
        raise ValueError(f"plan keys {sorted(plan)}: want scan, steps")
    for step in plan.get("steps", ()):
        kind = set(step) - {"on", "how", "aggs"}
        if kind == {"filter"}:
            validate_predicate(step["filter"])
        elif kind == {"join"}:
            if not isinstance(step.get("on"), str):
                raise ValueError(f"join needs one key column: {step}")
            if step.get("how", "inner") not in JOINS:
                raise ValueError(f"join how {step['how']!r} not in {JOINS}")
            validate(step["join"])
        elif kind == {"group_by"}:
            for how in (a[1] for a in step["aggs"].values()):
                if how not in AGGREGATES:
                    raise ValueError(f"aggregate {how!r} not in {AGGREGATES}")
        else:
            raise ValueError(f"unknown step {step}")


def validate_predicate(pred: list) -> None:
    op = pred[0]
    if op in ("and", "or"):
        for p in pred[1:]:
            validate_predicate(p)
    elif op in COMPARISONS:
        if len(pred) != 3:
            raise ValueError(f"{op} takes a column and a literal: {pred}")
    elif op == "in":
        if len(pred) != 3 or not pred[2]:
            raise ValueError(f"in takes a column and a literal list: {pred}")
    elif op == "between":
        if len(pred) != 4:
            raise ValueError(f"between takes a column, lo and hi: {pred}")
    elif op in LIKES:
        if len(pred) != 3 or not isinstance(pred[1], str) \
                or not isinstance(pred[2], str):
            raise ValueError(f"{op} takes a column and a pattern: {pred}")
        if "_" in pred[2] or "\\" in pred[2]:
            raise ValueError(f"{op} pattern {pred[2]!r}: only % is a "
                             f"wildcard, and there are no escapes")
    else:
        raise ValueError(f"unknown predicate {op!r}")
