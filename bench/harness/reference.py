"""The plain reference: the traffic's queries evaluated with numpy alone.

It reads the generated tables (``{table: {column: ndarray}}``, text as
numpy bytes arrays) and imports nothing of the program: a join sorts the
right key and binary-searches the left one, a group-by lexsorts its keys
and reduces each run with ``ufunc.reduceat``, a ``like`` matches its
pattern's pieces with ``np.strings``.  Integer aggregates accumulate in
int64, so every result is exact.

Nulls follow SQL as far as a left join brings them in.  Each right
column a left join brings in has a boolean validity array beside it
(``Frame.valid``); every aggregate leaves nulls out, so ``count``
counts a column's non-null values and a ``sum``, ``min`` or ``max``
over no value is null; ``rows`` turns a null into ``None``.  No traffic
joins, groups or filters on a nullable column, and the reference raises
where a plan does.

``run(plan, tables)`` returns the result rows (tuples of Python values,
text decoded, in no particular order) and the cardinalities of every
join, group-by and ``like``, from which ``harness.roofline`` computes
the bytes those operators need.  ``run(..., control=True)`` is the
control of the comparison, which must come out as not correct.  It takes the two
shortcuts a later change might be tempted to take:

* every number is cast to float32, the TPU's native 32-bit type, before
  the query runs, and sums accumulate in float32;
* ``count`` counts rows, nulls included: the validity mask dropped from
  the reducer.

In queries without nulls (SSB, TPC-H Q18) the second changes nothing.
Where a left join's misses are counted, the first may change nothing
(keys below 2**24, exact counts) and the second turns each miss's
count from 0 into 1.
"""

from __future__ import annotations

import operator
from collections import Counter
from functools import reduce
from typing import Dict, List, Optional, Tuple

import numpy as np

_CMP = {"==": operator.eq, "!=": operator.ne, "<": operator.lt,
        "<=": operator.le, ">": operator.gt, ">=": operator.ge}


class Frame(dict):
    """Columns by name; ``valid[name]`` is the validity of a column that
    can hold nulls (True where a value is there)."""

    def __init__(self, columns, valid: Optional[dict] = None):
        super().__init__(columns)
        self.valid: Dict[str, np.ndarray] = dict(valid or {})

    def take(self, idx) -> "Frame":
        return Frame({k: v[idx] for k, v in self.items()},
                     {k: ok[idx] for k, ok in self.valid.items()})

    def validity(self, name: str) -> np.ndarray:
        return self.valid.get(name, np.ones(len(self[name]), dtype=bool))

    def non_null(self, name: str, use: str) -> np.ndarray:
        """Column ``name``, which ``use`` needs free of nulls."""
        if name in self.valid:
            raise ValueError(f"{use} on {name!r}, a column a left join "
                             f"made nullable")
        return self[name]


def _literal(v):
    return v.encode() if isinstance(v, str) else v


def like(col: np.ndarray, pattern: str) -> np.ndarray:
    """SQL LIKE on a numpy bytes array, ``%`` the only wildcard.  The
    pattern's first piece is a prefix and its last a suffix; the middle
    pieces are found leftmost, in order, after the prefix, and the
    suffix starts at or after the end of the last of them."""
    if col.dtype.kind != "S":
        raise ValueError(f"like on a {col.dtype} column: want text")
    pieces = [p.encode() for p in pattern.split("%")]
    if len(pieces) == 1:
        return col == pieces[0]
    first, *middle, last = pieces
    ok = np.strings.startswith(col, first)
    at = np.full(len(col), len(first), dtype=np.int64)
    for piece in filter(None, middle):
        found = np.strings.find(col, piece, at)
        ok &= found >= 0
        at = found + len(piece)
    return ok & np.strings.endswith(col, last) \
        & (np.strings.str_len(col) - len(last) >= at)


def mask(pred: list, frame: Frame, stats: List[dict]) -> np.ndarray:
    op = pred[0]
    if op in ("and", "or"):
        parts = [mask(p, frame, stats) for p in pred[1:]]
        return reduce(np.logical_and if op == "and" else np.logical_or,
                      parts)
    col = frame.non_null(pred[1], "a filter")
    if op in ("like", "not like"):
        m = like(col, pred[2])
        stats.append({"op": "like", "rows": len(col),
                      "value_bytes": int(np.strings.str_len(col).sum()),
                      "matches": int(m.sum())})
        return m if op == "like" else ~m
    if op == "in":
        return np.isin(col, [_literal(v) for v in pred[2]])
    if op == "between":
        return (col >= _literal(pred[2])) & (col <= _literal(pred[3]))
    return _CMP[op](col, _literal(pred[2]))


def join(left: Frame, right: Frame, on: str, how: str,
         stats: List[dict]) -> Frame:
    """SQL join of ``left`` and ``right`` on the column ``on``, in
    left-major order; ``how="left"`` keeps each left row that matches
    nothing once, with its right columns null."""
    lk, rk = left.non_null(on, "a join"), right.non_null(on, "a join")
    order = np.argsort(rk, kind="stable")
    sk = rk[order]
    lo = np.searchsorted(sk, lk, side="left")
    counts = np.searchsorted(sk, lk, side="right") - lo
    miss = (counts == 0) if how == "left" else np.zeros(len(lk), bool)
    width = counts + miss                         # output rows per left row
    li = np.repeat(np.arange(len(lk)), width)
    first = np.repeat(np.cumsum(width) - width, width)
    hit = ~np.repeat(miss, width)
    ri = order[(np.repeat(lo, width) + np.arange(len(li)) - first)[hit]]
    out = left.take(li)
    for name, v in right.items():
        if name == on:
            continue
        name_out = name + "_right" if name in left else name
        out[name_out] = np.zeros(len(li), v.dtype)
        out[name_out][hit] = v[ri]
        if how == "left" or name in right.valid:
            valid = hit.copy()
            valid[hit] = right.validity(name)[ri]
            out.valid[name_out] = valid
    stats.append({"op": "join", "left_rows": len(lk), "right_rows": len(rk),
                  "key_bytes": lk.dtype.itemsize, "matches": len(ri),
                  "misses": int(miss.sum())})
    return out


def _null_fill(dtype: np.dtype, how: str):
    """The value a null takes so that it changes no ``how`` aggregate."""
    if how == "sum":
        return 0
    info = np.finfo if dtype.kind == "f" else np.iinfo
    return info(dtype).max if how == "min" else info(dtype).min


def _reduce(how: str, v: np.ndarray, valid: np.ndarray,
            starts: np.ndarray, control: bool) -> np.ndarray:
    """One aggregate over the sorted runs that begin at ``starts``,
    nulls (``~valid``) left out."""
    if how == "count":
        if control:
            valid = np.ones(len(v), dtype=bool)
        return np.add.reduceat(valid.astype(np.int64), starts)
    if not valid.all():
        v = np.where(valid, v, _null_fill(v.dtype, how)).astype(v.dtype)
    if how == "sum":
        acc = np.float32 if control else (
            np.uint64 if v.dtype == np.uint64 else np.int64)
        return np.add.reduceat(v.astype(acc), starts, dtype=acc)
    ufunc = np.minimum if how == "min" else np.maximum
    return ufunc.reduceat(v, starts)


def group_by(frame: Frame, keys: List[str], aggs: Dict[str, list],
             control: bool, stats: List[dict]) -> Frame:
    n = len(frame[keys[0]])
    order = np.lexsort([frame.non_null(k, "a group-by")
                        for k in reversed(keys)])
    new = np.zeros(n, dtype=bool)
    if n:
        new[0] = True
    for sk in (frame[k][order] for k in keys):
        new[1:] |= sk[1:] != sk[:-1]
    starts = np.nonzero(new)[0]
    out = Frame({k: frame[k][order[starts]] for k in keys})
    value_bytes = []
    for name, (column, how) in aggs.items():
        v, valid = frame[column][order], frame.validity(column)[order]
        value_bytes.append(v.dtype.itemsize)
        if not n:
            out[name] = np.empty(0, np.int64 if how == "count" else v.dtype)
            continue
        out[name] = _reduce(how, v, valid, starts, control)
        if how != "count":
            some = np.add.reduceat(valid.astype(np.int64), starts) > 0
            if not some.all():
                out.valid[name] = some
    stats.append({"op": "group_by", "rows": n, "groups": len(starts),
                  "value_bytes": value_bytes})
    return out


def evaluate(plan: dict, tables: Dict[str, Dict[str, np.ndarray]],
             control: bool, stats: List[dict]) -> Frame:
    frame = Frame(tables[plan["scan"]])
    if control:
        frame = Frame({k: v if v.dtype.kind == "S" else v.astype(np.float32)
                       for k, v in frame.items()})
    for step in plan.get("steps", ()):
        if "filter" in step:
            frame = frame.take(mask(step["filter"], frame, stats))
        elif "join" in step:
            right = evaluate(step["join"], tables, control, stats)
            frame = join(frame, right, step["on"],
                         step.get("how", "inner"), stats)
        else:
            frame = group_by(frame, step["group_by"], step["aggs"], control,
                             stats)
    return frame


def rows(frame: Frame) -> List[tuple]:
    """The frame's rows as tuples of Python values, text decoded, a null
    as ``None``."""
    cols = []
    for name, v in frame.items():
        values = [x.decode() for x in v.tolist()] if v.dtype.kind == "S" \
            else v.tolist()
        if name in frame.valid:
            values = [x if ok else None
                      for x, ok in zip(values, frame.valid[name].tolist())]
        cols.append(values)
    return list(zip(*cols))


def run(plan: dict, tables: Dict[str, Dict[str, np.ndarray]],
        control: bool = False) -> Tuple[List[tuple], List[dict]]:
    stats: List[dict] = []
    return rows(evaluate(plan, tables, control, stats)), stats


def wrong_rows(want: List[tuple], got: List[tuple]) -> int:
    """Rows in one result and not in the other, counted with their
    multiplicity: 0 exactly when the two results are equal as bags."""
    a, b = Counter(want), Counter(got)
    return sum(((a - b) + (b - a)).values())
