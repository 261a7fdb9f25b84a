"""The plain reference: the traffic's queries evaluated with numpy alone.

It reads the generated tables (``{table: {column: ndarray}}``, text as
numpy bytes arrays) and imports nothing of the program: a join sorts the
right key and binary-searches the left one, a group-by lexsorts its keys
and reduces each run with ``ufunc.reduceat``.  Integer aggregates
accumulate in int64, so every result is exact.

``run(plan, tables)`` returns the result rows (tuples of Python values,
text decoded, in no particular order) and the cardinalities of every
join and group-by, from which ``harness.roofline`` computes the bytes
those operators need.  ``run(..., control=True)`` is the control of the
comparison: every number is cast to float32, the TPU's native 32-bit
type, before the query runs, and sums accumulate in float32 — the
precision a later change might be tempted to compute in, which must
come out as not correct.
"""

from __future__ import annotations

import operator
from collections import Counter
from functools import reduce
from typing import Dict, List, Tuple

import numpy as np

Frame = Dict[str, np.ndarray]

_CMP = {"==": operator.eq, "!=": operator.ne, "<": operator.lt,
        "<=": operator.le, ">": operator.gt, ">=": operator.ge}


def _literal(v):
    return v.encode() if isinstance(v, str) else v


def mask(pred: list, frame: Frame) -> np.ndarray:
    op = pred[0]
    if op in ("and", "or"):
        parts = [mask(p, frame) for p in pred[1:]]
        return reduce(np.logical_and if op == "and" else np.logical_or,
                      parts)
    col = frame[pred[1]]
    if op == "in":
        return np.isin(col, [_literal(v) for v in pred[2]])
    if op == "between":
        return (col >= _literal(pred[2])) & (col <= _literal(pred[3]))
    return _CMP[op](col, _literal(pred[2]))


def inner_join(left: Frame, right: Frame, on: str,
               stats: List[dict]) -> Frame:
    lk, rk = left[on], right[on]
    order = np.argsort(rk, kind="stable")
    sk = rk[order]
    lo = np.searchsorted(sk, lk, side="left")
    counts = np.searchsorted(sk, lk, side="right") - lo
    li = np.repeat(np.arange(len(lk)), counts)
    first = np.repeat(np.cumsum(counts) - counts, counts)
    ri = order[np.repeat(lo, counts) + np.arange(len(li)) - first]
    out = {name: v[li] for name, v in left.items()}
    for name, v in right.items():
        if name != on:
            out[name + "_right" if name in left else name] = v[ri]
    stats.append({"op": "join", "left_rows": len(lk), "right_rows": len(rk),
                  "key_bytes": lk.dtype.itemsize, "matches": len(li)})
    return out


def group_by(frame: Frame, keys: List[str], aggs: Dict[str, list],
             control: bool, stats: List[dict]) -> Frame:
    n = len(frame[keys[0]])
    order = np.lexsort([frame[k] for k in reversed(keys)])
    sorted_keys = [frame[k][order] for k in keys]
    new = np.zeros(n, dtype=bool)
    if n:
        new[0] = True
    for sk in sorted_keys:
        new[1:] |= sk[1:] != sk[:-1]
    starts = np.nonzero(new)[0]
    out = {k: sk[starts] for k, sk in zip(keys, sorted_keys)}
    value_bytes = []
    for name, (column, how) in aggs.items():
        v = frame[column][order]
        value_bytes.append(v.dtype.itemsize)
        if not n:
            out[name] = np.empty(0, v.dtype)
        elif how == "count":
            out[name] = np.diff(np.append(starts, n))
        elif how == "sum":
            acc = np.float32 if control else (
                np.uint64 if v.dtype == np.uint64 else np.int64)
            out[name] = np.add.reduceat(v.astype(acc), starts, dtype=acc)
        else:
            ufunc = np.minimum if how == "min" else np.maximum
            out[name] = ufunc.reduceat(v, starts)
    stats.append({"op": "group_by", "rows": n, "groups": len(starts),
                  "value_bytes": value_bytes})
    return out


def evaluate(plan: dict, tables: Dict[str, Frame], control: bool,
             stats: List[dict]) -> Frame:
    frame = dict(tables[plan["scan"]])
    if control:
        frame = {k: v if v.dtype.kind == "S" else v.astype(np.float32)
                 for k, v in frame.items()}
    for step in plan.get("steps", ()):
        if "filter" in step:
            m = mask(step["filter"], frame)
            frame = {k: v[m] for k, v in frame.items()}
        elif "join" in step:
            right = evaluate(step["join"], tables, control, stats)
            frame = inner_join(frame, right, step["on"], stats)
        else:
            frame = group_by(frame, step["group_by"], step["aggs"], control,
                             stats)
    return frame


def rows(frame: Frame) -> List[tuple]:
    """The frame's rows as tuples of Python values, text decoded."""
    cols = [[x.decode() for x in v.tolist()] if v.dtype.kind == "S"
            else v.tolist() for v in frame.values()]
    return list(zip(*cols))


def run(plan: dict, tables: Dict[str, Frame], control: bool = False
        ) -> Tuple[List[tuple], List[dict]]:
    stats: List[dict] = []
    return rows(evaluate(plan, tables, control, stats)), stats


def wrong_rows(want: List[tuple], got: List[tuple]) -> int:
    """Rows in one result and not in the other, counted with their
    multiplicity: 0 exactly when the two results are equal as bags."""
    a, b = Counter(want), Counter(got)
    return sum(((a - b) + (b - a)).values())
