"""The system under test: a traffic query through Zerrow's normal path.

Each query is built with the plan frontend (``scan`` / ``filter`` /
``join`` / ``group_by``), lowered by ``compile_plans`` and run by a
thread-mode ``Executor`` with the relational kernels on the device
(``ZERROW_KERNEL_BACKEND=pallas``, set by ``run.py``).  Every query is
one pipeline run: its own ``BufferStore``, resource manager and
executor, no persistent cache root, so no query is answered from an
earlier one's outputs and every query loads its tables from zarquet.
"""

from __future__ import annotations

import operator
import os
from dataclasses import dataclass
from functools import reduce
from typing import Dict, List

import numpy as np

from repro.core import (BufferStore, Executor, RMConfig, ResourceManager,
                        zarquet)
from repro.core.arrow import Column, Table
from repro.core.plan import Plan, col, compile_plans, scan

_CMP = {"==": operator.eq, "!=": operator.ne, "<": operator.lt,
        "<=": operator.le, ">": operator.gt, ">=": operator.ge}


def _column(values: np.ndarray) -> Column:
    """A numpy array as an Arrow column; a bytes array becomes utf8."""
    if values.dtype.kind != "S":
        return Column.primitive(values)
    n, width = len(values), values.dtype.itemsize
    lengths = np.char.str_len(values)
    offsets = np.zeros(n + 1, dtype=np.int64)
    np.cumsum(lengths, out=offsets[1:])
    raw = np.ascontiguousarray(values).view(np.uint8).reshape(n, width)
    return Column.utf8(offsets, raw[np.arange(width) < lengths[:, None]])


def write_tables(tables: Dict[str, Dict[str, np.ndarray]],
                 workdir: str) -> Dict[str, str]:
    """Write each generated table as zarquet; returns the paths."""
    paths = {}
    for name, columns in tables.items():
        paths[name] = os.path.join(workdir, f"{name}.zq")
        zarquet.write_table(paths[name], Table.from_pydict(
            {c: _column(v) for c, v in columns.items()}))
    return paths


def predicate(pred: list):
    """A traffic predicate as a frontend ``Expr``.  ``like`` becomes
    ``col(c).like(pattern)``, a method the program does not have yet:
    a plan that uses it fails while it is built."""
    op = pred[0]
    if op in ("and", "or"):
        parts = [predicate(p) for p in pred[1:]]
        return reduce(operator.and_ if op == "and" else operator.or_, parts)
    c = col(pred[1])
    if op == "like":
        return c.like(pred[2])
    if op == "not like":
        return ~c.like(pred[2])
    if op == "in":
        return reduce(operator.or_, [c == v for v in pred[2]])
    if op == "between":
        return (c >= pred[2]) & (c <= pred[3])
    return _CMP[op](c, pred[2])


def build_plan(spec: dict, paths: Dict[str, str],
               dict_columns: Dict[str, tuple]) -> Plan:
    """The frontend plan of one traffic query (``harness.queryspec``)."""
    table = spec["scan"]
    plan = scan(paths[table], dict_columns=dict_columns.get(table, ()))
    for step in spec.get("steps", ()):
        if "filter" in step:
            plan = plan.filter(predicate(step["filter"]))
        elif "join" in step:
            plan = plan.join(build_plan(step["join"], paths, dict_columns),
                             on=step["on"], how=step.get("how", "inner"))
        else:
            plan = plan.group_by(step["group_by"],
                                 {k: tuple(v) for k, v in
                                  step["aggs"].items()})
    return plan


class _OpSpan:
    """A node function inside a profiler annotation named for its op."""

    def __init__(self, label: str, fn):
        self.label, self.fn = label, fn

    def __call__(self, *args, **kwargs):
        import jax
        with jax.profiler.TraceAnnotation(self.label):
            return self.fn(*args, **kwargs)


@dataclass
class QueryRun:
    rows: List[tuple]           # the result, as Python tuples
    load_s: float               # summed exec_latency of the loader nodes
    bytes_copied: int           # BufferStore.stats.bytes_copied


def run_query(plan: Plan, name: str, swap_dir: str,
              op_spans: bool = False) -> QueryRun:
    """One query through ``compile_plans`` and a thread ``Executor``.
    ``op_spans`` names each compute node's run in the profiler's trace
    (``op:<node kind>``), so idle gaps can be attributed to an op."""
    store = BufferStore(swap_dir=swap_dir)
    try:
        ex = Executor(store, ResourceManager(store, RMConfig()))
        cp = compile_plans({"result": plan}, name=name)
        if op_spans:
            for st in cp.dag.nodes.values():
                if st.spec.fn is not None:
                    kind = st.spec.fn.func.__name__.replace("_exec", "")
                    st.spec.fn = _OpSpan(f"op:{kind.replace('_node', '')}",
                                         st.spec.fn)
        ex.run([cp.dag])
        table = cp.read(store, "result")
        result = table.to_pydict()
        rows = list(zip(*result.values()))
        load_s = sum(st.exec_latency for st in cp.dag.nodes.values()
                     if st.is_loader)
        copied = store.stats.bytes_copied
        ex.close()
    finally:
        store.close()
    return QueryRun(rows, load_s, copied)
