"""``BENCHMARK.json`` and the files it names, found by name.

A cell (``workloads`` entry) names a configuration and a traffic mix.
The configuration's file is the entry's ``file``; its ``generator``
names ``bench/generators/<generator>.py``.  The traffic mix is
``bench/traffic/<traffic>.json``; its optional ``"kernels"`` lists the
device kernels (``kdispatch`` names) its queries must run on the chip
beyond the joins' and group-bys'.  A per-layer metric is read by
``bench/metrics/<metric name>.py``, whose ``read(run)`` returns the
value or None.  Adding a cell or a metric adds files and entries; no
code here changes.
"""

from __future__ import annotations

import importlib
import importlib.util
import json
import os
import re
from typing import List

import numpy as np

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")


def rng(seed: int) -> np.random.Generator:
    """The random generator a run's ``--seed`` names: any whole number,
    however large, and the same tables from the same seed."""
    return np.random.default_rng(np.random.SeedSequence(seed % 2 ** 64))


def load_benchmark(root: str = ROOT) -> dict:
    with open(os.path.join(root, "BENCHMARK.json")) as fh:
        return json.load(fh)


def cell(bench: dict, name: str) -> dict:
    for w in bench["workloads"]:
        if w["name"] == name:
            return w
    raise KeyError(f"no workload {name!r} in BENCHMARK.json; have "
                   f"{[w['name'] for w in bench['workloads']]}")


def config(bench: dict, cell_: dict, root: str = ROOT) -> dict:
    for c in bench["configs"]:
        if c["name"] == cell_["config"]:
            with open(os.path.join(root, c["file"])) as fh:
                return json.load(fh)
    raise KeyError(f"no config {cell_['config']!r} in BENCHMARK.json")


def traffic(cell_: dict) -> dict:
    with open(os.path.join(BENCH, "traffic", f"{cell_['traffic']}.json")) \
            as fh:
        return json.load(fh)


def path_kernels(mix: dict) -> List[str]:
    """The kernels a traffic mix names under ``"kernels"``, checked to
    be a list of names."""
    names = mix.get("kernels", [])
    if not isinstance(names, list) or not all(
            isinstance(k, str) and NAME.match(k) for k in names):
        raise ValueError(f"traffic kernels {names!r}: want a list of "
                         f"kernel names")
    return names


def generator(config_: dict):
    return importlib.import_module(f"generators.{config_['generator']}")


def _applies(metric: dict, cell_name: str) -> bool:
    return cell_name in metric.get("workloads", (cell_name,))


def end_to_end(bench: dict, cell_name: str) -> List[dict]:
    return [m for m in bench["end_to_end"] if _applies(m, cell_name)]


def per_layer(bench: dict, cell_name: str) -> List[dict]:
    """The cell's per-layer metrics: those listing it, and those without
    a list that move one of the cell's end-to-end metrics."""
    moved = {m["name"] for m in end_to_end(bench, cell_name)}
    return [m for m in bench["per_layer"] if _applies(m, cell_name)
            and ("workloads" in m or m["moves"] in moved)]


def reader(metric_name: str):
    """The module ``bench/metrics/<metric_name>.py``."""
    path = os.path.join(BENCH, "metrics", f"{metric_name}.py")
    spec_ = importlib.util.spec_from_file_location(
        f"bench_metric_{metric_name.replace('.', '_').replace('-', '_')}",
        path)
    module = importlib.util.module_from_spec(spec_)
    spec_.loader.exec_module(module)
    return module
