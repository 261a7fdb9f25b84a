#!/usr/bin/env python3
"""Bring-up smoke on one TPU: the star join + group-by through the
normal entry points, compiled on the chip, checked bit for bit against
the numpy vkernels (the plain reference).

    python chip_smoke.py [--seed N]

Phases, in order; any failure exits nonzero and prints no result:

  1. device    — jax's first device must be a TPU, Pallas must not be in
                 interpret mode (``ZERROW_PALLAS_INTERPRET`` not ``1``);
                 prints the device kind and the compile cache directory.
  2. self_check — ``kdispatch.self_check()``: every admitted device
                 kernel, compiled on the chip, against numpy.
  3. data      — a star schema from ``--seed``, written as zarquet: 2^23
                 orders (int64 ``cust``, int64 ``amount`` in cents, about
                 10% join misses) and 2^20 customers with a 64-value
                 ``country`` that the scan dictionary-encodes.  About
                 TPC-H SF 1.4 lineitem rows and SF 7 customer rows.
  4. plan      — ``scan`` -> ``join`` (left, on cust) -> ``group_by``
                 (country: sum/min/max/count of amount), ``compile_plans``,
                 ``Executor.run`` in thread mode: once with
                 ``ZERROW_KERNEL_BACKEND=numpy``, once with the device
                 backend (``kdispatch.DEVICE_BACKEND``).
  5. compare   — aggregate buffers (values and validity) bit for bit,
                 every kernel on the path served calls on the TPU; prints
                 the per-kernel device call counts, compile seconds and
                 each arm's wall time (a smoke, not a benchmark).

The last line of standard output is the device line, e.g.
``{"ok": true, "device": {"platform": "tpu", "kind": "TPU v5 lite",
"count": 1}}``.  Everything runs in this one process: a chip belongs to
one process at a time.
"""

from __future__ import annotations

import argparse
import json
import os
import shutil
import sys
import tempfile
import time

ROOT = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(ROOT, "src"))

import numpy as np  # noqa: E402

from repro.core import (BufferStore, Executor, RMConfig,  # noqa: E402
                        ResourceManager, kdispatch, obs, zarquet)
from repro.core.arrow import Column, Table  # noqa: E402
from repro.core.plan import compile_plans, scan  # noqa: E402

_BACKEND_ENV = kdispatch.ENV
_DEVICE = kdispatch.DEVICE_BACKEND
_INTERPRET_ENV = "ZERROW_PALLAS_INTERPRET"

ORDERS_LOG2 = 23
CUSTOMERS_LOG2 = 20
N_COUNTRIES = 64
MISS_FRACTION = 0.1

#: device kernels the star join + group-by runs through
PATH_KERNELS = ("hash_fixed", "combine_hashes", "filter_join_gather",
                "grouped_count", "grouped_sum", "grouped_min", "grouped_max")

AGGS = {"total": ("amount", "sum"), "lo": ("amount", "min"),
        "hi": ("amount", "max"), "n": ("amount", "count")}


def compile_s() -> float:
    """Seconds jax has spent compiling since the device kernels loaded
    (``core.obs``'s ``jax.compile_s``)."""
    return obs.totals("jax.").get("jax.compile_s", 0.0)


def check_device():
    """Phase 1: the chip, compiled.  Raises ``SystemExit`` otherwise."""
    import jax
    from repro.kernels.ops import default_interpret
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        raise SystemExit(f"chip_smoke: no TPU: jax's first device is "
                         f"{dev.platform!r} ({dev.device_kind})")
    if os.environ.get(_INTERPRET_ENV) == "1" or default_interpret():
        raise SystemExit(f"chip_smoke: Pallas is in interpret mode "
                         f"({_INTERPRET_ENV}={os.environ.get(_INTERPRET_ENV)})")
    return dev


def write_star(workdir: str, seed: int, orders_log2: int = ORDERS_LOG2,
               customers_log2: int = CUSTOMERS_LOG2):
    """Phase 3: the star schema as zarquet; returns (orders, customers)
    paths."""
    rng = np.random.default_rng(seed)
    n_orders, n_cust = 1 << orders_log2, 1 << customers_log2
    key_range = int(round(n_cust / (1 - MISS_FRACTION)))
    orders = Table.from_pydict({
        "cust": rng.integers(0, key_range, n_orders, dtype=np.int64),
        "amount": rng.integers(0, 1_000_000, n_orders, dtype=np.int64),
    })
    names = Column.from_strings(
        [f"country{i:03d}" for i in range(N_COUNTRIES)])
    customers = Table.from_pydict({
        "cust": np.arange(n_cust, dtype=np.int64),
        "country": Column.dictionary_encoded(
            rng.integers(0, N_COUNTRIES, n_cust), names),
    })
    paths = (os.path.join(workdir, "orders.zq"),
             os.path.join(workdir, "customers.zq"))
    zarquet.write_table(paths[0], orders)
    zarquet.write_table(paths[1], customers)
    return paths


def _snapshot(table: Table) -> dict:
    """Every aggregate column's logical values (dtype and bits) and
    validity, copied out of the store, plus the key column's values."""
    batch = table.combine().batches[0]
    rows = table.to_pydict()
    return {f.name: (np.array(c._logical()), np.array(c.valid_mask()))
            if c.type.is_primitive else rows[f.name]
            for f, c in zip(batch.schema.fields, batch.columns)}


def run_arm(backend: str, orders_path: str, customers_path: str,
            workdir: str) -> dict:
    """Phase 4, one arm: the plan through ``compile_plans`` and a
    thread-mode ``Executor.run`` with the given kernel backend."""
    os.environ[_BACKEND_ENV] = backend
    store = BufferStore(swap_dir=os.path.join(workdir, f"swap-{backend}"))
    try:
        ex = Executor(store, ResourceManager(store, RMConfig()))
        plan = (scan(orders_path)
                .join(scan(customers_path, dict_columns=("country",)),
                      on="cust", how="left")
                .group_by("country", AGGS))
        cp = compile_plans({"agg": plan}, name=f"star-{backend}")
        t0 = time.perf_counter()
        ex.run([cp.dag])
        wall = time.perf_counter() - t0
        snap = _snapshot(cp.read(store, "agg"))
        ex.close()
    finally:
        store.close()
        os.environ.pop(_BACKEND_ENV, None)
    return {"wall_s": wall, "aggregates": snap}


def compare(numpy_arm: dict, device_arm: dict, n_orders: int) -> None:
    """Phase 5: bit identity, and sanity of the reference itself."""
    a, b = numpy_arm["aggregates"], device_arm["aggregates"]
    if a.keys() != b.keys():
        raise AssertionError(f"columns differ: {sorted(a)} vs {sorted(b)}")
    for name in a:
        if isinstance(a[name], tuple):
            (va, ma), (vb, mb) = a[name], b[name]
            if va.dtype != vb.dtype or va.tobytes() != vb.tobytes() \
                    or not np.array_equal(ma, mb):
                raise AssertionError(f"{name}: device aggregate bits "
                                     f"differ from numpy")
        elif a[name] != b[name]:
            raise AssertionError(f"{name}: group keys differ")
    counts, _ = a["n"]
    if int(counts.sum()) != n_orders or len(counts) != N_COUNTRIES + 1:
        raise AssertionError(
            f"left join + group_by lost rows: {int(counts.sum())} of "
            f"{n_orders} in {len(counts)} groups (want {N_COUNTRIES} "
            f"countries + the null group of misses)")


def run_star(workdir: str, seed: int, platform: str,
             orders_log2: int = ORDERS_LOG2,
             customers_log2: int = CUSTOMERS_LOG2) -> dict:
    """Phases 3-5; returns the report.  ``platform`` is where the device
    kernels must have served every path kernel."""
    orders_path, customers_path = write_star(workdir, seed, orders_log2,
                                             customers_log2)
    report = {}
    arms = {}
    for backend in ("numpy", _DEVICE):
        kdispatch.reset_device_calls()
        c0 = compile_s()
        arms[backend] = run_arm(backend, orders_path, customers_path,
                                workdir)
        report[f"{backend}_wall_s"] = arms[backend]["wall_s"]
        report[f"{backend}_compile_s"] = compile_s() - c0
    calls = kdispatch.device_calls()
    report["device_calls"] = {k: calls.get((k, platform), 0)
                              for k in PATH_KERNELS}
    compare(arms["numpy"], arms[_DEVICE], 1 << orders_log2)
    missing = [k for k, n in report["device_calls"].items() if n == 0]
    if missing:
        raise AssertionError(f"path kernels served no call on "
                             f"{platform}: {missing} (calls: {calls})")
    return report


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    dev = check_device()
    import jax
    from repro.launch.compile_cache import enable_compile_cache
    cache_dir = enable_compile_cache()
    n_dev = len(jax.devices())
    print(f"device: {dev.platform} / {dev.device_kind} / {n_dev} device(s)")
    print(f"compile cache: {cache_dir}")

    os.environ[_BACKEND_ENV] = _DEVICE
    try:
        checked = kdispatch.self_check()
    finally:
        os.environ.pop(_BACKEND_ENV, None)
    print(f"self_check: {len(checked)} kernels bit-identical on the chip "
          f"({', '.join(checked)}); compile {compile_s():.3f} s")

    workdir = tempfile.mkdtemp(prefix="zerrow-chip-smoke-")
    try:
        report = run_star(workdir, args.seed, dev.platform)
    finally:
        shutil.rmtree(workdir, ignore_errors=True)
    print(f"star join + group_by (2^{ORDERS_LOG2} orders, "
          f"2^{CUSTOMERS_LOG2} customers, seed {args.seed}): device "
          f"aggregates bit-identical to numpy")
    print(f"device calls on {dev.platform}: "
          + ", ".join(f"{k}={n}" for k, n in report["device_calls"].items()))
    print("not a benchmark — wall includes loads and the device arm's "
          "compiles: " + ", ".join(
              f"{b} wall {report[f'{b}_wall_s']:.3f} s (compile "
              f"{report[f'{b}_compile_s']:.3f} s)"
              for b in ("numpy", _DEVICE)))
    print(f"compile total: {compile_s():.3f} s")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": n_dev}}))


if __name__ == "__main__":
    main()
