"""Accelerator-resident relational pipeline: the star-schema join +
group-by of ``bench_join`` with the kernel hot path running through the
device kernels (``ZERROW_KERNEL_BACKEND=pallas``) instead of numpy.

    load orders (fact: cust id + amount cents)  ─┐
                                                 ├─> join (left, cust)
    load customers (dim: cust id + dict country)─┘      │
                                                        └─> group_by
                                                   country: sum/min/max/
                                                   count(amount)

The fact payload is integer cents, so every aggregate sits on the
*eligible* side of the kernel registry — the whole join+group_by cone
(splitmix64 key hashing, sentinel join gathers, integer segment
reducers) runs on jax's default device — the CPU on CI runners, the
TPU on the chip — and must land on **exactly the numpy bits**.

Both arms run the same DAGs on the same thread-mode executor; the
backend env var is the only difference.  Recorded per arm: wall-clock
and the pallas/numpy wall ratio (on CPU runners this is a *semantics*
lane, not a speed lane: the number that matters there is the
bit-identity; ``chip_smoke.py`` drives the same path on the chip).
Always gated, in smoke too:

  * aggregate outputs bit-identical across backends (to_pydict AND raw
    primitive buffers: same dtypes, same bits, NaN-aware);
  * ``kdispatch.self_check()`` passes (it raises on any admitted
    kernel that no longer reproduces the numpy reference exactly);
  * the registry still documents its ineligible float entries (the PR 5
    sequential-float-sum contract must never silently flip to parallel).

    PYTHONPATH=src python -m benchmarks.run pallas_join

Results land in BENCH_pallas_join.json; ``--smoke`` checks the gates
and leaves the checked-in numbers untouched.
"""

from __future__ import annotations

import functools
import json
import os
import shutil
import tempfile

import numpy as np

from repro.core import DAG, NodeSpec, SipcReader
from repro.core import kdispatch, ops
from repro.core.arrow import Table

from .common import Csv, gb, make_env, timed, write_source

N_DAGS = 2
N_COUNTRIES = 64
SMOKE = os.environ.get("ZERROW_BENCH_SMOKE") == "1"
_BACKEND_ENV = "ZERROW_KERNEL_BACKEND"


def gen_star(orders_bytes: int, seed: int = 0):
    """Like ``bench_join.gen_star`` but with an integer-cents amount so
    the sum/min/max aggregates are registry-eligible for Pallas."""
    rng = np.random.default_rng(seed)
    n_orders = max(orders_bytes // 16, 64)
    n_cust = max(n_orders // 8, 8)
    orders = Table.from_pydict({
        "cust": rng.integers(0, int(n_cust * 1.1), size=n_orders).astype(
            np.int64),                           # ~10% misses -> left join
        "amount": rng.integers(0, 1_000_000, size=n_orders).astype(
            np.int64),
    })
    customers = Table.from_pydict({
        "cust": np.arange(n_cust, dtype=np.int64),
        "country": [f"country{i % N_COUNTRIES:03d}" for i in range(n_cust)],
    })
    return orders, customers


def _build(paths, est):
    join = functools.partial(ops.join_node, on="cust", how="left")
    agg = functools.partial(
        ops.group_by_node, keys="country",
        aggs={"total": ("amount", "sum"), "lo": ("amount", "min"),
              "hi": ("amount", "max"), "n": ("amount", "count")})
    return [DAG([
        NodeSpec("orders", source=po, est_mem=est),
        NodeSpec("cust", source=pc, est_mem=est,
                 dict_columns=("country",)),
        NodeSpec("join", fn=join, deps=["orders", "cust"], est_mem=est),
        NodeSpec("agg", fn=agg, deps=["join"], est_mem=est,
                 keep_output=True),
    ], name=f"star{i}") for i, (po, pc) in enumerate(paths)]


def _agg_tables(env, dags):
    reader = SipcReader(env.store)
    return [reader.read_table(d.nodes["agg"].output) for d in dags]


def _raw_bits(tables):
    """Per-column primitive buffers for the bit-level comparison (the
    pydict comparison alone would miss a dtype drift)."""
    out = []
    for t in tables:
        b = t.combine().batches[0]
        out.append({f.name: (str(c._logical().dtype), c._logical())
                    for f, c in zip(b.schema.fields, b.columns)
                    if c.type.is_primitive})
    return out


def _run_arm(backend: str, paths, est, reps: int):
    """Best-of-``reps`` runs of fresh DAGs on one warm thread-mode env
    with the given kernel backend; returns (wall, pydicts, raw bits)."""
    os.environ[_BACKEND_ENV] = backend
    assert kdispatch.active_backend() == backend
    env = make_env(workers=1, workers_mode="thread", decache=False)
    best = None
    try:
        for _ in range(reps):
            dags = _build(paths, est)
            with timed() as t:
                env.ex.run(dags)
            assert all(d.all_done() for d in dags)
            tables = _agg_tables(env, dags)
            out = (t[1], [tt.to_pydict() for tt in tables],
                   _raw_bits(tables))
            if best is None or out[0] < best[0]:
                best = out
    finally:
        env.close()
        os.environ.pop(_BACKEND_ENV, None)
    return best


def _assert_bit_identical(numpy_arm, pallas_arm):
    _, pd_np, raw_np = numpy_arm
    _, pd_pl, raw_pl = pallas_arm
    assert pd_np == pd_pl, \
        "pallas arm's aggregates differ from the numpy pipeline"
    for d_np, d_pl in zip(raw_np, raw_pl):
        assert d_np.keys() == d_pl.keys()
        for name in d_np:
            t_np, v_np = d_np[name]
            t_pl, v_pl = d_pl[name]
            assert t_np == t_pl, f"{name}: dtype {t_pl} != {t_np}"
            assert np.array_equal(v_np, v_pl,
                                  equal_nan=v_np.dtype.kind == "f"), \
                f"{name}: raw bits diverge across backends"


def main() -> None:
    from repro.kernels import relational   # deferred: needs jax
    os.environ[_BACKEND_ENV] = "pallas"
    try:
        # admission gate first: a kernel whose differential fails raises
        # here and FAILS the bench before it can serve a single query
        report = kdispatch.self_check()
        ineligible = [k for k, v in report.items()
                      if v.startswith("ineligible")]
        assert "grouped_sum:float" in ineligible, \
            "the sequential-float-sum contract lost its registry entry"
    finally:
        os.environ.pop(_BACKEND_ENV, None)

    size = gb(0.16) if SMOKE else gb(0.08)
    tables = [gen_star(size, seed=i) for i in range(N_DAGS)]
    est = int(tables[0][0].nbytes * 4)
    results = {"n_dags": N_DAGS, "smoke": SMOKE,
               "orders_bytes": sum(o.nbytes for o, _ in tables),
               "platform": relational.platform(),
               "self_check_ok": sorted(k for k, v in report.items()
                                       if v == "ok"),
               "self_check_ineligible": sorted(ineligible),
               "runs": []}
    srcdir = tempfile.mkdtemp(
        prefix="zerrow-bench-src-",
        dir="/dev/shm" if os.access("/dev/shm", os.W_OK) else None)
    try:
        paths = [(write_source(srcdir, f"orders{i}.zq", o),
                  write_source(srcdir, f"cust{i}.zq", c))
                 for i, (o, c) in enumerate(tables)]
        reps = 2 if SMOKE else 3
        arm_np = _run_arm("numpy", paths, est, reps)
        arm_pl = _run_arm("pallas", paths, est, reps)
    finally:
        shutil.rmtree(srcdir, ignore_errors=True)

    _assert_bit_identical(arm_np, arm_pl)
    t_np, t_pl = arm_np[0], arm_pl[0]
    results["runs"].append({"backend": "numpy", "wall_s": t_np,
                            "reps": reps})
    results["runs"].append({"backend": "pallas", "wall_s": t_pl,
                            "reps": reps,
                            "platform": results["platform"]})
    results["pallas_over_numpy"] = t_pl / t_np
    Csv.add("pallas_join_numpy", t_np, "bit_identity=pass")
    Csv.add("pallas_join_pallas", t_pl,
            f"{t_pl / t_np:.2f}x_of_numpy;"
            f"platform={results['platform']};"
            f"self_check={len(results['self_check_ok'])}ok")
    if SMOKE:
        print("# smoke: pallas arm bit-identical to numpy pipeline, "
              f"self_check admitted {len(results['self_check_ok'])} "
              f"kernels, {len(ineligible)} documented ineligible; "
              "BENCH_pallas_join.json left untouched")
        return
    out = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "BENCH_pallas_join.json")
    with open(out, "w") as fh:
        json.dump(results, fh, indent=2)
    print(f"# wrote {out}: numpy {t_np:.2f}s, pallas "
          f"{t_pl:.2f}s ({t_pl / t_np:.2f}x, platform="
          f"{results['platform']})")


if __name__ == "__main__":
    main()
