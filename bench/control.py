#!/usr/bin/env python3
"""The control of the comparison that decides ``correct``.

    python3 bench/control.py --workload ssb_sf1.q2q3 --seeds 5,6,7

For each seed, generates the cell's tables at their configured size,
evaluates every query of the rotation with the plain reference and with
its control (``reference.run(..., control=True)``: every number in
float32, sums accumulated in float32, ``count`` over rows with nulls
included), and prints the number a run
compares, ``wrong_rows`` summed over one pass of the rotation, as one
JSON line per seed.  A control that the comparison passes would make
the comparison worthless; its smallest reading over the seeds is the
upper reading the limit must stay below.  Needs no chip.
"""

import argparse
import json
import os
import sys
import time

BENCH = os.path.dirname(os.path.abspath(__file__))
sys.path[:0] = [BENCH]

from harness import reference, spec  # noqa: E402


def readings(workload: str, seed: int, config_hook=None) -> dict:
    bench = spec.load_benchmark()
    cell = spec.cell(bench, workload)
    config = spec.config(bench, cell)
    if config_hook is not None:
        config = config_hook(config)
    tables = spec.generator(config).generate(config, spec.rng(seed))
    per_query = {}
    for q in spec.traffic(cell)["queries"]:
        want, _ = reference.run(q["plan"], tables)
        got, _ = reference.run(q["plan"], tables, control=True)
        per_query[q["name"]] = reference.wrong_rows(want, got)
    return {"workload": workload, "seed": seed,
            "wrong_rows": sum(per_query.values()), "per_query": per_query}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True,
                    help="comma-separated seeds")
    args = ap.parse_args(argv)
    for seed in (int(s) for s in args.seeds.split(",")):
        t = time.perf_counter()
        r = readings(args.workload, seed)
        r["seconds"] = time.perf_counter() - t
        print(json.dumps(r), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
