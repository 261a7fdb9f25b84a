#!/usr/bin/env python3
"""Zerrow's chip benchmark: one run of one cell of ``BENCHMARK.json``.

    python3 bench/run.py --workload ssb_sf1.q2q3 --seed 7 --seconds 30 \\
        --trace 0

Runs from the root of a checkout, on a machine whose first JAX device
is a TPU.  The cell's tables are generated from ``--seed``, its traffic
runs through the plan frontend, the executor and the device kernels for
``--seconds`` (``harness.cellrun``), and every result is compared with
the plain reference.  The last line of standard output is the result:
``correct``, ``attempted``, ``failed``, ``metrics`` (the end-to-end
metrics, or with ``--trace 1`` the per-layer ones), ``device``, with
``--trace 1`` a ``breakdown``, and last ``compared``, each number
compared with its limit, which are also the last lines of standard
error.  Off a TPU, in Pallas interpret mode, or when a path kernel
served no call on the chip, it prints no result and exits nonzero.

JAX's persistent compilation cache is ``<checkout>/.jax_cache``, a
fixed path, so only a checkout's first run of a cell compiles.
"""

import time

T0 = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)


def parse(argv):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def main(argv=None) -> int:
    args = parse(argv)
    sys.path[:0] = [BENCH, os.path.join(ROOT, "src")]
    from harness import device
    device.configure(ROOT)
    from harness import cellrun

    try:
        result = cellrun.run_cell(args.workload, args.seed, args.seconds,
                                  bool(args.trace), T0)
    except (device.NoChip, cellrun.RunFailed) as e:
        print(f"bench: {e}", file=sys.stderr)
        return 2
    for name, c in result["compared"].items():
        print(f"compared {name}: {c['value']} (limit {c['limit']})",
              file=sys.stderr)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
