#!/usr/bin/env python3
"""Record a small traced run on the chip, for the trace-reduction test.

    python3 bench/record_trace.py OUT_PREFIX [--workload ssb_sf1.q2q3]

Runs the cell at a hundredth of its configured rows (the date and
supplier tables whole) for two seconds with the profiler on, prints the
trace's planes and lines with a few events each, and writes
``OUT_PREFIX.xplane.pb``, its reduction ``OUT_PREFIX.json`` (the
compact form of ``harness.trace.extract``) and ``OUT_PREFIX.expected.json``
(``harness.trace.reduce`` of it).  ``bench/tests/test_bench_trace.py``
checks the reduction against the committed copies in ``bench/testdata``.
"""

import time

T0 = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)


def shrink(config: dict) -> dict:
    for name, table in config["tables"].items():
        if "rows" in table and name not in ("date", "supplier"):
            table["rows"] = max(table["rows"] // 100, 50)
    return config


def describe(path: str) -> None:
    from jax.profiler import ProfileData
    for plane in ProfileData.from_file(path).planes:
        print(f"plane {plane.name!r}")
        for line in plane.lines:
            events = list(line.events)
            print(f"  line {line.name!r}: {len(events)} events")
            for e in events[:3]:
                print(f"    {e.name!r} start {e.start_ns} dur "
                      f"{e.duration_ns} stats {dict(e.stats)}")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("out")
    ap.add_argument("--workload", default="ssb_sf1.q2q3")
    args = ap.parse_args(argv)
    sys.path[:0] = [BENCH, os.path.join(ROOT, "src")]
    from harness import device
    device.configure(ROOT)
    from harness import cellrun, trace

    os.makedirs(os.path.dirname(os.path.abspath(args.out)), exist_ok=True)
    result = cellrun.run_cell(args.workload, 1, 2.0, True, T0,
                              config_hook=shrink, trace_out=args.out)
    describe(args.out + ".xplane.pb")
    with open(args.out + ".json") as fh:
        reduced = trace.reduce(json.load(fh))
    with open(args.out + ".expected.json", "w") as fh:
        json.dump(reduced, fh, indent=1)
    print(json.dumps(result))
    return 0


if __name__ == "__main__":
    sys.exit(main())
