#!/usr/bin/env python3
"""One traced run of a cell, read down to the program's own spans.

    python3 bench/span_report.py --workload ssb_sf1.q2q3 --seed 7 \\
        --seconds 51 [--out spans_ssb]

Runs the cell as ``bench/run.py --trace 1`` does (``harness.cellrun``),
keeps the profiler's trace, and reads the program's spans from it
(``harness.spans``).  The last line of standard output is one JSON
object: the run's result line (``result``); the traced window's rate
from the harness's log (``traced_rows_per_s``: rows of the queries
completed inside the window over the time to the last of them) and its
compile count; ``ops.host_s_per_query`` and
``kdispatch.call_s_per_query`` (self time of the ``ops.*`` and of the
``kernel.*`` spans per query, read by the per-layer readers that give
them in a traced result line); ``span_self_s``; ``idle_by_span``; and
the share of the device's idle time that a program span holds.  A
program without spans gives an empty ``span_self_s``, null span metrics
and all idle time outside program spans.  With ``--out`` the trace's
compact forms are written to ``OUT.json``.
"""

import time

T0 = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import re  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)

_WINDOW = re.compile(r"^window: \d+ queries issued, (\d+) completed inside "
                     r"\S+ s, last at (\S+) s, (\d+) rows scanned")
_COMPILES = re.compile(r"^compiles in the window: (\d+)")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--out")
    args = ap.parse_args(argv)
    sys.path[:0] = [BENCH, os.path.join(ROOT, "src")]
    from harness import device
    device.configure(ROOT)
    from harness import cellrun, spans

    lines = []

    def log(msg):
        lines.append(msg)
        print(msg, file=sys.stderr, flush=True)

    tmp = tempfile.mkdtemp(prefix="zerrow-spans-")
    prefix = os.path.join(tmp, "trace")
    try:
        result = cellrun.run_cell(args.workload, args.seed, args.seconds,
                                  True, T0, trace_out=prefix, log=log)
        with open(prefix + ".json") as fh:
            compact = json.load(fh)
        found = spans.extract(prefix + ".xplane.pb")
    except (device.NoChip, cellrun.RunFailed) as e:
        print(f"span_report: {e}", file=sys.stderr)
        return 2
    finally:
        shutil.rmtree(tmp, ignore_errors=True)
    if args.out:
        with open(args.out + ".json", "w") as fh:
            json.dump({"compact": compact, "spans": found}, fh)
    report = summarize(result, lines, compact, found)
    report.update(workload=args.workload, seed=args.seed)
    print(json.dumps(report), flush=True)
    return 0


def summarize(result: dict, lines: list, compact: dict,
              found: list) -> dict:
    """The report of one traced run: its result line, its log lines, and
    ``harness.trace.extract`` / ``harness.spans.extract`` of its trace."""
    from harness import cellrun, spans, spec, trace
    report = {"result": result}
    for line in lines:
        m = _WINDOW.match(line)
        if m:
            report["traced_rows_per_s"] = int(m.group(3)) / float(m.group(2))
            report["traced_completed"] = int(m.group(1))
        m = _COMPILES.match(line)
        if m:
            report["window_compiles"] = int(m.group(1))
    reduced = spans.reduce(compact, found)
    selfs = reduced["span_self_s"]
    # the per-layer readers themselves, over the window's queries
    record = cellrun.RunRecord(queries=[{}] * result["attempted"],
                               span_self_s=selfs)
    for name in ("ops.host_s_per_query", "kdispatch.call_s_per_query"):
        report[name] = spec.reader(name).read(record)
    report["span_self_s"] = dict(sorted(selfs.items(),
                                        key=lambda kv: -kv[1]))
    report["idle_by_span"] = reduced["idle_by_span"]
    report["idle_s"] = reduced["idle_s"]
    outside = dict(map(tuple, reduced["idle_by_span"])).get(spans.OUTSIDE,
                                                            0.0)
    report["idle_in_spans_pct"] = \
        100.0 * (1 - outside / reduced["idle_s"]) if reduced["idle_s"] \
        else None
    report["idle_gaps"] = trace.reduce(compact)["idle_gaps"]
    return report


if __name__ == "__main__":
    sys.exit(main())
