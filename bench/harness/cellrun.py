"""One run of one cell: set-up, the measured window, the comparison
with the reference, and the result line.

Set-up (counted in ``setup_s``, from process start): the tables are
generated from the seed and written as zarquet, every query of the
rotation is built with the plan frontend, and one warm-up pass over the
rotation compiles every kernel shape the window will use (from the
persistent compile cache after a checkout's first run).

Window: a closed loop with one stream — each query is issued when the
last returns, in the traffic file's fixed rotation, until ``seconds``
have passed.  ``rows_per_s`` is the rows scanned by the queries that
completed inside the window over the time from the window's start to
the last of those completions; the query in flight at the deadline
finishes, and is compared, but does not count.  With ``trace`` the
window runs under the profiler and the per-layer metrics are read
instead, from the reduced trace, the program's spans in it
(``harness.spans``), its counters and the reference's cardinalities.

After the window: the memory peak is read, the device-call and
compile counts are checked (every path kernel — the joins', each
group-by aggregate's and those the traffic names under ``"kernels"`` —
must have served calls on the chip), and every query's result is
compared, row for row, with the plain reference's, computed once per
query.
"""

from __future__ import annotations

import contextlib
import json
import os
import shutil
import tempfile
import time
from dataclasses import dataclass, field
from typing import Callable, Dict, List, Optional

from harness import (device, program, queryspec, reference, spans, spec,
                     trace)

#: relational kernels every query's joins run through
JOIN_KERNELS = ("hash_fixed", "combine_hashes", "filter_join_gather")


class RunFailed(RuntimeError):
    """The run cannot report: it prints no result and exits nonzero."""


@dataclass
class RunRecord:
    """What the per-layer readers (``bench/metrics/*.py``) read."""
    queries: List[dict] = field(default_factory=list)
    trace: Optional[dict] = None
    peaks: Optional[dict] = None
    #: self seconds per program span in the window, as ``spans.reduce``
    #: gives them; None untraced, empty for a program without spans
    span_self_s: Optional[Dict[str, float]] = None


def _annotate(on: bool, name: str):
    if not on:
        return contextlib.nullcontext()
    import jax
    return jax.profiler.TraceAnnotation(name)


def _profile_window(on: bool, logdir: str):
    if not on:
        return contextlib.nullcontext()
    import jax

    @contextlib.contextmanager
    def profiled():
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0
        options.host_tracer_level = 2
        jax.profiler.start_trace(logdir, profiler_options=options)
        try:
            yield
        finally:
            jax.profiler.stop_trace()
    return profiled()


def _trace_file(logdir: str) -> str:
    found = [os.path.join(d, f) for d, _, fs in os.walk(logdir)
             for f in fs if f.endswith(".xplane.pb")]
    if len(found) != 1:
        raise RunFailed(f"want one profiler trace in {logdir}, found "
                        f"{found}")
    return found[0]


def run_cell(workload: str, seed: int, seconds: float, traced: bool,
             t0: float, require_chip: Callable = device.require_chip,
             config_hook: Optional[Callable[[dict], dict]] = None,
             trace_out: Optional[str] = None, log=print) -> dict:
    """Run the cell once; returns the result line's object.  ``t0`` is
    the process start on ``time.perf_counter``'s clock.  Tests steer
    the run through ``require_chip`` and ``config_hook`` (which shrinks
    the configuration)."""
    from repro.core import kdispatch
    from repro.launch.compile_cache import enable_compile_cache

    bench = spec.load_benchmark()
    cell = spec.cell(bench, workload)
    config = spec.config(bench, cell)
    if config_hook is not None:
        config = config_hook(config)
    mix = spec.traffic(cell)
    queries = mix["queries"]
    for q in queries:
        queryspec.validate(q["plan"])
    named_kernels = spec.path_kernels(mix)

    devices = require_chip(cell["chips"])
    platform = devices[0].platform
    log(f"device: {platform} / {devices[0].device_kind} / "
        f"{len(devices)} chip(s); compile cache: {enable_compile_cache()}")
    clock = device.CompileClock()
    peaks = device.peaks(devices[0].device_kind) if traced else None

    tables = spec.generator(config).generate(config, spec.rng(seed))
    table_rows = {t: len(next(iter(cols.values())))
                  for t, cols in tables.items()}
    dict_columns = {t: tuple(s.get("dict_columns", ()))
                    for t, s in config["tables"].items()}
    workdir = tempfile.mkdtemp(prefix=f"zerrow-bench-{workload}-")
    try:
        paths = program.write_tables(tables, workdir)
        swap = os.path.join(workdir, "swap")
        rotation = [(q, program.build_plan(q["plan"], paths, dict_columns))
                    for q in queries]
        for q, plan in rotation:
            program.run_query(plan, q["name"], swap, op_spans=traced)
        warm_compiles = clock.compiles
        kdispatch.reset_device_calls()
        setup_s = time.perf_counter() - t0
        log(f"set-up {setup_s:.3f} s: tables {table_rows}, warm-up "
            f"{len(queries)} queries, {warm_compiles} compiles "
            f"({clock.seconds:.3f} s)")

        runs = []
        logdir = os.path.join(workdir, "profile")
        with _profile_window(traced, logdir):
            with _annotate(traced, trace.WINDOW):
                start = time.perf_counter()
                deadline = start + seconds
                i = 0
                while time.perf_counter() < deadline:
                    q, plan = rotation[i % len(rotation)]
                    with _annotate(traced, f"query {q['name']}"):
                        r = program.run_query(plan, q["name"], swap,
                                              op_spans=traced)
                    runs.append((q, r, time.perf_counter() - start))
                    i += 1
        window_compiles = clock.compiles - warm_compiles
        calls = kdispatch.device_calls()
        memory_peak = device.memory_peak_bytes(devices)
        compact = span_self_s = None
        if traced:
            path = _trace_file(logdir)
            compact = trace.extract(path)
            span_self_s = spans.self_seconds(spans.extract(path),
                                             *compact["window"])
            if trace_out:
                shutil.copy(path, trace_out + ".xplane.pb")
                with open(trace_out + ".json", "w") as fh:
                    json.dump(compact, fh)
    finally:
        shutil.rmtree(workdir, ignore_errors=True)

    needed = set(JOIN_KERNELS) | set(named_kernels) | {
        f"grouped_{a}" for q in queries
        for a in queryspec.aggregates_used(q["plan"])}
    idle = sorted(k for k in needed if calls.get((k, platform), 0) == 0)
    if idle:
        raise RunFailed(f"path kernels served no {platform} call in the "
                        f"window: {idle} (calls: {calls})")
    log(f"device calls on {platform} in the window: " + ", ".join(
        f"{k}={n}" for (k, p), n in sorted(calls.items()) if p == platform))
    log(f"compiles in the window: {window_compiles} (expected 0)")

    want = {q["name"]: reference.run(q["plan"], tables) for q in queries}
    wrong = [reference.wrong_rows(want[q["name"]][0], r.rows)
             for q, r, _ in runs]
    completed = [(q, t) for q, _, t in runs if t <= seconds]
    if not completed:
        raise RunFailed(f"no query completed inside the {seconds} s window")
    rows = sum(queryspec.rows_scanned(q["plan"], table_rows)
               for q, _ in completed)
    log(f"window: {len(runs)} queries issued, {len(completed)} completed "
        f"inside {seconds} s, last at {completed[-1][1]:.3f} s, {rows} "
        f"rows scanned")
    ends = [t for _, _, t in runs]
    took: dict = {}
    for (q, _, _), begin, end in zip(runs, [0.0] + ends, ends):
        took.setdefault(q["name"], []).append(end - begin)
    log("seconds per query: " + ", ".join(
        f"{name} {sum(v) / len(v):.3f} (x{len(v)})"
        for name, v in took.items()))

    result = {"correct": not any(wrong), "attempted": len(runs),
              "failed": sum(w > 0 for w in wrong), "metrics": {},
              "device": {"platform": platform,
                         "kind": devices[0].device_kind,
                         "count": len(devices),
                         "memory_peak_bytes": memory_peak}}
    if not traced:
        values = {"rows_per_s": rows / completed[-1][1], "setup_s": setup_s}
        for m in spec.end_to_end(bench, workload):
            if m["name"] not in values:
                raise RunFailed(f"end-to-end metric {m['name']!r} is not "
                                f"measured by this harness")
            result["metrics"][m["name"]] = {"value": values[m["name"]],
                                            "unit": m["unit"]}
    else:
        reduced = trace.reduce(compact)
        if reduced["busy_s"] <= 0:
            raise RunFailed("no device operation in the traced window")
        record = RunRecord(peaks=peaks, trace=reduced,
                           span_self_s=span_self_s, queries=[
            {"name": q["name"], "load_s": r.load_s,
             "bytes_copied": r.bytes_copied, "ops": want[q["name"]][1],
             "rows_scanned": queryspec.rows_scanned(q["plan"], table_rows)}
            for q, r, _ in runs])
        for m in spec.per_layer(bench, workload):
            value = spec.reader(m["name"]).read(record)
            if value is not None:
                result["metrics"][m["name"]] = {"value": value,
                                                "unit": m["unit"]}
        result["device"]["busy_s"] = reduced["busy_s"]
        result["device"]["window_s"] = reduced["window_s"]
        result["breakdown"] = {"device_ops": reduced["device_ops"],
                               "idle_gaps": reduced["idle_gaps"]}
    result["compared"] = {"wrong_rows": {"value": sum(wrong), "limit": 0}}
    return result
