"""Benchmark harness: one function per paper figure/table.

    PYTHONPATH=src python -m benchmarks.run [--smoke] [figure ...]

Prints ``name,us_per_call,derived`` CSV rows and exits nonzero if any
selected bench failed.  ``--smoke`` runs a quick CI-sized subset at a
heavily reduced scale (so the bench scripts cannot rot without the build
noticing); it must be passed before importing any benchmark module
because the scale is read at import time.
"""

import os
import sys

#: the CI smoke subset: one bench per subsystem family.  'kernels' also
#: asserts the thread-scaling sanity condition (workers=4 <= workers=1
#: x 1.05) so the per-row-loop GIL inversion cannot silently return;
#: 'join' asserts thread/process bit-identity and dictionary reshare
#: hits on the relational workload; 'query' asserts the logical
#: optimizer executes strictly fewer nodes AND loads strictly fewer
#: bytes than the naive plan, bit-identically, and that a one-source
#: diff re-run recomputes only the affected fingerprint cone; 'ingest'
#: asserts streaming micro-batch refreshes are bit-identical to a full
#: recompute while executing strictly fewer nodes per batch than a
#: cold run, with queries served concurrently throughout; 'serve_load'
#: asserts the overload/fault story — typed shed outcomes, a balanced
#: admission ledger, bounded fault-arm p99, and zero wrong results
#: while workers are being killed mid-request.
SMOKE_FIGURES = ("fig2", "fig6", "concurrency", "flight", "diffcache",
                 "kernels", "join", "query", "ingest", "serve_load")


def main() -> None:
    args = sys.argv[1:]
    smoke = "--smoke" in args
    if smoke:
        args = [a for a in args if a != "--smoke"]
        os.environ.setdefault("ZERROW_BENCH_SCALE", "256")
        os.environ["ZERROW_BENCH_SMOKE"] = "1"
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    from . import (bench_concurrency, bench_diffcache, bench_flight,
                   bench_ingest, bench_join, bench_kernels,
                   bench_query, bench_serve_load, fig2_copy_latency,
                   fig4_copy_avoidance, fig5_decache, fig6_resharing,
                   fig7_depth, fig8_dict_repeats, fig9_dict_norepeats,
                   fig10_eviction, roofline_table)
    figures = {
        "fig2": fig2_copy_latency.main,       # copy-avoidance latency
        "fig4": fig4_copy_avoidance.main,     # KernelZero vs memory limit
        "fig5": fig5_decache.main,            # shared deserialization
        "fig6": fig6_resharing.main,          # resharing across 9 ops
        "fig7": fig7_depth.main,              # deep add-column chains
        "fig8": fig8_dict_repeats.main,       # dictionaries, repeats
        "fig9": fig9_dict_norepeats.main,     # dictionaries, no repeats
        "fig10": fig10_eviction.main,         # eviction mechanisms
        "roofline": roofline_table.main,      # dry-run roofline summary
        "concurrency": bench_concurrency.main,  # worker-pool loader overlap
        "flight": bench_flight.main,          # process vs thread data plane
        "diffcache": bench_diffcache.main,    # cross-run differential cache
        "kernels": bench_kernels.main,        # vectorized kernels + scaling
        "join": bench_join.main,              # hash join + group-by engine
        "query": bench_query.main,            # plan frontend + optimizer
        "ingest": bench_ingest.main,          # streaming ingest + serving
        "serve_load": bench_serve_load.main,  # overload + fault resilience
    }
    selected = args or (list(SMOKE_FIGURES) if smoke else list(figures))
    print("name,us_per_call,derived")
    failed = []
    for name in selected:
        if name not in figures:
            print(f"{name},0.0,UNKNOWN (choose from {sorted(figures)})")
            failed.append(name)      # a renamed bench must not pass CI
            continue
        try:
            figures[name]()
        except Exception as e:  # keep the harness going
            failed.append(name)
            print(f"{name},0.0,ERROR:{e!r}")
    if failed:
        raise SystemExit(f"benchmarks failed: {failed}")


if __name__ == "__main__":
    main()
