"""A traced run reads the program's spans into its result line:
``ops.host_s_per_query`` and ``kdispatch.call_s_per_query`` are there,
and equal ``span_report.summarize``'s numbers on the same trace.  The
run skips the harness's look for a chip (jax's CPU device stands in),
shrinks the configuration and, since the CPU writes no TPU plane into
the trace, reads the window as busy for a millisecond."""

import json

import jax
import pytest

import span_report
from harness import cellrun, device, spans, trace

CELL = "tpch_sf1.q18"


def shrink(config):
    config["tables"]["orders"]["rows"] = 20000
    config["tables"]["customer"]["rows"] = 2000
    return config


def test_traced_run_reads_program_spans(tmp_path, monkeypatch):
    monkeypatch.setenv("ZERROW_KERNEL_BACKEND", "pallas")
    monkeypatch.setattr(device, "peaks",
                        lambda kind: {"hbm_bytes_per_s": 819e9})
    real = trace.reduce

    def reduce(compact):
        return dict(real(compact), busy_s=1e-3)
    monkeypatch.setattr(trace, "reduce", reduce)

    lines = []
    prefix = str(tmp_path / "trace")
    result = cellrun.run_cell(CELL, 2 ** 31 + 61, 1.0, True, 0.0,
                              require_chip=lambda n: jax.devices()[:n],
                              config_hook=shrink, trace_out=prefix,
                              log=lines.append)
    assert result["correct"]
    metrics = result["metrics"]
    for name in ("ops.host_s_per_query", "kdispatch.call_s_per_query"):
        assert metrics[name]["unit"] == "s" and metrics[name]["value"] > 0
    assert list(result["breakdown"]) == ["device_ops", "idle_gaps"]

    with open(prefix + ".json") as fh:
        compact = json.load(fh)
    found = spans.extract(prefix + ".xplane.pb")
    report = span_report.summarize(result, lines, compact, found)
    for name in ("ops.host_s_per_query", "kdispatch.call_s_per_query"):
        assert metrics[name]["value"] == pytest.approx(report[name],
                                                       rel=0, abs=1e-9)
