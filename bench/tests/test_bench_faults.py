"""A run whose timed path is broken comes out as not correct, and the
control of the comparison fails it.

The run skips the harness's look for a chip (jax's CPU device stands
in) and shrinks the configuration; everything else is a whole run.
The faults a relational cell can have are planted in the program's
group-by, where each query's answer is produced: half of the rows left
out, and one aggregate altered.  (No training state, no exchange
between chips: the cells run on one chip.)"""

import jax
import pytest

import control
from harness import cellrun, reference, spec
from repro.core import ops
from repro.core.arrow import Column, Table

SHRINK = {"ssb_sf1.q2q3": {"lineorder": 30000, "part": 3000,
                           "customer": 3000},
          "tpch_sf1.q18": {"orders": 150000, "customer": 15000}}


def shrink(cell):
    def hook(config):
        for table, n in SHRINK[cell].items():
            config["tables"][table]["rows"] = n
        return config
    return hook


def half_rows(real):
    def group_by(table, keys, aggs):
        return real(ops.slice_rows(table, 0, table.num_rows // 2), keys,
                    aggs)
    return group_by


def altered_answer(real):
    def group_by(table, keys, aggs):
        out = real(table, keys, aggs)
        b = out.batches[0]
        last = b.columns[-1]
        values = last._logical().copy()
        if len(values):
            values[0] += 1
        cols = list(b.columns[:-1]) + [
            Column.primitive(values, validity=last.validity)]
        return Table.from_batch(b.schema, cols)
    return group_by


def run(cell, monkeypatch, fault=None):
    monkeypatch.setenv("ZERROW_KERNEL_BACKEND", "pallas")
    if fault is not None:
        monkeypatch.setattr(ops, "group_by", fault(ops.group_by))
    return cellrun.run_cell(cell, 2 ** 31 + 29, 3.0, False, 0.0,
                            require_chip=lambda n: jax.devices()[:n],
                            config_hook=shrink(cell), log=lambda *_: None)


@pytest.mark.parametrize("cell", sorted(SHRINK))
def test_sound_run_is_correct(cell, monkeypatch):
    r = run(cell, monkeypatch)
    assert r["correct"] and r["failed"] == 0
    assert r["compared"] == {"wrong_rows": {"value": 0, "limit": 0}}
    assert list(r)[-1] == "compared"
    assert set(r["metrics"]) == {"rows_per_s", "setup_s"}


@pytest.mark.parametrize("fault", [half_rows, altered_answer])
@pytest.mark.parametrize("cell", sorted(SHRINK))
def test_fault_is_not_correct(cell, fault, monkeypatch):
    r = run(cell, monkeypatch, fault)
    assert not r["correct"]
    assert r["failed"] > 0 and r["compared"]["wrong_rows"]["value"] > 0


@pytest.mark.parametrize("cell", sorted(SHRINK))
def test_control_fails_the_comparison(cell):
    readings = [control.readings(cell, seed, shrink(cell))["wrong_rows"]
                for seed in (11, 2 ** 31 + 12, 13)]
    assert min(readings) > 0


def test_reference_agrees_with_itself():
    config = shrink("ssb_sf1.q2q3")(
        spec.config(spec.load_benchmark(),
                            {"config": "ssb_sf1"}))
    tables = spec.generator(config).generate(config,
                                                     spec.rng(3))
    for q in spec.traffic({"traffic": "q2q3"})["queries"]:
        a, _ = reference.run(q["plan"], tables)
        b, _ = reference.run(q["plan"], tables)
        assert reference.wrong_rows(a, b) == 0
