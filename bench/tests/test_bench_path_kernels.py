"""Path kernels a traffic mix names (``"kernels"``): a run in which one
of them served no call on the device fails, and the list is checked to
hold kernel names.  The run skips the harness's look for a chip (jax's
CPU device stands in) and shrinks the configuration."""

import jax
import pytest

from harness import cellrun, spec

CELL = "ssb_sf1.q2q3"


def shrink(config):
    for table, n in {"lineorder": 20000, "part": 2000,
                     "customer": 2000}.items():
        config["tables"][table]["rows"] = n
    return config


@pytest.mark.parametrize("kernel, served", [
    ("grouped_sum", True),          # every SSB query sums revenue
    ("hash_fixed", True),
    ("grouped_min", False),         # no SSB query takes a min
    ("like_utf8", False)])          # no such kernel
def test_named_kernel_must_serve_a_call(kernel, served, monkeypatch):
    monkeypatch.setenv("ZERROW_KERNEL_BACKEND", "pallas")
    real = spec.traffic
    monkeypatch.setattr(spec, "traffic",
                        lambda c: dict(real(c), kernels=[kernel]))

    def run():
        return cellrun.run_cell(CELL, 2 ** 31 + 53, 0.5, False, 0.0,
                                require_chip=lambda n: jax.devices()[:n],
                                config_hook=shrink, log=lambda *_: None)
    if served:
        assert run()["correct"]
    else:
        with pytest.raises(cellrun.RunFailed, match=kernel):
            run()


@pytest.mark.parametrize("kernels", [
    "grouped_sum", [7], ["grouped sum"], ["kernels/like"], [""], {"a": 1}])
def test_kernel_list_is_checked(kernels):
    with pytest.raises(ValueError, match="traffic kernels"):
        spec.path_kernels({"queries": [], "kernels": kernels})


@pytest.mark.parametrize("traffic", ["q2q3", "q18"])
def test_accepted_mixes_name_no_kernel(traffic):
    assert spec.path_kernels(spec.traffic({"traffic": traffic})) == []
