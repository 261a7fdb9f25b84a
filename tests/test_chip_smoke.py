"""``chip_smoke.py`` rehearsed without the chip.

The script itself must refuse to run anywhere but on a TPU, and must
fail when it is copied away from the repository; its phases (the star
schema, both backend arms through the plan frontend, the bit-level
comparison and the device-call check) run here at a tiny size on jax's
CPU device.
"""
import importlib.util
import json
import os
import shutil
import subprocess
import sys

import numpy as np
import pytest

pytest.importorskip("jax")

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SCRIPT = os.path.join(ROOT, "chip_smoke.py")


def _load():
    spec = importlib.util.spec_from_file_location("chip_smoke", SCRIPT)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _run(cwd, script):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run([sys.executable, script], cwd=cwd, env=env,
                          capture_output=True, text=True, timeout=120)


def _printed_result(stdout: str) -> bool:
    lines = stdout.strip().splitlines()
    if not lines:
        return False
    try:
        return "ok" in json.loads(lines[-1])
    except ValueError:
        return False


def test_smoke_fails_without_a_tpu():
    out = _run(ROOT, SCRIPT)
    assert out.returncode != 0
    assert "no TPU" in out.stderr
    assert not _printed_result(out.stdout)


def test_smoke_fails_alone(tmp_path):
    alone = shutil.copy(SCRIPT, tmp_path / "chip_smoke.py")
    out = _run(str(tmp_path), str(alone))
    assert out.returncode != 0
    assert not _printed_result(out.stdout)


def test_smoke_phases_on_cpu(tmp_path):
    import jax
    cs = _load()
    platform = jax.devices()[0].platform
    report = cs.run_star(str(tmp_path), seed=3, platform=platform,
                         orders_log2=13, customers_log2=10)
    assert set(report["device_calls"]) == set(cs.PATH_KERNELS)
    assert all(n > 0 for n in report["device_calls"].values())
    device = cs.kdispatch.DEVICE_BACKEND
    assert report["numpy_wall_s"] > 0 and report[f"{device}_wall_s"] > 0
    assert os.environ.get("ZERROW_KERNEL_BACKEND") is None


@pytest.mark.parametrize("env_dir", [None, "given"])
def test_compile_cache_location(tmp_path, monkeypatch, env_dir):
    """Unset: the fixed ``<checkout>/.jax_cache``.  Set: the given
    directory, and nothing in code overrides it."""
    import jax
    from repro.launch import compile_cache
    names = ("jax_compilation_cache_dir",
             "jax_persistent_cache_min_compile_time_secs")
    saved = {n: getattr(jax.config, n) for n in names}
    try:
        jax.config.update("jax_compilation_cache_dir", "untouched")
        if env_dir is None:
            monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
            want = os.path.join(ROOT, ".jax_cache")
            assert compile_cache.enable_compile_cache() == want
            assert jax.config.jax_compilation_cache_dir == want
        else:
            given = str(tmp_path / env_dir)
            monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", given)
            assert compile_cache.enable_compile_cache() == given
            assert jax.config.jax_compilation_cache_dir == "untouched"
    finally:
        for n, v in saved.items():
            jax.config.update(n, v)


def test_smoke_compare_catches_one_flipped_bit(tmp_path):
    cs = _load()
    orders, customers = cs.write_star(str(tmp_path), seed=4,
                                      orders_log2=12, customers_log2=9)
    arm = cs.run_arm("numpy", orders, customers, str(tmp_path))
    cs.compare(arm, arm, 1 << 12)
    vals, valid = arm["aggregates"]["total"]
    bad = {"aggregates": dict(arm["aggregates"])}
    bad["aggregates"]["total"] = (vals ^ np.int64(1), valid)
    with pytest.raises(AssertionError, match="total"):
        cs.compare(arm, bad, 1 << 12)
    with pytest.raises(AssertionError, match="lost rows"):
        cs.compare(arm, arm, (1 << 12) + 1)
