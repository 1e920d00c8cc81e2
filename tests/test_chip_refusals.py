"""Nothing on the chip path may hide the device or assume one.

CPU-only, fast: ``chip_smoke.py`` refuses to run without a TPU before
it spawns anything; the compile cache is placed from outside or at one
fixed path; an unknown chip has no peak; asking the mesh for a
platform the backend does not expose is an error.
"""

import os
import subprocess
import sys
import time

import pytest

import jax

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def test_chip_smoke_refuses_the_cpu_before_spawning_anything():
    t0 = time.time()
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "chip_smoke.py")],
        env={**os.environ, "JAX_PLATFORMS": "cpu"},
        capture_output=True,
        text=True,
        timeout=120,
    )
    assert proc.returncode != 0
    assert "'cpu'" in proc.stderr and "needs a TPU" in proc.stderr
    # no phase ran: no result line, no worker processes to wait for
    assert proc.stdout == ""
    assert time.time() - t0 < 60


class _ConfigUpdates:
    def __init__(self, monkeypatch):
        self.calls = []
        monkeypatch.setattr(
            jax.config, "update", lambda *a, **k: self.calls.append(a)
        )


def test_compile_cache_placed_from_outside_sets_nothing(monkeypatch):
    from ray_tpu.utils import platform

    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/somewhere/else")
    updates = _ConfigUpdates(monkeypatch)
    assert platform.ensure_compile_cache() == "/somewhere/else"
    assert updates.calls == []


def test_compile_cache_default_is_one_fixed_path(monkeypatch, tmp_path):
    from ray_tpu.utils import platform

    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    want = os.path.join(REPO, ".jax_cache")
    # the same path whatever the working directory
    code = (
        "from ray_tpu.utils.platform import compile_cache_dir;"
        "print(compile_cache_dir())"
    )
    env = {
        k: v
        for k, v in os.environ.items()
        if k != "JAX_COMPILATION_CACHE_DIR"
    }
    env["PYTHONPATH"] = REPO
    for cwd in (str(tmp_path), "/"):
        out = subprocess.run(
            [sys.executable, "-c", code],
            env=env,
            cwd=cwd,
            capture_output=True,
            text=True,
            timeout=60,
            check=True,
        )
        assert out.stdout.strip() == want
    # the CPU backend places nothing (and says so)...
    updates = _ConfigUpdates(monkeypatch)
    assert platform.ensure_compile_cache() is None
    assert updates.calls == []
    # ...an accelerator backend gets exactly that path
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert platform.ensure_compile_cache() == want
    assert updates.calls == [("jax_compilation_cache_dir", want)]


def test_unknown_device_kind_has_no_peak(monkeypatch):
    import bench
    from ray_tpu.telemetry import device as device_ledger

    monkeypatch.delenv("RAY_TPU_PEAK_FLOPS", raising=False)
    monkeypatch.delenv("RAY_TPU_PEAK_HBM_BPS", raising=False)
    device_ledger.set_peak_flops(None, 0.0)
    with pytest.raises(ValueError, match="unknown device_kind"):
        device_ledger.peak_flops_per_device("Quantum QPU")
    with pytest.raises(ValueError, match="unknown device_kind"):
        device_ledger.peak_hbm_bytes_per_s("Quantum QPU")
    # the rows tier-1 and the chip need are there
    assert device_ledger.peak_flops_per_device("cpu") > 0
    assert device_ledger.peak_flops_per_device("TPU v5 lite") == 197e12
    with pytest.raises(ValueError, match="unknown device_kind"):
        bench.chip_peak_tflops("Quantum QPU")
    # the CPU is not a chip: the bench has no peak for it either
    with pytest.raises(ValueError, match="unknown device_kind"):
        bench.chip_peak_tflops()
    assert bench.chip_peak_tflops("TPU v5 lite") == (197.0, "TPU v5 lite")
    with pytest.raises(SystemExit, match="measures a TPU"):
        bench.require_tpu()


def test_available_devices_raises_for_a_platform_not_there():
    from ray_tpu import sharding as sharding_lib

    assert len(sharding_lib.available_devices("cpu")) == len(jax.devices())
    with pytest.raises(RuntimeError, match="no 'tpu' devices"):
        sharding_lib.available_devices("tpu")
