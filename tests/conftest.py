"""Test configuration: force an 8-device virtual CPU platform.

Mirrors the reference's ``_fake_gpus`` testing strategy
(``rllib/policy/torch_policy.py:192-196``): multi-device semantics are tested
without hardware by asking XLA for 8 host devices. Must run before jax is
imported anywhere.
"""

import os

_HW = os.environ.get("RAY_TPU_HW_TEST") == "1"

if not _HW:
    # Hard override: tests always run on the virtual 8-device CPU
    # platform, whatever the machine has. RAY_TPU_HW_TEST=1 leaves the
    # real backend in place so the tests in test_tpu_hardware.py can
    # exercise the chip.
    os.environ["JAX_PLATFORMS"] = "cpu"
    flags = os.environ.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" not in flags:
        os.environ["XLA_FLAGS"] = (
            flags + " --xla_force_host_platform_device_count=8"
        ).strip()
os.environ.setdefault("RAY_TPU_TEST_MODE", "1")

import pathlib

import numpy as np
import pytest

# ---------------------------------------------------------------------------
# Test tiers (reference precedent: rllib/BUILD py_test size tiers).
#
#   default            fast unit tier, < ~8 min wall clock
#   -m regression      learning / step-heavy tests (listed in
#                      regression_tier.txt, regenerated from
#                      `pytest --durations=0`: everything >= ~10s)
#   -m slow            the longest learning regressions (explicit marks)
#   -m smoke           tiny bench-path sanity tier
#
# pytest.ini deselects `regression or slow` by default; run the full
# suite with `pytest tests/ -m ""`.
# ---------------------------------------------------------------------------

_TIER_FILE = pathlib.Path(__file__).parent / "regression_tier.txt"


def pytest_collection_modifyitems(config, items):
    listed = set()
    if _TIER_FILE.exists():
        listed = {
            ln.strip()
            for ln in _TIER_FILE.read_text().splitlines()
            if ln.strip() and not ln.startswith("#")
        }
    for item in items:
        # nodeid relative to the repo root, e.g. tests/test_ppo.py::name
        nodeid = item.nodeid.replace("\\", "/")
        base = nodeid.split("[")[0]  # a bare id marks every param case
        if nodeid in listed or base in listed:
            item.add_marker(pytest.mark.regression)


@pytest.fixture
def rng():
    return np.random.default_rng(0)
