"""Test fixture: force an 8-device virtual CPU mesh BEFORE jax initialises.

Mirrors the reference's testing stance (SURVEY.md §4): unit tests run
CPU-only; multi-device semantics (kvstore, model parallel) are exercised on
one host — the reference used multi-context CPU tests
(tests/python/unittest/test_model_parallel.py) and spawned-process clusters;
we use XLA's virtual host devices.

x64 is a TEST-ONLY setting (numeric pins compare against float64
references).  The chip runs jax's default x32; that configuration, and
the TPU itself, are covered by ``chip_smoke.py`` at the repo root.
"""
import os
import tempfile

os.environ["JAX_PLATFORMS"] = "cpu"
# telemetry artifacts with relative paths (flightrecorder_rank*.json,
# profile_rank*.json, metrics expositions) land in a throwaway dir
# instead of the CWD/repo root; subprocess workers inherit it.  Tests
# that assert on dumps pass absolute paths, which always win.
os.environ.setdefault("MXNET_DUMP_DIR",
                      tempfile.mkdtemp(prefix="mxnet-test-dumps-"))
os.environ.setdefault("JAX_ENABLE_X64", "1")
# the persistent compile cache defaults to a directory INSIDE the
# checkout (mxnet_tpu/compile_cache.py); a test run must not grow the
# tree the chip tool copies, so it is switched off through jax's own
# variable — inherited by every subprocess the tests start
os.environ["JAX_ENABLE_COMPILATION_CACHE"] = "false"
flags = os.environ.get("XLA_FLAGS", "")
if "host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (flags + " --xla_force_host_platform_device_count=8").strip()

# the config API wins over the environment as long as no backend has
# been initialised yet: tests never reach for an accelerator
import jax

jax.config.update("jax_platforms", "cpu")
jax.config.update("jax_enable_x64", True)
assert jax.devices()[0].platform == "cpu", "tests must run on the virtual CPU mesh"

import numpy as np
import pytest


@pytest.fixture(autouse=True)
def _seed():
    import mxnet_tpu as mx

    np.random.seed(0)
    mx.random.seed(0)
    yield


def pytest_configure(config):
    config.addinivalue_line(
        "markers", "slow: example-script smoke tests (subprocess, slower)")
