"""Test configuration: force an 8-device virtual CPU mesh BEFORE jax imports.

Mirrors the survey's test recipe (SURVEY.md §4): multi-chip sharding is
exercised on a faked host-platform mesh so the suite runs anywhere; the real
TPU path is covered by chip_smoke.py on hardware.
"""

import os

os.environ["JAX_PLATFORMS"] = "cpu"
_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (
        _flags + " --xla_force_host_platform_device_count=8"
    ).strip()
os.environ.setdefault("JAX_ENABLE_X64", "0")

import jax  # noqa: E402
import numpy as np  # noqa: E402
import pytest  # noqa: E402

from hpbandster_tpu.utils.compile_cache import (  # noqa: E402
    enable_persistent_compile_cache,
)

# Persist compiled executables across suite runs: the compile-heavy fused
# sweeps dominate wall-clock, and their programs are identical run to run.
# First run pays the compiles; repeats load from the cache. The suite also
# compiles thousands of sub-second kernels: persisting those would only
# churn the disk, so it keeps jax's own one-second threshold.
enable_persistent_compile_cache()
jax.config.update("jax_persistent_cache_min_compile_time_secs", 1.0)


# graftlint rule fixtures are deliberately-broken modules: parsed by the
# analysis tests, never collected or imported by pytest
collect_ignore_glob = ["analysis_fixtures/*"]


@pytest.fixture
def rng():
    return np.random.default_rng(0)
