"""Test configuration.

Forces an 8-device virtual CPU platform *before* jax initializes, so the
multi-chip sharding paths (mesh collectives, shard_map, pjit) run in CI
without TPU hardware — the TPU translation of the reference's
run-everything-against-the-CPU-emulator strategy (SURVEY §4).  Pallas
kernels run in interpret mode there (accl_tpu/utils/platform.py).  The
program runs on the chip through chip_smoke.py, and
tests/test_chip_compile.py compiles the kernels for a described v5e
topology without one.
"""
import os

os.environ["JAX_PLATFORMS"] = "cpu"
# Loaded CI hosts can stall a rank long enough for the 1 s reference
# receive budget to fire spuriously; widen the *default* engine timeout
# for tests (tests exercising timeout behavior pass explicit values).
os.environ.setdefault("ACCL_DEFAULT_TIMEOUT", "30000000")
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8"
    ).strip()

# a plugin may have imported jax before this file ran; the runtime
# config update pins the platform either way
import jax

jax.config.update("jax_platforms", "cpu")

import numpy as np
import pytest


@pytest.fixture
def rng():
    return np.random.default_rng(42)
