"""Test harness: force an 8-device virtual CPU mesh.

Mirrors the reference's test strategy of running the full stack without a
cluster (SURVEY §4: btl/self loopback + multi-rank over loopback tcp):
here, N virtual CPU devices stand in for N TPU chips so every collective
schedule executes a real multi-device program.

Must run before jax initializes its backends; JAX_PLATFORMS=cpu is set
here and through jax.config, so the suite runs on the CPU mesh even on a
machine with a TPU.
"""

import os

os.environ["XLA_FLAGS"] = (
    os.environ.get("XLA_FLAGS", "") + " --xla_force_host_platform_device_count=8"
)
os.environ["JAX_PLATFORMS"] = "cpu"

import jax

jax.config.update("jax_platforms", "cpu")

import pytest  # noqa: E402


@pytest.fixture
def devices():
    return jax.devices()
