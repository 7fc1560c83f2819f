"""Test configuration: hermetic 8-device CPU mesh.

The reference cannot test distributed execution without real GPUs
(SURVEY §4); we exploit jax's virtual CPU devices so every parallelism
strategy test runs hermetically.

IMPORTANT: tests must never initialize a TPU backend — a chip belongs
to one process at a time, and a pytest run that grabbed it would fail
or hang whatever else holds it (and would test a different backend
than the tier-1 contract names).  jax reads JAX_PLATFORMS once, at
import: if a pytest plugin imported jax before this file ran, the env
var set below arrives too late, so we also force jax_platforms=cpu
through jax.config, which holds until the first device query and keeps
`backends()` from ever creating a TPU client.
"""
import os

flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8"
    ).strip()
os.environ["JAX_PLATFORMS"] = "cpu"
os.environ.setdefault("JAX_ENABLE_X64", "0")

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")

import pytest  # noqa: E402


@pytest.fixture(scope="session")
def devices8():
    devs = jax.devices("cpu")
    assert len(devs) >= 8
    return devs[:8]
