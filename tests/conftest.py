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
import shutil
import tempfile

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


# One XLA compile cache a RUN (PR 46): identical programs (a toy model's
# weight maker and step built anew by a test, `jit(_normal)` and friends
# in every file) compile once a run, not once a worker and test: a fifth
# of its CPU-seconds.  The directory is the run's own: made by the
# process that owns the run (xdist's controller, or the one process of a
# run without workers), handed to the workers, removed at the end.
def pytest_configure(config):
    if hasattr(config, "workerinput"):  # an xdist worker
        path = config.workerinput["xla_cache"]
    else:
        path = config._xla_cache = tempfile.mkdtemp(prefix="t1-xla-")
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)


@pytest.hookimpl(optionalhook=True)
def pytest_configure_node(node):
    node.workerinput["xla_cache"] = node.config._xla_cache


def pytest_unconfigure(config):
    path = getattr(config, "_xla_cache", None)
    if path is not None:
        shutil.rmtree(path, ignore_errors=True)


@pytest.fixture(scope="session")
def devices8():
    devs = jax.devices("cpu")
    assert len(devs) >= 8
    return devs[:8]
