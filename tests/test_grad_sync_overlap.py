"""When the train step is compiled with the options that run its
gradient all-reduces asynchronously (PR 49): only on a TPU mesh of more
than one device with a gradient that is replicated over an axis; on
every CPU mesh (all of tier 1) `jax.jit` gets no `compiler_options` and
the step is the one it was.  CPU, toy sizes: conditions, counts, and two
steps' loss and weights; no time.
"""
import types

import jax
import numpy as np
import pytest

from flexflow_tpu import (ActiMode, AdamOptimizer, FFConfig, FFModel,
                          LossType)
from flexflow_tpu import executor as executor_mod
from flexflow_tpu.executor import (GRAD_SYNC_OVERLAP_OPTIONS, GraphExecutor,
                                   grad_sync_overlap_options)
from flexflow_tpu.strategy import data_parallel_strategy

BATCH, WIDTH, HIDDEN, CLASSES = 16, 32, 64, 8
PARAMS = WIDTH * HIDDEN + HIDDEN + HIDDEN * CLASSES + CLASSES


def toy(chips, zero_stage=0, remat=False):
    """A two-layer classifier, data parallel over `chips` CPU devices."""
    ff = FFModel(FFConfig(batch_size=BATCH, num_devices=chips,
                          zero_stage=zero_stage, remat=remat))
    x = ff.create_tensor([BATCH, WIDTH], name="x")
    t = ff.dense(x, HIDDEN, activation=ActiMode.RELU)
    ff.softmax(ff.dense(t, CLASSES))
    ff.compile(optimizer=AdamOptimizer(alpha=0.01),
               loss_type=LossType.SPARSE_CATEGORICAL_CROSSENTROPY,
               strategy=data_parallel_strategy(chips),
               devices=jax.devices("cpu")[:chips], seed=0)
    return ff


@pytest.fixture(scope="module")
def four():
    return toy(4)


def described(platform, count):
    """A mesh's devices as the helper reads them, on a platform this
    process does not have."""
    return np.array([types.SimpleNamespace(platform=platform)] * count)


def two_steps(ff):
    rng = np.random.RandomState(0)
    xs = rng.randn(2, BATCH, WIDTH).astype(np.float32)
    ys = rng.randint(0, CLASSES, (2, BATCH)).astype(np.int32)
    losses = [float(ff.train_step({"x": x}, y)["loss"])
              for x, y in zip(xs, ys)]
    return losses, jax.tree.map(np.asarray, ff.get_weights())


def gauge(ff, name):
    return ff.telemetry.metrics.gauge(name).value


# -- the helper's condition ------------------------------------------------
def test_one_device_gets_no_options():
    one = toy(1)
    assert one.executor.grad_sync_bytes() == 0
    assert one.executor.grad_sync_compiler_options() is None
    assert grad_sync_overlap_options(described("tpu", 1), 4 * PARAMS) is None
    assert gauge(one, "parallel/grad_sync_bytes") == 0
    assert gauge(one, "parallel/grad_sync_async") == 0


def test_cpu_mesh_of_four_gets_no_options(four):
    """The CPU compiler rejects `xla_tpu_*` names: nothing is passed."""
    assert four.executor.grad_sync_bytes() > 0
    assert four.executor.grad_sync_compiler_options() is None
    assert gauge(four, "parallel/grad_sync_async") == 0


def test_tpu_mesh_with_every_gradient_scattered_gets_no_options():
    """ZeRO-2: every leaf's gradient is scattered over the one axis (all
    of the toy's dims divide by 4), so there is no all-reduce."""
    scattered = toy(4, zero_stage=2)
    assert scattered.executor.grad_sync_bytes() == 0
    assert grad_sync_overlap_options(
        described("tpu", 4), scattered.executor.grad_sync_bytes()) is None


def test_tpu_mesh_of_four_with_replicated_weights_gets_the_landed_set(four):
    got = grad_sync_overlap_options(described("tpu", 4),
                                    four.executor.grad_sync_bytes())
    assert got == GRAD_SYNC_OVERLAP_OPTIONS and got
    got["mine"] = 1  # a copy: the caller's to change
    assert "mine" not in GRAD_SYNC_OVERLAP_OPTIONS


def test_grad_sync_bytes_counts_the_replicated_parameters(four):
    assert four.executor.grad_sync_bytes() == 4 * PARAMS
    assert gauge(four, "parallel/grad_sync_bytes") == 4 * PARAMS


# -- what `jax.jit` is handed on a CPU mesh --------------------------------
@pytest.mark.parametrize("remat", [False, True], ids=["build_step", "_at"])
def test_cpu_step_is_jitted_without_options_and_trains_as_before(
        monkeypatch, remat):
    """Both `jax.jit` sites of the train step ask the helper, get
    nothing on four CPU devices, and two steps give the loss and the
    weights of a step jitted with no `compiler_options` argument at
    all."""
    asked, handed = [], []
    real_helper = GraphExecutor.grad_sync_compiler_options
    real_jit = jax.jit

    def helper(self):
        asked.append(real_helper(self))
        return asked[-1]

    def jit(f, **kw):
        handed.append(kw.get("compiler_options", "absent"))
        return real_jit(f, **kw)

    monkeypatch.setattr(GraphExecutor, "grad_sync_compiler_options", helper)
    monkeypatch.setattr(executor_mod.jax, "jit", jit)
    ff = toy(4, remat=remat)
    assert isinstance(ff._step_fn, executor_mod._RematStep) == remat
    got = two_steps(ff)
    assert asked and set(asked) == {None}
    assert None in handed and "absent" in handed  # the step's, the others'
    assert all(h in (None, "absent") for h in handed)

    # the same model with the argument never passed (the parent's call)
    def plain_jit(f, **kw):
        kw.pop("compiler_options", None)
        return real_jit(f, **kw)

    monkeypatch.setattr(executor_mod.jax, "jit", plain_jit)
    want = two_steps(toy(4, remat=remat))
    assert got[0] == want[0]
    for a, b in zip(jax.tree.leaves(got[1]), jax.tree.leaves(want[1])):
        np.testing.assert_array_equal(a, b)
