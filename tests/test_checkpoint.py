"""Checkpoint/resume tests: orbax round trip, bitwise training resume,
cross-mesh restore, and the plain npz weight path."""
import numpy as np
import pytest
from _family import weights_equal

from flexflow_tpu import FFConfig, FFModel, LossType, MetricsType, SGDOptimizer
from flexflow_tpu.checkpoint import (
    CheckpointManager,
    load_weights_npz,
    save_weights_npz,
)
from flexflow_tpu.fftype import ActiMode


def _model(devices, seed=0):
    cfg = FFConfig(batch_size=16, num_devices=len(devices), seed=seed)
    ff = FFModel(cfg)
    x = ff.create_tensor([16, 8], name="x")
    t = ff.dense(x, 32, activation=ActiMode.RELU)
    t = ff.dense(t, 4)
    ff.softmax(t)
    ff.compile(optimizer=SGDOptimizer(lr=0.05),
               loss_type=LossType.SPARSE_CATEGORICAL_CROSSENTROPY,
               metrics=[MetricsType.SPARSE_CATEGORICAL_CROSSENTROPY],
               devices=devices, seed=seed)
    return ff


def _data(n=64, seed=0):
    rng = np.random.RandomState(seed)
    xs = rng.randn(n, 8).astype(np.float32)
    ys = rng.randint(0, 4, size=n).astype(np.int32)
    return xs, ys



def test_save_restore_round_trip(devices8, tmp_path):
    ff = _model(devices8)
    xs, ys = _data()
    ff.fit(xs, ys, epochs=1, verbose=False)
    saved = ff.get_weights()

    mgr = CheckpointManager(str(tmp_path / "ckpt"))
    mgr.save(ff, step=1)
    assert mgr.latest_step() == 1

    ff.fit(xs, ys, epochs=1, verbose=False)  # diverge
    step = mgr.restore(ff)
    assert step == 1
    weights_equal(ff.get_weights(), saved)
    meta = mgr.restore_meta()
    assert meta["step"] == 1 and meta["num_devices"] == 8
    mgr.close()


def test_resume_training_is_deterministic(devices8, tmp_path):
    xs, ys = _data(128)

    # uninterrupted: 4 epochs
    ff_a = _model(devices8, seed=11)
    ff_a.fit(xs, ys, epochs=2, verbose=False)
    mgr = CheckpointManager(str(tmp_path / "c1"))
    mgr.save(ff_a, step=2)
    ff_a.fit(xs, ys, epochs=2, verbose=False)

    # interrupted: fresh process-equivalent restores then continues
    ff_b = _model(devices8, seed=99)  # different init — must be overwritten
    mgr.restore(ff_b)
    ff_b.fit(xs, ys, epochs=2, verbose=False)

    weights_equal(ff_a.get_weights(), ff_b.get_weights())
    mgr.close()


def test_cross_mesh_restore(devices8, tmp_path):
    """Checkpoint on 8 devices, restore into a 1-device model."""
    ff8 = _model(devices8)
    xs, ys = _data()
    ff8.fit(xs, ys, epochs=1, verbose=False)
    mgr = CheckpointManager(str(tmp_path / "c2"))
    mgr.save(ff8, step=0)

    ff1 = _model(devices8[:1], seed=5)
    mgr.restore(ff1)
    weights_equal(ff1.get_weights(), ff8.get_weights())

    y8 = np.asarray(ff8.forward({"x": xs[:16]}))
    y1 = np.asarray(ff1.forward({"x": xs[:16]}))
    np.testing.assert_allclose(y8, y1, rtol=2e-5, atol=2e-5)
    mgr.close()


def test_npz_weights_round_trip(devices8, tmp_path):
    ff = _model(devices8)
    xs, ys = _data()
    ff.fit(xs, ys, epochs=1, verbose=False)
    path = str(tmp_path / "w.npz")
    save_weights_npz(ff, path)
    saved = ff.get_weights()

    ff.fit(xs, ys, epochs=1, verbose=False)
    load_weights_npz(ff, path)
    weights_equal(ff.get_weights(), saved)


def test_local_manager_round_trip_and_retention(devices8, tmp_path):
    from flexflow_tpu.checkpoint import LocalCheckpointManager

    ff = _model(devices8)
    xs, ys = _data()
    ff.fit(xs, ys, epochs=1, verbose=False)
    saved = ff.get_weights()

    mgr = LocalCheckpointManager(str(tmp_path / "lc"), max_to_keep=2)
    mgr.save(ff, step=1)
    assert mgr.latest_step() == 1
    meta = mgr.restore_meta()
    assert meta["step"] == 1 and meta["num_devices"] == 8

    ff.fit(xs, ys, epochs=1, verbose=False)  # diverge
    step = mgr.restore(ff)
    assert step == 1
    weights_equal(ff.get_weights(), saved)

    # keep-last-k pruning: saving steps 2 and 3 drops step 1
    mgr.save(ff, step=2)
    mgr.save(ff, step=3)
    assert mgr.all_steps() == [2, 3]


def test_local_manager_corrupt_latest_falls_back(devices8, tmp_path):
    """A corrupt/partial latest checkpoint is skipped: restore lands on
    the previous intact one."""
    import os

    from flexflow_tpu.checkpoint import LocalCheckpointManager

    ff = _model(devices8)
    xs, ys = _data()
    ff.fit(xs, ys, epochs=1, verbose=False)
    w1 = ff.get_weights()
    mgr = LocalCheckpointManager(str(tmp_path / "lc"))
    mgr.save(ff, step=1)
    ff.fit(xs, ys, epochs=1, verbose=False)
    mgr.save(ff, step=2)

    # simulate a torn write: step 2's npz is garbage
    npz = os.path.join(str(tmp_path / "lc"), "step_00000002", "state.npz")
    with open(npz, "wb") as f:
        f.write(b"not a checkpoint")
    ff.fit(xs, ys, epochs=1, verbose=False)  # diverge further
    step = mgr.restore(ff)
    assert step == 1
    weights_equal(ff.get_weights(), w1)

    # an explicitly requested corrupt step stays strict
    import pytest as _pytest
    with _pytest.raises(Exception):
        mgr.restore(ff, step=2)


def test_local_manager_cross_mesh_restore(devices8, tmp_path):
    from flexflow_tpu.checkpoint import LocalCheckpointManager

    ff8 = _model(devices8)
    xs, ys = _data()
    ff8.fit(xs, ys, epochs=1, verbose=False)
    mgr = LocalCheckpointManager(str(tmp_path / "lc"))
    mgr.save(ff8, step=0)

    ff1 = _model(devices8[:1], seed=5)
    mgr.restore(ff1)
    weights_equal(ff1.get_weights(), ff8.get_weights())
    y8 = np.asarray(ff8.forward({"x": xs[:16]}))
    y1 = np.asarray(ff1.forward({"x": xs[:16]}))
    np.testing.assert_allclose(y8, y1, rtol=2e-5, atol=2e-5)


def test_orbax_restore_falls_back_on_corrupt(devices8, tmp_path):
    """The orbax manager's latest-restore also skips a torn step."""
    import shutil

    ff = _model(devices8)
    xs, ys = _data()
    ff.fit(xs, ys, epochs=1, verbose=False)
    w1 = ff.get_weights()
    mgr = CheckpointManager(str(tmp_path / "oc"))
    mgr.save(ff, step=1)
    ff.fit(xs, ys, epochs=1, verbose=False)
    mgr.save(ff, step=2)

    shutil.rmtree(str(tmp_path / "oc" / "2" / "state"))
    ff.fit(xs, ys, epochs=1, verbose=False)
    step = mgr.restore(ff)
    assert step == 1
    weights_equal(ff.get_weights(), w1)
    mgr.close()


def test_model_checkpoint_callback(devices8, tmp_path):
    from flexflow_tpu.checkpoint import ModelCheckpoint

    ff = _model(devices8)
    xs, ys = _data()
    cb = ModelCheckpoint(str(tmp_path / "cb"), max_to_keep=2)
    ff.fit(xs, ys, epochs=3, verbose=False, callbacks=[cb])
    mgr = CheckpointManager(str(tmp_path / "cb"))
    assert mgr.latest_step() == 2          # epochs 0,1,2 -> keep last 2
    assert len(mgr.all_steps()) == 2
    mgr.close()
