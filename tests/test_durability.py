"""Durability-layer tests: async verified saves, integrity manifest +
per-leaf corruption fallback, preemption grace (SIGTERM emergency
checkpoints), restore-time layout validation, and the hung-step
watchdog — all on the hermetic 8-device CPU mesh.
"""
import json
import os
import shutil
import signal
import time

import numpy as np
import pytest
from _family import weights_equal

from flexflow_tpu import (
    FFConfig,
    FFModel,
    LossType,
    MetricsType,
    SGDOptimizer,
)
from flexflow_tpu.checkpoint import (
    CheckpointCompatibilityError,
    CheckpointManager,
    LocalCheckpointManager,
)
from flexflow_tpu.fftype import ActiMode
from flexflow_tpu.obs.trace import spans
from flexflow_tpu.resilience import (
    FaultKind,
    FaultPlan,
    HungStepTimeout,
    RestartBudgetExhausted,
    RetryPolicy,
    StepWatchdog,
    TrainingSupervisor,
)

NO_SLEEP = lambda s: None  # noqa: E731


def _model(devices, seed=0, hidden=32, optimizer=None, **cfg_over):
    cfg = FFConfig(batch_size=16, num_devices=len(devices), seed=seed,
                   **cfg_over)
    ff = FFModel(cfg)
    x = ff.create_tensor([16, 8], name="x")
    t = ff.dense(x, hidden, activation=ActiMode.RELU)
    t = ff.dense(t, 4)
    ff.softmax(t)
    ff.compile(optimizer=optimizer or SGDOptimizer(lr=0.05),
               loss_type=LossType.SPARSE_CATEGORICAL_CROSSENTROPY,
               metrics=[MetricsType.SPARSE_CATEGORICAL_CROSSENTROPY],
               devices=devices, seed=seed)
    return ff


def _data(n=128, seed=0):
    rng = np.random.RandomState(seed)
    xs = rng.randn(n, 8).astype(np.float32)
    ys = rng.randint(0, 4, size=n).astype(np.int32)
    return xs, ys



# -- async verified saves ------------------------------------------------

def test_async_save_visible_after_drain(devices8, tmp_path):
    """Satellite: save(wait=False) is a real async save — the write
    lands in the background and is restorable after drain()."""
    xs, ys = _data()
    ff = _model(devices8)
    ff.fit(xs, ys, epochs=1, verbose=False)
    mgr = LocalCheckpointManager(str(tmp_path / "a"))
    mgr.save(ff, step=5, wait=False)
    assert mgr.drain() == []  # no failures
    assert mgr.latest_step() == 5
    assert mgr.latest_verified_step() == 5
    saved = ff.get_weights()
    ff.fit(xs, ys, epochs=1, verbose=False)  # diverge
    assert mgr.restore(ff) == 5
    weights_equal(ff.get_weights(), saved)
    mgr.close()


def test_manifest_written_and_latest_pointer(devices8, tmp_path):
    """Every save carries a per-leaf crc32 manifest; the LATEST pointer
    names the verified step."""
    import zlib

    xs, ys = _data()
    ff = _model(devices8)
    ff.fit(xs, ys, epochs=1, verbose=False)
    mgr = LocalCheckpointManager(str(tmp_path / "m"))
    mgr.save(ff, step=3)
    step_dir = mgr._path(3)
    with open(os.path.join(step_dir, "manifest.json")) as f:
        manifest = json.load(f)
    assert manifest["manifest_version"] == 1
    assert manifest["step"] == 3
    assert manifest["leaves"]
    total = 0
    with np.load(os.path.join(step_dir, "state.npz")) as data:
        assert set(data.files) == set(manifest["leaves"])
        for key, spec in manifest["leaves"].items():
            arr = np.ascontiguousarray(data[key])
            assert zlib.crc32(arr.view(np.uint8).reshape(-1)) == spec["crc32"]
            assert list(arr.shape) == spec["shape"]
            total += arr.nbytes
    assert manifest["total_bytes"] == total
    with open(os.path.join(str(tmp_path / "m"), "LATEST")) as f:
        assert int(f.read()) == 3


def test_per_leaf_corruption_falls_back_to_verified(devices8, tmp_path):
    """Acceptance: a checkpoint whose npz still PARSES but whose bytes
    drifted (bit rot, torn page) fails crc re-verification on restore
    and falls back to the older verified step."""
    xs, ys = _data()
    ff = _model(devices8)
    ff.fit(xs, ys, epochs=1, verbose=False)
    w1 = ff.get_weights()
    mgr = LocalCheckpointManager(str(tmp_path / "c"))
    mgr.save(ff, step=1)
    ff.fit(xs, ys, epochs=1, verbose=False)
    mgr.save(ff, step=2)

    # corrupt ONE leaf of step 2 in a way np.load cannot notice
    npz = os.path.join(mgr._path(2), "state.npz")
    with np.load(npz) as data:
        flat = {k: np.array(data[k]) for k in data.files}
    key = sorted(k for k in flat if flat[k].dtype == np.float32)[0]
    leaf = flat[key].reshape(-1)
    leaf[0] += 1.0
    np.savez(npz, **flat)

    ff.fit(xs, ys, epochs=1, verbose=False)  # diverge further
    assert mgr.restore(ff) == 1
    weights_equal(ff.get_weights(), w1)
    # the pointer re-committed to the step that actually verified
    assert mgr.latest_verified_step() == 1
    # an explicitly requested corrupt step stays strict
    with pytest.raises(Exception):
        mgr.restore(ff, step=2)


def test_prune_never_deletes_newest_verified(devices8, tmp_path):
    """Satellite: keep-last-k pruning must not delete the newest
    VERIFIED checkpoint even when newer unverified (legacy-format)
    steps push it outside the retention window."""
    xs, ys = _data()
    ff = _model(devices8)
    ff.fit(xs, ys, epochs=1, verbose=False)
    mgr = LocalCheckpointManager(str(tmp_path / "p"), max_to_keep=2)
    mgr.save(ff, step=1)
    assert mgr.latest_verified_step() == 1
    # newer steps written by an older (pointer-less, manifest-less) code
    # path: restorable but never verified
    for s in (2, 3, 4):
        shutil.copytree(mgr._path(1), mgr._path(s))
        os.remove(os.path.join(mgr._path(s), "manifest.json"))
    mgr._prune()
    steps = mgr.all_steps()
    assert 1 in steps  # the verified step survived out-of-window
    assert steps[-2:] == [3, 4]  # retention window unchanged otherwise
    assert mgr.latest_verified_step() == 1


def test_async_write_failure_surfaces_at_drain(devices8, tmp_path,
                                               monkeypatch):
    """A background write failure never kills training — it is logged
    and returned by drain() for the supervisor to count."""
    xs, ys = _data()
    ff = _model(devices8)
    ff.fit(xs, ys, epochs=1, verbose=False)
    mgr = LocalCheckpointManager(str(tmp_path / "f"))
    monkeypatch.setattr(
        mgr, "_write_and_publish",
        lambda *a, **k: (_ for _ in ()).throw(OSError("disk full")),
    )
    mgr.save(ff, step=1, wait=False)
    failures = mgr.drain()
    assert len(failures) == 1 and failures[0][0] == 1
    assert isinstance(failures[0][1], OSError)
    assert mgr.latest_step() is None  # nothing published
    mgr.close()


def test_supervisor_async_crash_restore_bit_identical(devices8, tmp_path):
    """Acceptance: with checkpoint_async on, a crash restores from an
    async-written checkpoint (drained before the restore) and replays
    to weights bit-identical to the fault-free run."""
    xs, ys = _data(128)
    ff_clean = _model(devices8, seed=21)
    clean = TrainingSupervisor(ff_clean, str(tmp_path / "clean"),
                               checkpoint_every=2, sleep=NO_SLEEP)
    rep_clean = clean.run(xs, ys, num_steps=7)

    ff = _model(devices8, seed=21, checkpoint_async=True)
    sup = TrainingSupervisor(
        ff, str(tmp_path / "async"), checkpoint_every=2,
        fault_plan=FaultPlan.single(5, FaultKind.STEP_EXCEPTION),
        sleep=NO_SLEEP,
    )
    rep = sup.run(xs, ys, num_steps=7)
    assert rep.final_step == rep_clean.final_step == 7
    assert rep.counters["restarts"] == 1
    assert rep.losses == rep_clean.losses
    weights_equal(ff_clean.get_weights(), ff.get_weights())
    # post-run drain landed every queued save
    assert sup.manager.latest_verified_step() == 6


def test_async_save_backpressure_bounds_queue(devices8, tmp_path,
                                              monkeypatch):
    """A writer slower than the save cadence must not accumulate
    full-state host copies unboundedly: save(wait=False) drains the
    backlog once MAX_PENDING_SAVES jobs are queued."""
    xs, ys = _data()
    ff = _model(devices8)
    ff.fit(xs, ys, epochs=1, verbose=False)
    mgr = LocalCheckpointManager(str(tmp_path / "bp"))
    mgr.MAX_PENDING_SAVES = 1
    real_write = mgr._write_and_publish

    def slow_write(*a, **k):
        time.sleep(0.15)
        return real_write(*a, **k)

    monkeypatch.setattr(mgr, "_write_and_publish", slow_write)
    mgr.save(ff, step=1, wait=False)  # queues instantly
    t0 = time.perf_counter()
    mgr.save(ff, step=2, wait=False)  # backlog >= cap: waits step 1 out
    assert time.perf_counter() - t0 > 0.1
    assert mgr._writer_obj().queue_depth <= 1
    assert mgr.drain() == []
    assert mgr.latest_verified_step() == 2
    mgr.close()


# -- restore-time layout validation --------------------------------------

def test_compatibility_error_names_mismatched_fields(devices8, tmp_path):
    """Satellite: restoring into a structurally different model raises
    one clear CheckpointCompatibilityError naming the leaves, not a
    reshape/KeyError traceback."""
    xs, ys = _data()
    ff32 = _model(devices8, hidden=32)
    ff32.fit(xs, ys, epochs=1, verbose=False)
    mgr = LocalCheckpointManager(str(tmp_path / "lc"))
    mgr.save(ff32, step=1)

    ff64 = _model(devices8, hidden=64)
    with pytest.raises(CheckpointCompatibilityError) as ei:
        mgr.restore(ff64)
    msg = str(ei.value)
    assert "incompatible" in msg
    assert "dense_0" in msg and "shape" in msg
    # strict step request raises the same clear error
    with pytest.raises(CheckpointCompatibilityError):
        mgr.restore(ff64, step=1)
    # mesh-size changes stay COMPATIBLE (reshard-on-restore contract)
    ff1 = _model(devices8[:1], hidden=32, seed=5)
    assert mgr.restore(ff1) == 1


def test_compatibility_error_orbax(devices8, tmp_path):
    xs, ys = _data()
    ff32 = _model(devices8, hidden=32)
    ff32.fit(xs, ys, epochs=1, verbose=False)
    mgr = CheckpointManager(str(tmp_path / "oc"))
    mgr.save(ff32, step=1)
    ff64 = _model(devices8, hidden=64)
    with pytest.raises(CheckpointCompatibilityError) as ei:
        mgr.restore(ff64, step=1)
    assert "dense_0" in str(ei.value)
    mgr.close()


# -- hung-step watchdog --------------------------------------------------

def test_watchdog_unit():
    wd = StepWatchdog(0.05)
    assert wd.enabled
    with pytest.raises(HungStepTimeout) as ei:
        wd.sync(lambda: time.sleep(5.0), step=7)
    assert ei.value.step == 7
    assert wd.sync(lambda: 42, step=8) == 42
    with pytest.raises(ValueError, match="boom"):
        wd.sync(lambda: (_ for _ in ()).throw(ValueError("boom")))
    off = StepWatchdog(0.0)
    assert not off.enabled
    assert off.sync(lambda: "inline") == "inline"
    with pytest.raises(ValueError):
        StepWatchdog(-1.0)


def test_watchdog_recovers_after_timeout():
    """A timeout abandons the wedged worker; the next sync gets a
    fresh one and works (and the persistent worker is reused across
    calls — no thread spawn per step)."""
    wd = StepWatchdog(0.05)
    assert wd.sync(lambda: 1) == 1
    worker = wd._worker
    assert wd.sync(lambda: 2) == 2
    assert wd._worker is worker  # same worker served both
    with pytest.raises(HungStepTimeout):
        wd.sync(lambda: time.sleep(5.0))
    assert wd.sync(lambda: 3) == 3  # fresh worker after abandonment
    assert wd._worker is not worker


def test_check_step_health_watchdog_times_out():
    from flexflow_tpu.executor import check_step_health

    class SlowLoss:
        dtype = np.float32

        def __array__(self, dtype=None):
            time.sleep(5.0)
            return np.float32(1.0)

    with pytest.raises(HungStepTimeout):
        check_step_health({"loss": SlowLoss()}, step=3,
                          watchdog=StepWatchdog(0.05))
    # no watchdog/fast loss: unchanged semantics
    check_step_health({"loss": np.float32(1.0)}, step=3,
                      watchdog=StepWatchdog(5.0))


def test_hung_step_fault_recovers_bit_identical(devices8, tmp_path):
    """Satellite: an injected HungStepFault routes through the
    device-loss-style path (re-search + recompile the full mesh +
    reshard-restore) and the replay converges bit-identical to the
    fault-free run."""
    xs, ys = _data(128)
    ff_clean = _model(devices8, seed=11)
    clean = TrainingSupervisor(ff_clean, str(tmp_path / "clean"),
                               checkpoint_every=2, sleep=NO_SLEEP)
    rep_clean = clean.run(xs, ys, num_steps=7)

    ff = _model(devices8, seed=11)
    sup = TrainingSupervisor(
        ff, str(tmp_path / "hung"), checkpoint_every=2,
        fault_plan=FaultPlan.single(5, FaultKind.HUNG_STEP),
        step_timeout=30.0,  # watchdog armed; nothing actually hangs
        sleep=NO_SLEEP,
    )
    rep = sup.run(xs, ys, num_steps=7)
    assert rep.final_step == 7
    assert rep.counters["hung_steps"] == 1
    assert rep.counters["re_searches"] == 1
    assert rep.counters["restarts"] == 1
    assert rep.counters["device_losses"] == 0  # classified, not conflated
    assert ff.mesh.devices.size == 8  # full mesh: nothing was lost
    assert rep.losses == rep_clean.losses
    weights_equal(ff_clean.get_weights(), ff.get_weights())


def test_hung_step_exhausts_restart_budget(devices8, tmp_path):
    xs, ys = _data()
    ff = _model(devices8)
    plan = FaultPlan([
        {"step": s, "kind": FaultKind.HUNG_STEP} for s in (2, 3)
    ])
    sup = TrainingSupervisor(
        ff, str(tmp_path), checkpoint_every=2, fault_plan=plan,
        retry=RetryPolicy(max_restarts=1, base_backoff=0.0), sleep=NO_SLEEP,
    )
    with pytest.raises(RestartBudgetExhausted):
        sup.run(xs, ys, num_steps=6)
    assert sup.counters["hung_steps"] == 2


def test_sync_verify_failure_is_survivable(devices8, tmp_path, monkeypatch):
    """A write-time crc verification miss on a periodic SYNC save costs
    that save, never the run — same contract as CheckpointWriteFault."""
    from flexflow_tpu.checkpoint import CheckpointVerifyError

    xs, ys = _data()
    ff = _model(devices8)
    sup = TrainingSupervisor(ff, str(tmp_path), checkpoint_every=2,
                             sleep=NO_SLEEP)
    real_verify = type(sup.manager)._verify_dir  # staticmethod -> function
    calls = {"n": 0}

    def flaky_verify(path, manifest=None):
        calls["n"] += 1
        if calls["n"] == 2:  # fail exactly one save's verification
            raise CheckpointVerifyError("injected crc mismatch")
        return real_verify(path, manifest)

    monkeypatch.setattr(type(sup.manager), "_verify_dir",
                        staticmethod(flaky_verify))
    rep = sup.run(xs, ys, num_steps=6)
    assert rep.final_step == 6
    assert rep.counters["checkpoint_failures"] == 1
    assert rep.counters["restarts"] == 0


# -- preemption grace ----------------------------------------------------

def _sigterm_at(plan: FaultPlan, step: int, signum=signal.SIGTERM):
    """Arrange for `signum` to be raised in-process at the given
    supervisor step (delivered synchronously in the main thread)."""
    orig = plan.check_step

    def check(s):
        if s == step:
            signal.raise_signal(signum)
        orig(s)

    plan.check_step = check
    return plan


def test_sigterm_emergency_save_round_trip(devices8, tmp_path):
    """Acceptance: SIGTERM mid-run finishes the in-flight step, writes
    an emergency checkpoint at the boundary, and a resumed run restores
    it and converges bit-identical to an uninterrupted run."""
    xs, ys = _data(128)
    ff_clean = _model(devices8, seed=3)
    clean = TrainingSupervisor(ff_clean, str(tmp_path / "clean"),
                               checkpoint_every=100, sleep=NO_SLEEP)
    rep_clean = clean.run(xs, ys, num_steps=7)

    ff = _model(devices8, seed=3)
    sup = TrainingSupervisor(ff, str(tmp_path / "pre"),
                             checkpoint_every=100,  # cadence never fires
                             fault_plan=_sigterm_at(FaultPlan(), 3),
                             sleep=NO_SLEEP)
    rep = sup.run(xs, ys, num_steps=7)
    assert rep.preempted == "SIGTERM"
    assert rep.final_step == 4  # step 3 completed, then the boundary stop
    assert rep.counters["emergency_saves"] == 1
    assert sup.manager.latest_verified_step() == 4  # verified + restorable
    # the handler was uninstalled on exit
    assert signal.getsignal(signal.SIGTERM) not in (sup._on_grace_signal,)

    # replacement process: fresh model, resume from the emergency save
    ff2 = _model(devices8, seed=99)  # different init — must be overwritten
    sup2 = TrainingSupervisor(ff2, str(tmp_path / "pre"),
                              checkpoint_every=100, sleep=NO_SLEEP)
    rep2 = sup2.run(xs, ys, num_steps=7, resume=True)
    assert rep2.final_step == 7
    assert rep2.preempted is None
    weights_equal(ff_clean.get_weights(), ff2.get_weights())
    assert rep_clean.losses[4:] == rep2.losses  # replayed tail matches


def test_sigterm_during_final_step_still_checkpoints(devices8, tmp_path):
    """A signal landing during the LAST step must still produce the
    emergency checkpoint report.preempted promises — the flag is
    handled after the loop, not only at its top."""
    xs, ys = _data()
    ff = _model(devices8)
    sup = TrainingSupervisor(ff, str(tmp_path),
                             checkpoint_every=100,  # cadence never fires
                             fault_plan=_sigterm_at(FaultPlan(), 4),
                             sleep=NO_SLEEP)
    rep = sup.run(xs, ys, num_steps=5)  # signal during step 4 == the last
    assert rep.preempted == "SIGTERM"
    assert rep.final_step == 5
    assert rep.counters["emergency_saves"] == 1
    assert sup.manager.latest_verified_step() == 5  # restorable promise


def test_sigint_grace_and_async_drain(devices8, tmp_path):
    """SIGINT takes the same grace path; pending async saves are
    drained before the supervisor returns."""
    xs, ys = _data()
    ff = _model(devices8, checkpoint_async=True)
    sup = TrainingSupervisor(ff, str(tmp_path),
                             checkpoint_every=2,
                             fault_plan=_sigterm_at(FaultPlan(), 3,
                                                    signal.SIGINT),
                             sleep=NO_SLEEP)
    rep = sup.run(xs, ys, num_steps=8)
    assert rep.preempted == "SIGINT"
    assert rep.final_step == 4
    assert rep.counters["emergency_saves"] == 1
    # every queued save landed: the emergency step is verified on disk
    assert sup.manager.latest_verified_step() == 4
    assert signal.getsignal(signal.SIGINT) is signal.default_int_handler


def test_sigterm_zero1_sharded_slots_restore(devices8, tmp_path):
    """Acceptance: the emergency checkpoint round-trips ZeRO-1 sharded
    optimizer slots, including an 8 -> 4 elastic restore."""
    import jax

    from flexflow_tpu.optimizer import AdamOptimizer

    xs, ys = _data(128)
    ff = _model(devices8, seed=4, weight_update_sharding=True,
                optimizer=AdamOptimizer(alpha=0.01), checkpoint_async=True)
    sup = TrainingSupervisor(ff, str(tmp_path / "z"), checkpoint_every=100,
                             fault_plan=_sigterm_at(FaultPlan(), 3),
                             sleep=NO_SLEEP)
    rep = sup.run(xs, ys, num_steps=8)
    assert rep.preempted == "SIGTERM"
    saved_w = ff.get_weights()
    saved_opt = jax.tree.map(np.asarray, ff._opt_state)

    # 8 -> 4 elastic: restore the emergency save onto a half-size mesh
    ff4 = _model(devices8[:4], seed=9, weight_update_sharding=True,
                 optimizer=AdamOptimizer(alpha=0.01))
    mgr = LocalCheckpointManager(str(tmp_path / "z"))
    assert mgr.restore(ff4) == rep.final_step
    weights_equal(ff4.get_weights(), saved_w)
    weights_equal(jax.tree.map(np.asarray, ff4._opt_state), saved_opt)
    # the restored model keeps training on the survivor mesh
    ff4.fit(xs, ys, epochs=1, verbose=False)


# -- observability -------------------------------------------------------

def test_ckpt_spans_and_counters(devices8, tmp_path):
    """Satellite: checkpoint_write splits into snapshot/flush child
    spans, and the resilience/ckpt_* metrics land in the registry."""
    xs, ys = _data()
    ff = _model(devices8, telemetry=True)
    ff.fit(xs, ys, epochs=1, verbose=False)
    mgr = LocalCheckpointManager(str(tmp_path / "t"))
    before = {r.span_id for r in spans()}
    mgr.save(ff, step=1, wait=True)
    mgr.save(ff, step=2, wait=False)
    assert mgr.drain() == []

    names = [r.name for r in spans() if r.span_id not in before]
    assert names.count("checkpoint_write") == 2
    assert names.count("snapshot") == 2
    assert names.count("flush") == 2  # sync inline + async on the writer tid
    reg = ff.telemetry.metrics
    hist = reg.histogram("resilience/ckpt_write_latency_s")
    assert hist.count == 2 and hist.sum > 0
    assert reg.gauge("resilience/ckpt_queue_depth").value == 0  # drained
    mgr.close()


# -- pipeline <-> per-op restore layout mapping (ISSUE 9 satellite) ------

def _blocky_model(devices, strategy=None, seed=0, momentum=0.9):
    """4 identical dense blocks + head: the repeated-block graph the
    pipeline plan stacks, compiled per-op or under a pp strategy."""
    cfg = FFConfig(batch_size=16, num_devices=len(devices), seed=seed)
    ff = FFModel(cfg)
    x = ff.create_tensor([16, 8], name="x")
    t = x
    for i in range(4):
        t = ff.dense(t, 8, activation=ActiMode.RELU, name=f"blk{i}")
    t = ff.dense(t, 4, name="head")
    ff.softmax(t)
    ff.compile(optimizer=SGDOptimizer(lr=0.05, momentum=momentum),
               loss_type=LossType.SPARSE_CATEGORICAL_CROSSENTROPY,
               strategy=strategy, devices=devices, seed=seed)
    return ff


def _pp_strategy(dp, pp, M):
    from flexflow_tpu.strategy import Strategy

    axes = {"data": dp, "pipe": pp} if dp > 1 else {"pipe": pp}
    s = Strategy(
        mesh_axes=axes,
        pipeline={"degree": pp, "num_microbatches": M, "axis": "pipe",
                  "dp_axis": "data" if dp > 1 else None},
    )
    if dp > 1:
        s.edge_ops["__inputs__"] = [("repartition", {"dim": 0, "degree": dp})]
    return s


def test_restore_per_op_checkpoint_onto_pipeline(devices8, tmp_path):
    """A checkpoint saved under a per-op strategy restores onto a
    `__pipeline__`-stacked executor: restore maps the weight AND
    momentum-slot trees through _adapt_weight_layout (the mapping that
    lets elastic re-search pick pipeline winners mid-run)."""
    xs, ys = _data()
    ff = _blocky_model(devices8)
    for i in range(2):
        ff.train_step({"x": xs[i * 16:(i + 1) * 16]}, ys[i * 16:(i + 1) * 16])
    w_saved = ff.get_weights()
    import jax

    v_saved = jax.tree.map(np.asarray, ff._opt_state)["v"]
    mgr = LocalCheckpointManager(str(tmp_path / "c"))
    mgr.save(ff, step=2, wait=True)

    pp = _blocky_model(devices8[:4], strategy=_pp_strategy(2, 2, 4))
    assert "__pipeline__" in pp._weights
    step = LocalCheckpointManager(str(tmp_path / "c")).restore(pp, step=2)
    assert step == 2
    w_pp = pp.get_weights()
    v_pp = jax.tree.map(np.asarray, pp._opt_state)["v"]
    for k in range(4):
        for name in ("kernel", "bias"):
            np.testing.assert_array_equal(
                w_pp["__pipeline__"][f"0.{name}"][k], w_saved[f"blk{k}"][name]
            )
            np.testing.assert_array_equal(
                v_pp["__pipeline__"][f"0.{name}"][k], v_saved[f"blk{k}"][name]
            )
    np.testing.assert_array_equal(w_pp["head"]["kernel"],
                                  w_saved["head"]["kernel"])


def test_restore_pipeline_checkpoint_onto_per_op(devices8, tmp_path):
    """The reverse mapping: a checkpoint saved under a pipeline
    strategy restores onto a freshly compiled per-op executor."""
    xs, ys = _data()
    pp = _blocky_model(devices8[:4], strategy=_pp_strategy(2, 2, 4))
    for i in range(2):
        pp.train_step({"x": xs[i * 16:(i + 1) * 16]}, ys[i * 16:(i + 1) * 16])
    w_saved = pp.get_weights()
    mgr = LocalCheckpointManager(str(tmp_path / "c"))
    mgr.save(pp, step=2, wait=True)

    ff = _blocky_model(devices8)
    assert "__pipeline__" not in ff._weights
    step = LocalCheckpointManager(str(tmp_path / "c")).restore(ff, step=2)
    assert step == 2
    w = ff.get_weights()
    for k in range(4):
        for name in ("kernel", "bias"):
            np.testing.assert_array_equal(
                w[f"blk{k}"][name], w_saved["__pipeline__"][f"0.{name}"][k]
            )


def test_manifest_missing_leaf_is_unverifiable(devices8, tmp_path):
    """A manifest listing FEWER leaves than state.npz must fail
    verification — uncovered bytes would otherwise restore with no
    integrity check at all."""
    ff = _model(devices8)
    mgr = LocalCheckpointManager(str(tmp_path))
    mgr.save(ff, step=1, wait=True)
    mpath = os.path.join(str(tmp_path), "step_00000001", "manifest.json")
    with open(mpath) as f:
        manifest = json.load(f)
    dropped = sorted(manifest["leaves"])[0]
    del manifest["leaves"][dropped]
    with open(mpath, "w") as f:
        json.dump(manifest, f)
    from flexflow_tpu.checkpoint import CheckpointVerifyError

    with pytest.raises(CheckpointVerifyError, match="missing from the"):
        mgr.restore(ff, step=1)
