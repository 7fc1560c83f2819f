"""Resilience subsystem tests (resilience/): deterministic fault
injection, retry/backoff supervision, crash-restore bit-identity, and
elastic re-search + recompile on a degraded mesh — all on the hermetic
8-device CPU mesh, no hardware has to die.
"""
import numpy as np
import pytest
from _family import weights_equal

import flexflow_tpu
from flexflow_tpu import (
    FFConfig,
    FFModel,
    LossType,
    MetricsType,
    SGDOptimizer,
)
from flexflow_tpu.executor import NonFiniteLossError
from flexflow_tpu.fftype import ActiMode
from flexflow_tpu.resilience import (
    Fault,
    FaultKind,
    FaultPlan,
    RestartBudgetExhausted,
    RetryPolicy,
    StepFault,
    TrainingSupervisor,
)
from flexflow_tpu.strategy import data_parallel_strategy

NO_SLEEP = lambda s: None  # noqa: E731


def _model(devices, seed=0, strategy=None, **cfg_over):
    cfg = FFConfig(batch_size=16, num_devices=len(devices), seed=seed,
                   **cfg_over)
    ff = FFModel(cfg)
    x = ff.create_tensor([16, 8], name="x")
    t = ff.dense(x, 32, activation=ActiMode.RELU)
    t = ff.dense(t, 4)
    ff.softmax(t)
    ff.compile(optimizer=SGDOptimizer(lr=0.05),
               loss_type=LossType.SPARSE_CATEGORICAL_CROSSENTROPY,
               metrics=[MetricsType.SPARSE_CATEGORICAL_CROSSENTROPY],
               strategy=strategy, devices=devices, seed=seed)
    return ff


def _data(n=128, seed=0):
    rng = np.random.RandomState(seed)
    xs = rng.randn(n, 8).astype(np.float32)
    ys = rng.randint(0, 4, size=n).astype(np.int32)
    return xs, ys



# -- fault plan / retry policy units ------------------------------------

def test_fault_plan_seeded_deterministic_and_fires_once():
    a = FaultPlan.seeded(seed=3, num_steps=20, count=3)
    b = FaultPlan.seeded(seed=3, num_steps=20, count=3)
    assert [f.step for f in a.faults] == [f.step for f in b.faults]
    assert len({f.step for f in a.faults}) == 3
    step = a.faults[0].step
    with pytest.raises(StepFault):
        a.check_step(step)
    a.check_step(step)  # fired -> silent on replay after a restore
    assert len(a.remaining()) == 2


def test_fault_plan_json_round_trip():
    plan = FaultPlan([
        Fault(step=4, kind=FaultKind.DEVICE_LOSS, payload={"survivors": 4}),
        Fault(step=7, kind=FaultKind.CHECKPOINT_WRITE),
    ])
    back = FaultPlan.from_json(plan.to_json())
    assert [(f.step, f.kind, f.payload) for f in back.faults] == [
        (f.step, f.kind, f.payload) for f in plan.faults
    ]


def test_fault_plan_corrupt_batch_poisons_floats_once():
    plan = FaultPlan.single(2, FaultKind.NAN_LOSS)
    inputs = {"x": np.ones((4, 3), np.float32),
              "idx": np.arange(4, dtype=np.int32)}
    out = plan.corrupt_batch(2, inputs)
    assert np.isnan(out["x"]).all()
    np.testing.assert_array_equal(out["idx"], inputs["idx"])  # ints untouched
    again = plan.corrupt_batch(2, inputs)
    assert not np.isnan(again["x"]).any()  # one-shot


def test_retry_policy_backoff_deterministic_capped():
    p = RetryPolicy(max_restarts=3, base_backoff=0.5, multiplier=2.0,
                    max_backoff=2.0, jitter=0.25, seed=7)
    seq = [p.backoff(i) for i in (1, 2, 3, 6)]
    assert seq == [p.backoff(i) for i in (1, 2, 3, 6)]  # seeded jitter
    assert abs(seq[0] - 0.5) <= 0.5 * 0.25
    assert abs(seq[1] - 1.0) <= 1.0 * 0.25
    assert seq[3] <= 2.0 * 1.25  # capped before jitter
    assert p.admits(3) and not p.admits(4)
    with pytest.raises(ValueError):
        RetryPolicy(max_restarts=-1)
    with pytest.raises(ValueError):
        RetryPolicy(jitter=1.5)


# -- crash-restore bit-identity -----------------------------------------

@pytest.mark.parametrize(
    "kind", [FaultKind.STEP_EXCEPTION, FaultKind.HOST_PREEMPTION]
)
def test_crash_restore_bit_identical(devices8, tmp_path, kind):
    """Acceptance: a seeded FaultPlan crashing at an arbitrary step must
    restore and reach weights bit-identical to the fault-free run at the
    same step count on the same mesh."""
    import jax

    xs, ys = _data(128)

    ff_clean = _model(devices8, seed=11)
    clean = TrainingSupervisor(ff_clean, str(tmp_path / "clean"),
                               checkpoint_every=2, sleep=NO_SLEEP)
    rep_clean = clean.run(xs, ys, num_steps=7)

    ff_fault = _model(devices8, seed=11)
    fault = TrainingSupervisor(
        ff_fault, str(tmp_path / "fault"), checkpoint_every=2,
        fault_plan=FaultPlan.single(5, kind), sleep=NO_SLEEP,
    )
    rep_fault = fault.run(xs, ys, num_steps=7)

    assert rep_clean.final_step == rep_fault.final_step == 7
    weights_equal(ff_clean.get_weights(), ff_fault.get_weights())
    np.testing.assert_array_equal(
        np.asarray(jax.random.key_data(ff_clean._rng)),
        np.asarray(jax.random.key_data(ff_fault._rng)),
    )
    assert rep_fault.losses == rep_clean.losses  # replay, not drift
    assert rep_clean.counters["restarts"] == 0
    assert rep_fault.counters["restarts"] == 1
    assert rep_fault.counters["retries"] == 1
    assert rep_fault.counters["lost_steps"] == 1  # ckpt@4, crash@5


def test_seeded_fault_plan_run_bit_identical(devices8, tmp_path):
    """Acceptance, seeded form: crashes at rng-chosen arbitrary steps
    still converge to the fault-free weights at the same step count."""
    xs, ys = _data(160)
    ff_clean = _model(devices8, seed=21)
    TrainingSupervisor(ff_clean, str(tmp_path / "clean"), checkpoint_every=3,
                       sleep=NO_SLEEP).run(xs, ys, num_steps=10)

    ff = _model(devices8, seed=21)
    plan = FaultPlan.seeded(
        seed=123, num_steps=10, count=2,
        kinds=(FaultKind.STEP_EXCEPTION, FaultKind.HOST_PREEMPTION),
    )
    rep = TrainingSupervisor(ff, str(tmp_path / "fault"), checkpoint_every=3,
                             fault_plan=plan, sleep=NO_SLEEP
                             ).run(xs, ys, num_steps=10)
    assert rep.final_step == 10
    assert rep.counters["restarts"] == 2
    assert not plan.remaining()
    weights_equal(ff_clean.get_weights(), ff.get_weights())


def test_restart_budget_exhausted(devices8, tmp_path):
    xs, ys = _data()
    ff = _model(devices8)
    plan = FaultPlan([Fault(step=s, kind=FaultKind.STEP_EXCEPTION)
                      for s in (2, 3, 4)])
    sup = TrainingSupervisor(
        ff, str(tmp_path), checkpoint_every=2, fault_plan=plan,
        retry=RetryPolicy(max_restarts=2, base_backoff=0.0), sleep=NO_SLEEP,
    )
    with pytest.raises(RestartBudgetExhausted):
        sup.run(xs, ys, num_steps=8)
    assert sup.counters["retries"] == 3


def test_backoff_delays_follow_policy(devices8, tmp_path):
    xs, ys = _data()
    ff = _model(devices8)
    policy = RetryPolicy(max_restarts=5, base_backoff=0.5, jitter=0.25,
                         seed=3)
    delays = []
    plan = FaultPlan([Fault(step=s, kind=FaultKind.STEP_EXCEPTION)
                      for s in (2, 3)])
    sup = TrainingSupervisor(ff, str(tmp_path), checkpoint_every=2,
                             fault_plan=plan, retry=policy,
                             sleep=delays.append)
    rep = sup.run(xs, ys, num_steps=6)
    assert rep.final_step == 6
    assert delays == [policy.backoff(1), policy.backoff(2)]


def test_checkpoint_write_fault_is_survived(devices8, tmp_path):
    """A failed periodic save costs nothing but that save: training
    continues and the next cadence point writes a fresh checkpoint."""
    xs, ys = _data()
    ff = _model(devices8)
    plan = FaultPlan.single(3, FaultKind.CHECKPOINT_WRITE)
    sup = TrainingSupervisor(ff, str(tmp_path), checkpoint_every=2,
                             fault_plan=plan, sleep=NO_SLEEP)
    rep = sup.run(xs, ys, num_steps=6)
    assert rep.final_step == 6
    assert rep.counters["checkpoint_failures"] == 1
    assert rep.counters["restarts"] == 0
    assert sup.manager.latest_step() == 6  # save@4 failed, save@6 landed
    assert 4 not in sup.manager.all_steps()


# -- nan_policy ----------------------------------------------------------

def test_nan_policy_raise_propagates(devices8, tmp_path):
    xs, ys = _data()
    ff = _model(devices8)  # nan_policy defaults to "raise"
    sup = TrainingSupervisor(ff, str(tmp_path), checkpoint_every=2,
                             fault_plan=FaultPlan.single(3, FaultKind.NAN_LOSS),
                             sleep=NO_SLEEP)
    with pytest.raises(NonFiniteLossError):
        sup.run(xs, ys, num_steps=6)


def test_nan_policy_skip_step_counts_and_continues(devices8, tmp_path):
    xs, ys = _data()
    ff = _model(devices8, nan_policy="skip_step")
    sup = TrainingSupervisor(ff, str(tmp_path), checkpoint_every=2,
                             fault_plan=FaultPlan.single(3, FaultKind.NAN_LOSS),
                             sleep=NO_SLEEP)
    rep = sup.run(xs, ys, num_steps=6)
    assert rep.final_step == 6
    assert rep.counters["skipped_steps"] == 1
    assert rep.counters["restarts"] == 0
    assert len(rep.losses) == 5  # the poisoned batch recorded nothing
    assert all(np.isfinite(v) for v in rep.losses)
    for leaf in np.asarray(ff.get_parameter("dense_0", "kernel")).ravel():
        assert np.isfinite(leaf)


def test_nan_policy_restore_recovers_bit_identical(devices8, tmp_path):
    """restore policy: a transient NaN rolls back to the last checkpoint
    and replays — ending bit-identical to a clean run (the poisoned
    batch was transient, so the replay sees clean data)."""
    xs, ys = _data(128)
    ff_clean = _model(devices8, seed=5)
    clean = TrainingSupervisor(ff_clean, str(tmp_path / "clean"),
                               checkpoint_every=2, sleep=NO_SLEEP)
    clean.run(xs, ys, num_steps=6)

    ff = _model(devices8, seed=5, nan_policy="restore")
    sup = TrainingSupervisor(ff, str(tmp_path / "nan"), checkpoint_every=2,
                             fault_plan=FaultPlan.single(3, FaultKind.NAN_LOSS),
                             sleep=NO_SLEEP)
    rep = sup.run(xs, ys, num_steps=6)
    assert rep.final_step == 6
    assert rep.counters["restarts"] == 1
    assert all(np.isfinite(v) for v in rep.losses)
    weights_equal(ff_clean.get_weights(), ff.get_weights())


def test_skip_then_restore_losses_stay_aligned(devices8, tmp_path):
    """A skipped step records no loss, so a later restore must truncate
    the loss record by STEP, not by list position — losses and weights
    both stay identical to a restore-free run with the same skip."""
    xs, ys = _data(128)
    ff_clean = _model(devices8, seed=13, nan_policy="skip_step")
    clean = TrainingSupervisor(
        ff_clean, str(tmp_path / "clean"), checkpoint_every=2,
        fault_plan=FaultPlan.single(2, FaultKind.NAN_LOSS), sleep=NO_SLEEP,
    )
    rep_clean = clean.run(xs, ys, num_steps=7)
    assert len(rep_clean.losses) == 6  # step 2 recorded nothing

    ff = _model(devices8, seed=13, nan_policy="skip_step")
    plan = FaultPlan([Fault(step=2, kind=FaultKind.NAN_LOSS),
                      Fault(step=5, kind=FaultKind.STEP_EXCEPTION)])
    sup = TrainingSupervisor(ff, str(tmp_path / "fault"), checkpoint_every=2,
                             fault_plan=plan, sleep=NO_SLEEP)
    rep = sup.run(xs, ys, num_steps=7)
    assert rep.counters["skipped_steps"] == 1
    assert rep.counters["restarts"] == 1
    assert rep.losses == rep_clean.losses  # no duplicate/missing entries
    weights_equal(ff_clean.get_weights(), ff.get_weights())


# -- elastic recovery on a degraded mesh --------------------------------

def test_device_loss_elastic_resume_data_parallel(devices8, tmp_path):
    """8 -> 4 device loss: re-search on the surviving mesh (data-parallel
    fallback under search_budget=0), recompile, reshard-restore, and
    finish with a valid 4-device strategy — no manual intervention."""
    xs, ys = _data(128)
    ff = _model(devices8, seed=4)
    assert ff.mesh.devices.size == 8
    plan = FaultPlan.single(3, FaultKind.DEVICE_LOSS, survivors=4)
    sup = TrainingSupervisor(ff, str(tmp_path), checkpoint_every=2,
                             fault_plan=plan, sleep=NO_SLEEP)
    rep = sup.run(xs, ys, num_steps=6)
    assert rep.final_step == 6
    assert rep.counters["device_losses"] == 1
    assert rep.counters["re_searches"] == 1
    assert rep.counters["restarts"] == 1
    assert ff.mesh.devices.size == 4
    assert ff.strategy.total_devices == 4
    assert all(np.isfinite(v) for v in rep.losses)
    out = np.asarray(ff.forward({"x": xs[:16]}))
    assert np.isfinite(out).all()


def test_device_loss_carries_trained_state(devices8, tmp_path):
    """The restore after recompile reshards the checkpointed weights
    onto the surviving mesh: the recovery point equals the last durable
    pre-loss weights, not a fresh init."""
    xs, ys = _data(128)
    ff_clean = _model(devices8, seed=9)
    clean = TrainingSupervisor(ff_clean, str(tmp_path / "clean"),
                               checkpoint_every=4, sleep=NO_SLEEP)
    clean.run(xs, ys, num_steps=4)
    w4 = ff_clean.get_weights()  # durable state at the loss point

    ff = _model(devices8, seed=9)
    sup = TrainingSupervisor(
        ff, str(tmp_path / "loss"), checkpoint_every=4,
        fault_plan=FaultPlan.single(4, FaultKind.DEVICE_LOSS, survivors=2),
        sleep=NO_SLEEP,
    )
    # steps 0-3 run on the full mesh, ckpt@4 lands, then the loss fires
    # at step 4 -> recompile to 2 devices, reshard-restore, finish step 4
    rep = sup.run(xs, ys, num_steps=5)
    assert rep.final_step == 5
    assert rep.counters["device_losses"] == 1
    assert rep.counters["lost_steps"] == 0  # ckpt@4 == the loss point
    assert ff.mesh.devices.size == 2
    # rewind to the checkpoint the recovery restored from: it must be
    # the clean run's step-4 state, resharded onto the 2-device mesh
    step = sup.manager.restore(ff)
    assert step == 4
    assert ff.mesh.devices.size == 2
    weights_equal(ff.get_weights(), w4)


@pytest.mark.slow
def test_device_loss_researches_with_unity(devices8, tmp_path):
    """Degraded-mesh re-search with the real Unity search: 8 -> 4, the
    supervisor searches a fresh strategy for the surviving topology and
    training completes under it."""
    xs, ys = _data(128)
    ff = _model(devices8, seed=1, strategy=data_parallel_strategy(8),
                search_budget=5, rewrite_depth=1, rewrite_max_variants=1)
    plan = FaultPlan.single(3, FaultKind.DEVICE_LOSS, survivors=4)
    sup = TrainingSupervisor(ff, str(tmp_path), checkpoint_every=2,
                             fault_plan=plan, sleep=NO_SLEEP)
    rep = sup.run(xs, ys, num_steps=6)
    assert rep.final_step == 6
    assert rep.counters["re_searches"] == 1
    assert 1 <= ff.strategy.total_devices <= 4  # valid on survivors
    assert ff.mesh.devices.size == ff.strategy.total_devices
    assert all(np.isfinite(v) for v in rep.losses)


@pytest.mark.slow
def test_device_loss_pipeline_candidate_restores(devices8, tmp_path):
    """ISSUE 9 satellite — the ROADMAP 8->4 repro, with the pipeline
    exclusion LIFTED: 8->4 device loss on a 3x64-dense MLP (batch 16,
    budget 50, enable_parameter_parallel) makes the degraded-mesh
    re-search return a PIPELINE candidate; checkpoint restore now maps
    the per-op-keyed saved state onto the '__pipeline__' stacked
    layout (checkpoint._adapt_saved_layout), so the supervisor keeps
    whatever candidate the search picks and recovery completes
    through a reshard-restore onto it."""
    cfg = FFConfig(batch_size=16, num_devices=8, search_budget=50,
                   enable_parameter_parallel=True, rewrite_depth=1,
                   rewrite_max_variants=1)
    ff = FFModel(cfg)
    x = ff.create_tensor([16, 8], name="x")
    t = x
    for _ in range(3):
        t = ff.dense(t, 64, activation=ActiMode.RELU)
    t = ff.dense(t, 4)
    ff.softmax(t)
    ff.compile(optimizer=SGDOptimizer(lr=0.05),
               loss_type=LossType.SPARSE_CATEGORICAL_CROSSENTROPY,
               devices=devices8)
    xs, ys = _data(128)
    plan = FaultPlan.single(3, FaultKind.DEVICE_LOSS, survivors=4)
    sup = TrainingSupervisor(ff, str(tmp_path), checkpoint_every=2,
                             fault_plan=plan, sleep=NO_SLEEP)
    rep = sup.run(xs, ys, num_steps=6)
    assert rep.final_step == 6
    assert rep.counters["device_losses"] == 1
    # the exclusion (and its counter) are gone: the re-searched winner
    # — historically a pipeline strategy on this exact repro — is kept
    assert "re_search_pipeline_excluded" not in rep.counters
    assert ff.strategy.pipeline is not None
    assert ff.strategy.total_devices <= 4
    assert all(np.isfinite(v) for v in rep.losses)


# -- fit integration -----------------------------------------------------

def test_fit_resilient_entrypoint(devices8, tmp_path):
    xs, ys = _data(128)
    ff = _model(devices8, seed=2, checkpoint_every=2,
                checkpoint_dir=str(tmp_path / "fr"), retry_backoff=0.0)
    rep = ff.fit_resilient(
        xs, ys, epochs=1,
        fault_plan=FaultPlan.single(2, FaultKind.STEP_EXCEPTION),
    )
    assert rep.final_step == 8  # 128 rows / batch 16
    assert rep.counters["restarts"] == 1
    assert len(rep.losses) == 8


def test_fit_resilient_requires_directory(devices8):
    xs, ys = _data(32)
    ff = _model(devices8)
    with pytest.raises(ValueError, match="checkpoint directory"):
        ff.fit_resilient(xs, ys, epochs=1)


def test_supervisor_counters_logged(devices8, tmp_path, caplog):
    """Satellite: counters flow through RecursiveLogger.counters so
    bench runs can report recovery overhead."""
    import logging

    xs, ys = _data(64)
    ff = _model(devices8)
    sup = TrainingSupervisor(ff, str(tmp_path), checkpoint_every=2,
                             sleep=NO_SLEEP)
    with caplog.at_level(logging.INFO, logger="flexflow_tpu.resilience"):
        rep = sup.run(xs, ys, num_steps=4)
    assert rep.counters["checkpoints"] == 3  # anchor@0 + 2 + 4
    assert rep.counters["checkpoint_time_s"] > 0
    text = caplog.text
    assert "supervisor:" in text and "restarts=0" in text
    assert "checkpoint_time_s=" in text


def test_package_exports():
    assert flexflow_tpu.FaultPlan is FaultPlan
    assert flexflow_tpu.TrainingSupervisor is TrainingSupervisor
    assert flexflow_tpu.RetryPolicy is RetryPolicy
    assert flexflow_tpu.FaultKind is FaultKind
