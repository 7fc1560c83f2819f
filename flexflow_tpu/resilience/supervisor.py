"""TrainingSupervisor: "a failure happened, recover and keep training".

Composes three pieces the repo already had in isolation — checkpoints
that reshard on restore (checkpoint.py), `FFModel.recompile` strategy
swaps (recompile.py), and the strategy searches (pcg/search.py) — into
a supervised training loop:

  * periodic checkpoints at a configurable step cadence (plus an anchor
    at step 0, so the very first failure has a restore target), written
    synchronously or — with `checkpoint_async` — as async verified
    saves that stall the accelerator only for the host snapshot;
  * on a transient failure (injected step exception / host preemption,
    or a non-finite loss under nan_policy="restore"), restore the
    latest checkpoint and retry under a jittered-backoff RetryPolicy
    with a hard restart budget;
  * on device loss, re-run the strategy search (unity or MCMC per
    FFConfig, data-parallel fallback) on the SURVIVING mesh in the
    spirit of P²'s re-placement, `recompile()` onto the shrunken
    device set, and carry weights/optimizer state over via the
    checkpoint's reshard-on-restore — training continues at full
    remaining-hardware speed under a freshly searched strategy;
  * on a hung step — a per-step device sync exceeding `step_timeout`
    (watchdog.py), or an injected `HungStepFault` — classify it as a
    device-loss-style fault on the FULL current mesh: re-search,
    recompile (which resets the wedged collective state), and
    reshard-restore;
  * on SIGTERM/SIGINT (the standard TPU preemption notice), finish the
    in-flight step, write an emergency checkpoint at the step boundary,
    drain the async writer, and return a restorable report instead of
    dying checkpoint-less (`run(..., resume=True)` picks the next
    process up from it).

The loop is step-indexed and deterministic: batch `i` of a run is
always rows [i*bs, (i+1)*bs) modulo the dataset (no shuffle), and the
training RNG is checkpointed, so a crashed-and-restored run replays to
weights BIT-IDENTICAL to an uninterrupted run at the same step count on
the same mesh (tests/test_resilience.py enforces this).
"""
from __future__ import annotations

import dataclasses
import os
import signal
import threading
import time
from typing import Callable, Dict, List, Optional

import jax
import numpy as np

from ..checkpoint import CheckpointVerifyError
from ..executor import NonFiniteLossError, check_step_health
from ..logger import resilience_logger
from ..obs.metrics import emit_counters, registry_of
from ..obs.trace import span
from .faults import (
    CheckpointWriteFault,
    DeviceLossFault,
    FaultPlan,
    HungStepFault,
    PreemptionFault,
    StepFault,
)
from .retry import RetryPolicy
from .watchdog import HungStepTimeout, StepWatchdog

# failures the supervisor treats as restore-and-retry transients
TRANSIENT_FAULTS = (StepFault, PreemptionFault)
# failures classified as "the mesh wedged": recover by re-search +
# recompile of the full current mesh + reshard-restore
HUNG_FAULTS = (HungStepFault, HungStepTimeout)
# signals treated as a preemption notice (the TPU runtime sends SIGTERM
# ahead of reclaiming a preemptible slice; SIGINT covers operators)
GRACE_SIGNALS = (signal.SIGTERM, signal.SIGINT)


class RestartBudgetExhausted(RuntimeError):
    """Raised when failures outrun RetryPolicy.max_restarts."""


@dataclasses.dataclass
class SupervisorReport:
    """What a supervised run did: the step it reached, the per-step
    losses actually recorded, and the counters dict (also logged via
    RecursiveLogger.counters for bench runs to scrape).  `preempted`
    carries the signal name when the run stopped early on a
    SIGTERM/SIGINT emergency checkpoint (resume with
    `run(..., resume=True)`)."""

    final_step: int
    losses: List[float]
    counters: Dict[str, float]
    preempted: Optional[str] = None


class TrainingSupervisor:
    """Wraps a compiled FFModel's training loop with checkpointing,
    retry/backoff recovery, preemption grace, a hung-step watchdog,
    and elastic re-search on device loss.

    Knobs default from the model's FFConfig (checkpoint_every,
    checkpoint_keep, checkpoint_async, step_timeout, preempt_grace,
    max_restarts, retry_backoff, nan_policy); the keyword arguments
    override per-supervisor.  `sleep` is injectable so tests don't
    actually wait out backoffs; `search_fn(ff, n)` overrides the
    strategy re-search on device loss.
    """

    def __init__(
        self,
        ff,
        directory: str,
        *,
        checkpoint_every: Optional[int] = None,
        keep: Optional[int] = None,
        retry: Optional[RetryPolicy] = None,
        fault_plan: Optional[FaultPlan] = None,
        nan_policy: Optional[str] = None,
        search_fn: Optional[Callable] = None,
        backend: str = "local",
        async_save: Optional[bool] = None,
        step_timeout: Optional[float] = None,
        preempt_grace: Optional[bool] = None,
        offloader=None,
        blob_store=None,
        run_id: Optional[str] = None,
        sleep: Callable[[float], None] = time.sleep,
        logger=resilience_logger,
    ):
        from ..config import NAN_POLICIES

        cfg = ff.config
        self.ff = ff
        self.checkpoint_every = (
            cfg.checkpoint_every if checkpoint_every is None else checkpoint_every
        )
        self.retry = retry or RetryPolicy(
            max_restarts=cfg.max_restarts,
            base_backoff=cfg.retry_backoff,
            seed=cfg.seed,
        )
        self.fault_plan = fault_plan or FaultPlan()
        self.nan_policy = cfg.nan_policy if nan_policy is None else nan_policy
        if self.nan_policy not in NAN_POLICIES:
            raise ValueError(
                f"nan_policy must be one of {NAN_POLICIES}, got {self.nan_policy!r}"
            )
        self.search_fn = search_fn
        self.sleep = sleep
        self.log = logger
        self.async_save = (
            getattr(cfg, "checkpoint_async", False)
            if async_save is None else bool(async_save)
        )
        self.watchdog = StepWatchdog(
            getattr(cfg, "step_timeout", 0.0)
            if step_timeout is None else step_timeout
        )
        self.preempt_grace = (
            getattr(cfg, "preempt_grace", True)
            if preempt_grace is None else bool(preempt_grace)
        )
        self._preempt: Optional[str] = None
        # durable offload tier (resilience/offload.py): mirrors every
        # verified local checkpoint to object storage off the critical
        # path.  Tests inject a pre-built offloader (or a faulty blob
        # store); production resolves FFConfig.remote_store.
        self.offloader = offloader
        if self.offloader is None:
            from .offload import offloader_from_config

            self.offloader = offloader_from_config(
                cfg, blob=blob_store, fault_plan=self.fault_plan,
                registry=registry_of(ff), sleep=sleep,
            )
        # names the cross-host preemption-barrier rendezvous in the blob
        # store; every worker of one run must agree on it
        self._run_id_defaulted = run_id is None
        self.run_id = run_id or os.path.basename(
            os.path.abspath(directory)
        ) or "run"
        self.barrier_timeout = float(getattr(cfg, "barrier_timeout", 30.0))
        keep = cfg.checkpoint_keep if keep is None else keep
        if backend == "orbax":
            from ..checkpoint import CheckpointManager

            self.manager = CheckpointManager(
                directory, max_to_keep=keep,
                remote=(self.offloader.remote
                        if self.offloader is not None else None),
            )
        elif backend == "local":
            from ..checkpoint import LocalCheckpointManager

            self.manager = LocalCheckpointManager(
                directory, max_to_keep=keep, offloader=self.offloader,
            )
        else:
            raise ValueError(f"unknown checkpoint backend {backend!r}")
        self.counters: Dict[str, float] = {
            "steps_run": 0,        # train_step invocations, replays included
            "restarts": 0,         # restore events (transient + device loss)
            "retries": 0,          # transient-failure retry attempts
            "lost_steps": 0,       # steps of progress replayed after restores
            "skipped_steps": 0,    # batches dropped under nan_policy=skip_step
            "checkpoints": 0,
            "checkpoint_failures": 0,
            "checkpoint_time_s": 0.0,
            "checkpoint_time_last_s": 0.0,
            "device_losses": 0,
            "hung_steps": 0,       # watchdog timeouts + injected hangs
            "emergency_saves": 0,  # preemption-grace checkpoints
            "re_searches": 0,
            "re_search_store_hits": 0,  # elastic re-searches answered
                                        # by the strategy store
        }

    # -- deterministic batching -----------------------------------------
    def _x_map(self, x) -> Dict[str, np.ndarray]:
        input_ops = self.ff.layers.source_ops()
        if isinstance(x, dict):
            return dict(x)
        if isinstance(x, (list, tuple)):
            return {op.name: arr for op, arr in zip(input_ops, x)}
        return {input_ops[0].name: x}

    @staticmethod
    def _batch(x_map, y, step: int, batch_size: int, num_batches: int):
        i = step % num_batches
        sl = slice(i * batch_size, (i + 1) * batch_size)
        return {k: v[sl] for k, v in x_map.items()}, y[sl]

    # -- checkpoint / restore -------------------------------------------
    def _save_checkpoint(self, step: int, wait: Optional[bool] = None) -> None:
        self.fault_plan.check_checkpoint(step)
        if wait is None:
            wait = not self.async_save
        t0 = time.perf_counter()
        self.manager.save(self.ff, step, wait=wait)
        # async mode: this is the step-boundary STALL (snapshot +
        # enqueue), not the full write — the flush overlaps training
        dt = time.perf_counter() - t0
        self.counters["checkpoints"] += 1
        self.counters["checkpoint_time_s"] += dt
        self.counters["checkpoint_time_last_s"] = dt

    def _save_checkpoint_survivable(self, step: int,
                                    wait: Optional[bool] = None) -> None:
        """A failed periodic save — injected or real (disk full, NFS
        blip, a write-time crc verification miss) — costs that save,
        never the run: count it and keep training; the next cadence
        point writes a fresh one."""
        try:
            self._save_checkpoint(step, wait=wait)
        except (CheckpointWriteFault, CheckpointVerifyError, OSError) as e:
            self.counters["checkpoint_failures"] += 1
            self.log.info("checkpoint save failed at step %d: %s", step, e)

    def _drain_writer(self) -> None:
        """Wait out pending async saves; fold their failures into the
        checkpoint counters (an async write failure surfaces here, not
        at the save() call that queued it)."""
        for failed_step, err in self.manager.drain():
            self.counters["checkpoint_failures"] += 1
            self.log.info(
                "async checkpoint save failed at step %d: %s", failed_step, err
            )

    def _drain_offloader(self) -> None:
        """Wait out pending remote mirrors.  Upload failures were
        already folded into the offloader's counters by its budget
        logic; anything returned here is an uploader-thread crash."""
        if self.offloader is None:
            return
        skipped = self.offloader.skipped_step
        if skipped is not None and hasattr(self.manager, "offload_step"):
            # the uploader was saturated at the newest cadence point and
            # the run has no later one: without this its last checkpoint
            # would exist on this host only
            self.manager.offload_step(skipped)
        for failed_step, err in self.offloader.drain():
            self.counters["checkpoint_failures"] += 1
            self.log.info(
                "offload uploader crashed at step %d: %s", failed_step, err
            )

    def _restore_latest(self, step: int) -> int:
        # a pending async save may be the newest durable state — let it
        # land (or fail) before picking the restore target
        self._drain_writer()
        with span("restart", failed_step=step):
            restored = int(self.manager.restore(self.ff))
        self.counters["restarts"] += 1
        self.counters["lost_steps"] += max(0, step - restored)
        self.log.info(
            "restored step %d after failure at step %d", restored, step
        )
        return restored

    # -- recovery paths --------------------------------------------------
    def _retry_transient(self, err, step: int, restarts: int) -> int:
        self.counters["retries"] += 1
        if not self.retry.admits(restarts):
            raise RestartBudgetExhausted(
                f"restart budget ({self.retry.max_restarts}) exhausted at "
                f"step {step}: {err}"
            ) from err
        self.sleep(self.retry.backoff(restarts))
        return self._restore_latest(step)

    def _search_strategy(self, num_devices: int):
        if self.search_fn is not None:
            return self.search_fn(self.ff, num_devices)
        cfg = self.ff.config
        if cfg.search_budget > 0 and not cfg.only_data_parallel:
            # elastic fast path: the strategy store may already hold a
            # searched plan for this degraded mesh (a previous loss at
            # the same survivor count, or a pre-seeded fleet store) —
            # cached_search consults it before paying a full re-search
            # and publishes on a miss so the NEXT loss is instant
            from ..pcg.search import mcmc_search, unity_search
            from ..store import cached_search

            def _run():
                if cfg.search_algo == "mcmc":
                    s = mcmc_search(self.ff, num_devices)
                else:
                    s = unity_search(self.ff, num_devices)
                # same pre-publish provenance stamp as FFModel.compile's
                # search path: a store entry restored on another host
                # must carry the catalog identity its rewrite trace was
                # searched with (rewrite.rules_for_replay pins the hash)
                self.ff._stamp_catalog(s)
                return s

            # pipeline winners restore fine since checkpoint.py learned
            # the per-op <-> __pipeline__ stacked layout mapping
            # (_adapt_saved_layout), so the former pipeline-exclusion
            # re-run is gone: whatever the search picks, reshard-restore
            # carries the trained state onto it
            strategy = cached_search(self.ff, num_devices, _run)
            if (getattr(strategy, "search_stats", None) or {}).get(
                "store_hit"
            ):
                self.counters["re_search_store_hits"] += 1
            return strategy
        from ..strategy import data_parallel_strategy

        return data_parallel_strategy(num_devices)

    def _elastic_restart(self, survivors: List, step: int, reason: str) -> int:
        """Re-search placement for `survivors`, recompile onto them,
        and reshard-restore the latest checkpoint so trained state
        carries over to the rebuilt executor."""
        with span("re_search", survivors=len(survivors), reason=reason):
            strategy = self._search_strategy(len(survivors))
        self.counters["re_searches"] += 1
        # recompile rebuilds the executor (fresh shardings, fresh
        # collective state); the checkpoint restore then overwrites the
        # carried state with the last durable state, resharded onto it
        self.ff.recompile(
            strategy=strategy, devices=survivors[: strategy.total_devices]
        )
        return self._restore_latest(step)

    def _recover_device_loss(self, fault: DeviceLossFault, step: int) -> int:
        """Elastic recovery: re-search placement for the surviving
        topology, recompile onto it, and reshard-restore the latest
        checkpoint so trained state carries over to the new mesh."""
        survivors = list(self.ff.mesh.devices.flat)[: fault.survivors]
        if not survivors:
            raise RuntimeError(f"device loss left no survivors: {fault}")
        self.counters["device_losses"] += 1
        self.log.info(
            "device loss at step %d: %d devices survive, re-searching",
            step, len(survivors),
        )
        return self._elastic_restart(survivors, step, reason="device_loss")

    def _recover_hung_step(self, err, step: int, restarts: int) -> int:
        """A hung step (watchdog timeout or injected HungStepFault) is
        a device-loss-style fault with the FULL mesh surviving: the
        devices are still there, the collective state is wedged, and
        recompile + reshard-restore resets it.  Counts against the
        restart budget — a mesh that hangs on every recovery attempt
        must eventually fail loudly, not loop forever."""
        self.counters["hung_steps"] += 1
        if not self.retry.admits(restarts):
            raise RestartBudgetExhausted(
                f"restart budget ({self.retry.max_restarts}) exhausted at "
                f"hung step {step}: {err}"
            ) from err
        self.log.info("hung step %d (%s): recompiling the full mesh", step, err)
        survivors = list(self.ff.mesh.devices.flat)
        return self._elastic_restart(survivors, step, reason="hung_step")

    # -- preemption grace -------------------------------------------------
    def _on_grace_signal(self, signum, frame) -> None:
        self._preempt = signal.Signals(signum).name
        # signal-handler context: only set the flag and note it — the
        # heavy work happens at the next step boundary on the main path
        self.log.info(
            "%s received: emergency checkpoint at the next step boundary",
            self._preempt,
        )

    def _install_grace_handlers(self) -> Dict:
        """SIGTERM/SIGINT -> request an emergency save at the next step
        boundary.  Returns the displaced handlers (restored on exit);
        empty when not on the main thread (signal.signal would raise)."""
        if not self.preempt_grace:
            return {}
        if threading.current_thread() is not threading.main_thread():
            return {}
        installed = {}
        for sig in GRACE_SIGNALS:
            try:
                installed[sig] = signal.signal(sig, self._on_grace_signal)
            except (ValueError, OSError):  # exotic embeddings
                break
        return installed

    def _preempt_rendezvous(self, step: int) -> int:
        """Agree with the run's other workers on ONE emergency step
        (blob-store preemption barrier, max of posts).  The run loop
        keeps stepping a lagging host FORWARD to the returned step
        before the emergency save, so every host commits the SAME
        state.  Without a remote tier (or on any barrier failure) the
        host's own step stands."""
        if self.offloader is None:
            return step
        from ..distributed import preemption_barrier

        try:
            import jax

            if self._run_id_defaulted and jax.process_count() > 1:
                # the default run_id is the checkpoint dir's basename:
                # hosts with differing per-host paths would rendezvous
                # under DIFFERENT prefixes and each poll a quorum of one
                self.log.warning(
                    "preemption-barrier run_id defaulted to %r from the "
                    "checkpoint directory — pass TrainingSupervisor("
                    "run_id=...) with one fleet-wide value if per-host "
                    "paths differ", self.run_id,
                )
            agreed = int(preemption_barrier(
                self.offloader.remote.blob, self.run_id, step,
                timeout_s=self.barrier_timeout,
                sleep=self.sleep,
            ))
        except Exception as e:  # noqa: BLE001 — never block the save
            self.log.info("preemption barrier failed (%s); saving "
                          "without cross-host agreement", e)
            return step
        if agreed != step:
            self.log.info(
                "preemption barrier agreed on step %d (this host is at "
                "%d): running forward to it before the emergency save",
                agreed, step,
            )
        return agreed

    def _emergency_stop(self, step: int) -> None:
        """The preemption deadline is unknown — synchronously write one
        final checkpoint at this step boundary, drain the async writer,
        and leave the directory restorable.  With a remote tier
        configured the step was already barrier-agreed by the run loop
        (_preempt_rendezvous); the emergency step is force-mirrored
        regardless of cadence."""
        registry = registry_of(self.ff)
        with span("emergency_checkpoint", step=step, reason=self._preempt):
            # drain FIRST: a queued async save may still be flushing on
            # the writer thread, and the sync emergency write must not
            # race it on the step dir / LATEST pointer
            self._drain_writer()
            self._save_checkpoint_survivable(step, wait=True)
        if self.offloader is not None and hasattr(self.manager,
                                                  "offload_step"):
            # the last checkpoint before the host disappears is exactly
            # the one the remote tier exists for
            self.manager.offload_step(step)
        self.counters["emergency_saves"] += 1
        if registry is not None:
            registry.counter("resilience/ckpt_emergency_saves").inc()
        self.log.info(
            "emergency checkpoint at step %d after %s; exiting restorable",
            step, self._preempt,
        )

    # -- the supervised loop ----------------------------------------------
    def run(self, x, y, num_steps: int, batch_size: Optional[int] = None,
            resume: bool = False) -> SupervisorReport:
        """Train for `num_steps` supervised steps over (x, y).

        resume=True restores the newest verified checkpoint in the
        directory (if any) and continues from its step — the companion
        of the preemption-grace exit, for the replacement process."""
        ff = self.ff
        assert ff._step_fn is not None, "call compile() first"
        batch_size = batch_size or ff.config.batch_size
        x_map = self._x_map(x)
        num_batches = len(y) // batch_size
        if num_batches < 1:
            raise ValueError(
                f"need at least one batch: {len(y)} samples < "
                f"batch_size {batch_size}"
            )
        # keyed by step so restores truncate exactly (a skipped step
        # records nothing, so a plain list would drift out of phase)
        loss_by_step: Dict[int, float] = {}
        step = 0
        restarts = 0
        preempt_target: Optional[int] = None
        self._preempt = None
        if self.offloader is not None:
            # stale rendezvous posts from the incarnation this run is
            # resuming FROM must never satisfy a future quorum
            from ..distributed import clear_preemption_barrier

            clear_preemption_barrier(self.offloader.remote.blob,
                                     self.run_id)
        if resume and self.manager.any_restorable():
            # any_restorable consults BOTH tiers: a fresh host with an
            # empty directory resumes from the remote mirror
            step = int(self.manager.restore(ff))
            self.log.info("resumed from checkpoint step %d", step)
        else:
            self._save_checkpoint_survivable(0)  # anchor: first failure has a target
        displaced = self._install_grace_handlers()
        try:
            while step < num_steps:
                if self._preempt is not None:
                    # rendezvous ONCE, then keep stepping until this
                    # host reaches the fleet-agreed emergency step (the
                    # max posted — laggards run forward, nobody rewinds)
                    if preempt_target is None:
                        preempt_target = self._preempt_rendezvous(step)
                    if step >= preempt_target:
                        break
                try:
                    self.fault_plan.check_step(step)
                    inputs, labels = self._batch(
                        x_map, y, step, batch_size, num_batches
                    )
                    inputs = self.fault_plan.corrupt_batch(step, inputs)
                    snap = self._snapshot() if self.nan_policy == "skip_step" else None
                    m = ff.train_step(inputs, labels)
                    self.counters["steps_run"] += 1
                    # the per-step device sync, under the hung-step
                    # watchdog: a wedged collective raises
                    # HungStepTimeout here instead of blocking forever
                    loss_val = self.watchdog.sync(
                        lambda: float(np.asarray(m["loss"])), step=step
                    )
                    try:
                        check_step_health({"loss": loss_val}, step=step,
                                          nan_policy=self.nan_policy)
                    except NonFiniteLossError:
                        if self.nan_policy != "skip_step":
                            raise  # "raise" propagates; "restore" caught below
                        # full step rollback (weights/opt/state/rng), then
                        # move past the poisoned batch
                        self._rollback(snap)
                        self.counters["skipped_steps"] += 1
                        loss_val = None
                    if loss_val is not None:
                        loss_by_step[step] = loss_val
                    step += 1
                    if self.checkpoint_every > 0 and step % self.checkpoint_every == 0:
                        self._save_checkpoint_survivable(step)
                except DeviceLossFault as f:
                    step = self._recover_device_loss(f, step)
                    loss_by_step = {s: v for s, v in loss_by_step.items() if s < step}
                except HUNG_FAULTS as e:
                    restarts += 1
                    step = self._recover_hung_step(e, step, restarts)
                    loss_by_step = {s: v for s, v in loss_by_step.items() if s < step}
                except TRANSIENT_FAULTS + (NonFiniteLossError,) as e:
                    if isinstance(e, NonFiniteLossError) and self.nan_policy == "raise":
                        raise
                    restarts += 1
                    step = self._retry_transient(e, step, restarts)
                    # replayed steps re-record their losses
                    loss_by_step = {s: v for s, v in loss_by_step.items() if s < step}
            if self._preempt is not None:
                # AFTER the loop, not at its top: a signal during the
                # final step must still get its boundary checkpoint —
                # report.preempted promises a restorable directory
                if preempt_target is None:
                    # the signal landed during the final step, so the
                    # loop exited before the top-of-loop rendezvous
                    # ran.  Post anyway: peers block on num_hosts posts
                    # and would otherwise stall to the deadline and
                    # commit a divergent step.  This host completed
                    # every step, so the agreed max cannot exceed it.
                    self._preempt_rendezvous(step)
                self._emergency_stop(step)
        finally:
            for sig, handler in displaced.items():
                signal.signal(sig, handler)
            # every exit path — clean, preempted, budget-exhausted —
            # waits out the async writer AND the remote mirror: queued
            # saves/uploads must land (or be counted failed/abandoned)
            # before the process can go away
            self._drain_writer()
            self._drain_offloader()
        # same "supervisor: k=v ..." log line as before, now also folded
        # into the run's metrics registry (-> run_telemetry.jsonl)
        tel = getattr(self.ff, "telemetry", None)
        emit_counters(
            self.log, "supervisor", self.counters,
            registry=tel.metrics if tel is not None else None,
            group="resilience",
        )
        if tel is not None and tel.enabled:
            tel.flush()
        # the report carries the mirror's counters too (offload_*) —
        # they already live in the registry as real Counters, so they
        # ride the report dict only, not the gauge fold above
        counters = dict(self.counters)
        if self.offloader is not None:
            counters.update(self.offloader.counters)
        return SupervisorReport(
            final_step=step,
            losses=[loss_by_step[s] for s in sorted(loss_by_step)],
            counters=counters,
            preempted=self._preempt,
        )

    # -- nan handling -----------------------------------------------------
    def _snapshot(self):
        """Host copies of the full train state.  The step function
        donates its weight/opt/state buffers (build_step
        donate_argnums), so pre-step device arrays are dead after the
        step — only a host copy can roll one back."""
        ff = self.ff
        return (
            jax.tree.map(np.asarray, ff._weights),
            jax.tree.map(np.asarray, ff._opt_state),
            jax.tree.map(np.asarray, ff._state),
            ff._rng,
        )

    def _rollback(self, snap) -> None:
        from ..model import device_put_like

        w, opt, st, rng = snap
        ff = self.ff
        ff.set_weights(w)
        ff._opt_state = device_put_like(opt, ff._opt_state)
        ff._state = device_put_like(st, ff._state)
        ff._rng = rng
