"""Supervised serving replica: one ContinuousScheduler under the
training-side resilience primitives (resilience/).

The training supervisor (resilience/supervisor.py) classifies failures
into transients (restore + retry) and device-loss-style faults
(re-search + recompile + reshard-restore).  A serving replica inherits
the same taxonomy, adapted to a stateless decode engine:

  * **transient step exception** — the scheduler's existing per-step
    handling stands: only the in-flight batch fails (the front requeues
    those requests), the replica keeps serving;
  * **hung decode step** — the decode dispatch runs under a
    `StepWatchdog(step_timeout)`; a dispatch that never returns raises
    `HungStepTimeout` instead of wedging the worker forever.  That (and
    its injected twin `HungStepFault`) is FATAL to the engine: the
    wedged collective state only resets with a rebuilt engine;
  * **device loss** — `DeviceLossFault(survivors=k)` kills the engine
    and the rebuild happens on the surviving device count; the model
    factory's compile consults the strategy store's degraded-mesh key
    first (docs/STORE.md), so the re-search is warm whenever any
    replica or training run has paid it before.

Fatal faults are marked with ``fatal_to_engine = True`` — the
scheduler's contract for "drain everything and die" (scheduler.py) —
which fires the replica's `on_death` hook.  The replica's supervisor
thread then restarts the engine under a jittered-backoff `RetryPolicy`
with a hard restart budget; a replica that outruns the budget goes
permanently ``dead`` and `/v2/health` says so.

Fault injection is the training side's seeded `FaultPlan`: the plan's
step index counts DECODE steps (cumulative across restarts), so a
seeded replica kill replays exactly (tests/test_serving_front.py).
"""
from __future__ import annotations

import itertools
import threading
import time
from typing import Callable, Dict, Optional

from ..logger import resilience_logger
from ..resilience.faults import DeviceLossFault, FaultPlan, HungStepFault
from ..resilience.retry import RetryPolicy
from ..resilience.watchdog import HungStepTimeout, StepWatchdog
from .scheduler import ContinuousScheduler

#: failures that kill the ENGINE, not just the in-flight batch — the
#: supervisor answers them with a restart (cf. supervisor.HUNG_FAULTS)
FATAL_DECODE_FAULTS = (DeviceLossFault, HungStepFault, HungStepTimeout)

#: per-replica scheduler counters folded into `stats()` across restarts
_CARRIED_COUNTERS = ("batches_run", "requests_done", "tokens_generated",
                     "pass_decode_tokens", "dispatches_ahead",
                     "overrun_tokens", "step_failures", "admitted",
                     "queue_wait_s_sum")


class SupervisedDecodeModel:
    """Decode-model wrapper adding the resilience instrumentation to
    every step: seeded fault injection, then the watchdog-bounded
    dispatch.  Proxies the geometry attributes ContinuousScheduler
    reads (batch_slots, page_size, num_blocks, ...)."""

    def __init__(self, model, watchdog: StepWatchdog,
                 fault_plan: FaultPlan, step_counter):
        self._model = model
        self._watchdog = watchdog
        self._fault_plan = fault_plan
        self._steps = step_counter  # replica-lifetime, restart-spanning
        for name in ("batch_slots", "page_size", "num_blocks",
                     "max_blocks_per_seq", "max_seq", "vocab"):
            setattr(self, name, getattr(model, name))
        # prefix-cache / chunked-prefill surface (PagedKVDecodeModel;
        # absent on bare test fakes -> the scheduler degrades cleanly)
        self.prefill_chunk = getattr(model, "prefill_chunk", 0)
        self.prefill_passes = getattr(model, "prefill_passes",
                                      self.prefill_chunk)
        self.prefix_cache = getattr(model, "prefix_cache", True)
        # sampling programs that keep the greedy id on the device: the
        # scheduler may then leave a dispatch in flight (`launch_step`
        # / `launch_prefill`) and `land` it behind the next one
        self.keeps_ids = bool(getattr(model, "keeps_ids", False))
        # fused-kernel surface: which paged formulation runs + the
        # per-block byte unit the scheduler's read telemetry uses
        self.paged_kernel = getattr(model, "paged_kernel", "gather")
        self.kv_block_bytes = getattr(model, "kv_block_bytes", 0)
        # tensor-parallel surface: how many chips this engine spans and
        # the per-chip share of each KV block (1 chip / full block on
        # single-device engines and bare test fakes)
        self.tp = getattr(model, "tp", 1)
        self.mesh_shape = dict(getattr(model, "mesh_shape", {}) or {})
        self.kv_block_bytes_per_chip = getattr(
            model, "kv_block_bytes_per_chip", self.kv_block_bytes)
        # speculative surface (docs/SERVING.md "Speculative
        # decoding"): mode/k/verify geometry proxied; the draft twin
        # is handed through RAW — its dispatches belong to the
        # proposer and are fault-isolated there (a draft death
        # degrades to plain decode, it never counts against this
        # replica's fault plan or watchdog)
        self.spec_decode = getattr(model, "spec_decode", "off")
        self.spec_k = getattr(model, "spec_k", 0)
        self.verify_chunk = getattr(model, "verify_chunk", 0)
        self.draft_model = getattr(model, "draft_model", None)
        if getattr(model, "prefill_step", None) is None:
            self.prefill_chunk = 0
        self._has_verify = (self.spec_decode != "off" and getattr(
            model, "verify_step", None) is not None)
        if not self._has_verify:
            self.spec_decode = "off"
        self._has_copy = getattr(model, "copy_block", None) is not None
        # per-slot state: the scheduler then passes `row_tokens` to both
        # step programs, and zeroes a slot's state at admission
        # (`reset_slot_state`) where some of it is zeroed (`rstate_bytes`)
        self.has_slot_state = bool(getattr(model, "has_slot_state", False))
        self.rstate_bytes = getattr(model, "rstate_bytes", 0)
        # what the model's mixers tell of themselves and count of a
        # dispatch (`Op.dispatch_group`; {} / None without a group)
        self.groups = getattr(model, "groups", None) or {}
        self.dispatch_counts = getattr(model, "dispatch_counts", None)
        self._has_export = (
            getattr(model, "export_block", None) is not None
            and getattr(model, "import_block", None) is not None)
        # repeated regions of the twin's graph ({} / 0 without one)
        self.loop = dict(getattr(model, "loop", {}) or {})
        self.loop_steps = getattr(model, "loop_steps", 0)

    @property
    def exit_last(self):
        # each row's exit pdf from the last decode dispatch (None where
        # the family has no exit gate)
        return getattr(self._model, "exit_last", None)

    def reset(self):
        reset = getattr(self._model, "reset", None)
        if reset is not None:
            reset()

    @property
    def moe_last(self):
        # routed-expert counts of the last decode dispatch (None where
        # the model has no such layer)
        return getattr(self._model, "moe_last", None)

    def _supervised(self, fn, idx=None):
        """`fn()`, a device dispatch, under the replica-lifetime step
        index, the seeded fault plan and the hang watchdog; a hung or
        lost-device dispatch is marked fatal, so the scheduler
        drains-and-dies into a supervised restart instead of failing
        the in-flight batch alone.  `idx`: the wait for a dispatch
        launched under that index (no new index, no second injection:
        it is the same dispatch, and the wait is where a hang shows)."""
        launch = idx is None
        if launch:
            idx = next(self._steps)
        try:
            if launch:
                self._fault_plan.check_step(idx)
            return idx, self._watchdog.sync(fn, step=idx)
        except FATAL_DECODE_FAULTS as e:
            e.fatal_to_engine = True
            raise

    def reset_slot_state(self, slot):
        # a device dispatch like copy_block: a wedged reset surfaces as
        # a hung step
        return self._supervised(
            lambda: self._model.reset_slot_state(slot))[1]

    def step(self, tokens, seq_lens, block_tables, *row_tokens):
        return self._supervised(lambda: self._model.step(
            tokens, seq_lens, block_tables, *row_tokens))[1]

    def prefill_step(self, tokens, positions, block_tables, *row_tokens,
                     meanwhile=None):
        # chunked prefill is a decode-fleet step like any other
        # (`meanwhile`: the one-pass program's; see the model's)
        beside = {} if meanwhile is None else {"meanwhile": meanwhile}
        return self._supervised(lambda: self._model.prefill_step(
            tokens, positions, block_tables, *row_tokens, **beside))[1]

    def launch_step(self, tokens, seq_lens, block_tables, *row_tokens,
                    take_prev=None):
        """(step index, what the enqueued decode step left on the
        device): `land` takes it back."""
        return self._supervised(lambda: self._model.launch_step(
            tokens, seq_lens, block_tables, *row_tokens,
            take_prev=take_prev))

    def launch_prefill(self, tokens, positions, block_tables, *row_tokens,
                       take_prev=None):
        return self._supervised(lambda: self._model.launch_prefill(
            tokens, positions, block_tables, *row_tokens,
            take_prev=take_prev))

    def land(self, launched, behind=False):
        idx, launched = launched
        return self._supervised(
            lambda: self._model.land(launched, behind=behind), idx)[1]

    @property
    def verify_step(self):
        # speculative verify is a decode-fleet dispatch like any step:
        # fault injection and the hang watchdog see it under the same
        # replica-lifetime step index, and a hung/lost-device verify is
        # marked fatal so the scheduler drains-and-dies into a
        # supervised restart.  A TRANSIENT verify fault stays
        # non-fatal: the scheduler disables speculation and the
        # in-flight slots continue on the plain decode path.
        # None-propagating capability probe like copy_block.
        if not self._has_verify:
            return None

        def _verify(tokens, seq_lens, counts, block_tables):
            return self._supervised(lambda: self._model.verify_step(
                tokens, seq_lens, counts, block_tables))[1]

        return _verify

    @property
    def copy_block(self):
        # exposed as an attribute so the scheduler's capability probe
        # (getattr(..., "copy_block", None)) reflects the wrapped
        # model's.  The copy is a device dispatch like any step, so it
        # runs under the same fault plan + hang watchdog — a wedged
        # COW must surface as HungStepTimeout (fatal -> supervised
        # restart), not silently park the scheduler worker.
        if not self._has_copy:
            return None

        def _copy(src, dst):
            return self._supervised(
                lambda: self._model.copy_block(src, dst))[1]

        return _copy

    @property
    def export_block(self):
        # KV migration surface (serving/kv_transfer.py): eager
        # host<->device copies on the worker thread, not watchdogged
        # step dispatches — a wedged device read surfaces on the next
        # stepped dispatch.  None-propagating capability probe like
        # copy_block: a fake model without pools disables migration.
        if not self._has_export:
            return None
        return self._model.export_block

    @property
    def import_block(self):
        if not self._has_export:
            return None
        return self._model.import_block


class ServingReplica:
    """One supervised engine slot of a ServingFront.

    `model_factory(replica_id, survivors=None)` builds the decode model
    (a PagedKVDecodeModel for real GPTs; anything with the same step
    contract in tests).  `survivors` is the device count a
    DeviceLossFault left standing — a real factory maps it to a device
    list and recompiles, which consults the strategy store's
    degraded-mesh key before paying a search (docs/STORE.md).

    States: ``live`` (serving — READY), ``restarting`` (death observed,
    rebuild pending/underway), ``draining`` (autoscaler scale-down or
    SIGTERM grace: no new dispatches, in-flight slots run to
    completion), ``retired`` (drain finished — the engine and its KV
    pool are released, permanently out of the fleet), ``dead`` (restart
    budget exhausted — permanent), ``closed``.  `on_state_change` (set
    by the front) fires on every transition so the dispatcher never
    polls.
    """

    def __init__(
        self,
        replica_id: int,
        model_factory: Callable,
        *,
        eos_id: int = -1,
        registry=None,
        seed: int = 0,
        step_timeout: float = 0.0,
        retry: Optional[RetryPolicy] = None,
        fault_plan: Optional[FaultPlan] = None,
        close_timeout_s: float = 5.0,
        sleep: Callable[[float], None] = time.sleep,
        logger=resilience_logger,
        role: str = "mixed",
        check_invariants: bool = False,
        reqtrace=None,
    ):
        self.replica_id = int(replica_id)
        self.model_factory = model_factory
        # replica class in a disaggregated fleet (serving/disagg.py):
        # "prefill" runs prompt passes whose KV migrates out, "decode"
        # serves client requests, "mixed" (default) does both — the
        # colocated fleet unchanged
        if role not in ("prefill", "decode", "mixed"):
            raise ValueError(
                f"replica role {role!r}: pick from "
                "['prefill', 'decode', 'mixed']")
        self.role = role
        self._check_invariants = bool(check_invariants)
        # request tracer shared fleet-wide (obs/reqtrace.py): every
        # rebuild hands it to the fresh scheduler with this replica's
        # id as the Perfetto track (pid)
        self._reqtrace = reqtrace
        self.eos_id = int(eos_id)
        self.registry = registry
        self.seed = int(seed)
        self.retry = retry or RetryPolicy()
        self.fault_plan = fault_plan or FaultPlan()
        self.watchdog = StepWatchdog(step_timeout)
        self.close_timeout_s = float(close_timeout_s)
        self.sleep = sleep
        self.log = logger
        self.on_state_change: Optional[Callable] = None
        # dispatch bookkeeping owned by the front (under ITS lock)
        self.outstanding = 0
        self.state = "restarting"  # -> live after the first build
        self.restarts = 0       # successful rebuilds
        self.deaths = 0         # fatal engine exits observed
        self.last_death_t: Optional[float] = None
        self.last_live_t: Optional[float] = None
        self.last_recovery_s: Optional[float] = None
        self.last_error: Optional[Exception] = None
        self.scheduler: Optional[ContinuousScheduler] = None
        self._steps = itertools.count()  # decode-step index, all lives
        self._carried: Dict[str, int] = {k: 0 for k in _CARRIED_COUNTERS}
        self._survivors: Optional[int] = None
        self._death_evt = threading.Event()
        self._closed = False
        self._draining = False
        self._retire_guard = threading.Lock()
        self._retire_done = False
        self._on_retired: Optional[Callable] = None
        self.drain_started_t: Optional[float] = None
        self.retired_t: Optional[float] = None
        self._build()
        self._set_state("live")
        self._supervisor = threading.Thread(
            target=self._supervise, daemon=True,
            name=f"serving-replica-{replica_id}",
        )
        self._supervisor.start()

    # -- state ----------------------------------------------------------
    @property
    def alive(self) -> bool:
        return self.state == "live" and self.scheduler is not None

    def _set_state(self, state: str) -> None:
        if self._closed and state != "closed":
            return  # a rebuild that raced close() must not resurrect us
        if self.state == "retired" and state not in ("closed",):
            return  # retirement is permanent — no resurrection
        self.state = state
        if state == "live":
            self.last_live_t = time.monotonic()
            if self.last_death_t is not None:
                self.last_recovery_s = self.last_live_t - self.last_death_t
        hook = self.on_state_change
        if hook is not None:
            try:
                hook(self)
            except Exception:  # noqa: BLE001 — never kill the supervisor
                pass

    def _count(self, name: str) -> None:
        if self.registry is not None:
            self.registry.counter(f"serving/{name}").inc()

    # -- engine lifecycle ------------------------------------------------
    def _build(self) -> None:
        model = self.model_factory(self.replica_id,
                                   survivors=self._survivors)
        wrapped = SupervisedDecodeModel(model, self.watchdog,
                                        self.fault_plan, self._steps)
        self.scheduler = ContinuousScheduler(
            wrapped,
            eos_id=self.eos_id,
            registry=self.registry,
            seed=self.seed + 7919 * self.replica_id,
            close_timeout_s=self.close_timeout_s,
            on_death=self._on_death,
            check_invariants=self._check_invariants,
            reqtrace=self._reqtrace,
            trace_pid=self.replica_id,
        )

    def _on_death(self, exc: Exception) -> None:
        """Runs on the dying scheduler worker: record why, flip the
        state so the dispatcher stops routing here, and wake the
        supervisor thread to do the heavy rebuild off this stack."""
        self.last_error = exc
        self.last_death_t = time.monotonic()
        if isinstance(exc, DeviceLossFault):
            self._survivors = exc.survivors
        self.deaths += 1
        self._count("replica_deaths")
        self.log.info("serving replica %d died: %s", self.replica_id, exc)
        if not self._draining:
            # a DRAINING replica was leaving anyway: stay in draining
            # (the supervisor retires it instead of rebuilding)
            self._set_state("restarting")
        self._death_evt.set()

    def _fold_carried(self) -> None:
        sched = self.scheduler
        if sched is None:
            return
        for k in _CARRIED_COUNTERS:
            self._carried[k] += getattr(sched, k, 0)

    def _supervise(self) -> None:
        """Restart loop: each observed death costs one unit of the
        retry budget; past the budget the replica is permanently dead
        (a replica that dies on every rebuild must fail loudly, not
        flap forever)."""
        while True:
            self._death_evt.wait()
            self._death_evt.clear()
            if self._closed:
                return
            if self._draining:
                # death observed while leaving the fleet: the front
                # already requeued the stranded in-flight requests —
                # retire instead of paying a rebuild nobody wants
                self._retire()
                return
            self._fold_carried()
            self.scheduler = None
            attempt = self.deaths
            if not self.retry.admits(attempt):
                self._set_state("dead")
                self.log.info(
                    "serving replica %d: restart budget (%d) exhausted — "
                    "permanently dead", self.replica_id,
                    self.retry.max_restarts,
                )
                continue  # stay parked until close()
            self.sleep(self.retry.backoff(attempt))
            if self._closed:
                return
            try:
                self._build()
            except Exception as e:  # noqa: BLE001 — a failed rebuild is
                # another death: budget-capped, never an escaped crash
                self.last_error = e
                self.deaths += 1
                self._count("replica_deaths")
                self.log.info(
                    "serving replica %d rebuild failed: %s",
                    self.replica_id, e,
                )
                self._death_evt.set()
                continue
            if self._closed:
                # close() raced the rebuild (its bounded join expired
                # while _build was compiling): the fresh engine must
                # not leak a worker thread or flip us back to live
                sched = self.scheduler
                self.scheduler = None
                if sched is not None:
                    sched.close(self.close_timeout_s)
                return
            self.restarts += 1
            self._count("replica_restarts")
            self._survivors_note()
            self._set_state("live")

    def _survivors_note(self) -> None:
        if self._survivors is not None:
            self.log.info(
                "serving replica %d restarted on %d surviving devices "
                "(restart %d)", self.replica_id, self._survivors,
                self.restarts,
            )
        else:
            self.log.info("serving replica %d restarted (restart %d)",
                          self.replica_id, self.restarts)

    # -- drain lifecycle (autoscaler scale-down / SIGTERM grace) ---------
    def drain(self, on_retired: Optional[Callable] = None) -> bool:
        """READY -> DRAINING: stop taking new work, let in-flight slots
        run to completion (token-identical — decode is undisturbed),
        then retire and release the engine + KV pool.  Returns False if
        the replica is not currently live (nothing to drain).

        `on_retired(replica)` fires exactly once when the drain
        completes — including when a fault kills the draining engine
        (in-flight requests are requeued by the front; a leaving
        replica is never rebuilt)."""
        sched = self.scheduler
        if self.state != "live" or sched is None or self._closed:
            return False
        self._draining = True
        self._on_retired = on_retired
        self.drain_started_t = time.monotonic()
        self._count("replica_drains")
        self.log.info("serving replica %d draining", self.replica_id)
        self._set_state("draining")  # dispatcher stops routing here
        sched.drain(on_drained=self._retire)
        return True

    def _retire(self) -> None:
        """DRAINING -> RETIRED: release the engine (the KV pool goes
        with it) and notify the front.  Idempotent under CONCURRENT
        callers — a clean drain completion, a death-while-draining,
        and a force_retire may all arrive, from different threads;
        exactly one runs the body (else _fold_carried double-counts
        and on_retired fires twice)."""
        with self._retire_guard:
            if self._retire_done or self.state == "retired":
                return
            self._retire_done = True
        self._fold_carried()
        self.scheduler = None  # drops the pool: KV blocks are freed
        self.retired_t = time.monotonic()
        if self.drain_started_t is not None and self.registry is not None:
            self.registry.histogram("serving/drain_ms").observe(
                (self.retired_t - self.drain_started_t) * 1e3)
        self._count("replica_retired")
        self.log.info("serving replica %d retired", self.replica_id)
        self._set_state("retired")
        hook = self._on_retired
        self._on_retired = None
        if hook is not None:
            try:
                hook(self)
            except Exception:  # noqa: BLE001 — never kill the worker
                pass           # or supervisor retiring us
        # retirement is the replica's end of life: release the parked
        # supervisor thread too.  front.close() only sweeps fleet
        # members, so without this every clean scale-down would leave
        # one daemon thread blocked on _death_evt until process exit.
        self._closed = True
        self._death_evt.set()

    def force_retire(self, timeout_s: Optional[float] = None) -> None:
        """Bounded end of a wedged drain: close the engine (in-flight
        requests fail and the front requeues them onto survivors),
        then retire.  The autoscaler calls this when a drain outlives
        its deadline."""
        sched = self.scheduler
        if sched is not None:
            sched.close(timeout_s if timeout_s is not None
                        else self.close_timeout_s)
        self._retire()

    # -- front-facing ----------------------------------------------------
    def submit(self, prompt, max_new_tokens, temperature, on_done,
               trace=None, seed=None, resume=None):
        sched = self.scheduler
        if self.state != "live" or sched is None:
            raise RuntimeError(
                f"serving replica {self.replica_id} is {self.state}")
        return sched.generate_async(prompt, max_new_tokens, temperature,
                                    on_done=on_done, trace=trace,
                                    seed=seed, resume=resume)

    def request_handoff(self, **kw) -> bool:
        """Ask the scheduler to pause in-flight generations for
        handoff (see ContinuousScheduler.request_handoff).  Unlike
        submit this works while DRAINING — that is its main caller:
        a draining replica migrates its long generations off instead
        of waiting them out.  Returns False when there is no engine
        to ask (the on_paused callback will not fire)."""
        sched = self.scheduler
        if sched is None or self.state in ("retired", "closed"):
            return False
        try:
            sched.request_handoff(**kw)
            return True
        except Exception:  # noqa: BLE001 — racing a death/close
            return False

    def stats(self) -> Dict:
        sched = self.scheduler
        out = {
            "id": self.replica_id,
            "state": self.state,
            "role": self.role,
            "restarts": self.restarts,
            "deaths": self.deaths,
            "outstanding": self.outstanding,
            "last_recovery_s": self.last_recovery_s,
        }
        for k in _CARRIED_COUNTERS:
            out[k] = self._carried[k] + (getattr(sched, k, 0) or 0)
        if sched is not None:
            sstats = sched.stats()
            out["queue_depth"] = sstats["queue_depth"]
            # weight passes of one prefill dispatch (the scan: the
            # chunk; a family's one-pass program: 1)
            out["prefill_passes"] = sstats["prefill_passes"]
            # why dispatches were fetched at once, or flights ended out
            # of turn (this engine's, since its last build)
            out["lookahead_drains"] = sstats["lookahead_drains"]
            # this engine's pool (peak_used_blocks since its last build)
            out["kv_pool"] = sstats["kv_pool"]
            # prefix-cache visibility per replica (each pool caches
            # independently; shared blocks counted once per pool)
            if "prefix_cache" in sstats:
                out["prefix_cache"] = sstats["prefix_cache"]
            # which paged formulation this replica runs + its fused
            # kernel's KV-read counters (zeroes under the gather oracle);
            # tensor-parallel geometry; routed-expert layers' counts and
            # a repeated region's weight passes, by program; and every
            # group the model's mixers named (`Op.dispatch_group`):
            # their dispatch args summed by program, beside their
            # geometry
            for k in ("paged_kernel", "tp", "moe", "loop",
                      *sched.group_totals):
                if k in sstats:
                    out[k] = sstats[k]
        return out

    def close(self, timeout_s: Optional[float] = None) -> None:
        self._closed = True
        self._death_evt.set()  # unpark the supervisor so it exits
        bound = timeout_s if timeout_s is not None else self.close_timeout_s
        sched = self.scheduler
        if sched is not None:
            sched.close(bound)
        self._supervisor.join(timeout=2.0)
        # a rebuild may have landed between the close above and the
        # supervisor noticing _closed; the supervisor's own post-build
        # check handles the still-in-_build case
        sched = self.scheduler
        self.scheduler = None
        if sched is not None:
            sched.close(bound)
        self._set_state("closed")
