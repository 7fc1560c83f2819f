"""SLO-driven autoscaling of the replicated serving front.

The paper's thesis is that placement decisions should be measured and
costed, not hardcoded; the serving fleet treats its replica count the
same way — a controlled variable driven by the load signals the front
already emits (PR 8), not a static ``--serving-replicas`` knob:

  * **queue depth per live replica** — the admission backlog the
    dispatcher hasn't placed yet, normalized by fleet size;
  * **windowed p99 TTFT** — the user-facing SLO, from the front's
    rolling TTFT window;
  * **KV-pool occupancy** — the capacity signal: a fleet whose pools
    run full queues at admission even when TTFT still looks fine.

Control discipline (the loop must not flap):

  * **hysteresis bands**: scale-up and scale-down thresholds are
    separated (`queue_high` vs `queue_low`, SLO breach vs comfortable
    margin), so a signal oscillating around one threshold cannot
    bounce the fleet;
  * **cooldown**: after any action the loop holds for `cooldown_s`
    before deciding again — a freshly spawned replica needs time to
    absorb load before the signals mean anything;
  * **bounds**: `min_replicas <= fleet <= max_replicas`, the
    ``--serving-min/max-replicas`` contract;
  * **one transition at a time**: while a drain or spin-up is in
    flight, the loop only watches (and bounds a wedged drain with
    `drain_timeout_s` -> `force_retire`, which requeues the stragglers
    onto survivors).

Scale-up spawns through the front's `model_factory` — warm via the
strategy store (docs/STORE.md), so spin-up is compile-cache-bounded,
not search-bounded.  Scale-down picks the least-loaded live replica
and DRAINS it (READY -> DRAINING -> RETIRED, serving/replica.py): the
dispatcher stops routing to it, in-flight slots run to completion
token-identically, then the engine retires and frees its KV pool.

Metrics (obs.metrics, docs/OBSERVABILITY.md "serving/autoscaler_*"):
current/target replica gauges, scale_up/scale_down/hold counters, a
decision event per action, and the drain-duration histogram the
replica emits.  /v2/stats surfaces `stats()` as the "autoscaler"
block.  docs/SERVING.md "Autoscaling & drain lifecycle".
"""
from __future__ import annotations

import math
import threading
import time
from collections import deque
from typing import Callable, Dict, Optional

from ..logger import resilience_logger


class ServingAutoscaler:
    """Control loop over a ServingFront's load gauges.

    Deterministic core: `observe()` -> signals, `decide(signals)` ->
    (action, reason), `tick()` -> one observe/decide/act cycle.  Tests
    drive `tick()` directly with a fake `time_fn`; production calls
    `start()` for the daemon-thread loop at `interval_s`.
    """

    def __init__(
        self,
        front,
        min_replicas: int = 1,
        max_replicas: int = 4,
        *,
        interval_s: float = 1.0,
        cooldown_s: float = 5.0,
        queue_high: float = 4.0,
        queue_low: float = 0.5,
        slo_ttft_s: float = 0.0,
        kv_high: float = 0.9,
        rebalance_kv: float = 0.0,
        drain_timeout_s: float = 30.0,
        predictive: bool = False,
        predict_horizon_s: float = 10.0,
        slo_per_token_s: float = 0.0,
        history: int = 256,
        registry=None,
        time_fn: Callable[[], float] = time.monotonic,
        logger=resilience_logger,
    ):
        if min_replicas < 1:
            raise ValueError(
                f"min_replicas must be >= 1, got {min_replicas}")
        if max_replicas < min_replicas:
            raise ValueError(
                f"max_replicas ({max_replicas}) must be >= "
                f"min_replicas ({min_replicas})")
        if queue_low >= queue_high:
            raise ValueError(
                f"hysteresis band inverted: queue_low ({queue_low}) "
                f"must be < queue_high ({queue_high})")
        if interval_s <= 0:
            raise ValueError(
                f"interval_s must be > 0, got {interval_s}")
        if drain_timeout_s <= 0:
            raise ValueError(
                f"drain_timeout_s must be > 0, got {drain_timeout_s}")
        if not 0.0 <= rebalance_kv < 1.0:
            raise ValueError(
                f"rebalance_kv must be in [0, 1) (occupancy fraction; "
                f"0 disables), got {rebalance_kv}")
        self.front = front
        self.min_replicas = int(min_replicas)
        self.max_replicas = int(max_replicas)
        self.interval_s = float(interval_s)
        self.cooldown_s = float(cooldown_s)
        self.queue_high = float(queue_high)
        self.queue_low = float(queue_low)
        self.slo_ttft_s = float(slo_ttft_s)
        self.kv_high = float(kv_high)
        # hot-replica rebalance (mid-decode handoff, serving/handoff.py):
        # a live replica whose KV occupancy exceeds this fraction while
        # a peer sits below half of it pauses its longest-remaining
        # generation onto the handoff path.  0 = off; needs the front's
        # handoff flag too.
        self.rebalance_kv = float(rebalance_kv)
        self.drain_timeout_s = float(drain_timeout_s)
        # predictive scaling (--autoscale-predictive): project the
        # admission queue forward from the measured admission-rate
        # slope and scale BEFORE the reactive thresholds breach — a
        # ramp of arrivals is visible in the slope several intervals
        # before it is visible in the queue
        self.predictive = bool(predictive)
        self.predict_horizon_s = float(predict_horizon_s)
        # decode-class per-token SLO (role-aware fleets; 0 = off)
        self.slo_per_token_s = float(slo_per_token_s)
        self._admit_samples: "deque[tuple]" = deque(maxlen=8)
        self.registry = registry if registry is not None \
            else front.registry
        self.time_fn = time_fn
        self.log = logger
        self.scale_ups = 0
        self.scale_downs = 0
        self.spawn_failures = 0  # add_replica refusals (chip budget,
        #                          compile errors) observed by tick()
        self.forced_retires = 0
        self.rebalances = 0
        self._last_rebalance_t: Optional[float] = None
        self.ticks = 0
        self.last_action_t: Optional[float] = None
        self.last_decision: Optional[Dict] = None
        self.up_role: Optional[str] = None  # roles fleet: class the
        #                                     next scale-up grows
        self.history: "deque[Dict]" = deque(maxlen=history)
        self._draining = None  # replica with a drain in flight
        self._spawning = False  # a scale-up build (compile) in flight
        self._stop_evt = threading.Event()
        self._thread: Optional[threading.Thread] = None
        front.autoscaler = self  # /v2/stats picks up the block

    @classmethod
    def from_config(cls, front, cfg, **kw) -> "ServingAutoscaler":
        """Bounds + pacing from the FFConfig serving knobs
        (--serving-min/max-replicas, --autoscale-interval,
        --autoscale-cooldown, --serving-slo-ttft,
        --serving-drain-timeout).  serving_max_replicas=0 means
        autoscaling is OFF (the documented static-fleet contract) —
        building a scaler anyway would drain a --serving-replicas N
        fleet down to min_replicas, so refuse loudly."""
        if cfg.serving_max_replicas <= 0:
            raise ValueError(
                "autoscaling is off (serving_max_replicas=0): set "
                "--serving-max-replicas >= --serving-min-replicas to "
                "enable, or don't build a ServingAutoscaler")
        kw.setdefault("interval_s", cfg.autoscale_interval)
        kw.setdefault("cooldown_s", cfg.autoscale_cooldown)
        kw.setdefault("slo_ttft_s", cfg.serving_slo_ttft)
        kw.setdefault("drain_timeout_s", cfg.serving_drain_timeout)
        kw.setdefault("predictive",
                      getattr(cfg, "autoscale_predictive", False))
        kw.setdefault("rebalance_kv",
                      float(getattr(cfg, "serving_rebalance_kv", 0.0)
                            or 0.0))
        return cls(front, cfg.serving_min_replicas,
                   cfg.serving_max_replicas, **kw)

    # -- signals ---------------------------------------------------------
    def observe(self) -> Dict:
        """One sample of the control inputs, from gauges the front and
        schedulers already maintain — observing never blocks decode."""
        front = self.front
        with front._cv:
            replicas = list(front.replicas)
            queue_depth = len(front._admission)
            admitted = int(getattr(front, "requests_admitted", 0))
        live = [r for r in replicas if r.alive]
        draining = [r for r in replicas if r.state == "draining"]
        # restarting replicas come back live after their rebuild, so
        # they count against max_replicas (permanently-dead ones hold
        # no engine and never return — they don't)
        restarting = [r for r in replicas if r.state == "restarting"]
        outstanding = sum(r.outstanding for r in live)
        # disaggregated fleets (serving/disagg.py) scale the two
        # classes on their OWN signals: KV occupancy is a DECODE-class
        # signal there (the prefill pool recycles per pass and its
        # occupancy says nothing about serving capacity)
        roles_active = any(r.role != "mixed" for r in replicas)
        occ = 0.0
        for r in live:
            sched = r.scheduler
            if sched is None or (roles_active and r.role == "prefill"):
                continue
            try:
                occ = max(occ, sched.pool.occupancy())
            except Exception:  # noqa: BLE001 — a dying replica's
                pass           # pool must not kill the loop
        ttft = front.ttft_stats()  # percentile_summary keys, in ms
        t = self.time_fn()
        s = {
            "t": t,
            "live": len(live),
            "draining": len(draining),
            "restarting": len(restarting),
            "fleet": len(replicas),
            "queue_depth": queue_depth,
            "outstanding": outstanding,
            "queue_per_replica": queue_depth / max(len(live), 1),
            "p99_ttft_s": (ttft.get("p99_ms", 0.0) or 0.0) / 1e3,
            "kv_occupancy": occ,
            "roles_active": roles_active,
        }
        if roles_active:
            s["prefill_live"] = sum(1 for r in live
                                    if r.role == "prefill")
            s["decode_live"] = sum(1 for r in live
                                   if r.role != "prefill")
            tok = None
            with front._lat_lock:
                samples = sorted(front._class_tok.get("decode", ()))
            if len(samples) >= 3:  # nearest-rank p99
                tok = samples[min(len(samples) - 1,
                                  math.ceil(0.99 * len(samples)) - 1)]
            s["decode_per_token_s"] = tok
            s["decode_rate_rps"] = front.service_rate("decode")
        # admission-rate slope (predictive scaling): completions/s the
        # queue is FILLING at, measured over the sample window
        self._admit_samples.append((t, admitted))
        rate = None
        if len(self._admit_samples) >= 2:
            (t0, a0), (t1, a1) = (self._admit_samples[0],
                                  self._admit_samples[-1])
            if t1 > t0:
                rate = (a1 - a0) / (t1 - t0)
        s["admit_rate_rps"] = rate
        # the measured drain rate the projection subtracts: the decode
        # class's own window in a roles fleet, the fleet's otherwise
        drain_rate = (s.get("decode_rate_rps") if roles_active
                      else front.service_rate())
        s["drain_rate_rps"] = drain_rate
        return s

    # -- policy ----------------------------------------------------------
    def decide(self, s: Dict) -> tuple:
        """(action, reason) for one signal sample.  Pure policy over
        the sample (directly unit-testable); in a roles fleet it also
        records WHICH class a scale-up targets (self.up_role — queue/
        TTFT breaches grow the prefill class, KV-occupancy/per-token
        breaches grow decode), which tick() passes to add_replica."""
        self.up_role = None
        if self._draining is not None:
            return "hold", "drain in flight"
        if (self.last_action_t is not None
                and s["t"] - self.last_action_t < self.cooldown_s):
            return "hold", "cooldown"
        if s["live"] == 0:
            # replica supervision (restarts) owns total outages; the
            # autoscaler only sizes a serving fleet
            return "hold", "no live replicas"
        committed = s["live"] + s["draining"] + s.get("restarting", 0)
        if committed < self.min_replicas:
            # a permanently-dead replica leaves the fleet below its
            # contracted floor with NO load signal to restore it —
            # min_replicas is a bound, not a suggestion
            return "up", (f"fleet {committed} < "
                          f"min_replicas={self.min_replicas}")
        # the TTFT window is count-based (last N completions), so with
        # NO traffic it never refreshes: a past burst's p99 would pin
        # an idle fleet at max forever (and block its drain).  Gate the
        # TTFT signal on actual load — an idle fleet breaches no SLO.
        busy = s["queue_depth"] + s["outstanding"] > 0
        roles = bool(s.get("roles_active"))
        # ingest-side breaches (grow the PREFILL class in a roles
        # fleet: the queue backs up when prompts wait for a pass)
        ingest_reasons = []
        # capacity-side breaches (grow the DECODE class: its pools and
        # per-token pace bound how many streams the fleet sustains)
        capacity_reasons = []
        if s["queue_per_replica"] > self.queue_high:
            ingest_reasons.append(
                f"queue/replica {s['queue_per_replica']:.1f} > "
                f"{self.queue_high:.1f}")
        if (self.slo_ttft_s > 0 and busy
                and s["p99_ttft_s"] > self.slo_ttft_s):
            ingest_reasons.append(
                f"p99 TTFT {s['p99_ttft_s'] * 1e3:.0f}ms > SLO "
                f"{self.slo_ttft_s * 1e3:.0f}ms")
        if (self.predictive and s.get("admit_rate_rps") is not None):
            # a ramp of arrivals: the admission-rate slope projects a queue
            # breach before the reactive threshold sees it
            drain = s.get("drain_rate_rps") or 0.0
            growth = s["admit_rate_rps"] - drain
            if growth > 0:
                projected = (s["queue_depth"]
                             + growth * self.predict_horizon_s
                             ) / max(s["live"], 1)
                if projected > self.queue_high:
                    ingest_reasons.append(
                        f"projected queue/replica {projected:.1f} > "
                        f"{self.queue_high:.1f} within "
                        f"{self.predict_horizon_s:.0f}s (admit "
                        f"{s['admit_rate_rps']:.2f}/s vs drain "
                        f"{drain:.2f}/s)")
        if s["kv_occupancy"] > self.kv_high:
            capacity_reasons.append(
                f"KV occupancy {s['kv_occupancy']:.2f} > "
                f"{self.kv_high:.2f}")
        tok = s.get("decode_per_token_s")
        if (roles and self.slo_per_token_s > 0 and busy
                and tok is not None and tok > self.slo_per_token_s):
            capacity_reasons.append(
                f"decode p99 per-token {tok * 1e3:.0f}ms > SLO "
                f"{self.slo_per_token_s * 1e3:.0f}ms")
        up_reasons = ingest_reasons + capacity_reasons
        if up_reasons:
            if roles:
                # capacity first: a decode class out of KV headroom
                # queues admissions no matter how fast prefill runs
                self.up_role = ("decode" if capacity_reasons
                                else "prefill")
            max_fleet = self._max_fleet()
            if committed >= max_fleet:
                cap = (f"chip budget "
                       f"{getattr(self.front, 'chip_budget', 0)} caps "
                       f"the fleet at {max_fleet}"
                       if max_fleet < self.max_replicas
                       else f"at max_replicas={self.max_replicas}")
                return "hold", f"{cap} ({'; '.join(up_reasons)})"
            return "up", "; ".join(up_reasons)
        # scale-down wants EVERY signal comfortable (hysteresis: the
        # down band sits well below the up band)
        calm = (
            s["queue_per_replica"] < self.queue_low
            and (self.slo_ttft_s <= 0 or not busy
                 or s["p99_ttft_s"] < 0.5 * self.slo_ttft_s)
            and s["kv_occupancy"] < 0.5 * self.kv_high
        )
        if calm and s["live"] > self.min_replicas:
            return "down", (
                f"queue/replica {s['queue_per_replica']:.1f} < "
                f"{self.queue_low:.1f} and SLO margin ample")
        return "hold", "within bands"

    def _max_fleet(self) -> int:
        """max_replicas, further capped by the front's chip budget:
        each replica spans chips_per_replica chips (its tensor-parallel
        degree), so a budget of B chips holds at most B // tp engines
        regardless of what --serving-max-replicas allows."""
        budget = int(getattr(self.front, "chip_budget", 0) or 0)
        if not budget:
            return self.max_replicas
        per = max(1, int(getattr(self.front, "chips_per_replica", 1)))
        return min(self.max_replicas, budget // per)

    # -- actuation -------------------------------------------------------
    def _pick_drain_target(self):
        """Least-loaded live replica — the cheapest one to retire.  In
        a roles fleet, never the last decode-capable one (a healthy
        prefill class cannot serve a single client request); with the
        decode class at its floor, an idle prefill replica drains
        instead (the fleet degrades to colocated re-prefill)."""
        live = self.front._live()
        if len(live) <= self.min_replicas:
            return None
        if any(r.role != "mixed" for r in live):
            serving = [r for r in live if r.role != "prefill"]
            if len(serving) <= 1:
                live = [r for r in live if r.role == "prefill"]
                if not live:
                    return None
        return min(live, key=lambda r: r.outstanding)

    def _record(self, action: str, reason: str, s: Dict) -> None:
        entry = {
            "t": s["t"],
            "action": action,
            "reason": reason,
            "replicas": s["fleet"],
            "live": s["live"],
            "queue_depth": s["queue_depth"],
            "p99_ttft_s": round(s["p99_ttft_s"], 4),
            "kv_occupancy": round(s["kv_occupancy"], 4),
        }
        if action == "up" and self.up_role is not None:
            entry["role"] = self.up_role
        self.history.append(entry)
        if action != "hold":
            self.last_decision = entry
            self.last_action_t = s["t"]
            self.log.info("autoscaler %s (fleet %d): %s",
                          action, s["fleet"], reason)
        if self.registry is not None:
            reg = self.registry
            reg.gauge("serving/autoscaler_replicas").set(s["fleet"])
            # the target this TICK decided — not target_replicas(),
            # which would re-run decide() AFTER last_action_t/_draining
            # were updated and always report the pre-action size
            cur = (s["live"] + s["draining"]
                   + s.get("restarting", 0))
            reg.gauge("serving/autoscaler_target").set(
                self._target_for(action, cur))
            reg.counter(f"serving/autoscaler_{action}").inc()
            if action != "hold":
                reg.event("serving/autoscaler_decision", **entry)

    def _target_for(self, action: str, cur: int) -> int:
        if action == "up":
            return min(cur + 1, self.max_replicas)
        if action == "down":
            return max(cur - 1, self.min_replicas)
        return max(min(cur, self.max_replicas), self.min_replicas)

    def target_replicas(self, s: Optional[Dict] = None) -> int:
        """The fleet size the policy is steering toward right now."""
        if s is None:
            s = self.observe()
        action, _ = self.decide(s)
        cur = s["live"] + s["draining"] + s.get("restarting", 0)
        return self._target_for(action, cur)

    def tick(self) -> Dict:
        """One control cycle: observe -> decide -> act.  Returns the
        history entry (action + signals) for this cycle."""
        self.ticks += 1
        self._sweep_drain()
        s = self.observe()
        self._maybe_rebalance(s)
        action, reason = self.decide(s)
        if action == "up":
            self._spawning = True  # visible while the build compiles
            try:
                self.front.add_replica(role=self.up_role or "mixed")
                self.scale_ups += 1
            except Exception as e:  # noqa: BLE001 — a failed spawn
                action, reason = "hold", f"spawn failed: {e}"
                self.spawn_failures += 1
                # _record only logs non-hold actions and only they set
                # the cooldown: without both, a persistent build
                # failure retries a full compile every tick, silently
                self.log.info("autoscaler scale-up failed: %s", e)
                self.last_action_t = s["t"]
                if self.registry is not None:
                    self.registry.counter(
                        "serving/autoscaler_spawn_failed").inc()
            finally:
                self._spawning = False
        elif action == "down":
            target = self._pick_drain_target()
            if target is not None and self.front.drain_replica(target):
                self._draining = (target, s["t"])
                self.scale_downs += 1
            else:
                action, reason = "hold", "no drainable replica"
        self._record(action, reason, s)
        return self.history[-1]

    def _maybe_rebalance(self, s: Dict) -> None:
        """KV-occupancy rebalance trigger (mid-decode handoff): when a
        live decode-capable replica's pool runs past `rebalance_kv`
        while a peer sits below half of it, pause the hot replica's
        longest-remaining generation onto the handoff path so it
        resumes on the cool one.  Its own cooldown (shared constant,
        separate clock) keeps one hot pool from shedding a sequence
        every tick."""
        if self.rebalance_kv <= 0:
            return
        front = self.front
        if not getattr(front, "handoff", False):
            return
        t = s["t"]
        if (self._last_rebalance_t is not None
                and t - self._last_rebalance_t < self.cooldown_s):
            return
        hot = cool = None
        for r in front._live():
            sched = r.scheduler
            if sched is None or r.role == "prefill":
                continue
            try:
                occ = sched.pool.occupancy()
            except Exception:  # noqa: BLE001 — a dying replica's pool
                continue       # must not kill the loop
            if occ > self.rebalance_kv and (hot is None
                                            or occ > hot[1]):
                hot = (r, occ)
            if occ < 0.5 * self.rebalance_kv and (cool is None
                                                  or occ < cool[1]):
                cool = (r, occ)
        if hot is None or cool is None or hot[0] is cool[0]:
            return
        if front.rebalance_replica(hot[0], max_sequences=1):
            self.rebalances += 1
            self._last_rebalance_t = t
            self.log.info(
                "autoscaler rebalance: replica %d KV occupancy %.2f > "
                "%.2f (coolest peer %.2f) — pausing 1 sequence for "
                "handoff", hot[0].replica_id, hot[1],
                self.rebalance_kv, cool[1])

    def _sweep_drain(self) -> None:
        """Resolve an in-flight drain: done, or wedged past the
        deadline -> bounded force_retire (in-flight requests requeue
        onto survivors through the front's settle hooks)."""
        if self._draining is None:
            return
        replica, t0 = self._draining
        if replica.state in ("retired", "dead", "closed"):
            self._draining = None
            return
        if self.time_fn() - t0 > self.drain_timeout_s:
            self.log.info(
                "autoscaler: drain of replica %d wedged past %.1fs — "
                "forcing retirement", replica.replica_id,
                self.drain_timeout_s)
            self.forced_retires += 1
            if self.registry is not None:
                self.registry.counter(
                    "serving/autoscaler_forced_retire").inc()
            replica.force_retire()
            self._draining = None

    # -- loop ------------------------------------------------------------
    def start(self) -> "ServingAutoscaler":
        """Run tick() every interval_s on a daemon thread."""
        if self._thread is not None:
            return self
        self._stop_evt.clear()
        self._thread = threading.Thread(
            target=self._loop, daemon=True, name="serving-autoscaler")
        self._thread.start()
        return self

    def _loop(self) -> None:
        while not self._stop_evt.wait(self.interval_s):
            try:
                self.tick()
            except Exception:  # noqa: BLE001 — the control loop must
                # outlive any single bad cycle (a dying replica's race
                # is the replica supervisor's problem, not ours)
                self.log.exception("autoscaler tick failed")

    def stop(self) -> None:
        self._stop_evt.set()
        t = self._thread
        if t is not None:
            t.join(timeout=2.0)
            self._thread = None

    # -- surfaces --------------------------------------------------------
    def stats(self) -> Dict:
        """The /v2/stats "autoscaler" block."""
        front = self.front
        with front._cv:
            current = len(front.replicas)
            meshes = [
                {"id": r.replica_id,
                 "mesh_shape": dict(getattr(
                     getattr(r.scheduler, "model", None),
                     "mesh_shape", None) or {})}
                for r in front.replicas if r.scheduler is not None
            ]
        # single read: the loop thread clears _draining concurrently
        draining = self._draining
        per = max(1, int(getattr(front, "chips_per_replica", 1)))
        return {
            "current_replicas": current,
            "target_replicas": self.target_replicas(),
            "min_replicas": self.min_replicas,
            "max_replicas": self.max_replicas,
            "max_fleet": self._max_fleet(),
            "chips_per_replica": per,
            "chip_budget": int(getattr(front, "chip_budget", 0) or 0),
            "fleet_chips": current * per,
            "replica_meshes": meshes,
            "predictive": self.predictive,
            "scale_ups": self.scale_ups,
            "scale_downs": self.scale_downs,
            "spawn_failures": self.spawn_failures,
            "forced_retires": self.forced_retires,
            "rebalances": self.rebalances,
            "rebalance_kv": self.rebalance_kv,
            "ticks": self.ticks,
            "drain_in_flight": (draining[0].replica_id
                                if draining is not None else None),
            "spawn_in_flight": self._spawning,
            "last_decision": self.last_decision,
        }
