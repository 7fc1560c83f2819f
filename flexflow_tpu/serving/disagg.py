"""Disaggregated prefill/decode serving fleet (DistServe
arXiv:2401.09670, Splitwise arXiv:2311.18677).

Prefill is compute-bound (one big batched matmul over the prompt);
decode is memory-bandwidth-bound (one token per step against a growing
KV cache).  Colocating them makes each request's prefill stall every
other request's decode step.  The disaggregated fleet splits the
replica classes instead:

    client ──> admission queue ──> dispatcher
                                     │  cost: migrate vs re-prefill
                          ┌──────────┴──────────┐
                    [prefill replica]      [decode replica]
                     prompt pass             client decodes
                     (max_new=1)                  ▲
                          │   KV blocks           │ requeue as a
                          └──── KVMigrator ───────┘ prefix-cache hit

A MIGRATED request is a remote prefix-cache population: the prefill
replica runs the prompt once (its pool indexes every block-aligned
boundary), the finished blocks stream through a KVTransferFabric
(serving/kv_transfer.py), the decode replica adopts them as shared
cached blocks, and the request re-enters the admission queue where
cache-affine dispatch routes it to the adopter — its prefill becomes a
block-table metadata hit.  The decode replica would have written
BIT-IDENTICAL bytes for the same prefix (the KV content is a pure
function of the token prefix and the weights), so completions are
token-identical to the colocated fleet, and EVERY failure mode — torn
stream, dead fabric, died replica — degrades to a plain requeue that
re-prefills, never to wrong tokens.

A request the cost model routes the other way (sub-page prompt:
nothing block-aligned to ship; slow fabric: streaming costs more than
recomputing) dispatches straight to the decode class and re-prefills
there.  Both decisions are recorded per request and counted
(serving/disagg_migrate_decisions / disagg_reprefill_decisions).
docs/SERVING.md "Disaggregated fleet".
"""
from __future__ import annotations

import math
import time
from typing import Callable, Dict, List, Optional, Sequence

from ..logger import resilience_logger
from .front import FrontRequest, ServingFront
from .kv_transfer import InProcessFabric, KVMigrator, KVTransferFabric
from .replica import ServingReplica

#: decode-step seconds assumed before the first EWMA sample lands —
#: only the migrate/re-prefill RATIO matters, so any positive value
#: keeps the decision well-defined on a cold fleet
_DEFAULT_STEP_S = 5e-3


def parse_serving_roles(spec: str,
                        num_replicas: Optional[int] = None
                        ) -> Optional[List[str]]:
    """--serving-roles "prefill=1,decode=2" -> per-replica role list.

    Empty/None means a colocated fleet (None: every replica mixed).
    Counts must sum to `num_replicas` when given, and at least one
    replica must be decode-capable (decode or mixed) — a prefill-only
    fleet could admit requests but never finish one."""
    spec = (spec or "").strip()
    if not spec:
        return None
    roles: List[str] = []
    for part in spec.split(","):
        part = part.strip()
        if not part:
            continue
        name, sep, count_s = part.partition("=")
        name = name.strip()
        if sep:
            try:
                count = int(count_s)
            except ValueError:
                raise ValueError(
                    f"--serving-roles: bad count {count_s!r} in "
                    f"{part!r}") from None
        else:
            count = 1
        if name not in ("prefill", "decode", "mixed"):
            raise ValueError(
                f"--serving-roles: unknown role {name!r} (pick from "
                "['prefill', 'decode', 'mixed'])")
        if count < 0:
            raise ValueError(
                f"--serving-roles: count for {name} must be >= 0, "
                f"got {count}")
        roles.extend([name] * count)
    if not roles:
        raise ValueError(f"--serving-roles: empty spec {spec!r}")
    if all(r == "prefill" for r in roles):
        raise ValueError(
            "--serving-roles: fleet needs at least one decode-capable "
            "replica (decode or mixed)")
    if num_replicas is not None and len(roles) != num_replicas:
        raise ValueError(
            f"--serving-roles names {len(roles)} replica(s) but the "
            f"fleet has {num_replicas}")
    return roles


class MigrationCostModel:
    """Migrate vs re-prefill, priced with the topology model's
    interconnect terms (sim/machine_model.py TpuPodModel defaults):

      migrate_s    = hop_latency + block_bytes * new_blocks / hop_bw
                     + ceil(tail_tokens / C) * step_s
      reprefill_s  = ceil(prompt_len / C) * step_s

    where C is the chunked-prefill width (1 without chunking), step_s
    the DECODE replica's measured per-dispatch EWMA, tail_tokens the
    sub-page remainder the decode replica must still prefill after
    adoption, and the hop terms come from the fabric class: an
    in-process handoff prices as one ICI hop, a blob-store hop as DCN.
    Migrate wins iff new_blocks > 0 and
    migrate_s <= cost_cap * reprefill_s (--migration-cost-cap)."""

    def __init__(self, cost_cap: float = 1.0, fabric_kind: str = "inproc",
                 machine=None):
        if cost_cap <= 0:
            raise ValueError(
                f"migration cost cap must be > 0, got {cost_cap}")
        self.cost_cap = float(cost_cap)
        if machine is None:
            from ..sim.machine_model import TpuPodModel

            machine = TpuPodModel()
        # ICI for a same-host handoff, DCN for a store-tier hop
        if fabric_kind == "blob":
            self.hop_bw = float(machine.dcn_bw)
            self.hop_lat = float(machine.dcn_lat)
        else:
            self.hop_bw = float(machine.ici_bw)
            self.hop_lat = float(machine.ici_lat)

    def decide(self, *, prompt_len: int, new_blocks: int,
               page_size: int, block_bytes: int, chunk: int,
               step_s: float) -> Dict:
        """One routing decision; returns the record stored on the
        request ({"decision", "migrate_s", "reprefill_s", ...})."""
        C = max(1, int(chunk))
        step = step_s if step_s > 0 else _DEFAULT_STEP_S
        reprefill_s = math.ceil(prompt_len / C) * step
        tail = prompt_len - (prompt_len // page_size) * page_size
        migrate_s = (self.hop_lat
                     + (block_bytes * new_blocks) / self.hop_bw
                     + math.ceil(tail / C) * step)
        migrate = (new_blocks > 0
                   and migrate_s <= self.cost_cap * reprefill_s)
        return {
            "decision": "migrate" if migrate else "reprefill",
            "new_blocks": int(new_blocks),
            "migrate_s": round(migrate_s, 6),
            "reprefill_s": round(reprefill_s, 6),
        }

    def decide_handoff(self, *, written: int, page_size: int,
                       block_bytes: int, chunk: int,
                       step_s: float) -> Dict:
        """Mid-decode handoff pricing (serving/handoff.py): ship every
        written block — prompt AND generated, including the partial
        tail page — vs replaying the whole written prefix as chunked
        prefill on the destination.  Unlike decide() there is no tail-
        replay term on the migrate side (the verified tail block rides
        the resume record into a private block), but the replay side
        grows with the GENERATED length: the longer a sequence has
        decoded, the more a handoff is worth."""
        C = max(1, int(chunk))
        step = step_s if step_s > 0 else _DEFAULT_STEP_S
        n_blocks = -(-written // page_size) if page_size > 0 else 0
        replay_s = math.ceil(written / C) * step
        handoff_s = (self.hop_lat
                     + (block_bytes * n_blocks) / self.hop_bw
                     + step)  # one adoption pass on the destination
        handoff = (n_blocks > 0
                   and handoff_s <= self.cost_cap * replay_s)
        return {
            "decision": "handoff" if handoff else "replay",
            "blocks": int(n_blocks),
            "handoff_s": round(handoff_s, 6),
            "replay_s": round(replay_s, 6),
        }


class DisaggServingFront(ServingFront):
    """ServingFront whose dispatcher costs every request's handoff.

    The cache-affine pick (decode-capable replicas only — the base
    front's role filter) stays the serving target; _divert_plan then
    decides, under the front lock, whether a prefill-class pass + KV
    migration beats re-prefilling on that target.  A diverted request
    runs max_new=1 on the least-loaded prefill replica, its finished
    block-aligned prefix streams through the migrator into the
    target's pool, and the request requeues at the HEAD of the
    admission queue — cache-affine dispatch then routes it to the
    adopter and its prompt admits as a prefix-cache hit.  Failures at
    ANY stage requeue the same way without the migration, so the
    request re-prefills: the fallback path IS the normal path.
    """

    def __init__(self, model_factory, num_replicas: int = 2, *,
                 fabric: Optional[KVTransferFabric] = None,
                 migration_cost_cap: float = 1.0,
                 machine=None,
                 **kw):
        self.fabric = fabric if fabric is not None else InProcessFabric()
        self.cost_model = MigrationCostModel(
            cost_cap=migration_cost_cap, fabric_kind=self.fabric.kind,
            machine=machine)
        self.migrator = KVMigrator(
            self.fabric, registry=kw.get("registry"),
            logger=kw.get("logger", resilience_logger),
            reqtrace=kw.get("reqtrace"))
        self.migrate_decisions = 0
        self.reprefill_decisions = 0
        self.migrations_ok = 0
        self.migrations_failed = 0
        try:
            super().__init__(model_factory, num_replicas, **kw)
        except BaseException:
            self.migrator.close()
            raise

    # -- routing ---------------------------------------------------------
    def _pick_prefill(self) -> Optional[ServingReplica]:
        """Least-loaded live prefill-class replica with slot headroom;
        None when the prefill class is absent, down, or full — the
        request then just re-prefills on the decode class."""
        best = None
        for r in self.replicas:
            sched = r.scheduler
            if r.role != "prefill" or r.state != "live" or sched is None:
                continue
            if r.outstanding >= sched.model.batch_slots:
                continue
            if best is None or r.outstanding < best.outstanding:
                best = r
        return best

    def _divert_plan(self, req: FrontRequest,
                     replica: ServingReplica) -> Optional[Callable]:
        # one migration attempt per request: a requeued request (post-
        # migration OR post-failure) always dispatches directly
        if req.migration is not None:
            return None
        # a resumed generation never takes the prefill-class detour:
        # its KV state (adopted blocks or the replay feed) belongs on
        # the decode class where it will finish
        if req.resume is not None:
            return None
        if self._terminating or self._closed:
            return None
        prefill_r = self._pick_prefill()
        dsched = replica.scheduler
        if prefill_r is None or dsched is None:
            return None
        psched = prefill_r.scheduler
        if psched is None:
            return None
        # both engines must expose the migration surface (fake models
        # without pools degrade to the colocated behavior)
        if (getattr(psched.model, "export_block", None) is None
                or getattr(dsched.model, "import_block", None) is None):
            return None
        page = dsched.pool.page_size
        plen = len(req.prompt)
        try:
            have = dsched.cached_prefix_tokens(req.prompt)
        except Exception:  # noqa: BLE001 — a probe must never stall
            have = 0       # dispatch
        # blocks the migration would actually ship: the block-aligned
        # prefix minus what the target already caches
        new_blocks = max(0, plen // page - have // page)
        step_ms = dsched.step_ms_ewma or psched.step_ms_ewma
        record = self.cost_model.decide(
            prompt_len=plen, new_blocks=new_blocks, page_size=page,
            block_bytes=int(getattr(dsched.model, "kv_block_bytes", 0)),
            chunk=int(getattr(dsched.model, "prefill_chunk", 0)),
            step_s=step_ms / 1e3)
        req.migration = record
        if req.trace is not None:
            # the priced decision lands on the open dispatch span:
            # trace_analyze and Perfetto show WHY this request migrated
            # (or re-prefilled) next to what it cost
            req.trace.annotate(
                "dispatch", decision=record["decision"],
                new_blocks=record["new_blocks"],
                migrate_s=record["migrate_s"],
                reprefill_s=record["reprefill_s"])
        if record["decision"] != "migrate":
            self.reprefill_decisions += 1
            if self.registry is not None:
                self.registry.counter(
                    "serving/disagg_reprefill_decisions").inc()
            return None  # dispatch normally: re-prefill on `replica`
        self.migrate_decisions += 1
        if self.registry is not None:
            self.registry.counter(
                "serving/disagg_migrate_decisions").inc()
        # book the prefill slot under _cv (we hold it) so concurrent
        # divert decisions see the load; released in _on_prefill_done
        prefill_r.outstanding += 1
        self._observe_depth(prefill_r)
        return lambda: self._begin_migration(req, prefill_r, replica)

    # -- migration pipeline ----------------------------------------------
    def _begin_migration(self, req: FrontRequest,
                         prefill_r: ServingReplica,
                         decode_r: ServingReplica) -> None:
        """Outside the front lock: run the prompt on the prefill
        replica.  max_new=1 — the pass exists to WRITE the prompt's KV
        and index every block boundary, not to generate."""
        if req.trace is not None:
            req.trace.end("dispatch")
            req.trace.begin("migration",
                            prefill_replica=prefill_r.replica_id,
                            decode_replica=decode_r.replica_id)
        try:
            prefill_r.submit(
                req.prompt, 1, 0.0, trace=req.trace,
                on_done=lambda h: self._on_prefill_done(
                    req, prefill_r, decode_r, h))
        except Exception:  # noqa: BLE001 — died between pick and submit
            with self._cv:
                prefill_r.outstanding -= 1
                self._observe_depth(prefill_r)
            self._settle_migration(req, False)

    def _on_prefill_done(self, req: FrontRequest,
                         prefill_r: ServingReplica,
                         decode_r: ServingReplica, handle) -> None:
        """Fires on the PREFILL replica's worker thread, between its
        steps — the only thread allowed to read the donated state, so
        the device->host block export happens here, synchronously,
        before any admission or eviction can reuse the blocks."""
        with self._cv:
            prefill_r.outstanding -= 1
            self._observe_depth(prefill_r)
            self._cv.notify_all()
        now = time.monotonic()
        self._note_class_done("prefill", now)
        psched = prefill_r.scheduler
        if handle.error is not None or psched is None:
            self._settle_migration(req, False)
            return
        try:
            blocks, pages = psched.pool.export_prefix(req.prompt)
            exporter = psched.model.export_block
            if not blocks or exporter is None:
                self._settle_migration(req, False)
                return
            arrays = [exporter(b) for b in blocks]
        except Exception:  # noqa: BLE001 — an export failure is a
            # re-prefill, never a dead prefill worker
            self._settle_migration(req, False)
            return
        dsched = decode_r.scheduler
        if dsched is None:  # target died while we prefilled
            self._settle_migration(req, False)
            return
        # the trace context rides the FFKV frame header (wire dict):
        # the adopting decode replica's kv_adopt span joins this tree
        # as a child of the migration span
        wire = (req.trace.wire(parent=req.trace.open_id("migration"))
                if req.trace is not None else None)
        self.migrator.migrate(
            prompt=req.prompt, pages=pages, blocks=arrays,
            page_size=psched.pool.page_size, target=dsched,
            wire=wire,
            on_done=lambda ok: self._settle_migration(req, ok))

    def _settle_migration(self, req: FrontRequest, ok: bool) -> None:
        """Exactly-once tail of every migration attempt, success or
        failure: record the outcome and requeue the request at the
        admission HEAD (it keeps its seniority; a migration never
        consumes a retry — the request did nothing wrong).  Cache-
        affine dispatch then finds the adopted prefix on the target,
        or re-prefills if nothing (or only a partial prefix) landed."""
        if ok:
            self.migrations_ok += 1
        else:
            self.migrations_failed += 1
        if isinstance(req.migration, dict):
            req.migration["ok"] = bool(ok)
        if req.trace is not None:
            req.trace.end("migration", ok=bool(ok))
            req.trace.begin("queue", requeued=True,
                            post_migration=True)
        with self._cv:
            if self._closed:
                self._fail(req, RuntimeError("ServingFront is closed"))
                return
            self._admission.appendleft(req)
            self._cv.notify_all()

    # -- stats / lifecycle -----------------------------------------------
    def stats(self) -> Dict:
        out = super().stats()
        out["mode"] = "disaggregated"
        out["disagg"] = {
            "migrate_decisions": self.migrate_decisions,
            "reprefill_decisions": self.reprefill_decisions,
            "migrations_ok": self.migrations_ok,
            "migrations_failed": self.migrations_failed,
            "cost_cap": self.cost_model.cost_cap,
            "kv_transfer": self.migrator.stats(),
        }
        return out

    def close(self, timeout_s: Optional[float] = None):
        super().close(timeout_s)
        # after the fleet: every pending migration's on_done has fired
        # (scheduler close settles handles; run_on_worker drops fire
        # on_dropped) or gets failed by the migrator's close drain
        self.migrator.close()


def build_front(ff_train, cfg=None, *, eos_id: int = -1, registry=None,
                fabric: Optional[KVTransferFabric] = None,
                **kw):
    """Config-driven front: a plain ServingFront when --serving-roles
    is empty, a DisaggServingFront (roles + costed migration) when
    set.  The roles spec also sizes the fleet when --serving-replicas
    disagrees (the spec is the more explicit statement)."""
    cfg = cfg if cfg is not None else ff_train.config
    roles = parse_serving_roles(getattr(cfg, "serving_roles", ""))
    if roles is None:
        return ServingFront.from_trained(
            ff_train, eos_id=eos_id, registry=registry, **kw)
    from ..decoding import require_carried

    require_carried(ff_train, "disaggregated",
                    f"--serving-roles {cfg.serving_roles}")
    if fabric is None:
        from .kv_transfer import resolve_kv_transfer

        fabric = resolve_kv_transfer(
            getattr(cfg, "kv_transfer", "inproc") or "inproc",
            root=getattr(cfg, "strategy_store", None) or None)
    return DisaggServingFront.from_trained(
        ff_train, num_replicas=len(roles), eos_id=eos_id,
        registry=registry, roles=roles, fabric=fabric,
        migration_cost_cap=float(getattr(cfg, "migration_cost_cap",
                                         1.0) or 1.0),
        **kw)
