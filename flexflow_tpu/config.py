"""Runtime configuration — TPU-native analogue of the reference FFConfig.

Reference: /root/reference/include/flexflow/config.h:92-160 and the
hand-rolled parse_args at src/runtime/model.cc:3556-3720 (~40 CLI flags:
training -e/-b/--lr/--wd, Legion -ll:* resource flags, search flags,
simulator/machine-model flags, --fusion, control replication).

TPU translation: the Legion resource flags (-ll:gpu/-ll:fsize/-ll:zsize)
become mesh/device-count + HBM-budget settings; NCCL vs PS becomes the
ParameterSyncType hint consumed by the simulator; control replication is
inherent to SPMD.
"""
from __future__ import annotations

import argparse
import dataclasses
from typing import Dict, List, Optional

from .fftype import ParameterSyncType

# single source of truth for the flash-attention crossover (see the
# flash_min_seq field comment); attention ops fall back to this when
# used outside FFModel.compile.  From this key length up the attention
# core runs in Pallas kernels that keep the [s, s] scores in VMEM
# (ops/pallas/flash_attention.py `pick_tiling`: whole-row "one_tile"
# kernels up to 1,024 keys, the online-softmax kernels above).
# Measured on this machine (TPU v5e, jax 0.9.0, libtpu 0.0.34; my chip
# runs, PR 33, scripts/attn_core_probe.py --mode sweep: b 8, h 16, d 64,
# bf16, forward + backward of the core alone, ms a call):
#
#     kv      dense (XLA)   one-tile kernels   long-row kernels
#     128        0.066          0.128              0.201
#     256        0.131          0.242              0.360
#     512        0.923          0.524              0.655
#     1,024      5.355          1.643              2.076
#
# XLA keeps the scores of a short row on the chip by itself; from 512
# keys on they go through HBM (14 score-sized passes a layer) and the
# kernels win: the crossover lies between 256 and 512.  Inside
# BERT-large's seq-512 step the dense core costs ~24 ms of 75.9, the
# long-row kernels with their four transposes a layer ~16 (PERF.md §6,
# PR 33).  The long-row kernels at >= 2,048 keys have no time on this
# machine yet; docs/flash_ceiling_r5.json holds an older machine's
# (jax 0.4.x: 18.8 % dense utilisation at seq 2,048), kept as history.
DEFAULT_FLASH_MIN_SEQ = 512

# valid FFConfig.nan_policy values (consumed by the resilience
# supervisor's step-health handling, resilience/supervisor.py).
# "off" disables the per-step health check: check_step_health returns
# without touching the device value, so callers that don't otherwise
# consume the loss pay no sync for it (the supervisor itself still
# syncs once per step to record the loss in its report).
NAN_POLICIES = ("raise", "skip_step", "restore", "off")

# valid FFConfig.serving_mode values (serving/, docs/SERVING.md):
# "continuous" = iteration-level batching on the paged KV pool
# (serving/scheduler.py); "static" = the whole-scan GenerationBatcher
# fallback (one program per coalesced batch, dense per-slot caches).
SERVING_MODES = ("continuous", "static")

# valid FFConfig.kv_transfer values (serving/kv_transfer.py): the
# fabric a disaggregated fleet streams KV blocks over — "inproc" =
# same-host handoff, "blob" = store-tier hop (store/blobstore.py).
KV_TRANSFER_FABRICS = ("inproc", "blob")

# valid FFConfig.spec_decode values (docs/SERVING.md "Speculative
# decoding"): "off" = one dispatch per generated token; "ngram" =
# prompt-lookup drafter mining the request's own tokens; "draft" = a
# smaller GPT from the same builder drafting through its own paged
# decode engine.  Both verify through the chunk-twin program and
# accept greedily, so output stays token-identical to "off".
SPEC_DECODE_MODES = ("off", "ngram", "draft")


class ConfigError(ValueError):
    """A configuration that can never run in this build/runtime —
    raised at BUILD time with the fix spelled out, so a bad flag never
    surfaces as a deep ImportError mid-compile."""


def resolve_serving_tp(
    tp: int,
    num_heads: Optional[int] = None,
    visible_devices: Optional[int] = None,
) -> int:
    """Validate a replica's tensor-parallel degree at BUILD time
    (docs/SERVING.md "Tensor-parallel replicas").  A tp that cannot
    shard the model raises ConfigError here, with the fix spelled out —
    never a shape error from inside a GSPMD trace.  Returns the
    validated degree."""
    tp = int(tp)
    if tp < 1:
        raise ConfigError(
            f"--serving-tp must be >= 1 (1 = single-chip replica), "
            f"got {tp}")
    if num_heads is not None and num_heads % tp != 0:
        raise ConfigError(
            f"--serving-tp {tp} does not divide the attention head "
            f"count ({num_heads}) — the KV pool shards the head axis "
            f"over the 'model' mesh axis, so tp must divide num_heads "
            f"(try one of "
            f"{[d for d in range(1, num_heads + 1) if num_heads % d == 0]})")
    if visible_devices is None and tp > 1:
        try:
            import jax

            visible_devices = len(jax.devices())
        except Exception:
            visible_devices = None
    if visible_devices is not None and tp > visible_devices:
        raise ConfigError(
            f"--serving-tp {tp} exceeds the {visible_devices} visible "
            f"device(s) — a replica's mesh spans tp chips, so tp must "
            f"be <= the device count available to it")
    return tp


def resolve_spec_decode(
    spec_decode: str,
    spec_k: int,
    beam_size: int = 1,
) -> str:
    """Validate a speculative-decoding configuration at BUILD time
    (docs/SERVING.md "Speculative decoding").  Returns the validated
    mode.  Speculation verifies drafts by accepting the longest
    GREEDY-matching prefix, which is only meaningful for single-path
    decoding — a beam consumer (gpt_beam_search_cached keeps multiple
    live hypotheses per step) must pass its beam_size here so the
    incompatible combination raises ConfigError with the fix spelled
    out instead of silently decoding the wrong thing."""
    if spec_decode not in SPEC_DECODE_MODES:
        raise ConfigError(
            f"--spec-decode must be one of {SPEC_DECODE_MODES}, "
            f"got {spec_decode!r}")
    if spec_decode != "off":
        if int(spec_k) < 1:
            raise ConfigError(
                f"--spec-k must be >= 1 when --spec-decode is "
                f"{spec_decode!r}, got {spec_k}")
        if int(beam_size) > 1:
            raise ConfigError(
                f"--spec-decode {spec_decode!r} cannot be combined "
                f"with beam search (beam_size={beam_size}): "
                f"verification accepts the longest greedy-matching "
                f"draft prefix, which has no analogue across beam "
                f"hypotheses — use --spec-decode off for beam decoding")
    return spec_decode


@dataclasses.dataclass
class FFConfig:
    # -- training (reference: -e, -b, --lr, --wd, parse_args model.cc:3560-3600)
    epochs: int = 1
    batch_size: int = 64
    learning_rate: float = 0.01
    weight_decay: float = 1e-4
    seed: int = 0

    # -- machine resources (reference: -ll:gpu/-ll:cpu/-ll:fsize/-ll:zsize)
    num_devices: int = -1  # -1 = all visible jax devices
    num_nodes: int = 1
    memory_per_device: int = 16 * 1024**3  # HBM budget (reference fsize, MB→bytes)

    # -- strategy search (reference: --budget/--alpha/--enable-*-parallel/
    #    --only-data-parallel/--search-num-nodes/--substitution-json/--memory-search)
    search_budget: int = 0
    search_alpha: float = 0.05
    search_algo: str = "unity"  # "unity" (default, OSDI'22 path) | "mcmc" (SysML'19 legacy)
    # MCMC propagate move (reference FF_USE_PROPAGATE, model.cc:3180-
    # 3258): a rewrite may spread to structurally identical ops — big
    # convergence win on deep nets with repeated layers
    search_propagate: bool = True
    # incremental strategy evaluation (pcg/evaluator.py): memoize
    # revisited candidates and delta-simulate single-op moves instead of
    # re-simulating the whole graph.  Off = the always-full-eval path
    # (delta_eval == full_eval is a tested invariant, so this is a
    # debugging escape hatch, not a correctness knob).
    search_eval_cache: bool = True
    # rewrite enumeration breadth in the Unity search: how many rewrite
    # steps deep and how many graph variants per subproblem.  The
    # defaults keep default-config searches cheap; raise them when
    # hunting catalog wins (scripts/inception_taso_ab.py uses 3/16)
    rewrite_depth: int = 2
    rewrite_max_variants: int = 8
    only_data_parallel: bool = False
    enable_parameter_parallel: bool = False
    enable_attribute_parallel: bool = False
    # partition a non-batch sample dim across a 'sample' mesh axis
    # (reference config.h:134); consumed by UnitySearch._sample_candidates
    enable_sample_parallel: bool = False
    # NOTE: the reference's --enable-inplace-optimizations
    # (model.cc:2884-2919, in-place relu buffers) has no analogue here:
    # XLA buffer assignment + donated weight/opt-state buffers subsume it
    # entirely, so the flag is intentionally NOT carried.
    # credit gradient sync as mostly hidden behind remaining backward
    # compute in search costing (reference config.h:130)
    search_overlap_backward_update: bool = False
    # TASO catalog (JSON or binary .pb, auto-detected).  None = default-
    # on: resolve via rewrite.default_substitution_catalog() ($env,
    # then the in-repo substitutions/ dir); ""/"none" = explicitly off.
    substitution_json: Optional[str] = None
    # calibrate search costs by timing real jitted kernels on the chip
    # (reference inner_measure_operator_cost, model.cu:38-75).
    # None = auto: on when a real TPU backend is present, off on CPU
    # meshes (where the analytic roofline is the right proxy).
    search_calibrate: Optional[bool] = None
    # measured (node_key -> seconds) cache persisted across runs
    op_cost_cache_file: Optional[str] = None
    memory_search: bool = False
    memory_lambda: float = 1.0
    export_strategy_file: Optional[str] = None
    import_strategy_file: Optional[str] = None
    # persistent strategy + compile artifact store (store/,
    # docs/STORE.md): searched strategies keyed by (graph signature,
    # mesh fingerprint, simulator version) survive the process, so a
    # preempted worker, an elastic re-search on a degraded mesh, or a
    # new serving replica restores instead of re-searching.  None =
    # fall through to $FLEXFLOW_TPU_STORE_DIR (fleet deployments);
    # ""/"none" = explicitly off (the substitution_json pattern).
    strategy_store: Optional[str] = None
    # JAX persistent compilation cache dir so the compiled step
    # function itself survives process death: a path, or "auto" =
    # <strategy store root>/xla_cache.  None = <checkout>/.jax_cache
    # on an accelerator backend, off on CPU.  $JAX_COMPILATION_CACHE_DIR
    # overrides all of these (store.enable_compilation_cache).
    compilation_cache: Optional[str] = None

    # -- simulator / machine model (reference: --machine-model-version/-file,
    #    --simulator-segment-size)
    machine_model_version: int = 0
    machine_model_file: Optional[str] = None
    # -- multi-slice topology (topology/, docs/TOPOLOGY.md): slices > 1
    #    models a pod of identical slices — fast ICI inside each slice,
    #    slow DCN between.  The machine model becomes a SliceHierarchy,
    #    *placement* (which mesh axis spans the DCN boundary) becomes a
    #    searched strategy dimension, and the executor lowers the
    #    cross-slice grad reduction to the hierarchical form on a
    #    two-level mesh.  1 slice (the default) is exactly the flat
    #    pre-topology behavior — same costs, and the slice/DCN knobs
    #    never enter a flat run's store key.
    slices: int = 1
    dcn_bandwidth: float = 25e9   # bytes/s per host across slices
    dcn_latency: float = 10e-6    # seconds per cross-slice hop
    # per-slice ICI torus shape, e.g. "4x4" or "2,2,2"; None = a 1-D
    # ring of num_devices/slices chips
    slice_topology: Optional[str] = None
    # DCN grad-sync coalescing bucket (MB): the cost model amortizes a
    # weight leaf's DCN all-reduce LATENCY term over the fraction of a
    # bucket its DCN-leg bytes fill (real runtimes coalesce grad
    # all-reduces into ~25MB buckets), so many-leaf models stop paying
    # the per-leaf DCN launch latency on dp-crossing placements.
    # Bandwidth/byte terms are untouched.  Only consulted on
    # multi-slice (SliceHierarchy) machines — flat runs have no DCN leg
    # and their store keys carry no bucket field.
    dcn_bucket_mb: float = 25.0
    # bounds per-region search enumeration (its reference role: cap
    # per-segment simulation work); can only lower the built-in cap
    simulator_segment_size: int = 16777216

    # -- execution
    # ZeRO ladder stage (docs/PERF.md "The ZeRO ladder"; ZeRO-1 is
    # Xu et al. arXiv:2004.13336, stages 2-3 are Rajbhandari et al.
    # arXiv:1910.02054):
    #   0 = replicated update (every replica runs the full optimizer
    #       pass and keeps full grads/slots/master weights);
    #   1 = sharded update: reduce-scatter grads along `wus_axis`, run
    #       the update on the 1/N shard where the slots permanently
    #       live, all-gather the updated weights back (slot HBM / N);
    #   2 = stage 1 + gradients stay reduce-scattered THROUGH the
    #       update — the per-device gradient buffer is the 1/N shard
    #       (grad HBM / N);
    #   3 = stage 2 + master weights live permanently sharded along
    #       `wus_axis` with just-in-time per-layer all-gather on use
    #       and double-buffered prefetch (FSDP: weight-resident
    #       HBM / N, per-layer all-gather traffic).
    # Every stage is numerically equivalent to stage 0 and is a costed
    # simulator mode (sim/simulator.py zero_stage); with
    # --memory-search the searches CHOOSE the stage per model
    # (pcg/mcmc.py search_stage_candidates).
    zero_stage: int = 0
    # DEPRECATED alias for zero_stage=1 (the pre-ladder knob): True
    # maps to stage 1 in __post_init__; after init it always mirrors
    # `zero_stage >= 1` so existing consumers keep working.
    weight_update_sharding: bool = False
    wus_axis: str = "data"  # mesh axis the update shards over
    # reference --fusion (apply_fusion model.cc:2495): fold trailing
    # activations into producers at compile; XLA fuses kernels anyway,
    # this shrinks the PCG/search space
    perform_fusion: bool = False
    # rematerialise segment internals in backward (jax.checkpoint at
    # single-tensor-boundary cuts): trades recompute FLOPs for HBM —
    # a TPU-native capability the reference cannot express
    remat: bool = False
    profiling: bool = False
    # gradient-sync cost model: ALL_REDUCE rings vs PS flat 2*size/BW
    # (reference ParameterSyncType config.h:55-59, simulator.cc:786-813)
    parameter_sync: ParameterSyncType = ParameterSyncType.ALL_REDUCE
    compute_dtype: str = "float32"  # bf16 on TPU for perf runs
    # use the Pallas flash-attention kernel only at KV length >= this;
    # 0 forces flash everywhere (see DEFAULT_FLASH_MIN_SEQ above)
    flash_min_seq: int = DEFAULT_FLASH_MIN_SEQ

    # -- exports (reference: --taskgraph/--compgraph/--include-costs-dot-graph)
    export_taskgraph_file: Optional[str] = None
    export_compgraph_file: Optional[str] = None
    include_costs_dot_graph: bool = False

    # -- resilience (resilience/supervisor.py): checkpoint cadence,
    #    restart budget, retry backoff, and non-finite-loss policy.
    #    The reference has no analogue — it leans on Legion for fault
    #    handling; these knobs drive the TPU-native supervisor.
    checkpoint_every: int = 0  # steps between periodic checkpoints; 0 = off
    checkpoint_dir: Optional[str] = None
    checkpoint_keep: int = 3   # keep-last-k retention
    # async verified saves: the step boundary stalls only for the
    # device->host snapshot; serialize/fsync/verify/publish run on a
    # background writer (checkpoint.py + resilience/async_writer.py)
    checkpoint_async: bool = False
    # hung-step watchdog: per-step device sync deadline in seconds
    # (resilience/watchdog.py); 0 disables the watchdog entirely
    step_timeout: float = 0.0
    # SIGTERM/SIGINT preemption grace: emergency checkpoint at the next
    # step boundary instead of dying checkpoint-less
    preempt_grace: bool = True
    max_restarts: int = 3      # restore-and-retry budget per run
    retry_backoff: float = 0.1  # base backoff seconds (exponential, jittered)
    nan_policy: str = "raise"  # raise | skip_step | restore | off
    # -- durable offload tier (resilience/offload.py, store/blobstore.py;
    #    docs/RESILIENCE.md "Durable offload & host-loss recovery"):
    #    mirror every verified local checkpoint — and the strategy
    #    store — to an object store so a FULL HOST LOSS keeps a restore
    #    target.  URI: file:///path or a bare path (filesystem backend;
    #    an NFS mount used this way is a production deployment);
    #    gs://... names the cloud backend once its SDK is provisioned.
    #    None/"none" = offload off (single-tier, pre-PR-9 behavior).
    remote_store: Optional[str] = None
    offload_every: int = 1   # mirror every Nth verified local checkpoint
    remote_keep: int = 3     # keep-last-k retention in the remote tier
    # how long a preempted worker waits for its peers' barrier posts
    # before committing the best agreement so far — size it WELL below
    # the platform's preemption grace window, since the emergency save
    # only starts after the rendezvous returns
    barrier_timeout: float = 30.0

    # -- observability (obs/, docs/OBSERVABILITY.md).  trace_dir turns
    #    on the full telemetry pipeline and names where the artifacts
    #    land (trace.json Chrome trace + run_telemetry.jsonl metrics);
    #    telemetry=True captures logs, the fidelity record and request
    #    traces in memory without writing files (drain via
    #    FFModel.telemetry).  Host spans (obs.trace.span) are recorded
    #    whatever these say: no field turns them on or off.
    trace_dir: Optional[str] = None
    telemetry: bool = False
    # jax.profiler.trace device capture around a step window,
    # "start:count" (e.g. "3:2" profiles steps 3 and 4); needs trace_dir
    profile_steps: Optional[str] = None
    # per-request serving trace sampling probability
    # (obs/reqtrace.py, docs/OBSERVABILITY.md "Request tracing"):
    # 1.0 traces every admitted request (tests/smoke), load tests and
    # production runs rate-limit by sampling down; 0.0 disables request
    # tracing even with telemetry on
    trace_sample: float = 1.0

    # -- serving (serving/, docs/SERVING.md): generation tier mode and
    #    paged KV-cache pool geometry.  Consumed by the serving entry
    #    points (examples/serve_gpt.py, bench serving leg) — training
    #    never reads these.
    serving_mode: str = "continuous"  # continuous | static (fallback)
    kv_page_size: int = 16     # tokens per KV block (must divide max_seq)
    kv_pool_blocks: int = 0    # physical blocks incl. scratch; 0 = auto
    serving_slots: int = 8     # continuous decode batch slots
    # prefix cache & chunked prefill (docs/SERVING.md "Prefix cache &
    # chunked prefill"): copy-on-write sharing of block-aligned prompt
    # prefixes in the KV pool, and a second [slots, C] compiled step
    # that prefills C prompt tokens per dispatch (0/1 = one-token
    # prefill, the PR 6 path).  Both preserve greedy token-identity.
    prefill_chunk: int = 8
    prefix_cache: bool = True
    # replicated front (serving/front.py, docs/SERVING.md "Replicated
    # front"): N supervised ContinuousScheduler replicas behind one
    # admission queue.  1 = single supervised replica (still gains the
    # watchdog + restart supervision); the decode-step watchdog is off
    # at 0 like the training step_timeout.
    serving_replicas: int = 1
    serving_step_timeout: float = 0.0  # decode-step watchdog deadline, s
    serving_max_restarts: int = 3      # per-replica restart budget
    request_retry_limit: int = 2       # requeues before a 503 retriable
    # SLO-driven autoscaling (serving/autoscaler.py, docs/SERVING.md
    # "Autoscaling & drain lifecycle"): the fleet sizes itself between
    # [min, max] from the queue-depth / p99-TTFT / KV-occupancy gauges;
    # scale-down DRAINS (graceful, token-identical) instead of killing.
    # max = 0 leaves autoscaling off (static --serving-replicas fleet).
    serving_min_replicas: int = 1
    serving_max_replicas: int = 0
    autoscale_interval: float = 1.0    # control-loop tick period, s
    autoscale_cooldown: float = 5.0    # hold-off after any scale action
    serving_slo_ttft: float = 0.0      # p99 TTFT target, s (0 = ignore)
    serving_drain_timeout: float = 30.0  # wedged-drain force bound, s
    # overload admission control: shed at admission when predicted TTFT
    # (backlog / measured service rate) exceeds this many seconds
    # (0 = off; per-request deadline_s overrides)
    admission_deadline_s: float = 0.0
    # tensor-parallel degree of ONE serving replica (docs/SERVING.md
    # "Tensor-parallel replicas"): each replica spans tp chips under
    # GSPMD — attention heads and the paged KV block pools shard over a
    # 'model' mesh axis, so per-chip KV bytes are 1/tp and a replica
    # can hold a model bigger than one chip.  Must divide the head
    # count and fit the visible devices (resolve_serving_tp validates
    # at build time).  1 = single-chip replicas (prior behavior).
    serving_tp: int = 1
    # total chips the serving fleet may hold (0 = unbounded): the front
    # refuses an add_replica that would push
    # len(replicas) * serving_tp past the budget, and the autoscaler
    # counts the refusal as a spawn failure instead of flapping
    serving_chip_budget: int = 0
    # disaggregated prefill/decode fleet (serving/disagg.py,
    # docs/SERVING.md "Disaggregated fleet"): per-replica role spec
    # "prefill=N,decode=M[,mixed=K]" — counts must include at least one
    # decode-capable replica; "" = colocated fleet (every replica
    # mixed, the prior behavior).  Validated by parse_serving_roles.
    serving_roles: str = ""
    # KV block streaming fabric between replicas: "inproc" (same-host
    # handoff) or "blob" (store tier hop — inherits the blob fault
    # matrix, so torn streams degrade to re-prefill)
    kv_transfer: str = "inproc"
    # migrate iff migrate_time <= cap * reprefill_time (the dispatcher
    # costs each handoff with the topology interconnect terms); lower
    # caps migrate less, must be > 0
    migration_cost_cap: float = 1.0
    # predictive autoscaling: project the admission queue forward from
    # the measured admission-rate slope and scale BEFORE the reactive
    # queue threshold breaches (serving/autoscaler.py)
    autoscale_predictive: bool = False
    # speculative decoding (serving/speculative.py, docs/SERVING.md
    # "Speculative decoding"): propose up to spec_k draft tokens per
    # eligible slot per round and verify them in ONE chunk-twin
    # dispatch, accepting the longest greedy-matching prefix plus the
    # first corrected token — token-identical to "off" by
    # construction.  "ngram" mines the request's own prompt+generated
    # tokens (no second model); "draft" runs a smaller GPT through its
    # own paged decode engine (needs a draft model at engine build).
    # Acceptance-rate-adaptive k shrinks toward 1 when drafts miss, so
    # the feature is never worse than one-token decode.
    spec_decode: str = "off"
    spec_k: int = 4
    # resumable mid-decode handoff (serving/handoff.py, docs/SERVING.md
    # "Mid-decode handoff"): with the flag on, a DRAINING / terminating
    # / rebalanced replica pauses its in-flight generations (resume
    # record + optional live KV-block stream) and the front resumes
    # them on a surviving replica, token-identically.  Off keeps the
    # classic drain semantics (every slot runs to completion).
    serving_handoff: bool = False
    # hot-replica rebalance threshold: a live replica whose KV-pool
    # occupancy exceeds this fraction (while a peer sits below half of
    # it) hands one generation off via the autoscaler's tick.  0 = off;
    # needs --serving-handoff.
    serving_rebalance_kv: float = 0.0

    def __post_init__(self):
        if self.serving_mode not in SERVING_MODES:
            raise ValueError(
                f"serving_mode must be one of {SERVING_MODES}, "
                f"got {self.serving_mode!r}"
            )
        if self.kv_page_size < 1:
            raise ValueError(
                f"kv_page_size must be >= 1, got {self.kv_page_size}"
            )
        if self.kv_pool_blocks < 0:
            raise ValueError(
                f"kv_pool_blocks must be >= 0 (0 = auto), "
                f"got {self.kv_pool_blocks}"
            )
        if self.serving_slots < 1:
            raise ValueError(
                f"serving_slots must be >= 1, got {self.serving_slots}"
            )
        if self.prefill_chunk < 0:
            raise ValueError(
                f"prefill_chunk must be >= 0 (0 = one-token prefill), "
                f"got {self.prefill_chunk}"
            )
        if self.serving_replicas < 1:
            raise ValueError(
                f"serving_replicas must be >= 1, got {self.serving_replicas}"
            )
        if self.serving_step_timeout < 0:
            raise ValueError(
                f"serving_step_timeout must be >= 0 (0 = watchdog off), "
                f"got {self.serving_step_timeout}"
            )
        if self.serving_max_restarts < 0:
            raise ValueError(
                f"serving_max_restarts must be >= 0, "
                f"got {self.serving_max_restarts}"
            )
        if self.request_retry_limit < 0:
            raise ValueError(
                f"request_retry_limit must be >= 0, "
                f"got {self.request_retry_limit}"
            )
        if self.serving_min_replicas < 1:
            raise ValueError(
                f"serving_min_replicas must be >= 1, "
                f"got {self.serving_min_replicas}"
            )
        if (self.serving_max_replicas != 0
                and self.serving_max_replicas < self.serving_min_replicas):
            raise ValueError(
                f"serving_max_replicas ({self.serving_max_replicas}) must "
                f"be 0 (autoscaling off) or >= serving_min_replicas "
                f"({self.serving_min_replicas})"
            )
        if self.autoscale_interval <= 0:
            raise ValueError(
                f"autoscale_interval must be > 0, "
                f"got {self.autoscale_interval}"
            )
        if self.autoscale_cooldown < 0:
            raise ValueError(
                f"autoscale_cooldown must be >= 0, "
                f"got {self.autoscale_cooldown}"
            )
        if self.serving_slo_ttft < 0:
            raise ValueError(
                f"serving_slo_ttft must be >= 0 (0 = ignore), "
                f"got {self.serving_slo_ttft}"
            )
        if self.serving_drain_timeout <= 0:
            raise ValueError(
                f"serving_drain_timeout must be > 0, "
                f"got {self.serving_drain_timeout}"
            )
        if self.admission_deadline_s < 0:
            raise ValueError(
                f"admission_deadline_s must be >= 0 (0 = off), "
                f"got {self.admission_deadline_s}"
            )
        if self.serving_tp < 1:
            raise ValueError(
                f"serving_tp must be >= 1 (1 = single-chip replicas), "
                f"got {self.serving_tp}"
            )
        if self.serving_chip_budget < 0:
            raise ValueError(
                f"serving_chip_budget must be >= 0 (0 = unbounded), "
                f"got {self.serving_chip_budget}"
            )
        if self.serving_roles:
            # full spec validation (role names, counts, decode-capable
            # floor) lives with the parser the front consumes
            from .serving.disagg import parse_serving_roles

            parse_serving_roles(self.serving_roles)
        if self.kv_transfer not in KV_TRANSFER_FABRICS:
            raise ValueError(
                f"kv_transfer must be one of {KV_TRANSFER_FABRICS}, "
                f"got {self.kv_transfer!r}"
            )
        if self.migration_cost_cap <= 0:
            raise ValueError(
                f"migration_cost_cap must be > 0, "
                f"got {self.migration_cost_cap}"
            )
        if self.spec_decode not in SPEC_DECODE_MODES:
            raise ValueError(
                f"spec_decode must be one of {SPEC_DECODE_MODES}, "
                f"got {self.spec_decode!r}"
            )
        if self.spec_k < 1:
            raise ValueError(
                f"spec_k must be >= 1, got {self.spec_k}"
            )
        if not 0.0 <= self.serving_rebalance_kv < 1.0:
            raise ValueError(
                f"serving_rebalance_kv must be in [0, 1) (occupancy "
                f"fraction; 0 = off), got {self.serving_rebalance_kv}"
            )
        if self.serving_rebalance_kv > 0 and not self.serving_handoff:
            raise ValueError(
                "serving_rebalance_kv needs --serving-handoff: the "
                "rebalance trigger pauses generations onto the "
                "handoff path"
            )
        if not 0.0 <= self.trace_sample <= 1.0:
            raise ValueError(
                f"trace_sample must be in [0.0, 1.0], got "
                f"{self.trace_sample}"
            )
        if self.nan_policy not in NAN_POLICIES:
            raise ValueError(
                f"nan_policy must be one of {NAN_POLICIES}, "
                f"got {self.nan_policy!r}"
            )
        if self.checkpoint_every < 0:
            raise ValueError(
                f"checkpoint_every must be >= 0, got {self.checkpoint_every}"
            )
        if self.checkpoint_keep < 1:
            raise ValueError(
                f"checkpoint_keep must be >= 1, got {self.checkpoint_keep}"
            )
        if self.max_restarts < 0:
            raise ValueError(
                f"max_restarts must be >= 0, got {self.max_restarts}"
            )
        if self.retry_backoff < 0:
            raise ValueError(
                f"retry_backoff must be >= 0, got {self.retry_backoff}"
            )
        if self.step_timeout < 0:
            raise ValueError(
                f"step_timeout must be >= 0 (0 = watchdog off), "
                f"got {self.step_timeout}"
            )
        if self.offload_every < 1:
            raise ValueError(
                f"offload_every must be >= 1, got {self.offload_every}"
            )
        if self.remote_keep < 1:
            raise ValueError(
                f"remote_keep must be >= 1, got {self.remote_keep}"
            )
        if self.barrier_timeout <= 0:
            raise ValueError(
                f"barrier_timeout must be > 0, got {self.barrier_timeout}"
            )
        if self.slices < 1:
            raise ValueError(f"slices must be >= 1, got {self.slices}")
        if self.dcn_bandwidth <= 0:
            raise ValueError(
                f"dcn_bandwidth must be > 0 bytes/s, got {self.dcn_bandwidth}"
            )
        if self.dcn_latency < 0:
            raise ValueError(
                f"dcn_latency must be >= 0 seconds, got {self.dcn_latency}"
            )
        if self.slice_topology is not None:
            from .topology.hierarchy import parse_slice_topology

            parse_slice_topology(self.slice_topology)  # raises on bad spec
        if self.dcn_bucket_mb <= 0:
            raise ValueError(
                f"dcn_bucket_mb must be > 0 MB, got {self.dcn_bucket_mb}"
            )
        if self.zero_stage not in (0, 1, 2, 3):
            raise ValueError(
                f"zero_stage must be one of (0, 1, 2, 3), "
                f"got {self.zero_stage!r}"
            )
        # deprecation shim: the pre-ladder --weight-update-sharding
        # flag is exactly stage 1; after normalization the bool always
        # mirrors the stage so old consumers stay correct
        if self.weight_update_sharding and self.zero_stage == 0:
            self.zero_stage = 1
        self.weight_update_sharding = self.zero_stage >= 1
        if not self.wus_axis:
            raise ValueError("wus_axis must be a non-empty mesh axis name")
        if self.compilation_cache is not None and not str(
            self.compilation_cache
        ).strip():
            raise ValueError(
                "compilation_cache must be a directory path or 'auto' "
                "(None disables it)"
            )
        if self.profile_steps is not None:
            from .obs import parse_profile_steps

            parse_profile_steps(self.profile_steps)  # raises on bad spec
            if not self.trace_dir:
                raise ValueError(
                    "profile_steps needs trace_dir set (the jax profiler "
                    "capture is written under it)"
                )

    def resolve_store_dir(self) -> Optional[str]:
        """Effective strategy-store root (None = store off); resolution
        rules live with the store (store.resolve_store_dir)."""
        from .store import resolve_store_dir

        return resolve_store_dir(self)

    def should_calibrate(self) -> bool:
        """Resolve search_calibrate's auto mode: measured costs when a
        real accelerator backend is live, analytic roofline otherwise."""
        if self.search_calibrate is not None:
            return self.search_calibrate
        import jax

        return jax.default_backend() != "cpu"

    def resolve_num_devices(self) -> int:
        if self.num_devices > 0:
            return self.num_devices
        import jax

        return len(jax.devices())

    @property
    def workers_per_node(self) -> int:
        return max(1, self.resolve_num_devices() // max(1, self.num_nodes))

    # ------------------------------------------------------------------
    @classmethod
    def from_args(cls, argv: Optional[List[str]] = None) -> "FFConfig":
        """Parse the reference's CLI flag set (model.cc:3556-3720 names kept)."""
        p = argparse.ArgumentParser(add_help=False)
        p.add_argument("-e", "--epochs", type=int, default=1)
        p.add_argument("-b", "--batch-size", type=int, default=64)
        p.add_argument("--lr", "--learning-rate", dest="lr", type=float, default=0.01)
        p.add_argument("--wd", "--weight-decay", dest="wd", type=float, default=1e-4)
        p.add_argument("--seed", type=int, default=0)
        p.add_argument("-ll:gpu", "--num-devices", dest="num_devices", type=int, default=-1)
        p.add_argument("--nodes", type=int, default=1)
        p.add_argument("-ll:fsize", dest="fsize_mb", type=int, default=16384)
        p.add_argument("--budget", "--search-budget", dest="budget", type=int, default=0)
        p.add_argument("--alpha", "--search-alpha", dest="alpha", type=float, default=0.05)
        p.add_argument("--no-propagate", dest="search_propagate",
                       action="store_false", default=True)
        p.add_argument("--no-search-eval-cache", dest="search_eval_cache",
                       action="store_false", default=True)
        p.add_argument("--search-algo", dest="search_algo", type=str, default="unity",
                       choices=("unity", "mcmc"))
        p.add_argument("--only-data-parallel", action="store_true")
        p.add_argument("--enable-parameter-parallel", action="store_true")
        p.add_argument("--enable-attribute-parallel", action="store_true")
        p.add_argument("--enable-sample-parallel", action="store_true")
        p.add_argument("--search-overlap-backward-update", "--overlap",
                       dest="overlap_backward_update", action="store_true")
        p.add_argument("--parameter-sync", dest="parameter_sync", type=str,
                       default="all_reduce", choices=("none", "ps", "all_reduce"))
        p.add_argument("--substitution-json", type=str, default=None)
        p.add_argument("--rewrite-depth", type=int, default=2)
        p.add_argument("--rewrite-max-variants", type=int, default=8)
        p.add_argument("--search-calibrate", dest="search_calibrate",
                       action="store_true", default=None)
        p.add_argument("--no-search-calibrate", dest="search_calibrate",
                       action="store_false")
        p.add_argument("--op-cost-cache", dest="op_cost_cache", type=str,
                       default=None)
        p.add_argument("--memory-search", action="store_true")
        p.add_argument("--machine-model-version", type=int, default=0)
        p.add_argument("--machine-model-file", type=str, default=None)
        p.add_argument("--simulator-segment-size", type=int, default=16777216)
        p.add_argument("--slices", dest="slices", type=int, default=1)
        p.add_argument("--dcn-bandwidth", dest="dcn_bandwidth", type=float,
                       default=25e9)
        p.add_argument("--dcn-latency", dest="dcn_latency", type=float,
                       default=10e-6)
        p.add_argument("--slice-topology", dest="slice_topology", type=str,
                       default=None)
        p.add_argument("--dcn-bucket-mb", dest="dcn_bucket_mb", type=float,
                       default=25.0)
        # default None so an EXPLICIT --zero-stage 0 is distinguishable
        # from the default: the explicit stage wins over the deprecated
        # flag below (including 0), the shim only fills the default
        p.add_argument("--zero-stage", dest="zero_stage", type=int,
                       default=None, choices=(0, 1, 2, 3))
        # deprecated: equivalent to --zero-stage 1 (shim in __post_init__)
        p.add_argument("--weight-update-sharding", dest="weight_update_sharding",
                       action="store_true")
        p.add_argument("--wus-axis", dest="wus_axis", type=str, default="data")
        p.add_argument("--fusion", action="store_true")
        p.add_argument("--remat", action="store_true")
        p.add_argument("--profiling", action="store_true")
        p.add_argument("--flash-min-seq", dest="flash_min_seq", type=int,
                       default=DEFAULT_FLASH_MIN_SEQ)
        p.add_argument("--export-strategy", dest="export_strategy", type=str, default=None)
        p.add_argument("--import-strategy", dest="import_strategy", type=str, default=None)
        p.add_argument("--strategy-store", dest="strategy_store", type=str,
                       default=None)
        p.add_argument("--no-strategy-store", dest="strategy_store",
                       action="store_const", const="none")
        p.add_argument("--compilation-cache", dest="compilation_cache",
                       type=str, nargs="?", const="auto", default=None)
        p.add_argument("--taskgraph", type=str, default=None)
        p.add_argument("--compgraph", type=str, default=None)
        p.add_argument("--include-costs-dot-graph", action="store_true")
        p.add_argument("--checkpoint-every", dest="checkpoint_every",
                       type=int, default=0)
        p.add_argument("--checkpoint-dir", dest="checkpoint_dir", type=str,
                       default=None)
        p.add_argument("--checkpoint-keep", dest="checkpoint_keep", type=int,
                       default=3)
        p.add_argument("--checkpoint-async", dest="checkpoint_async",
                       action="store_true")
        p.add_argument("--step-timeout", dest="step_timeout", type=float,
                       default=0.0)
        p.add_argument("--no-preempt-grace", dest="preempt_grace",
                       action="store_false", default=True)
        p.add_argument("--max-restarts", dest="max_restarts", type=int,
                       default=3)
        p.add_argument("--retry-backoff", dest="retry_backoff", type=float,
                       default=0.1)
        p.add_argument("--nan-policy", dest="nan_policy", type=str,
                       default="raise", choices=NAN_POLICIES)
        p.add_argument("--remote-store", dest="remote_store", type=str,
                       default=None)
        p.add_argument("--no-remote-store", dest="remote_store",
                       action="store_const", const="none")
        p.add_argument("--offload-every", dest="offload_every", type=int,
                       default=1)
        p.add_argument("--remote-keep", dest="remote_keep", type=int,
                       default=3)
        p.add_argument("--barrier-timeout", dest="barrier_timeout",
                       type=float, default=30.0)
        p.add_argument("--trace-dir", dest="trace_dir", type=str, default=None)
        p.add_argument("--telemetry", dest="telemetry", action="store_true")
        p.add_argument("--profile-steps", dest="profile_steps", type=str,
                       default=None)
        p.add_argument("--trace-sample", dest="trace_sample", type=float,
                       default=1.0)
        p.add_argument("--serving-mode", dest="serving_mode", type=str,
                       default="continuous", choices=SERVING_MODES)
        p.add_argument("--kv-page-size", dest="kv_page_size", type=int,
                       default=16)
        p.add_argument("--kv-pool-blocks", dest="kv_pool_blocks",
                       type=int, default=0)
        p.add_argument("--serving-slots", dest="serving_slots", type=int,
                       default=8)
        p.add_argument("--prefill-chunk", dest="prefill_chunk",
                       type=int, default=8)
        p.add_argument("--no-prefix-cache", dest="prefix_cache",
                       action="store_false")
        p.add_argument("--serving-replicas", dest="serving_replicas",
                       type=int, default=1)
        p.add_argument("--serving-step-timeout",
                       dest="serving_step_timeout", type=float,
                       default=0.0)
        p.add_argument("--serving-max-restarts",
                       dest="serving_max_restarts", type=int, default=3)
        p.add_argument("--request-retry-limit",
                       dest="request_retry_limit", type=int, default=2)
        p.add_argument("--serving-min-replicas",
                       dest="serving_min_replicas", type=int, default=1)
        p.add_argument("--serving-max-replicas",
                       dest="serving_max_replicas", type=int, default=0)
        p.add_argument("--autoscale-interval",
                       dest="autoscale_interval", type=float,
                       default=1.0)
        p.add_argument("--autoscale-cooldown",
                       dest="autoscale_cooldown", type=float,
                       default=5.0)
        p.add_argument("--serving-slo-ttft", dest="serving_slo_ttft",
                       type=float, default=0.0)
        p.add_argument("--serving-drain-timeout",
                       dest="serving_drain_timeout", type=float,
                       default=30.0)
        p.add_argument("--admission-deadline",
                       dest="admission_deadline_s", type=float,
                       default=0.0)
        p.add_argument("--serving-tp", dest="serving_tp", type=int,
                       default=1)
        p.add_argument("--serving-chip-budget",
                       dest="serving_chip_budget", type=int, default=0)
        p.add_argument("--serving-roles", dest="serving_roles", type=str,
                       default="")
        p.add_argument("--kv-transfer", dest="kv_transfer", type=str,
                       default="inproc", choices=KV_TRANSFER_FABRICS)
        p.add_argument("--migration-cost-cap", dest="migration_cost_cap",
                       type=float, default=1.0)
        p.add_argument("--autoscale-predictive",
                       dest="autoscale_predictive", action="store_true")
        p.add_argument("--spec-decode", dest="spec_decode", type=str,
                       default="off", choices=SPEC_DECODE_MODES)
        p.add_argument("--spec-k", dest="spec_k", type=int, default=4)
        p.add_argument("--serving-handoff", dest="serving_handoff",
                       action="store_true")
        p.add_argument("--serving-rebalance-kv",
                       dest="serving_rebalance_kv", type=float,
                       default=0.0)
        args, _ = p.parse_known_args(argv)
        return cls(
            epochs=args.epochs,
            batch_size=args.batch_size,
            learning_rate=args.lr,
            weight_decay=args.wd,
            seed=args.seed,
            num_devices=args.num_devices,
            num_nodes=args.nodes,
            memory_per_device=args.fsize_mb * 1024**2,
            search_budget=args.budget,
            search_alpha=args.alpha,
            search_propagate=args.search_propagate,
            search_eval_cache=args.search_eval_cache,
            search_algo=args.search_algo,
            only_data_parallel=args.only_data_parallel,
            enable_parameter_parallel=args.enable_parameter_parallel,
            enable_attribute_parallel=args.enable_attribute_parallel,
            enable_sample_parallel=args.enable_sample_parallel,
            search_overlap_backward_update=args.overlap_backward_update,
            parameter_sync=ParameterSyncType(args.parameter_sync),
            substitution_json=args.substitution_json,
            rewrite_depth=args.rewrite_depth,
            rewrite_max_variants=args.rewrite_max_variants,
            search_calibrate=args.search_calibrate,
            op_cost_cache_file=args.op_cost_cache,
            memory_search=args.memory_search,
            machine_model_version=args.machine_model_version,
            machine_model_file=args.machine_model_file,
            simulator_segment_size=args.simulator_segment_size,
            slices=args.slices,
            dcn_bandwidth=args.dcn_bandwidth,
            dcn_latency=args.dcn_latency,
            slice_topology=args.slice_topology,
            dcn_bucket_mb=args.dcn_bucket_mb,
            zero_stage=(args.zero_stage if args.zero_stage is not None
                        else (1 if args.weight_update_sharding else 0)),
            weight_update_sharding=(args.weight_update_sharding
                                    if args.zero_stage is None else False),
            wus_axis=args.wus_axis,
            perform_fusion=args.fusion,
            remat=args.remat,
            profiling=args.profiling,
            flash_min_seq=args.flash_min_seq,
            export_strategy_file=args.export_strategy,
            import_strategy_file=args.import_strategy,
            strategy_store=args.strategy_store,
            compilation_cache=args.compilation_cache,
            export_taskgraph_file=args.taskgraph,
            export_compgraph_file=args.compgraph,
            include_costs_dot_graph=args.include_costs_dot_graph,
            checkpoint_every=args.checkpoint_every,
            checkpoint_dir=args.checkpoint_dir,
            checkpoint_keep=args.checkpoint_keep,
            checkpoint_async=args.checkpoint_async,
            step_timeout=args.step_timeout,
            preempt_grace=args.preempt_grace,
            max_restarts=args.max_restarts,
            retry_backoff=args.retry_backoff,
            nan_policy=args.nan_policy,
            remote_store=args.remote_store,
            offload_every=args.offload_every,
            remote_keep=args.remote_keep,
            barrier_timeout=args.barrier_timeout,
            trace_dir=args.trace_dir,
            telemetry=args.telemetry,
            profile_steps=args.profile_steps,
            trace_sample=args.trace_sample,
            serving_mode=args.serving_mode,
            kv_page_size=args.kv_page_size,
            kv_pool_blocks=args.kv_pool_blocks,
            serving_slots=args.serving_slots,
            prefill_chunk=args.prefill_chunk,
            prefix_cache=args.prefix_cache,
            serving_replicas=args.serving_replicas,
            serving_step_timeout=args.serving_step_timeout,
            serving_max_restarts=args.serving_max_restarts,
            request_retry_limit=args.request_retry_limit,
            serving_min_replicas=args.serving_min_replicas,
            serving_max_replicas=args.serving_max_replicas,
            autoscale_interval=args.autoscale_interval,
            autoscale_cooldown=args.autoscale_cooldown,
            serving_slo_ttft=args.serving_slo_ttft,
            serving_drain_timeout=args.serving_drain_timeout,
            admission_deadline_s=args.admission_deadline_s,
            serving_tp=args.serving_tp,
            serving_chip_budget=args.serving_chip_budget,
            serving_roles=args.serving_roles,
            kv_transfer=args.kv_transfer,
            migration_cost_cap=args.migration_cost_cap,
            autoscale_predictive=args.autoscale_predictive,
            spec_decode=args.spec_decode,
            spec_k=args.spec_k,
            serving_handoff=args.serving_handoff,
            serving_rebalance_kv=args.serving_rebalance_kv,
        )


@dataclasses.dataclass
class FFIterationConfig:
    """Per-iteration config threaded through forward/backward.

    Reference: config.h:162-167 — carries seq_length for early truncation
    (consumed by BatchMatmul/attention; model.cc:2415-2419).
    """

    seq_length: int = -1

    def reset(self):
        self.seq_length = -1
