"""Persistent strategy + compile artifact store (docs/STORE.md).

The reference FlexFlow ships searched strategies as on-disk artifacts
(--export-strategy/--import-strategy, graph.cc:2164-2400) because the
search is the expensive, reusable part of the system.  This package
makes that a first-class, content-addressed tier:

  * StrategyStore — durable searched strategies keyed by
    (graph signature, mesh fingerprint, simulator version), with
    verify-then-publish writes and corrupt-entry tolerance (store.py);
  * cached_search — the one consult-then-publish wrapper every search
    site uses: FFModel.compile, the resilience supervisor's elastic
    re-search, and (through compile) serving replica spin-up;
  * enable_compilation_cache — where JAX's persistent compilation
    cache lives ($JAX_COMPILATION_CACHE_DIR, else the config, else
    <checkout>/.jax_cache on accelerators), so the compiled step
    function itself survives process death alongside the strategy
    that produced it.

Config surface: FFConfig.strategy_store / --strategy-store DIR /
--no-strategy-store (or the FLEXFLOW_TPU_STORE_DIR env var for fleet
deployments), FFConfig.compilation_cache / --compilation-cache [DIR]
(yields to $JAX_COMPILATION_CACHE_DIR).
"""
from __future__ import annotations

import os
import time
from typing import Callable, Optional

from ..logger import store_logger
from .key import (
    StoreKey,
    graph_signature,
    mesh_fingerprint,
    simulator_version,
    store_key_for,
)
from .blobstore import (
    BlobNotFound,
    BlobPreconditionFailed,
    BlobStore,
    BlobStoreError,
    BlobUnavailableError,
    FaultyBlobStore,
    LocalBlobStore,
    blobstore_from_uri,
)
from .store import (
    MANIFEST_VERSION,
    RemoteStrategyMirror,
    StoreVerifyError,
    StrategyStore,
)

#: env var naming a shared store root for every process in a fleet
#: (per-run --strategy-store overrides it; --no-strategy-store opts out)
STORE_DIR_ENV = "FLEXFLOW_TPU_STORE_DIR"


def resolve_store_dir(cfg) -> Optional[str]:
    """FFConfig.strategy_store -> effective store root, or None when
    the store is off.  None falls through to $FLEXFLOW_TPU_STORE_DIR;
    ''/'none' is an explicit opt-out (the substitution_json pattern)."""
    v = cfg.strategy_store
    if v is None:
        v = os.environ.get(STORE_DIR_ENV) or None
    if not v or str(v).strip().lower() == "none":
        return None
    return str(v)


def store_from_config(cfg, registry=None) -> Optional[StrategyStore]:
    """The run's StrategyStore, or None when disabled/unusable.  An
    unwritable root degrades to store-off with a log line — persistence
    is an accelerator, never a crash source.  FFConfig.remote_store
    attaches the fleet mirror (docs/STORE.md "Fleet mirror"): lookups
    consult local -> remote and publishes mirror through, sharing the
    checkpoint offload tier's blob root under its `strategies/`
    prefix."""
    root = resolve_store_dir(cfg)
    if root is None:
        return None
    remote = None
    uri = getattr(cfg, "remote_store", None)
    if uri and str(uri).strip().lower() != "none":
        try:
            from .blobstore import blobstore_from_uri
            from .store import RemoteStrategyMirror

            remote = RemoteStrategyMirror(blobstore_from_uri(uri))
        except (OSError, ValueError, NotImplementedError) as e:
            store_logger.info(
                "fleet mirror %r unusable (%s); continuing with the "
                "local store only", uri, e,
            )
    try:
        return StrategyStore(root, registry=registry, remote=remote)
    except OSError as e:
        store_logger.info(
            "strategy store root %s unusable (%s); continuing without "
            "the store", root, e,
        )
        return None


#: jax's own env var for the persistent cache directory.  When it is
#: set the operator (or the chip tool) has placed the cache from
#: OUTSIDE: jax reads it itself and this package sets no directory.
COMPILATION_CACHE_ENV = "JAX_COMPILATION_CACHE_DIR"

#: accelerator default when neither the env var nor the config names a
#: directory: <checkout>/.jax_cache, derived from this package's own
#: location.  The path is part of what a later process must find again,
#: so it is fixed — never a tempfile, a pid or a timestamp.
DEFAULT_COMPILATION_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))),
    ".jax_cache",
)


def enable_compilation_cache(cfg) -> Optional[str]:
    """Decide where JAX's persistent compilation cache lives for this
    process, so a restarted process re-loads its XLA executables from
    disk instead of recompiling.  Returns the cache dir, or None when
    off.  Precedence:

      1. $JAX_COMPILATION_CACHE_DIR set -> jax already reads it; NO
         directory is set from code, and a disagreeing
         FFConfig.compilation_cache is ignored with one log line;
      2. FFConfig.compilation_cache = DIR, or 'auto' = <store
         root>/xla_cache;
      3. neither: ON at DEFAULT_COMPILATION_CACHE_DIR on an accelerator
         backend (cold start is minutes there), off on the CPU backend.

    GLOBAL jax config: the most recent compile's setting wins for the
    whole process, so point every model in one process at the same
    cache (content-addressed internally — sharing is safe; split dirs
    only cost duplicate executables)."""
    import jax

    spec = cfg.compilation_cache
    on_accelerator = jax.default_backend() != "cpu"
    path = os.environ.get(COMPILATION_CACHE_ENV) or None
    if path is not None:
        if spec and os.path.abspath(str(spec)) != os.path.abspath(path):
            store_logger.info(
                "compilation_cache=%r ignored: $%s=%s places the XLA "
                "cache for this process", spec, COMPILATION_CACHE_ENV,
                path,
            )
    else:
        if not spec:
            if not on_accelerator:
                return None
            path = DEFAULT_COMPILATION_CACHE_DIR
        elif str(spec).strip().lower() == "auto":
            root = resolve_store_dir(cfg)
            if root is None:
                raise ValueError(
                    "compilation_cache='auto' ties the XLA cache to the "
                    "strategy store root, but no store is configured — "
                    f"set --strategy-store/${STORE_DIR_ENV} or pass an "
                    "explicit --compilation-cache DIR"
                )
            path = os.path.join(root, "xla_cache")  # StrategyStore layout
        else:
            path = str(spec)
        os.makedirs(path, exist_ok=True)
        jax.config.update("jax_compilation_cache_dir", path)
    if on_accelerator:
        # cache EVERY executable on accelerators: cold start is the
        # point, and the cache dir is operator-provisioned space (gc
        # via docs/STORE.md).  The CPU backend keeps jax's thresholds:
        # the reload crash once seen on jax 0.4.37 does not reproduce
        # on 0.9.0 (re-checked PR 21), but every reload of a
        # force-cached CPU executable logs an XLA:CPU machine-feature
        # mismatch and a CPU recompile is sub-second
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
        jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    return path


def cached_search(model, num_devices: int,
                  run_search: Callable[[], "object"]):
    """Consult-then-publish around one strategy search.

    Store off -> run_search() unchanged.  Store on: a hit returns the
    published strategy with search_stats carrying store_hit=True (the
    search is skipped entirely); a miss runs the search and publishes
    the winner under the same key so every later process — a preempted
    worker's replacement, an elastic re-search on the degraded mesh, a
    new serving replica — restores it instead of re-paying the search.
    """
    cfg = model.config
    registry = getattr(getattr(model, "telemetry", None), "metrics", None)
    store = store_from_config(cfg, registry=registry)
    if store is None:
        return run_search()
    key = store_key_for(cfg, model.layers, num_devices)
    hit = store.lookup(key)
    if hit is not None:
        store_logger.info(
            "store hit %s: strategy restored for %d devices, search "
            "skipped", key.digest[:16], num_devices,
        )
        return hit
    strategy = run_search()
    stats = getattr(strategy, "search_stats", None)
    if stats is None:
        stats = {}
        strategy.search_stats = stats
    stats["store_hit"] = False
    stats["store_key"] = key.digest
    store.publish(
        key,
        strategy,
        searched_cost=getattr(strategy, "search_cost", None),
        search_stats=stats,
        created_at=time.time(),
    )
    return strategy


__all__ = [
    "COMPILATION_CACHE_ENV",
    "DEFAULT_COMPILATION_CACHE_DIR",
    "MANIFEST_VERSION",
    "STORE_DIR_ENV",
    "BlobNotFound",
    "BlobPreconditionFailed",
    "BlobStore",
    "BlobStoreError",
    "BlobUnavailableError",
    "FaultyBlobStore",
    "LocalBlobStore",
    "RemoteStrategyMirror",
    "StoreKey",
    "StoreVerifyError",
    "StrategyStore",
    "blobstore_from_uri",
    "cached_search",
    "enable_compilation_cache",
    "graph_signature",
    "mesh_fingerprint",
    "resolve_store_dir",
    "simulator_version",
    "store_from_config",
    "store_key_for",
]
