"""Checkpoint / resume with verified, off-critical-path saves.

The reference has no real checkpoint format — weights round-trip through
numpy by hand (parallel_tensor.cc:650-750) and SURVEY §5 flags
checkpoint/resume as a gap to close fresh.  TPU-native answer: sharded
saves of the full training state (weights, optimizer state, op state,
step, rng) plus the strategy JSON and a config snapshot, so `restore`
can rebuild byte-identical training on a fresh process — including onto
a *different* mesh (every leaf reshards onto the current executor's
shardings on restore).

Durability layer (docs/RESILIENCE.md "Async checkpointing"):

  * **async saves** — `save(..., wait=False)` snapshots device arrays
    to host (the only accelerator stall) and hands serialization,
    fsync, verification and atomic publish to a background
    `resilience.async_writer.AsyncCheckpointWriter`; `wait=True` keeps
    fully synchronous semantics.  `drain()` blocks until pending
    writes land (the supervisor drains before restores and on exit).
  * **integrity manifest** — each local checkpoint carries a per-leaf
    crc32 manifest (`manifest.json`); a save only publishes, and the
    `LATEST` pointer only advances, after the written bytes re-read and
    verify.  Restore re-verifies every leaf and falls back past
    corrupt/unverifiable steps to the newest intact one.
  * **layout validation** — restoring a checkpoint whose saved state
    tree does not match the current run (different model / optimizer /
    op-state structure) raises `CheckpointCompatibilityError` naming
    every mismatched leaf, instead of a cryptic reshape/resharding
    traceback.  Mesh-size and weight-update-sharding layout changes
    remain *compatible* by design — reshard-on-restore handles them.
  * **pipeline layout mapping** — a checkpoint saved under a per-op
    strategy restores onto a pipeline (`__pipeline__` stacked) executor
    and vice versa: restore routes the weight and optimizer-slot trees
    through `FFModel._adapt_weight_layout` before spec validation, so
    the supervisor's elastic re-search may pick pipeline winners
    mid-run (the former `re_search_pipeline_excluded` gate is gone).
  * **remote tier** — with a configured offload tier
    (`resilience/offload.py`, FFConfig.remote_store), every verified
    local publish is mirrored to object storage off the critical path,
    and restore walks local -> remote PER CHECKPOINT: a corrupt local
    step falls back to its verified remote mirror (downloaded,
    crc-verified, materialized locally) before giving up progress to
    an older step; a brand-new empty host restores entirely from the
    remote tier.
"""
from __future__ import annotations

import itertools
import json
import logging
import os
import re
import shutil
import time
import zlib
from typing import Any, Dict, List, Optional, Tuple

import jax
import numpy as np

from .obs.metrics import registry_of
from .obs.trace import span

_log = logging.getLogger("flexflow_tpu.checkpoint")

MANIFEST_VERSION = 1
_LATEST_FILE = "LATEST"


class CheckpointVerifyError(RuntimeError):
    """A checkpoint's bytes do not match its integrity manifest."""


class CheckpointCompatibilityError(RuntimeError):
    """The checkpoint's state tree is incompatible with the current run.

    Raised instead of a cryptic KeyError/reshape traceback when the
    saved leaves (names, shapes, dtypes) don't match the compiled
    model's — e.g. a different architecture, optimizer, or op-state
    layout.  Mesh-size / ZeRO-1-layout differences never raise this:
    restore reshards onto the current shardings by contract."""

    def __init__(self, step: int, mismatches: List[str],
                 meta: Optional[Dict] = None):
        self.step = step
        self.mismatches = list(mismatches)
        meta = meta or {}
        context = (
            f" (saved with num_devices={meta.get('num_devices')}, "
            f"zero_stage={meta.get('zero_stage')}, "
            f"wus_axis={meta.get('wus_axis')})" if meta else ""
        )
        shown = "; ".join(self.mismatches[:8])
        more = (f"; ... {len(self.mismatches) - 8} more"
                if len(self.mismatches) > 8 else "")
        super().__init__(
            f"checkpoint step {step} is incompatible with the current "
            f"run{context}: {shown}{more}"
        )


def _meta(ff, step: int) -> Dict[str, Any]:
    return {
        "step": step,
        "version": 1,
        "strategy": ff.strategy.to_json() if ff.strategy is not None else None,
        "batch_size": ff.config.batch_size,
        "num_devices": ff.config.num_devices,
        # ZeRO ladder layout marker: restore reshards every leaf onto
        # the CURRENT executor's shardings either way (any stage <->
        # any stage — incl. stage-3 scattered master weights — and
        # elastic meshes all round-trip, since leaves are saved as
        # GLOBAL arrays); recorded so tooling can see which layout
        # produced the artifact.  zero_stage is the EFFECTIVE stage
        # the executor ran (search-chosen stages included).
        "zero_stage": int(
            getattr(getattr(ff, "executor", None), "zero_stage",
                    getattr(ff.config, "zero_stage", 0)) or 0
        ),
        "weight_update_sharding": bool(
            getattr(ff.config, "weight_update_sharding", False)
        ),
        "wus_axis": getattr(ff.config, "wus_axis", None),
    }


def _leaf_crc(arr: np.ndarray) -> int:
    return zlib.crc32(np.ascontiguousarray(arr).view(np.uint8).reshape(-1))


def _build_manifest(step: int, flat: Dict[str, np.ndarray]) -> Dict[str, Any]:
    leaves = {
        key: {
            "crc32": _leaf_crc(arr),
            "bytes": int(arr.nbytes),
            "shape": list(arr.shape),
            "dtype": str(arr.dtype),
        }
        for key, arr in flat.items()
    }
    return {
        "manifest_version": MANIFEST_VERSION,
        "step": step,
        "total_bytes": sum(v["bytes"] for v in leaves.values()),
        "leaves": leaves,
    }


def _write_json_fsync(path: str, obj: Dict) -> None:
    with open(path, "w") as f:
        json.dump(obj, f)
        f.flush()
        os.fsync(f.fileno())


def _fsync_dir(path: str) -> None:
    try:
        fd = os.open(path, os.O_RDONLY)
    except OSError:  # pragma: no cover — platform without dir fds
        return
    try:
        os.fsync(fd)
    finally:
        os.close(fd)


class _LatestPointer:
    """Crash-safe `LATEST` pointer file: names the newest checkpoint
    step that passed integrity verification.  Advanced only after a
    save verifies and publishes, so a reader that trusts the pointer
    never lands on a torn or unverified write."""

    def __init__(self, directory: str):
        self.directory = directory
        self.path = os.path.join(directory, _LATEST_FILE)

    def read(self) -> Optional[int]:
        try:
            with open(self.path) as f:
                return int(f.read().strip())
        except (OSError, ValueError):
            return None

    def advance(self, step: int, force: bool = False) -> None:
        cur = self.read()
        if not force and cur is not None and cur >= step:
            return
        # thread-unique tmp name: the writer thread and a synchronous
        # caller (emergency save) must not clobber each other's staging
        import threading

        tmp = f"{self.path}.tmp-{os.getpid()}-{threading.get_ident()}"
        with open(tmp, "w") as f:
            f.write(str(step))
            f.flush()
            os.fsync(f.fileno())
        os.replace(tmp, self.path)
        _fsync_dir(self.directory)


class CheckpointManager:
    """Orbax-backed manager bound to a compiled FFModel.

    save/restore the full train state; `max_to_keep` rotates old steps.
    Restore reshards to the model's *current* executor shardings, so a
    checkpoint taken on one mesh resumes on another.  `wait=False`
    returns after orbax's host snapshot (serialization continues in
    orbax's background machinery); `drain()` blocks until pending saves
    land and only then advances the `LATEST` pointer.  Integrity inside
    a step is orbax's commit protocol; the per-leaf crc32 manifest is a
    LocalCheckpointManager feature."""

    def __init__(self, directory: str, max_to_keep: int = 3, remote=None):
        import orbax.checkpoint as ocp

        self.directory = os.path.abspath(directory)
        os.makedirs(self.directory, exist_ok=True)
        self._mgr = ocp.CheckpointManager(
            self.directory,
            options=ocp.CheckpointManagerOptions(
                max_to_keep=max_to_keep, create=True
            ),
        )
        self._ocp = ocp
        self._latest = _LatestPointer(self.directory)
        # remote tier (resilience/offload.py RemoteCheckpointStore):
        # restore-side fallback only — the mirror's flat-npz format is
        # backend-agnostic, so an orbax run can recover from a mirror a
        # LocalCheckpointManager uploaded (uploading is the local
        # manager's job; orbax's own commit layout is not mirrored)
        self.remote = remote
        # wait=False (step, submit_time, registry) not yet drained
        self._pending: List[Tuple[int, float, Any]] = []

    # -- save -----------------------------------------------------------
    def save(self, ff, step: int, wait: bool = True):
        """Persist weights + optimizer state + op state + rng + strategy.

        wait=True blocks until the checkpoint is durable (and advances
        the LATEST pointer); wait=False returns after the host snapshot
        and defers durability to orbax's writer — call drain() before
        relying on the step being restorable."""
        ocp = self._ocp
        state = {
            "weights": ff._weights,
            "opt_state": ff._opt_state,
            "op_state": ff._state,
            "rng": jax.random.key_data(ff._rng),
        }
        meta = _meta(ff, step)
        meta["leaf_specs"] = _tree_specs(state)
        registry = registry_of(ff)
        t0 = time.perf_counter()
        with span("checkpoint_write", step=step, backend="orbax",
                  mode="sync" if wait else "async"):
            with span("snapshot", step=step):
                self._mgr.save(
                    step,
                    args=ocp.args.Composite(
                        state=ocp.args.StandardSave(state),
                        meta=ocp.args.JsonSave(meta),
                    ),
                )
            if wait:
                with span("flush", step=step):
                    self._mgr.wait_until_finished()
                self._latest.advance(step)
                if registry is not None:
                    registry.histogram(
                        "resilience/ckpt_write_latency_s"
                    ).observe(time.perf_counter() - t0)
            else:
                # latency for async saves is observed at drain() — the
                # save-call duration here is snapshot-only and would
                # understate the metric's documented submit->durable
                # semantics ~30x
                self._pending.append((step, t0, registry))

    def drain(self) -> List[Tuple[int, Exception]]:
        """Block until every pending async save lands; advance the
        LATEST pointer past them and record their submit->durable
        latency.  Returns the (step, error) failures — an orbax wait
        failure is attributed to all pending steps."""
        if not self._pending:
            return []
        pending, self._pending = self._pending, []
        try:
            self._mgr.wait_until_finished()
        except Exception as e:  # noqa: BLE001 — surface, don't crash
            steps = [s for s, _, _ in pending]
            _log.warning("async orbax save(s) %s failed: %s", steps, e)
            return [(s, e) for s in steps]
        now = time.perf_counter()
        for step, t0, registry in pending:
            if registry is not None:
                registry.histogram(
                    "resilience/ckpt_write_latency_s"
                ).observe(now - t0)
        self._latest.advance(max(s for s, _, _ in pending))
        return []

    # -- restore --------------------------------------------------------
    def latest_step(self) -> Optional[int]:
        return self._mgr.latest_step()

    def latest_verified_step(self) -> Optional[int]:
        """The newest step the LATEST pointer has committed to, None if
        absent or stale — orbax's max_to_keep rotation can delete a
        pointed-at step whose successors were never drained."""
        step = self._latest.read()
        if step is None or step not in set(self._mgr.all_steps()):
            return None
        return step

    def any_restorable(self) -> bool:
        """True when either the orbax directory or the remote mirror
        tier holds at least one restorable checkpoint."""
        if self.latest_step() is not None:
            return True
        if self.remote is None:
            return False
        try:
            return bool(self.remote.list_steps())
        except Exception:  # noqa: BLE001 — unreachable mirror
            return False

    def all_steps(self):
        return list(self._mgr.all_steps())

    def _mirrored_steps(self) -> set:
        """Steps the remote tier can serve (empty on any store failure —
        the caller then surfaces its local error instead)."""
        if self.remote is None:
            return set()
        try:
            return set(self.remote.list_steps())
        except Exception:  # noqa: BLE001 — unreachable mirror
            return set()

    def restore(self, ff, step: Optional[int] = None) -> int:
        """Load a step (default: latest) into a compiled FFModel,
        resharding every leaf to the current executor's shardings.
        Returns the restored step.

        With step=None a corrupt/partial/incompatible latest checkpoint
        is skipped and the previous one restored instead (the crash
        that truncated the write is usually the crash being recovered
        from); an explicitly requested step stays strict.  With a
        remote tier configured, steps the local directory cannot serve
        fall back to their verified remote mirrors."""
        if step is not None:
            try:
                return self._restore_step(ff, step)
            except CheckpointCompatibilityError as compat_err:
                # UNLIKE the npz manager (where both tiers share one
                # verify-adapt path) the orbax local restore cannot
                # adapt per-op <-> __pipeline__ layouts, but the flat-npz
                # mirror restore can — try it before giving up
                if self.remote is None or step not in self._mirrored_steps():
                    raise
                try:
                    return self._restore_remote_step(ff, step)
                except Exception:  # noqa: BLE001
                    raise compat_err  # the actionable report, not blob noise
            except Exception:
                if self.remote is None:
                    raise
                if step not in self._mirrored_steps():
                    raise  # surface the local failure, not BlobNotFound
                return self._restore_remote_step(ff, step)
        steps = sorted(self._mgr.all_steps(), reverse=True)
        remote_steps: List[int] = []
        if self.remote is not None:
            try:
                remote_steps = sorted(self.remote.list_steps(), reverse=True)
            except Exception as e:  # noqa: BLE001 — any store failure
                _log.warning(
                    "remote checkpoint tier unlistable (%s); restoring "
                    "from the local tier only", e,
                )
        if not steps and not remote_steps:
            raise FileNotFoundError(f"no checkpoints in {self.directory}")
        last_err: Optional[Exception] = None
        # ONE newest-first walk over BOTH tiers — an older local step
        # must never win over a newer verified remote-only mirror
        for s in sorted(set(steps) | set(remote_steps), reverse=True):
            if s in steps:
                try:
                    restored = self._restore_step(ff, s)
                except Exception as e:  # noqa: BLE001 — orbax raises various
                    if s in remote_steps:
                        try:
                            restored = self._restore_remote_step(ff, s)
                        except Exception as re_err:  # noqa: BLE001
                            _log.warning(
                                "checkpoint step %d unrestorable locally "
                                "(%s) and remotely (%s); falling back",
                                s, e, re_err,
                            )
                            last_err = re_err
                            continue
                    else:
                        _log.warning(
                            "checkpoint step %d in %s unrestorable (%s); "
                            "falling back to the previous step",
                            s, self.directory, e,
                        )
                        last_err = e
                        continue
            else:
                try:
                    restored = self._restore_remote_step(ff, s)
                except Exception as e:  # noqa: BLE001
                    _log.warning(
                        "remote checkpoint step %d unrestorable (%s); "
                        "falling back to the previous step", s, e,
                    )
                    last_err = e
                    continue
            if last_err is not None:
                _log.warning(
                    "restored OLDER step %d from %s — newer step(s) were "
                    "corrupt/partial, their progress is lost",
                    restored, self.directory,
                )
            return restored
        raise last_err

    def _restore_remote_step(self, ff, step: int) -> int:
        """Fill the model from a remote mirror (flat-npz format): crc
        re-verify the downloaded bytes, adapt layouts, device_put onto
        the current shardings."""
        import io

        from jax.tree_util import tree_unflatten

        files = self.remote.download_step(step)
        manifest = json.loads(files["manifest.json"])
        meta = json.loads(files["meta.json"])
        with np.load(io.BytesIO(files["state.npz"])) as data:
            arrays = {key: data[key] for key in data.files}
        target = {
            "weights": ff._weights,
            "opt_state": ff._opt_state,
            "op_state": ff._state,
            "rng": jax.random.key_data(ff._rng),
        }
        new_leaves, treedef = _verify_adapt_put(
            ff, target, arrays, manifest, meta, step
        )
        restored = tree_unflatten(treedef, new_leaves)
        ff._weights = restored["weights"]
        ff._opt_state = restored["opt_state"]
        ff._state = restored["op_state"]
        ff._rng = jax.random.wrap_key_data(restored["rng"])
        if hasattr(ff, "sync_decode_pos"):
            ff.sync_decode_pos()
        registry = registry_of(ff)
        if registry is not None:
            registry.counter("resilience/offload_remote_restores").inc()
        _log.info("step %d restored from the remote tier (orbax local "
                  "tier could not serve it)", step)
        return int(step)

    def _restore_step(self, ff, step: int) -> int:
        ocp = self._ocp
        target = {
            "weights": ff._weights,
            "opt_state": ff._opt_state,
            "op_state": ff._state,
            "rng": jax.random.key_data(ff._rng),
        }
        # layout validation up front: a structurally incompatible
        # checkpoint fails with one clear error naming the leaves,
        # not a restore-time reshape traceback from orbax internals
        try:
            meta = self.restore_meta(step)
        except Exception:  # meta unreadable -> let the restore itself fail
            meta = None
        if meta and meta.get("leaf_specs"):
            mismatches = _spec_mismatches(meta["leaf_specs"],
                                          _tree_specs(target))
            if mismatches:
                raise CheckpointCompatibilityError(step, mismatches, meta)
        abstract = jax.tree.map(
            lambda x: jax.ShapeDtypeStruct(
                x.shape, x.dtype,
                sharding=getattr(x, "sharding", None),
            ),
            target,
        )
        restored = self._mgr.restore(
            step,
            args=ocp.args.Composite(
                state=ocp.args.StandardRestore(abstract),
                meta=ocp.args.JsonRestore(),
            ),
        )
        state = restored["state"]
        ff._weights = state["weights"]
        ff._opt_state = state["opt_state"]
        ff._state = state["op_state"]
        ff._rng = jax.random.wrap_key_data(state["rng"])
        # restored cache_pos may be mid-sequence; rebuild the host-side
        # decode guard from the device value (ADVICE r4)
        if hasattr(ff, "sync_decode_pos"):
            ff.sync_decode_pos()
        return int(step)

    def restore_meta(self, step: Optional[int] = None) -> Dict[str, Any]:
        ocp = self._ocp
        if step is None:
            step = self._mgr.latest_step()
            if step is None:
                raise FileNotFoundError(f"no checkpoints in {self.directory}")
        restored = self._mgr.restore(
            step, args=ocp.args.Composite(meta=ocp.args.JsonRestore())
        )
        return dict(restored["meta"])

    def close(self):
        self.drain()
        self._mgr.close()


def _tree_specs(tree) -> Dict[str, Dict[str, Any]]:
    """keystr-keyed {shape, dtype} specs for every leaf of a state
    tree — the structural signature layout validation compares."""
    from jax.tree_util import keystr, tree_flatten_with_path

    leaves, _ = tree_flatten_with_path(tree)
    return {
        keystr(path): {
            "shape": list(np.shape(leaf)),
            "dtype": str(getattr(leaf, "dtype", np.asarray(leaf).dtype)),
        }
        for path, leaf in leaves
    }


def _verify_adapt_put(ff, target, arrays: Dict[str, np.ndarray],
                      manifest: Optional[Dict], meta: Optional[Dict],
                      step: int):
    """The shared restore core for flat (keystr-keyed) checkpoints:
    crc-verify against the manifest's saved-layout keys FIRST (so
    corruption surfaces as a verify error and falls back, never
    masquerading as a layout problem), map per-op <-> `__pipeline__`
    stacked layouts onto the current executor, validate leaf specs,
    then device_put every leaf onto the target's shardings.  Returns
    (new_leaves, treedef) for the target tree."""
    from jax.tree_util import keystr, tree_flatten_with_path

    if manifest is not None:
        for key, spec in manifest["leaves"].items():
            arr = arrays.get(key)
            if arr is None:
                raise CheckpointVerifyError(
                    f"step {step}: leaf {key!r} in manifest but not in "
                    "state.npz"
                )
            crc = _leaf_crc(arr)
            if crc != spec["crc32"]:
                raise CheckpointVerifyError(
                    f"step {step}: leaf {key!r} crc32 {crc:#010x} "
                    f"!= manifest {spec['crc32']:#010x}"
                )
        # every saved leaf must be covered: a manifest that lists fewer
        # leaves than state.npz (torn/older/hand-edited) would otherwise
        # let the uncovered bytes restore with no integrity check at all
        unverified = sorted(set(arrays) - set(manifest["leaves"]))
        if unverified:
            shown = ", ".join(repr(k) for k in unverified[:5])
            more = (f", ... {len(unverified) - 5} more"
                    if len(unverified) > 5 else "")
            raise CheckpointVerifyError(
                f"step {step}: leaves in state.npz but missing from the "
                f"manifest (unverifiable): {shown}{more}"
            )
    arrays = _adapt_saved_layout(ff, arrays)
    leaves, treedef = tree_flatten_with_path(target)
    # layout validation before materializing: one clear error naming
    # every mismatched leaf beats a KeyError/reshape traceback from
    # whichever leaf happened to differ
    saved_specs = {
        key: {"shape": list(arr.shape), "dtype": str(arr.dtype)}
        for key, arr in arrays.items()
    }
    current_specs = {
        keystr(path): {
            "shape": list(cur.shape),
            "dtype": str(cur.dtype),
        }
        for path, cur in leaves
    }
    mismatches = _spec_mismatches(saved_specs, current_specs)
    if mismatches:
        raise CheckpointCompatibilityError(step, mismatches, meta)
    new_leaves = []
    for path, cur in leaves:
        arr = arrays[keystr(path)]
        sh = getattr(cur, "sharding", None)
        new_leaves.append(
            jax.device_put(arr, sh) if sh is not None else arr
        )
    return new_leaves, treedef


_KEYSTR_TOKEN_RE = re.compile(r"\['([^']*)'\]")


def _unflatten_keystr(flat: Dict[str, Any]) -> Optional[Dict]:
    """Rebuild the nested dict tree a keystr-keyed flat mapping came
    from.  Returns None when any key is not a pure string-keyed dict
    path (lists/custom nodes) — callers then skip layout adaptation and
    let spec validation report the mismatch."""
    root: Dict = {}
    for key, leaf in flat.items():
        toks = _KEYSTR_TOKEN_RE.findall(key)
        if not toks or "".join(f"['{t}']" for t in toks) != key:
            return None
        d = root
        for t in toks[:-1]:
            d = d.setdefault(t, {})
            if not isinstance(d, dict):
                return None
        d[toks[-1]] = leaf
    return root


def _flatten_keystr(tree) -> Dict[str, Any]:
    from jax.tree_util import keystr, tree_flatten_with_path

    leaves, _ = tree_flatten_with_path(tree)
    return {keystr(path): leaf for path, leaf in leaves}


def _adapt_saved_layout(ff, arrays: Dict[str, np.ndarray]
                        ) -> Dict[str, np.ndarray]:
    """Map a flat saved state between the per-op and the
    `__pipeline__`-stacked weight layouts to match the CURRENT
    executor, reusing `FFModel._adapt_weight_layout` for the weight
    tree and each weight-shaped optimizer-slot subtree (exactly
    recompile's carry).  This is what lets the supervisor's elastic
    re-search restore a per-op-keyed checkpoint onto a freshly
    compiled pipeline strategy (and back).  A failed adaptation
    returns the arrays unchanged so spec validation reports the real
    mismatch instead of a mapping traceback."""
    saved_pp = any(
        k.startswith("['weights']['__pipeline__']") for k in arrays
    )
    cur_pp = "__pipeline__" in (getattr(ff, "_weights", None) or {})
    if saved_pp == cur_pp:
        return arrays
    adapt = getattr(ff, "_adapt_weight_layout", None)
    nested = _unflatten_keystr(arrays)
    if adapt is None or nested is None or "weights" not in nested:
        return arrays
    try:
        out = dict(nested)
        out["weights"] = adapt(nested["weights"])
        if isinstance(nested.get("opt_state"), dict):
            out["opt_state"] = {
                k: adapt(sub) if isinstance(sub, dict) else sub
                for k, sub in nested["opt_state"].items()
            }
        return _flatten_keystr(out)
    except Exception as e:  # genuinely incompatible trees
        _log.warning(
            "pipeline layout adaptation failed (%s); restoring with the "
            "saved layout as-is", e,
        )
        return arrays


def _spec_mismatches(saved: Dict[str, Dict], current: Dict[str, Dict]
                     ) -> List[str]:
    """Human-readable list of structural differences between a saved
    tree signature and the current model's (empty == compatible)."""
    problems = []
    for key in sorted(set(saved) - set(current)):
        problems.append(f"{key}: in checkpoint but not in current state")
    for key in sorted(set(current) - set(saved)):
        problems.append(f"{key}: required by current state, missing "
                        "from checkpoint")
    for key in sorted(set(saved) & set(current)):
        s, c = saved[key], current[key]
        if list(s["shape"]) != list(c["shape"]):
            problems.append(
                f"{key}: shape {tuple(s['shape'])} in checkpoint vs "
                f"{tuple(c['shape'])} in current state"
            )
        elif str(s["dtype"]) != str(c["dtype"]):
            problems.append(
                f"{key}: dtype {s['dtype']} in checkpoint vs "
                f"{c['dtype']} in current state"
            )
    return problems


# -- orbax-free full-state checkpoints ----------------------------------

_STEP_DIR_RE = re.compile(r"step_(\d{8})")


class LocalCheckpointManager:
    """Self-contained full-train-state checkpoints without orbax: one
    flat .npz + meta.json + crc32 manifest.json per step.

    Robustness contract (the supervisor's default backend):
      * atomic verified writes — each step is staged in a `.tmp-*` dir,
        fsynced, re-read and crc-verified against its manifest, and
        only then `os.replace`d into place; the `LATEST` pointer
        advances only after that verification, so a crash or kill at
        any point mid-write never leaves `latest` naming a torn or
        unverified checkpoint;
      * async saves — `save(..., wait=False)` stalls training only for
        the device->host snapshot; serialization/fsync/verify/publish
        run on a background writer thread (`drain()` to wait them out);
      * keep-last-k retention with pruning of older step dirs — never
        of the newest *verified* checkpoint, even when it falls outside
        the retention window;
      * restore re-verifies the manifest and detects corrupt/partial/
        incompatible steps, falling back to the previous intact one,
        oldest-surviving last.

    Restore device_puts every leaf onto the model's CURRENT shardings,
    so a checkpoint taken on one mesh resumes on another (the same
    reshard-on-restore contract as the orbax manager) — this is what
    carries trained state onto the surviving mesh after a device loss.
    """

    # async backpressure: a save(wait=False) finding this many jobs
    # already queued drains the backlog first.  Each queued job holds a
    # full host copy of the train state (3x weight bytes under Adam), so
    # an unbounded queue behind a slow disk would OOM the host — the
    # durability layer must never be the thing that kills the run.
    MAX_PENDING_SAVES = 2

    def __init__(self, directory: str, max_to_keep: int = 3,
                 offloader=None, remote=None):
        if max_to_keep < 1:
            raise ValueError(f"max_to_keep must be >= 1, got {max_to_keep}")
        self.directory = os.path.abspath(directory)
        self.max_to_keep = max_to_keep
        # second durability tier (resilience/offload.py): the offloader
        # mirrors every verified publish; `remote` alone is enough for
        # restore-only consumers (a fresh host, tools/checkpoint_fsck)
        self.offloader = offloader
        self.remote = remote if remote is not None else (
            offloader.remote if offloader is not None else None
        )
        os.makedirs(self.directory, exist_ok=True)
        # tmp dirs from a writer that died mid-save are dead weight
        for name in os.listdir(self.directory):
            if name.startswith(".tmp-"):
                shutil.rmtree(
                    os.path.join(self.directory, name), ignore_errors=True
                )
        self._latest = _LatestPointer(self.directory)
        self._writer = None  # lazy: only wait=False saves pay for a thread
        self._tmp_ids = itertools.count()

    def _path(self, step: int) -> str:
        return os.path.join(self.directory, f"step_{step:08d}")

    def all_steps(self):
        out = []
        for name in os.listdir(self.directory):
            m = _STEP_DIR_RE.fullmatch(name)
            if m:
                out.append(int(m.group(1)))
        return sorted(out)

    def latest_step(self) -> Optional[int]:
        steps = self.all_steps()
        return steps[-1] if steps else None

    def latest_verified_step(self) -> Optional[int]:
        """Newest step the LATEST pointer committed to after write-time
        verification (None when the pointer is absent/stale — e.g. a
        directory written entirely by older code)."""
        step = self._latest.read()
        if step is None or not os.path.isdir(self._path(step)):
            return None
        return step

    def any_restorable(self) -> bool:
        """True when EITHER tier holds at least one checkpoint — the
        resume gate for a fresh host whose local directory is empty but
        whose remote mirror survived the old host's loss."""
        return self.latest_step() is not None or bool(self._remote_steps())

    @staticmethod
    def _state_tree(ff):
        return {
            "weights": ff._weights,
            "opt_state": ff._opt_state,
            "op_state": ff._state,
            "rng": jax.random.key_data(ff._rng),
        }

    # -- save -----------------------------------------------------------
    def _writer_obj(self):
        if self._writer is None:
            from .resilience.async_writer import AsyncCheckpointWriter

            self._writer = AsyncCheckpointWriter()
        return self._writer

    def save(self, ff, step: int, wait: bool = True):
        """Write one full-train-state checkpoint.

        wait=True (default): snapshot + serialize + fsync + verify +
        publish inline — the call returns with the step durable.
        wait=False: only the device->host snapshot happens here (the
        step-boundary stall); the rest runs on the background writer.
        The step becomes visible to latest_step()/restore() once the
        writer publishes it — drain() to wait for that."""
        from jax.tree_util import keystr, tree_flatten_with_path

        registry = registry_of(ff)
        with span("checkpoint_write", step=step, backend="local",
                  mode="sync" if wait else "async"):
            with span("snapshot", step=step):
                # async snapshots must own their memory: np.asarray can
                # alias a live device buffer on CPU backends, and the
                # next step DONATES those buffers — a view would be
                # overwritten mid-write.  The sync path writes before
                # returning, so the cheaper view is safe there.
                conv = np.asarray if wait else (lambda x: np.array(x))
                tree = jax.tree.map(conv, self._state_tree(ff))
                leaves, _ = tree_flatten_with_path(tree)
                flat = {keystr(path): leaf for path, leaf in leaves}
                meta = _meta(ff, step)
            if wait:
                with span("flush", step=step):
                    self._write_and_publish(step, flat, meta, registry)
            else:
                writer = self._writer_obj()
                if registry is not None:
                    gauge = registry.gauge("resilience/ckpt_queue_depth")
                    writer.depth_cb = gauge.set
                if writer.queue_depth >= self.MAX_PENDING_SAVES:
                    # backpressure: the writer is slower than the save
                    # cadence — block until the backlog clears instead
                    # of accumulating full-state host copies unboundedly
                    _log.warning(
                        "async checkpoint writer backlog (%d pending) at "
                        "step %d: draining before the next save — the "
                        "cadence outruns disk bandwidth",
                        writer.queue_depth, step,
                    )
                    writer.wait()  # failures stay for the owner's drain()
                writer.submit(
                    step,
                    lambda: self._flush_job(step, flat, meta, registry),
                )

    def _flush_job(self, step, flat, meta, registry):
        """Writer-thread half of an async save (shows up in the trace
        as a `flush` span on the writer's tid, overlapping the next
        training steps)."""
        with span("flush", step=step, backend="local", mode="async"):
            self._write_and_publish(step, flat, meta, registry)

    def _write_and_publish(self, step, flat, meta, registry=None):
        """Serialize -> fsync -> re-read + crc-verify -> atomic publish
        -> advance LATEST -> prune.  Any failure leaves the previous
        published state (and pointer) untouched."""
        t0 = time.perf_counter()
        manifest = _build_manifest(step, flat)
        tmp = os.path.join(
            self.directory,
            f".tmp-{step}-{os.getpid()}-{next(self._tmp_ids)}",
        )
        os.makedirs(tmp)
        try:
            with open(os.path.join(tmp, "state.npz"), "wb") as f:
                np.savez(f, **flat)
                f.flush()
                os.fsync(f.fileno())
            _write_json_fsync(os.path.join(tmp, "meta.json"), meta)
            _write_json_fsync(os.path.join(tmp, "manifest.json"), manifest)
            try:
                self._verify_dir(tmp, manifest)
            except CheckpointVerifyError:
                if registry is not None:
                    registry.counter("resilience/ckpt_verify_failures").inc()
                raise
            final = self._path(step)
            if os.path.exists(final):
                # a restored run replaying past an old cadence point
                # re-saves the same step; the fresh write wins
                shutil.rmtree(final)
            os.replace(tmp, final)
            _fsync_dir(self.directory)
        except BaseException:
            shutil.rmtree(tmp, ignore_errors=True)
            raise
        self._latest.advance(step)
        self._prune()
        if registry is not None:
            registry.histogram("resilience/ckpt_write_latency_s").observe(
                time.perf_counter() - t0
            )
        self._offload_published(step)

    def _offload_published(self, step: int, force: bool = False) -> bool:
        """Hand one just-published (verified) step to the offload tier.
        The bytes are re-read from the published dir so the mirror
        uploads exactly what write-time verification passed.  Runs on
        the async writer thread for wait=False saves — already off the
        step path — and never raises into the publish (the local tier
        must stay intact even when the mirror is broken)."""
        if self.offloader is None:
            return False
        final = self._path(step)
        try:
            files = {}
            for name in ("state.npz", "meta.json", "manifest.json"):
                with open(os.path.join(final, name), "rb") as f:
                    files[name] = f.read()
        except OSError as e:  # pruned/raced away: the mirror skips it
            _log.warning(
                "offload of step %d skipped: published files unreadable "
                "(%s)", step, e,
            )
            return False
        return self.offloader.maybe_submit(step, files, force=force)

    def offload_step(self, step: int) -> bool:
        """Force-mirror one published step regardless of cadence (the
        supervisor's emergency-save path: the last checkpoint before a
        preemption must reach the durable tier)."""
        return self._offload_published(step, force=True)

    @staticmethod
    def _verify_dir(path: str, manifest: Optional[Dict] = None) -> Dict:
        """Re-read a checkpoint dir and check every leaf against its
        manifest crc32; raises CheckpointVerifyError on any mismatch.
        Returns the manifest used."""
        if manifest is None:
            with open(os.path.join(path, "manifest.json")) as f:
                manifest = json.load(f)
        with np.load(os.path.join(path, "state.npz")) as data:
            for key, spec in manifest["leaves"].items():
                if key not in data.files:
                    raise CheckpointVerifyError(
                        f"{path}: leaf {key!r} in manifest but not in "
                        "state.npz"
                    )
                crc = _leaf_crc(data[key])
                if crc != spec["crc32"]:
                    raise CheckpointVerifyError(
                        f"{path}: leaf {key!r} crc32 {crc:#010x} != "
                        f"manifest {spec['crc32']:#010x}"
                    )
            # restore refuses leaves the manifest can't vouch for, so
            # verification must too — a step with extra npz leaves
            # would verify green here and then fail to restore
            for key in data.files:
                if key not in manifest["leaves"]:
                    raise CheckpointVerifyError(
                        f"{path}: leaf {key!r} in state.npz but missing "
                        "from the manifest (unverifiable)"
                    )
        return manifest

    def drain(self) -> List[Tuple[int, Exception]]:
        """Wait for every pending async save to publish (or fail);
        returns the accumulated (step, error) failures."""
        if self._writer is None:
            return []
        return self._writer.drain()

    def _prune(self):
        steps = self.all_steps()
        keep = set(steps[-self.max_to_keep:])
        # the newest VERIFIED checkpoint is the durability floor: never
        # prune it, even when newer (legacy/unverified) steps push it
        # out of the retention window
        verified = self.latest_verified_step()
        if verified is not None:
            keep.add(verified)
        for s in steps:
            if s not in keep:
                shutil.rmtree(self._path(s), ignore_errors=True)

    # -- restore --------------------------------------------------------
    def _remote_steps(self) -> List[int]:
        """Steps the remote tier claims to hold; empty when no remote
        is configured or the remote is unreachable (restore then runs
        local-only — the mirror is an upgrade, never a dependency)."""
        if self.remote is None:
            return []
        try:
            return self.remote.list_steps()
        except Exception as e:  # noqa: BLE001 — any store failure
            _log.warning(
                "remote checkpoint tier unlistable (%s); restoring from "
                "the local tier only", e,
            )
            return []

    def _materialize_remote(self, step: int) -> None:
        """Download one remote step, crc-verify the downloaded bytes in
        a staging dir, and atomically publish it as a LOCAL step dir —
        after this the normal local load path (and every later restore)
        serves it.  A torn/corrupt remote copy never lands locally."""
        files = self.remote.download_step(step)
        tmp = os.path.join(
            self.directory,
            f".tmp-remote-{step}-{os.getpid()}-{next(self._tmp_ids)}",
        )
        os.makedirs(tmp)
        try:
            for name, data in files.items():
                with open(os.path.join(tmp, name), "wb") as f:
                    f.write(data)
                    f.flush()
                    os.fsync(f.fileno())
            self._verify_dir(tmp)
            with open(os.path.join(tmp, "meta.json")) as f:
                json.load(f)  # must parse before the dir can publish
            final = self._path(step)
            if os.path.exists(final):
                # the corrupt local copy loses to its verified mirror
                shutil.rmtree(final)
            os.replace(tmp, final)
            _fsync_dir(self.directory)
        except BaseException:
            shutil.rmtree(tmp, ignore_errors=True)
            raise
        self._latest.advance(step)

    def restore(self, ff, step: Optional[int] = None) -> int:
        """Load a step (default: latest, falling back past corrupt or
        incompatible ones) into a compiled FFModel, re-verifying the
        crc32 manifest and resharding every leaf onto the current
        executor's shardings.  Returns the restored step.

        With a remote tier configured the walk is PER CHECKPOINT,
        local -> remote: a corrupt/missing local step falls back to its
        verified remote mirror (downloaded + re-verified + materialized
        locally) before any progress is given up to an older step — a
        brand-new empty directory restores entirely from remote."""
        from jax.tree_util import tree_unflatten

        strict = step is not None
        local_steps = set(self.all_steps())
        remote_steps = set(self._remote_steps())
        candidates = ([step] if strict
                      else sorted(local_steps | remote_steps, reverse=True))
        if not candidates:
            where = f"no checkpoints in {self.directory}"
            if self.remote is not None:
                where += " (remote tier empty too)"
            raise FileNotFoundError(where)
        last_err: Optional[Exception] = None
        registry = registry_of(ff)
        for s in candidates:
            from_remote = False
            try:
                if s in local_steps or (strict and s not in remote_steps):
                    try:
                        new_leaves, treedef = self._load_step(ff, s)
                    except CheckpointCompatibilityError:
                        raise  # the mirror is byte-identical: same result
                    except Exception as e:
                        if self.remote is None or s not in remote_steps:
                            raise
                        _log.warning(
                            "local step %d unrestorable (%s); trying its "
                            "remote mirror", s, e,
                        )
                        self._materialize_remote(s)
                        new_leaves, treedef = self._load_step(ff, s)
                        from_remote = True
                else:
                    self._materialize_remote(s)
                    new_leaves, treedef = self._load_step(ff, s)
                    from_remote = True
            except Exception as e:  # unreadable/partial -> previous step
                if strict:
                    raise
                _log.warning(
                    "checkpoint step %d in %s unrestorable (%s); "
                    "falling back to the previous step", s, self.directory, e,
                )
                last_err = e
                continue
            if last_err is not None:
                _log.warning(
                    "restored OLDER step %d from %s — newer step(s) were "
                    "corrupt/partial, their progress is lost",
                    s, self.directory,
                )
                # newer steps failed verification: re-point LATEST at
                # the step that actually restored
                self._latest.advance(s, force=True)
            if from_remote:
                _log.info(
                    "step %d restored from the remote tier into %s",
                    s, self.directory,
                )
                if registry is not None:
                    registry.counter(
                        "resilience/offload_remote_restores"
                    ).inc()
            restored = tree_unflatten(treedef, new_leaves)
            ff._weights = restored["weights"]
            ff._opt_state = restored["opt_state"]
            ff._state = restored["op_state"]
            ff._rng = jax.random.wrap_key_data(restored["rng"])
            if hasattr(ff, "sync_decode_pos"):
                ff.sync_decode_pos()
            return int(s)
        raise last_err

    def _load_step(self, ff, step: int):
        """Read + verify + validate one step dir; returns (leaves,
        treedef) device_put onto the current shardings."""
        from jax.tree_util import keystr, tree_flatten_with_path

        with open(os.path.join(self._path(step), "meta.json")) as f:
            meta = json.load(f)  # meta must parse for the step to count
        manifest = None
        manifest_path = os.path.join(self._path(step), "manifest.json")
        if os.path.exists(manifest_path):  # absent in pre-manifest ckpts
            with open(manifest_path) as f:
                manifest = json.load(f)
        with np.load(os.path.join(self._path(step), "state.npz")) as data:
            # one decompression per leaf: each data[key] access re-reads
            arrays = {key: data[key] for key in data.files}
        return _verify_adapt_put(
            ff, self._state_tree(ff), arrays, manifest, meta, step
        )

    def restore_meta(self, step: Optional[int] = None) -> Dict[str, Any]:
        if step is None:
            step = self.latest_step()
            if step is None:
                raise FileNotFoundError(f"no checkpoints in {self.directory}")
        with open(os.path.join(self._path(step), "meta.json")) as f:
            return dict(json.load(f))

    def close(self):
        if self._writer is not None:
            self._writer.close()
            self._writer = None


# -- plain numpy weight files (reference-parity path) -------------------

def save_weights_npz(ff, path: str):
    """Weights-only flat .npz (the reference's manual numpy round-trip,
    flexflow_cffi.py Tensor get_weights)."""
    flat = {}
    for op_name, wdict in ff.get_weights().items():
        for wname, arr in wdict.items():
            flat[f"{op_name}/{wname}"] = np.asarray(arr)
    np.savez(path, **flat)


def load_weights_npz(ff, path: str):
    data = np.load(path)
    nested: Dict[str, Dict[str, np.ndarray]] = {}
    for key in data.files:
        op_name, wname = key.rsplit("/", 1)
        nested.setdefault(op_name, {})[wname] = data[key]
    ff.set_weights(nested)


class ModelCheckpoint:
    """Keras-style callback saving every epoch via CheckpointManager.

    async_save=True uses wait=False saves (the epoch boundary stalls
    only for the snapshot); `fit` drains the manager on every exit so a
    crash mid-epoch still lands the last queued save."""

    def __init__(self, directory: str, max_to_keep: int = 3,
                 async_save: bool = False):
        self.manager = CheckpointManager(directory, max_to_keep=max_to_keep)
        self.async_save = async_save

    def on_train_begin(self, ffmodel):
        pass

    def on_epoch_end(self, ffmodel, epoch: int, metrics):
        self.manager.save(ffmodel, epoch, wait=not self.async_save)

    def on_train_end(self, ffmodel):
        self.manager.close()
