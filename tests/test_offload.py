"""Durable offload tier tests: the blob-store abstraction + fault
wrapper, the REMOTE_LATEST verify-then-advance protocol, the upload
fault matrix (partial/transient/unavailable), two-tier restore
fallback, the strategy-store fleet mirror, the cross-host preemption
barrier, and the full host-loss drill — all hermetic on the 8-device
CPU mesh with a filesystem blob backend.
"""
import io
import json
import os
import shutil
import threading
import zipfile
import zlib

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
from flexflow_tpu.checkpoint import LocalCheckpointManager
from flexflow_tpu.distributed import preemption_barrier
from flexflow_tpu.fftype import ActiMode
from flexflow_tpu.optimizer import AdamOptimizer
from flexflow_tpu.resilience import (
    CheckpointOffloader,
    Fault,
    FaultKind,
    FaultPlan,
    RemoteCheckpointStore,
    RemoteVerifyError,
    RetryPolicy,
    TrainingSupervisor,
)
from flexflow_tpu.store.blobstore import (
    BlobNotFound,
    BlobPreconditionFailed,
    BlobUnavailableError,
    FaultyBlobStore,
    LocalBlobStore,
    blobstore_from_uri,
)

NO_SLEEP = lambda s: None  # noqa: E731


def _model(devices, seed=0, optimizer=None, **cfg_over):
    cfg = FFConfig(batch_size=16, num_devices=len(devices), seed=seed,
                   **cfg_over)
    ff = FFModel(cfg)
    x = ff.create_tensor([16, 8], name="x")
    t = ff.dense(x, 32, activation=ActiMode.RELU)
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



def _offloader(blob, **kw):
    kw.setdefault("retry", RetryPolicy(max_restarts=3, base_backoff=0.0))
    kw.setdefault("sleep", NO_SLEEP)
    return CheckpointOffloader(RemoteCheckpointStore(blob), **kw)


def _fake_step_files(step, value=1.0):
    arr = np.full(8, value, dtype=np.float32)
    buf = io.BytesIO()
    np.savez(buf, **{"['weights']['d']['k']": arr})
    state = buf.getvalue()
    manifest = {
        "manifest_version": 1,
        "step": step,
        "leaves": {
            "['weights']['d']['k']": {
                "crc32": zlib.crc32(
                    np.ascontiguousarray(arr).view(np.uint8).reshape(-1)
                ),
                "bytes": int(arr.nbytes),
                "shape": [8],
                "dtype": "float32",
            }
        },
    }
    return {
        "state.npz": state,
        "meta.json": json.dumps({"step": step}).encode(),
        "manifest.json": json.dumps(manifest).encode(),
    }


# -- blob store units ----------------------------------------------------

def test_local_blobstore_round_trip(tmp_path):
    b = LocalBlobStore(str(tmp_path))
    gen = b.put("ckpt/a.bin", b"hello")
    assert gen == 1
    assert b.get("ckpt/a.bin") == b"hello"
    assert b.list("") == ["ckpt/a.bin"]
    assert b.list("ckpt/") == ["ckpt/a.bin"]
    assert b.list("other/") == []
    info = b.stat("ckpt/a.bin")
    assert info.size == 5 and info.generation == 1
    assert b.delete("ckpt/a.bin") is True
    assert b.delete("ckpt/a.bin") is False
    assert b.stat("ckpt/a.bin") is None
    with pytest.raises(BlobNotFound):
        b.get("ckpt/a.bin")


def test_local_blobstore_conditional_put(tmp_path):
    b = LocalBlobStore(str(tmp_path))
    # create-if-absent
    gen = b.put("p", b"v1", if_generation_match=0)
    assert gen == 1
    with pytest.raises(BlobPreconditionFailed):
        b.put("p", b"v2", if_generation_match=0)
    gen = b.put("p", b"v2", if_generation_match=gen)
    assert gen == 2 and b.get("p") == b"v2"
    with pytest.raises(BlobPreconditionFailed):
        b.put("p", b"v3", if_generation_match=1)


def test_local_blobstore_rejects_bad_keys(tmp_path):
    b = LocalBlobStore(str(tmp_path))
    for bad in ("", "/abs", "a//b", "a/../b", "trailing/"):
        with pytest.raises(ValueError):
            b.put(bad, b"x")


def test_blobstore_from_uri(tmp_path):
    assert isinstance(blobstore_from_uri(str(tmp_path)), LocalBlobStore)
    s = blobstore_from_uri(f"file://{tmp_path}")
    assert isinstance(s, LocalBlobStore) and s.root == str(tmp_path)
    with pytest.raises(NotImplementedError):
        blobstore_from_uri("gs://bucket/prefix")


# -- fault wrapper -------------------------------------------------------

def test_faulty_blobstore_transient_fires_once(tmp_path):
    plan = FaultPlan.single(1, FaultKind.BLOB_TRANSIENT)
    b = FaultyBlobStore(LocalBlobStore(str(tmp_path)), plan, sleep=NO_SLEEP)
    with pytest.raises(BlobUnavailableError):
        b.put("k", b"v")
    # transient: the retry succeeds and the object lands intact
    b.put("k", b"v")
    assert b.get("k") == b"v"
    assert b.counters["transient_errors"] == 1


def test_faulty_blobstore_partial_upload_truncates(tmp_path):
    plan = FaultPlan.single(1, FaultKind.BLOB_PARTIAL_UPLOAD, fraction=0.25)
    b = FaultyBlobStore(LocalBlobStore(str(tmp_path)), plan, sleep=NO_SLEEP)
    b.put("k", b"x" * 100)  # lands TRUNCATED, no error raised
    assert len(b.get("k")) == 25
    assert b.counters["partial_uploads"] == 1
    b.put("k", b"y" * 100)  # fault fired once; full bytes now
    assert len(b.get("k")) == 100


def test_faulty_blobstore_unavailability_window(tmp_path):
    plan = FaultPlan.single(2, FaultKind.BLOB_UNAVAILABLE, ops=3)
    b = FaultyBlobStore(LocalBlobStore(str(tmp_path)), plan, sleep=NO_SLEEP)
    b.put("a", b"1")  # op 1: before the window
    for _ in range(4):  # op 2 opens the window; ops 3-5 inside it
        with pytest.raises(BlobUnavailableError):
            b.put("b", b"2")
    b.put("b", b"2")  # window over
    assert b.counters["unavailable_rejections"] == 4


def test_faulty_blobstore_latency_calls_sleep(tmp_path):
    slept = []
    plan = FaultPlan.single(1, FaultKind.BLOB_LATENCY, delay_s=0.123)
    b = FaultyBlobStore(LocalBlobStore(str(tmp_path)), plan,
                        sleep=slept.append)
    b.put("k", b"v")
    assert slept == [0.123]
    assert b.counters["latency_injections"] == 1


# -- FaultPlan support for the new kinds (satellite) ---------------------

def test_fault_plan_blob_kinds_round_trip():
    plan = FaultPlan([
        Fault(step=3, kind=FaultKind.BLOB_PARTIAL_UPLOAD,
              payload={"fraction": 0.25}),
        Fault(step=5, kind=FaultKind.BLOB_UNAVAILABLE, payload={"ops": 7}),
        Fault(step=1, kind=FaultKind.BLOB_TRANSIENT),
        Fault(step=2, kind=FaultKind.BLOB_LATENCY,
              payload={"delay_s": 0.5}),
    ])
    loaded = FaultPlan.from_json(plan.to_json())
    assert [(f.step, f.kind, f.payload) for f in loaded.faults] == \
        [(f.step, f.kind, f.payload) for f in plan.faults]
    single = FaultPlan.single(4, FaultKind.BLOB_PARTIAL_UPLOAD, fraction=0.1)
    reloaded = FaultPlan.from_json(single.to_json())
    assert reloaded.faults[0].kind == FaultKind.BLOB_PARTIAL_UPLOAD
    assert reloaded.faults[0].payload == {"fraction": 0.1}


def test_fault_plan_seeded_supports_blob_kinds():
    kinds = (FaultKind.BLOB_TRANSIENT, FaultKind.BLOB_UNAVAILABLE)
    a = FaultPlan.seeded(seed=7, num_steps=30, kinds=kinds, count=4)
    b = FaultPlan.seeded(seed=7, num_steps=30, kinds=kinds, count=4)
    assert [(f.step, f.kind) for f in a.faults] == \
        [(f.step, f.kind) for f in b.faults]
    assert all(f.kind in kinds for f in a.faults)
    assert a.blob_faults() == a.faults


def test_fault_plan_offload_target_separation():
    """CheckpointWriteFault with target=remote fires only on the
    uploader path; the plain kind only on local saves."""
    from flexflow_tpu.resilience import CheckpointWriteFault

    plan = FaultPlan([
        Fault(step=2, kind=FaultKind.CHECKPOINT_WRITE),
        Fault(step=2, kind=FaultKind.CHECKPOINT_WRITE,
              payload={"target": "remote"}),
    ])
    plan.check_offload(1)  # before either fault's step: silent
    with pytest.raises(CheckpointWriteFault):
        plan.check_checkpoint(2)
    plan.check_checkpoint(3)  # local fault spent; remote one untouched
    with pytest.raises(CheckpointWriteFault):
        plan.check_offload(2)
    plan.check_offload(3)  # both spent
    assert plan.remaining() == []


# -- REMOTE_LATEST protocol ---------------------------------------------

def test_remote_store_upload_verify_advance(tmp_path):
    r = RemoteCheckpointStore(LocalBlobStore(str(tmp_path)))
    assert r.list_steps() == [] and r.latest_verified_step() is None
    r.upload_step(2, _fake_step_files(2))
    r.upload_step(4, _fake_step_files(4))
    assert r.list_steps() == [2, 4]
    assert r.latest_verified_step() == 4
    # pointer is monotonic: re-uploading an older step can't regress it
    r.advance_latest(2)
    assert r.latest_verified_step() == 4
    man = r.verify_step(4)
    assert man["step"] == 4


def test_remote_store_partial_upload_never_advances_pointer(tmp_path):
    """Acceptance: a seeded partial/truncated upload leaves
    REMOTE_LATEST on the previous verified step, and the corrupted
    remote step is quarantined as a miss."""
    blob = LocalBlobStore(str(tmp_path))
    r = RemoteCheckpointStore(blob)
    r.upload_step(2, _fake_step_files(2))
    assert r.latest_verified_step() == 2
    # op 1 of the NEXT upload is state.npz: truncate it
    faulty = FaultyBlobStore(
        blob, FaultPlan.single(1, FaultKind.BLOB_PARTIAL_UPLOAD),
        sleep=NO_SLEEP,
    )
    rf = RemoteCheckpointStore(faulty)
    with pytest.raises(RemoteVerifyError):
        rf.upload_step(4, _fake_step_files(4))
    assert faulty.counters["partial_uploads"] == 1
    # pointer still on the previous verified step; step 4 quarantined
    assert r.latest_verified_step() == 2
    assert r.list_steps() == [2]
    assert blob.list("ckpt/step_00000004/") == []


def test_remote_store_prune_keeps_pointer_step(tmp_path):
    r = RemoteCheckpointStore(LocalBlobStore(str(tmp_path)))
    for s in (2, 4, 6, 8):
        r.upload_step(s, _fake_step_files(s))
    r.prune(keep=2)
    assert r.list_steps() == [6, 8]
    # pointer step survives pruning even out of the retention window
    r.advance_latest(6, force=True)
    r.prune(keep=1)
    assert 6 in r.list_steps() and r.list_steps()[-1] == 8


# -- offloader through the supervisor ------------------------------------

def test_supervised_run_mirrors_checkpoints(devices8, tmp_path):
    blob = LocalBlobStore(str(tmp_path / "remote"))
    ff = _model(devices8)
    sup = TrainingSupervisor(
        ff, str(tmp_path / "ckpt"), checkpoint_every=2,
        offloader=_offloader(blob), sleep=NO_SLEEP,
    )
    xs, ys = _data(128)
    rep = sup.run(xs, ys, num_steps=6)
    assert rep.final_step == 6
    remote = RemoteCheckpointStore(blob)
    # anchor (0) is mirrored too; keep-last-3 remote retention
    assert remote.latest_verified_step() == 6
    assert rep.counters["offload_uploads"] >= 3
    assert rep.counters["offload_failures"] == 0
    assert rep.counters["offload_bytes"] > 0


def test_offload_cadence_and_keep(devices8, tmp_path):
    blob = LocalBlobStore(str(tmp_path / "remote"))
    ff = _model(devices8)
    sup = TrainingSupervisor(
        ff, str(tmp_path / "ckpt"), checkpoint_every=1,
        offloader=_offloader(blob, every=2, keep=2), sleep=NO_SLEEP,
    )
    xs, ys = _data(128)
    rep = sup.run(xs, ys, num_steps=6)
    assert rep.final_step == 6
    remote = RemoteCheckpointStore(blob)
    steps = remote.list_steps()
    assert len(steps) <= 3  # keep=2 plus possibly the pointer step
    # every=2: half the publishes mirrored (anchor + every other step)
    assert rep.counters["offload_uploads"] <= 4


def test_unavailability_degrades_to_local_only(devices8, tmp_path):
    """Acceptance: an unavailability window degrades to local-only
    with a counter — it never stalls or fails the training run."""
    blob = FaultyBlobStore(
        LocalBlobStore(str(tmp_path / "remote")),
        FaultPlan.single(1, FaultKind.BLOB_UNAVAILABLE, ops=10_000),
        sleep=NO_SLEEP,
    )
    ff = _model(devices8)
    sup = TrainingSupervisor(
        ff, str(tmp_path / "ckpt"), checkpoint_every=2,
        offloader=_offloader(
            blob, retry=RetryPolicy(max_restarts=1, base_backoff=0.0),
        ),
        sleep=NO_SLEEP,
    )
    xs, ys = _data(128)
    rep = sup.run(xs, ys, num_steps=6)
    assert rep.final_step == 6  # the run NEVER stalls on the mirror
    assert rep.counters["offload_unavailable"] >= 1
    assert rep.counters["offload_uploads"] == 0
    # local tier is intact: restore still works
    assert sup.manager.latest_verified_step() == 6


def test_transient_upload_errors_retry_within_budget(devices8, tmp_path):
    blob = FaultyBlobStore(
        LocalBlobStore(str(tmp_path / "remote")),
        FaultPlan([
            Fault(step=1, kind=FaultKind.BLOB_TRANSIENT),
            Fault(step=4, kind=FaultKind.BLOB_TRANSIENT),
        ]),
        sleep=NO_SLEEP,
    )
    ff = _model(devices8)
    sup = TrainingSupervisor(
        ff, str(tmp_path / "ckpt"), checkpoint_every=2,
        offloader=_offloader(blob), sleep=NO_SLEEP,
    )
    xs, ys = _data(128)
    rep = sup.run(xs, ys, num_steps=6)
    assert rep.final_step == 6
    assert rep.counters["offload_retries"] >= 1
    assert rep.counters["offload_failures"] == 0
    assert RemoteCheckpointStore(blob.inner).latest_verified_step() == 6


def test_uploader_checkpoint_write_fault_retries(devices8, tmp_path):
    """Satellite: CheckpointWriteFault injection covers the uploader
    path (target=remote) without touching local saves."""
    blob = LocalBlobStore(str(tmp_path / "remote"))
    plan = FaultPlan([
        Fault(step=2, kind=FaultKind.CHECKPOINT_WRITE,
              payload={"target": "remote"}),
    ])
    ff = _model(devices8)
    sup = TrainingSupervisor(
        ff, str(tmp_path / "ckpt"), checkpoint_every=2, fault_plan=plan,
        offloader=_offloader(blob, fault_plan=plan), sleep=NO_SLEEP,
    )
    xs, ys = _data(128)
    rep = sup.run(xs, ys, num_steps=6)
    assert rep.final_step == 6
    # local saves never failed; the upload retried past the injection
    assert rep.counters["checkpoint_failures"] == 0
    assert rep.counters["offload_retries"] >= 1
    assert RemoteCheckpointStore(blob).latest_verified_step() == 6


# -- two-tier restore ----------------------------------------------------

def test_restore_prefers_local_falls_back_per_checkpoint(devices8, tmp_path):
    """Acceptance: restore prefers local bytes; a corrupt local step
    falls back to ITS remote mirror (same step — no progress lost)
    rather than an older local step."""
    blob = LocalBlobStore(str(tmp_path / "remote"))
    ckpt = str(tmp_path / "ckpt")
    ff = _model(devices8)
    sup = TrainingSupervisor(ff, ckpt, checkpoint_every=2,
                             offloader=_offloader(blob), sleep=NO_SLEEP)
    xs, ys = _data(128)
    sup.run(xs, ys, num_steps=6)
    w6 = ff.get_weights()
    # corrupt the newest LOCAL step: the last byte of a named leaf's
    # data, where the leaf's crc (and the archive's own) sees it
    state = os.path.join(ckpt, "step_00000006", "state.npz")
    with zipfile.ZipFile(state) as zf:
        leaf = next(i for i in zf.infolist() if "weights" in i.filename)
        with zf.open(leaf) as member:
            member.read()
            end_of_data = zf.fp.tell()
    blob_bytes = bytearray(open(state, "rb").read())
    blob_bytes[end_of_data - 1] ^= 0xFF
    with open(state, "wb") as f:
        f.write(bytes(blob_bytes))
    mgr = LocalCheckpointManager(
        ckpt, offloader=None, remote=RemoteCheckpointStore(blob),
    )
    step = mgr.restore(ff)
    assert step == 6  # the SAME step, served by the mirror
    weights_equal(ff.get_weights(), w6)
    # and the mirror's verified bytes were re-materialized locally
    assert LocalCheckpointManager(ckpt).restore(ff) == 6


def test_run_end_mirrors_the_checkpoint_a_saturated_uploader_skipped(
        devices8, tmp_path):
    """A cadence point that finds the uploader saturated is skipped, so
    that the step loop never waits for the mirror; the run's end is the
    last cadence point, and what was skipped newest is mirrored there.
    (The flicker of the case above until PR 31: under load the upload of
    step 6 was skipped, and the corrupt local step fell back to step 4.)
    The store is held shut until step 6 has been offered."""
    offered = threading.Event()

    class GatedBlob(LocalBlobStore):
        def put(self, *a, **kw):
            assert offered.wait(60.0)
            return super().put(*a, **kw)

    blob = GatedBlob(str(tmp_path / "remote"))
    off = _offloader(blob)
    submit = off.maybe_submit

    def watched(step, files, force=False):
        queued = submit(step, files, force=force)
        if step == 6:
            offered.set()
        return queued

    off.maybe_submit = watched
    ff = _model(devices8)
    sup = TrainingSupervisor(ff, str(tmp_path / "ckpt"), checkpoint_every=2,
                             offloader=off, sleep=NO_SLEEP)
    xs, ys = _data(128)
    rep = sup.run(xs, ys, num_steps=6)
    # steps 0 and 2 filled the queue; 4 and 6 found it full
    assert rep.counters["offload_skipped"] == 2
    remote = RemoteCheckpointStore(blob)
    assert remote.list_steps() == [0, 2, 6]
    assert remote.latest_verified_step() == 6


def test_fresh_host_restores_from_remote_only(devices8, tmp_path):
    blob = LocalBlobStore(str(tmp_path / "remote"))
    ff = _model(devices8)
    sup = TrainingSupervisor(ff, str(tmp_path / "ckpt"), checkpoint_every=2,
                             offloader=_offloader(blob), sleep=NO_SLEEP)
    xs, ys = _data(128)
    sup.run(xs, ys, num_steps=4)
    w4 = ff.get_weights()
    # a brand-new host: fresh model, EMPTY local directory
    ff2 = _model(devices8)
    mgr = LocalCheckpointManager(str(tmp_path / "fresh"),
                                 remote=RemoteCheckpointStore(blob))
    assert mgr.any_restorable()
    step = mgr.restore(ff2)
    assert step == 4
    weights_equal(ff2.get_weights(), w4)


def test_orbax_restore_prefers_newer_remote_step(devices8, tmp_path):
    """The orbax manager's default restore walks BOTH tiers newest
    first: an older local step must not win over a newer verified
    remote-only mirror (progress would silently be lost)."""
    from flexflow_tpu.checkpoint import CheckpointManager

    blob = LocalBlobStore(str(tmp_path / "remote"))
    ff = _model(devices8)
    sup = TrainingSupervisor(ff, str(tmp_path / "ckpt"), checkpoint_every=2,
                             offloader=_offloader(blob), sleep=NO_SLEEP)
    xs, ys = _data(128)
    sup.run(xs, ys, num_steps=6)  # the mirror holds steps 2, 4, 6
    w6 = ff.get_weights()
    # an orbax directory that only ever saw step 2 (stale local tier)
    ff2 = _model(devices8, seed=1)
    mgr = CheckpointManager(str(tmp_path / "oc"),
                            remote=RemoteCheckpointStore(blob))
    mgr.save(ff2, step=2)
    step = mgr.restore(ff2)
    assert step == 6  # the newer remote-only step wins
    weights_equal(ff2.get_weights(), w6)
    mgr.close()


def test_host_loss_drill_bit_identical(devices8, tmp_path):
    """THE acceptance drill: train with offload under a seeded mid-run
    upload fault, destroy the entire local checkpoint directory, resume
    on a fresh directory from the remote tier, and continue to weights
    BIT-IDENTICAL to an uninterrupted run — including ZeRO-1 sharded
    Adam optimizer slots."""
    def make_model():
        return _model(devices8, optimizer=AdamOptimizer(alpha=0.01),
                      weight_update_sharding=True)

    xs, ys = _data(128)
    # the uninterrupted reference: 8 steps straight through
    ref = make_model()
    ref_sup = TrainingSupervisor(ref, str(tmp_path / "ref"),
                                 checkpoint_every=0, sleep=NO_SLEEP)
    ref_rep = ref_sup.run(xs, ys, num_steps=8)
    assert ref_rep.final_step == 8

    # host A: train 6 steps with offload, a transient fault mid-run
    blob_inner = LocalBlobStore(str(tmp_path / "remote"))
    blob = FaultyBlobStore(
        blob_inner, FaultPlan.single(4, FaultKind.BLOB_TRANSIENT),
        sleep=NO_SLEEP,
    )
    ckpt_a = str(tmp_path / "host_a")
    ff_a = make_model()
    sup_a = TrainingSupervisor(ff_a, ckpt_a, checkpoint_every=2,
                               offloader=_offloader(blob), sleep=NO_SLEEP)
    rep_a = sup_a.run(xs, ys, num_steps=6)
    assert rep_a.final_step == 6
    assert rep_a.counters["offload_uploads"] >= 3

    # the host dies: local checkpoints AND the model are gone
    shutil.rmtree(ckpt_a)
    del ff_a, sup_a

    # host B: brand-new process, EMPTY directory, same remote store
    ckpt_b = str(tmp_path / "host_b")
    ff_b = make_model()
    sup_b = TrainingSupervisor(ff_b, ckpt_b, checkpoint_every=2,
                               offloader=_offloader(blob_inner),
                               sleep=NO_SLEEP)
    rep_b = sup_b.run(xs, ys, num_steps=8, resume=True)
    assert rep_b.final_step == 8
    assert rep_b.counters["restarts"] == 0  # resume, not crash-recovery

    weights_equal(ff_b.get_weights(), ref.get_weights())
    # ZeRO-1 optimizer slots carried bit-identically too
    import jax

    weights_equal(
        jax.tree.map(np.asarray, ff_b._opt_state),
        jax.tree.map(np.asarray, ref._opt_state),
    )


# -- strategy store fleet mirror -----------------------------------------

def _searchable_model(devices, store_root, remote_uri, seed=0):
    cfg = FFConfig(batch_size=16, num_devices=len(devices), seed=seed,
                   search_budget=5, rewrite_depth=1, rewrite_max_variants=1,
                   strategy_store=store_root, remote_store=remote_uri)
    ff = FFModel(cfg)
    x = ff.create_tensor([16, 8], name="x")
    t = ff.dense(x, 32, activation=ActiMode.RELU)
    t = ff.dense(t, 4)
    ff.softmax(t)
    ff.compile(optimizer=SGDOptimizer(lr=0.05),
               loss_type=LossType.SPARSE_CATEGORICAL_CROSSENTROPY,
               devices=devices, seed=seed)
    return ff


def test_strategy_store_fleet_mirror_warms_fresh_host(devices8, tmp_path):
    remote_uri = str(tmp_path / "blob")
    # host A: cold compile pays the search, publishes locally AND through
    ff_a = _searchable_model(devices8, str(tmp_path / "store_a"),
                             remote_uri)
    assert not ff_a.strategy.search_stats.get("store_hit")
    blob = LocalBlobStore(remote_uri)
    assert any(k.startswith("strategies/") for k in blob.list(""))
    # host B: EMPTY local store, warms from the fleet mirror — no search
    ff_b = _searchable_model(devices8, str(tmp_path / "store_b"),
                             remote_uri)
    stats = ff_b.strategy.search_stats
    assert stats.get("store_hit") and stats.get("store_remote_hit")
    assert ff_b.strategy.to_json() == ff_a.strategy.to_json()
    # the remote hit materialized locally: a third compile on host B's
    # store is a plain LOCAL hit
    ff_b2 = _searchable_model(devices8, str(tmp_path / "store_b"),
                              remote_uri)
    assert ff_b2.strategy.search_stats.get("store_hit")
    assert not ff_b2.strategy.search_stats.get("store_remote_hit")


def test_fleet_mirror_best_cost_upgrade(tmp_path):
    from flexflow_tpu.store.store import RemoteStrategyMirror

    blob = LocalBlobStore(str(tmp_path))
    mirror = RemoteStrategyMirror(blob)
    from flexflow_tpu.store.key import strategy_sha256
    from flexflow_tpu.strategy import Strategy

    def manifest_for(text, cost):
        return {
            "manifest_version": 1,
            "key_digest": "d" * 64,
            "strategy_sha256": strategy_sha256(text),
            "searched_cost": cost,
            "search_stats": {},
            "created_at": 1.0,
        }

    t1 = Strategy(mesh_axes={"data": 4}).to_json()
    t2 = Strategy(mesh_axes={"data": 8}).to_json()
    assert mirror.push("d" * 64, manifest_for(t1, 10.0), t1) is True
    # equal/worse costs lose to the incumbent
    assert mirror.push("d" * 64, manifest_for(t2, 10.0), t2) is False
    assert mirror.push("d" * 64, manifest_for(t2, 11.0), t2) is False
    # strictly better replaces
    assert mirror.push("d" * 64, manifest_for(t2, 9.0), t2) is True
    manifest, text = mirror.fetch("d" * 64)
    assert manifest["searched_cost"] == 9.0 and text == t2


def test_fleet_mirror_torn_pair_quarantined(tmp_path):
    from flexflow_tpu.store.store import RemoteStrategyMirror

    blob = LocalBlobStore(str(tmp_path))
    mirror = RemoteStrategyMirror(blob)
    from flexflow_tpu.store.key import strategy_sha256
    from flexflow_tpu.strategy import Strategy

    text = Strategy(mesh_axes={"data": 4}).to_json()
    digest = "e" * 64
    mirror.push(digest, {
        "manifest_version": 1, "key_digest": digest,
        "strategy_sha256": strategy_sha256(text), "searched_cost": None,
        "search_stats": {}, "created_at": 1.0,
    }, text)
    # tear the pair: strategy bytes no longer match the manifest sha
    blob.put(f"strategies/{digest}/strategy.json", b"{garbage")
    assert mirror.fetch(digest) is None
    # quarantined: the whole entry is gone, a future push repairs it
    assert blob.list(f"strategies/{digest}/") == []


# -- preemption barrier --------------------------------------------------

def test_preemption_barrier_single_host_is_instant(tmp_path):
    blob = LocalBlobStore(str(tmp_path))
    assert preemption_barrier(blob, "run1", 7, host_id=0, num_hosts=1,
                              sleep=NO_SLEEP) == 7
    assert blob.list("barrier/") == []  # no rendezvous needed


def test_preemption_barrier_agrees_on_max_step(tmp_path):
    """Workers at steps 5/6/6 rendezvous; everyone commits 6 — the
    newest state any host holds (laggards run forward to it; nobody
    can rewind)."""
    blob = LocalBlobStore(str(tmp_path))
    # hosts 1 and 2 post first (simulated sequentially: their barrier
    # calls would block polling, so post their records directly)
    for host, step in ((1, 6), (2, 6)):
        blob.put(f"barrier/run2/host_{host:05d}",
                 json.dumps({"host": host, "step": step}).encode())
    agreed = preemption_barrier(blob, "run2", 5, host_id=0,
                                num_hosts=3, sleep=NO_SLEEP)
    assert agreed == 6


def test_preemption_barrier_cleared_between_incarnations(tmp_path):
    """A previous incarnation's posts must never satisfy a later
    quorum: the supervisor clears barrier/<run_id>/ at run() start."""
    from flexflow_tpu.distributed import clear_preemption_barrier

    blob = LocalBlobStore(str(tmp_path))
    for host in (0, 1):
        blob.put(f"barrier/runX/host_{host:05d}",
                 json.dumps({"host": host, "step": 100}).encode())
    assert clear_preemption_barrier(blob, "runX") == 2
    assert blob.list("barrier/runX/") == []
    # with the stale posts gone, a new rendezvous must time out (no
    # peer) instead of instantly agreeing on the obsolete step 100
    agreed = preemption_barrier(blob, "runX", 500, host_id=0, num_hosts=2,
                                timeout_s=0.05, poll_s=0.01)
    assert agreed == 500


def test_preemption_runs_forward_to_agreed_step(devices8, tmp_path,
                                                monkeypatch):
    """A host behind the fleet's agreed emergency step keeps stepping
    to it before the emergency save, so every host commits the SAME
    step (the barrier's whole point)."""
    blob = LocalBlobStore(str(tmp_path / "remote"))
    ff = _model(devices8)
    sup = TrainingSupervisor(ff, str(tmp_path / "ckpt"), checkpoint_every=2,
                             offloader=_offloader(blob), sleep=NO_SLEEP)
    rendezvous_at = []

    def fake_rendezvous(step):
        rendezvous_at.append(step)
        return step + 2  # the fleet is two steps ahead of this host

    monkeypatch.setattr(sup, "_preempt_rendezvous", fake_rendezvous)
    orig_step = ff.train_step
    calls = {"n": 0}

    def stepper(*a, **kw):
        calls["n"] += 1
        if calls["n"] == 2:  # "SIGTERM" lands mid-step-1
            sup._preempt = "SIGTERM"
        return orig_step(*a, **kw)

    monkeypatch.setattr(ff, "train_step", stepper)
    xs, ys = _data(128)
    rep = sup.run(xs, ys, num_steps=8)
    assert rep.preempted == "SIGTERM"
    assert rendezvous_at == [2]  # barrier ran once, at the notice step
    assert rep.final_step == 4   # ran FORWARD to the agreed step
    # the agreed emergency step is durable in BOTH tiers
    assert sup.manager.latest_verified_step() == 4
    assert RemoteCheckpointStore(blob).latest_verified_step() == 4


def test_preemption_on_final_step_still_posts_barrier(devices8, tmp_path,
                                                      monkeypatch):
    """A SIGTERM during the FINAL step exits the run loop before the
    top-of-loop rendezvous ever runs — the host must still post, or
    its peers stall to the barrier deadline and commit a divergent
    step."""
    blob = LocalBlobStore(str(tmp_path / "remote"))
    ff = _model(devices8)
    sup = TrainingSupervisor(ff, str(tmp_path / "ckpt"), checkpoint_every=2,
                             offloader=_offloader(blob), sleep=NO_SLEEP)
    rendezvous_at = []

    def fake_rendezvous(step):
        rendezvous_at.append(step)
        return step

    monkeypatch.setattr(sup, "_preempt_rendezvous", fake_rendezvous)
    orig_step = ff.train_step
    calls = {"n": 0}

    def stepper(*a, **kw):
        calls["n"] += 1
        if calls["n"] == 4:  # "SIGTERM" lands during the last step
            sup._preempt = "SIGTERM"
        return orig_step(*a, **kw)

    monkeypatch.setattr(ff, "train_step", stepper)
    xs, ys = _data(128)
    rep = sup.run(xs, ys, num_steps=4)
    assert rep.preempted == "SIGTERM"
    assert rep.final_step == 4
    assert rendezvous_at == [4]  # posted at loop exit, not skipped


def test_local_blobstore_oserror_wraps_unavailable(tmp_path):
    """Filesystem trouble surfaces as BlobUnavailableError from every
    verb, so `except BlobStoreError` handlers (the supervisor's barrier
    clear, the offloader's retry classifier) see it — a raw OSError
    would crash fit_resilient at run start."""
    blob = LocalBlobStore(str(tmp_path))
    # a directory squatting on the object path defeats put and delete
    (tmp_path / "ckpt" / "obj").mkdir(parents=True)
    with pytest.raises(BlobUnavailableError):
        blob.put("ckpt/obj", b"data")
    with pytest.raises(BlobUnavailableError):
        blob.delete("ckpt/obj")


def test_fleet_mirror_orphan_manifest_repaired(tmp_path):
    """A manifest without its strategy.json (a quarantine raced a
    concurrent push) must be quarantined on fetch — left in place,
    push()'s first-write-wins would honor the orphan forever and the
    key would be a permanent fleet-wide miss."""
    from flexflow_tpu.store.key import strategy_sha256
    from flexflow_tpu.store.store import RemoteStrategyMirror
    from flexflow_tpu.strategy import Strategy

    blob = LocalBlobStore(str(tmp_path))
    mirror = RemoteStrategyMirror(blob)
    text = Strategy(mesh_axes={"data": 4}).to_json()
    digest = "f" * 64
    manifest = {
        "manifest_version": 1, "key_digest": digest,
        "strategy_sha256": strategy_sha256(text), "searched_cost": None,
        "search_stats": {}, "created_at": 1.0,
    }
    blob.put(f"strategies/{digest}/manifest.json",
             json.dumps(manifest).encode())
    assert mirror.fetch(digest) is None
    assert blob.list(f"strategies/{digest}/") == []  # orphan quarantined
    assert mirror.push(digest, manifest, text) is True  # repair succeeds
    assert mirror.fetch(digest) == (manifest, text)


def test_force_resubmit_after_abandoned_upload(tmp_path):
    """An emergency force-mirror of a step whose earlier upload was
    abandoned (outage past the retry budget) must re-upload, not hit
    the queued-step dedupe."""
    inner = LocalBlobStore(str(tmp_path))
    faulty = FaultyBlobStore(
        inner, FaultPlan.single(1, FaultKind.BLOB_TRANSIENT),
        sleep=NO_SLEEP,
    )
    off = CheckpointOffloader(
        RemoteCheckpointStore(faulty),
        retry=RetryPolicy(max_restarts=0, base_backoff=0.0), sleep=NO_SLEEP,
    )
    files = _fake_step_files(4)
    assert off.maybe_submit(4, files) is True
    off.drain()
    assert off.counters["offload_failures"] == 1  # abandoned: zero budget
    assert RemoteCheckpointStore(inner).latest_verified_step() is None
    # the store recovers; the emergency force-mirror gets its retry
    assert off.maybe_submit(4, files, force=True) is True
    off.drain()
    assert RemoteCheckpointStore(inner).latest_verified_step() == 4
    # a force re-submit of an ALREADY-mirrored step is a no-op
    assert off.maybe_submit(4, files, force=True) is False


def test_barrier_timeout_threaded_from_config(devices8, tmp_path):
    ff = _model(devices8, barrier_timeout=2.5)
    sup = TrainingSupervisor(ff, str(tmp_path / "c"), sleep=NO_SLEEP)
    assert sup.barrier_timeout == 2.5


def test_force_submit_skips_already_queued_duplicate(tmp_path):
    """An emergency force-submit racing the cadence upload of the SAME
    step must not upload the payload twice — the duplicate job skips at
    execution time once the first lands verified (the grace window is
    too precious to re-upload identical bytes)."""
    inner = LocalBlobStore(str(tmp_path))
    off = CheckpointOffloader(
        RemoteCheckpointStore(inner),
        retry=RetryPolicy(max_restarts=3, base_backoff=0.0), sleep=NO_SLEEP,
    )
    files = _fake_step_files(2)
    assert off.maybe_submit(2, files) is True            # cadence upload
    assert off.maybe_submit(2, files, force=True) is True  # emergency
    off.drain()
    assert off.counters["offload_uploads"] == 1
    assert RemoteCheckpointStore(inner).latest_verified_step() == 2


def test_upload_rejects_unmanifested_leaf(tmp_path):
    """A state.npz leaf the manifest can't vouch for must fail the
    upload verify — restore refuses such a leaf, so blessing it would
    advance REMOTE_LATEST to a step that cannot actually restore."""
    store = RemoteCheckpointStore(LocalBlobStore(str(tmp_path)))
    files = _fake_step_files(3)
    with np.load(io.BytesIO(files["state.npz"])) as d:
        arrays = {k: d[k] for k in d.files}
    arrays["rogue"] = np.ones(3, np.float32)
    buf = io.BytesIO()
    np.savez(buf, **arrays)
    files["state.npz"] = buf.getvalue()
    with pytest.raises(RemoteVerifyError, match="rogue"):
        store.upload_step(3, files)
    assert store.latest_verified_step() is None


def test_preemption_barrier_times_out_conservatively(tmp_path):
    """A quorum that never completes returns the best agreement so far
    instead of hanging through the preemption deadline."""
    blob = LocalBlobStore(str(tmp_path))
    agreed = preemption_barrier(blob, "run3", 9, host_id=0, num_hosts=2,
                                timeout_s=0.05, poll_s=0.01)
    assert agreed == 9  # only our own post: agree with ourselves


# -- fsck tool -----------------------------------------------------------

def test_checkpoint_fsck_clean_and_corrupt(devices8, tmp_path):
    import importlib.util

    spec = importlib.util.spec_from_file_location(
        "checkpoint_fsck",
        os.path.join(os.path.dirname(__file__), "..", "tools",
                     "checkpoint_fsck.py"),
    )
    fsck = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(fsck)

    blob_root = str(tmp_path / "remote")
    ckpt = str(tmp_path / "ckpt")
    ff = _model(devices8)
    sup = TrainingSupervisor(ff, ckpt, checkpoint_every=2,
                             offloader=_offloader(LocalBlobStore(blob_root)),
                             sleep=NO_SLEEP)
    xs, ys = _data(128)
    sup.run(xs, ys, num_steps=4)

    assert fsck.main([ckpt, "--remote", blob_root]) == 0

    # corrupt one local leaf -> nonzero exit, the step named
    state = os.path.join(ckpt, "step_00000004", "state.npz")
    raw = bytearray(open(state, "rb").read())
    raw[len(raw) // 2] ^= 0xFF
    with open(state, "wb") as f:
        f.write(bytes(raw))
    assert fsck.main([ckpt, "--remote", blob_root]) == 1

    # dangling LATEST in an otherwise-empty dir
    empty = str(tmp_path / "empty")
    os.makedirs(empty)
    with open(os.path.join(empty, "LATEST"), "w") as f:
        f.write("42")
    assert fsck.main([empty]) == 1


# -- telemetry: the Durability summary section ---------------------------

def test_telemetry_summary_renders_durability_section(devices8, tmp_path):
    import subprocess
    import sys

    trace_dir = tmp_path / "trace"
    blob = LocalBlobStore(str(tmp_path / "remote"))
    ff = _model(devices8, trace_dir=str(trace_dir))
    offl = _offloader(blob, registry=ff.telemetry.metrics)
    sup = TrainingSupervisor(ff, str(tmp_path / "ckpt"), checkpoint_every=2,
                             offloader=offl, sleep=NO_SLEEP)
    xs, ys = _data(128)
    rep = sup.run(xs, ys, num_steps=4)
    assert rep.counters["offload_uploads"] >= 2
    ff.telemetry.flush()
    out = subprocess.run(
        [sys.executable,
         os.path.join(os.path.dirname(__file__), "..", "tools",
                      "telemetry_summary.py"),
         str(trace_dir)],
        capture_output=True, text=True, check=True,
    ).stdout
    assert "Durability" in out
    assert "offload_uploads" in out and "offload_bytes" in out
    assert "offload_upload_ms" in out


# -- per-leaf delta mirror (ISSUE 12 satellite) ---------------------------

def _multi_leaf_files(step, a_value, b_value):
    leaves = {"['weights']['a']['k']": np.full(8, a_value, np.float32),
              "['weights']['b']['k']": np.full(8, b_value, np.float32)}
    buf = io.BytesIO()
    np.savez(buf, **leaves)
    manifest = {
        "manifest_version": 1, "step": step,
        "leaves": {
            k: {
                "crc32": zlib.crc32(
                    np.ascontiguousarray(v).view(np.uint8).reshape(-1)
                ),
                "bytes": int(v.nbytes), "shape": [8], "dtype": "float32",
            }
            for k, v in leaves.items()
        },
    }
    return {
        "state.npz": buf.getvalue(),
        "meta.json": json.dumps({"step": step}).encode(),
        "manifest.json": json.dumps(manifest).encode(),
    }


def test_delta_mirror_skips_unchanged_leaves(tmp_path):
    """The second upload drops the leaf whose crc matched the previous
    mirrored step, annotates it in the remote manifest, and restore
    reassembles the FULL step bit-identically."""
    from flexflow_tpu.resilience.offload import RemoteCheckpointStore

    r = RemoteCheckpointStore(LocalBlobStore(str(tmp_path)))
    rep1 = r.upload_step(2, _multi_leaf_files(2, 1.0, 5.0))
    assert rep1.leaves_skipped == 0
    # leaf 'a' unchanged, leaf 'b' changed
    rep2 = r.upload_step(4, _multi_leaf_files(4, 1.0, 7.0),
                         base_step=2, base_manifest=rep1.manifest)
    assert rep2.leaves_skipped == 1
    assert rep2.bytes_uploaded < rep1.bytes_uploaded
    # the remote state.npz really lacks the unchanged leaf
    raw = r.blob.get(r._step_prefix(4) + "state.npz")
    with np.load(io.BytesIO(raw)) as data:
        assert list(data.files) == ["['weights']['b']['k']"]
    # verify passes (base vouches for the delta leaf)...
    man = r.verify_step(4)
    assert man["leaves"]["['weights']['a']['k']"]["base_step"] == 2
    # ...and download reassembles a SELF-CONTAINED full step
    files = r.download_step(4)
    with np.load(io.BytesIO(files["state.npz"])) as data:
        np.testing.assert_array_equal(
            data["['weights']['a']['k']"], np.full(8, 1.0, np.float32))
        np.testing.assert_array_equal(
            data["['weights']['b']['k']"], np.full(8, 7.0, np.float32))
    out_man = json.loads(files["manifest.json"])
    assert "base_step" not in out_man["leaves"]["['weights']['a']['k']"]


def test_delta_mirror_prune_keeps_referenced_base(tmp_path):
    """keep-last-1 pruning must NOT delete the base step a kept delta
    still resolves its leaves through."""
    from flexflow_tpu.resilience.offload import RemoteCheckpointStore

    r = RemoteCheckpointStore(LocalBlobStore(str(tmp_path)))
    rep1 = r.upload_step(2, _multi_leaf_files(2, 1.0, 5.0))
    r.upload_step(4, _multi_leaf_files(4, 1.0, 7.0),
                  base_step=2, base_manifest=rep1.manifest)
    r.prune(keep=1)
    assert r.list_steps() == [2, 4]  # base survives the prune
    files = r.download_step(4)      # and the delta still reassembles
    with np.load(io.BytesIO(files["state.npz"])) as data:
        assert len(data.files) == 2


def test_delta_chain_reanchors_at_bound(tmp_path):
    """A delta chain re-uploads the full step once the bound is hit, so
    restores never chase unbounded base chains."""
    from flexflow_tpu.resilience.offload import (
        MAX_DELTA_CHAIN, RemoteCheckpointStore,
    )

    r = RemoteCheckpointStore(LocalBlobStore(str(tmp_path)))
    rep = r.upload_step(0, _multi_leaf_files(0, 1.0, 0.0))
    step, deltas = 0, []
    for i in range(1, MAX_DELTA_CHAIN + 3):
        step = 2 * i
        rep2 = r.upload_step(step, _multi_leaf_files(step, 1.0, float(i)),
                             base_step=step - 2, base_manifest=rep.manifest)
        deltas.append(rep2.leaves_skipped > 0)
        rep = rep2
    # MAX deltas, then one full re-anchor, then the chain restarts
    assert deltas == [True] * MAX_DELTA_CHAIN + [False, True]
    files = r.download_step(step)
    with np.load(io.BytesIO(files["state.npz"])) as data:
        assert len(data.files) == 2


def test_offloader_counts_skipped_leaves(tmp_path):
    """End to end through the offloader thread: the second cadence
    upload skips the unchanged leaf and counts it."""
    from flexflow_tpu.resilience.offload import (
        CheckpointOffloader, RemoteCheckpointStore,
    )

    r = RemoteCheckpointStore(LocalBlobStore(str(tmp_path)))
    off = CheckpointOffloader(r, every=1, keep=3, sleep=NO_SLEEP)
    try:
        off.maybe_submit(2, _multi_leaf_files(2, 1.0, 5.0))
        off.drain()
        off.maybe_submit(4, _multi_leaf_files(4, 1.0, 7.0))
        off.drain()
    finally:
        off.close()
    assert off.counters["offload_uploads"] == 2
    assert off.counters["offload_leaves_skipped"] == 1
    assert r.latest_verified_step() == 4


def test_delta_mirror_prune_aborts_on_unreadable_manifest(tmp_path):
    """A transient store fault while resolving a kept delta's bases
    must SKIP the prune round, not delete the base (review finding:
    deleting it would leave REMOTE_LATEST unrestorable)."""
    from flexflow_tpu.resilience.offload import RemoteCheckpointStore
    from flexflow_tpu.store.blobstore import BlobUnavailableError

    blob = LocalBlobStore(str(tmp_path))
    r = RemoteCheckpointStore(blob)
    rep1 = r.upload_step(2, _multi_leaf_files(2, 1.0, 5.0))
    r.upload_step(4, _multi_leaf_files(4, 1.0, 7.0),
                  base_step=2, base_manifest=rep1.manifest)

    real_get = blob.get

    def flaky_get(key):
        if key.endswith("step_00000004/manifest.json"):
            raise BlobUnavailableError("store blip")
        return real_get(key)

    blob.get = flaky_get
    try:
        assert r.prune(keep=1) == 0  # aborted, nothing deleted
    finally:
        blob.get = real_get
    assert r.list_steps() == [2, 4]
    files = r.download_step(4)  # base intact: delta still reassembles
    with np.load(io.BytesIO(files["state.npz"])) as data:
        assert len(data.files) == 2


def test_delta_chain_flattens_to_the_anchor_step(tmp_path):
    """Delta annotations point at the step that HOLDS the bytes (the
    anchor), not the immediately previous delta — one base fetch per
    restore, and prune retains anchors only (review finding)."""
    from flexflow_tpu.resilience.offload import RemoteCheckpointStore

    r = RemoteCheckpointStore(LocalBlobStore(str(tmp_path)))
    rep = r.upload_step(0, _multi_leaf_files(0, 1.0, 0.0))
    for i in (1, 2, 3):
        rep = r.upload_step(2 * i, _multi_leaf_files(2 * i, 1.0, float(i)),
                            base_step=2 * (i - 1), base_manifest=rep.manifest)
    man = json.loads(
        r.blob.get(r._step_prefix(6) + "manifest.json")
    )
    # leaf 'a' unchanged since step 0: annotated straight to the anchor
    assert man["leaves"]["['weights']['a']['k']"]["base_step"] == 0
    assert r._base_steps_of(6) == [0]
