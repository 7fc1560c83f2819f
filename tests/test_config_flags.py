"""Every FFConfig field has a consumer (VERDICT r1 Weak #4).

Reference flag semantics: config.h:92-160 + parse_args
model.cc:3556-3720.  Covers: weight_decay -> default optimizer,
--fusion compile pass, sample parallelism, ParameterSyncType PS cost
model, --search-overlap-backward-update sync credit,
--simulator-segment-size search cap, --include-costs-dot-graph, and
strategy-reachable FusedParallelOp.
"""
import numpy as np
import pytest

from flexflow_tpu import FFConfig, FFModel, SGDOptimizer
from flexflow_tpu.fftype import ActiMode, OperatorType, ParameterSyncType
from flexflow_tpu.strategy import Strategy, data_parallel_strategy


def _mlp_relu(cfg):
    ff = FFModel(cfg)
    x = ff.create_tensor([cfg.batch_size, 16], name="x")
    t = ff.dense(x, 32, name="fc1")
    t = ff.relu(t)
    t = ff.dense(t, 4, name="fc2")
    ff.softmax(t)
    return ff


def test_weight_decay_reaches_default_optimizer(devices8):
    cfg = FFConfig(batch_size=8, weight_decay=0.123)
    ff = _mlp_relu(cfg)
    ff.compile(devices=devices8[:1])
    assert ff.optimizer.weight_decay == pytest.approx(0.123)


def test_perform_fusion_folds_activations(devices8):
    cfg = FFConfig(batch_size=8, perform_fusion=True)
    ff = _mlp_relu(cfg)
    ff.compile(optimizer=SGDOptimizer(lr=0.01), devices=devices8[:1])
    types = [op.op_type for op in ff.operators.ops]
    assert OperatorType.ELEMENT_UNARY not in types
    fused = next(op for op in ff.operators.ops if op.name == "fc1")
    assert fused.params.activation == ActiMode.RELU
    x = np.random.randn(8, 16).astype(np.float32)
    y = np.random.randint(0, 4, (8,))
    assert np.isfinite(float(ff.train_step({"x": x}, y)["loss"]))


def test_perform_fusion_respects_strategy_references(devices8):
    """A strategy edge chain on the relu output tensor protects it."""
    cfg = FFConfig(batch_size=8, num_devices=2, perform_fusion=True)
    ff = _mlp_relu(cfg)
    relu_out = next(
        op for op in ff.layers.ops
        if op.op_type == OperatorType.ELEMENT_UNARY
    ).outputs[0].name
    s = Strategy(mesh_axes={"data": 2})
    s.edge_ops["__inputs__"] = [("repartition", {"dim": 0, "degree": 2})]
    s.edge_ops[relu_out] = [
        ("combine", {"dim": 0, "degree": 2}),
        ("repartition", {"dim": 0, "degree": 2}),
    ]
    ff.compile(optimizer=SGDOptimizer(lr=0.01), strategy=s,
               devices=devices8[:2])
    assert any(
        op.op_type == OperatorType.ELEMENT_UNARY for op in ff.operators.ops
    )


def test_sample_parallel_candidates_and_training(devices8):
    from flexflow_tpu.pcg.unity import UnitySearch
    from flexflow_tpu.sim.machine_model import TpuPodModel
    from flexflow_tpu.sim.simulator import OpCostModel

    cfg = FFConfig(batch_size=8, num_devices=8, enable_sample_parallel=True)
    ff = FFModel(cfg)
    x = ff.create_tensor([8, 16, 32], name="x")  # [b, rows, d]
    t = ff.dense(x, 32, activation=ActiMode.RELU, name="fc1")
    t = ff.dense(t, 4, name="fc2")
    t = ff.softmax(t)
    machine = TpuPodModel(topology=(2, 4))
    search = UnitySearch(ff.layers, 8, machine, OpCostModel(machine),
                         enable_sample_parallel=True,
                         rewrite_max_variants=1)
    cands = list(search._sample_candidates())
    assert cands, "sample-parallel candidates missing"
    meshes = [s.mesh_axes for s, _, _, _ in cands]
    assert any("sample" in m for m in meshes)
    # disabled flag -> no candidates
    search_off = UnitySearch(ff.layers, 8, machine, OpCostModel(machine),
                             rewrite_max_variants=1)
    assert not list(search_off._sample_candidates())
    # one of them trains end to end on the CPU mesh
    s = next(s for s, _, _, _ in cands if s.total_devices == 8)
    ff.compile(optimizer=SGDOptimizer(lr=0.01), strategy=s,
               devices=devices8[:8])
    xx = np.random.randn(8, 16, 32).astype(np.float32)
    yy = np.random.randint(0, 4, (8, 16))  # per-row labels
    assert np.isfinite(float(ff.train_step({"x": xx}, yy)["loss"]))


def test_parameter_sync_ps_changes_sync_cost():
    from flexflow_tpu.sim.machine_model import TpuPodModel
    from flexflow_tpu.sim.simulator import Simulator

    m = TpuPodModel(topology=(2, 4))
    ar = Simulator(m)
    ps = Simulator(m, parameter_sync="ps")
    size = 64 * 1024**2
    assert ar.sync_time(size, 8) != ps.sync_time(size, 8)
    # PS estimate is the reference's flat 2*size/BW + latency
    bw, lat = m.ps_link()
    assert ps.sync_time(size, 8) == pytest.approx(2 * lat + 2 * size / bw)
    # NONE means no gradient sync at all (reference config.h:55)
    none = Simulator(m, parameter_sync="none")
    assert none.sync_time(size, 8) == 0.0


def test_search_overlap_backward_update_credits_sync():
    from flexflow_tpu.sim.machine_model import TpuPodModel
    from flexflow_tpu.sim.simulator import Simulator
    from flexflow_tpu.strategy import apply_strategy, assign_views

    cfg = FFConfig(batch_size=64)
    ff = _mlp_relu(cfg)
    s = data_parallel_strategy(8)
    g = apply_strategy(ff.layers, s)
    assign_views(g, s.mesh_axes)
    m = TpuPodModel(topology=(2, 4))
    base = Simulator(m).simulate(g, s.mesh_axes)
    overlapped = Simulator(m, sync_overlap_fraction=0.7).simulate(
        g, s.mesh_axes
    )
    assert base.sync_time > 0
    assert overlapped.total_time < base.total_time


def test_simulator_segment_size_lowers_search_cap():
    from flexflow_tpu.pcg.unity import UnitySearch, _MAX_SEGMENT_ASSIGNMENTS
    from flexflow_tpu.sim.machine_model import TpuPodModel
    from flexflow_tpu.sim.simulator import OpCostModel

    cfg = FFConfig(batch_size=8)
    ff = _mlp_relu(cfg)
    m = TpuPodModel(topology=(2, 4))
    s = UnitySearch(ff.layers, 8, m, OpCostModel(m), max_assignments=7)
    assert s._cap() == 7
    s2 = UnitySearch(ff.layers, 8, m, OpCostModel(m),
                     max_assignments=10 ** 12)
    assert s2._cap() == _MAX_SEGMENT_ASSIGNMENTS


def test_include_costs_dot_graph(tmp_path, devices8):
    path = str(tmp_path / "taskgraph.dot")
    cfg = FFConfig(batch_size=8, export_taskgraph_file=path,
                   include_costs_dot_graph=True)
    ff = _mlp_relu(cfg)
    ff.compile(optimizer=SGDOptimizer(lr=0.01), devices=devices8[:1])
    text = open(path).read()
    assert "cost=" in text


def test_fused_parallel_op_strategy_reachable(devices8):
    """FusedParallelOp is emittable from a Strategy edge chain, costed
    by the simulator, and JSON round-trips (reference
    fused_parallel_op.cc)."""
    from flexflow_tpu.sim.machine_model import TpuPodModel
    from flexflow_tpu.sim.simulator import Simulator

    cfg = FFConfig(batch_size=8, num_devices=4)
    ff = _mlp_relu(cfg)
    s = Strategy(mesh_axes={"data": 4})
    s.edge_ops["__inputs__"] = [("repartition", {"dim": 0, "degree": 4})]
    s.edge_ops["fc1.out0"] = [(
        "fused",
        {"ops": [["combine", {"dim": 0, "degree": 2}],
                 ["repartition", {"dim": 0, "degree": 2}]]},
    )]
    text = s.to_json()
    s2 = Strategy.from_json(text)
    assert s2.edge_ops["fc1.out0"][0][0] == "fused"

    ff.compile(optimizer=SGDOptimizer(lr=0.01), strategy=s2,
               devices=devices8[:4])
    fused_ops = [
        op for op in ff.operators.ops
        if op.op_type == OperatorType.FUSED_PARALLEL
    ]
    assert fused_ops
    m = TpuPodModel(topology=(2, 2))
    assert Simulator(m).xfer_cost(fused_ops[0], s2.mesh_axes) > 0
    x = np.random.randn(8, 16).astype(np.float32)
    y = np.random.randint(0, 4, (8,))
    assert np.isfinite(float(ff.train_step({"x": x}, y)["loss"]))


def test_cli_flags_parse():
    cfg = FFConfig.from_args([
        "--enable-sample-parallel", "--search-overlap-backward-update",
        "--parameter-sync", "ps", "--fusion",
        "--simulator-segment-size", "128",
    ])
    assert cfg.enable_sample_parallel
    assert cfg.search_overlap_backward_update
    assert cfg.parameter_sync == ParameterSyncType.PS
    assert cfg.perform_fusion
    assert cfg.simulator_segment_size == 128


def test_resilience_cli_flags_parse():
    cfg = FFConfig.from_args([
        "--checkpoint-every", "5", "--checkpoint-dir", "/tmp/ckpt",
        "--checkpoint-keep", "2", "--max-restarts", "7",
        "--retry-backoff", "0.5", "--nan-policy", "skip_step",
    ])
    assert cfg.checkpoint_every == 5
    assert cfg.checkpoint_dir == "/tmp/ckpt"
    assert cfg.checkpoint_keep == 2
    assert cfg.max_restarts == 7
    assert cfg.retry_backoff == pytest.approx(0.5)
    assert cfg.nan_policy == "skip_step"
    # defaults: resilience off until opted into
    base = FFConfig.from_args([])
    assert base.checkpoint_every == 0 and base.nan_policy == "raise"


def test_durability_cli_flags_parse():
    cfg = FFConfig.from_args([
        "--checkpoint-async", "--step-timeout", "45.5", "--no-preempt-grace",
    ])
    assert cfg.checkpoint_async is True
    assert cfg.step_timeout == pytest.approx(45.5)
    assert cfg.preempt_grace is False
    # defaults: sync saves, watchdog off, grace on
    base = FFConfig.from_args([])
    assert base.checkpoint_async is False
    assert base.step_timeout == 0.0
    assert base.preempt_grace is True


def test_offload_cli_flags_parse():
    cfg = FFConfig.from_args([
        "--remote-store", "file:///fleet/ckpt", "--offload-every", "4",
        "--remote-keep", "5",
    ])
    assert cfg.remote_store == "file:///fleet/ckpt"
    assert cfg.offload_every == 4
    assert cfg.remote_keep == 5
    # defaults: no remote tier, mirror every verified save, keep 3
    base = FFConfig.from_args([])
    assert base.remote_store is None
    assert base.offload_every == 1
    assert base.remote_keep == 3
    # explicit opt-out (the --no-strategy-store pattern)
    off = FFConfig.from_args(["--no-remote-store"])
    assert off.remote_store == "none"
    from flexflow_tpu.resilience.offload import offloader_from_config

    assert offloader_from_config(off) is None
    assert offloader_from_config(base) is None


def test_offload_config_validated():
    with pytest.raises(ValueError):
        FFConfig(offload_every=0)
    with pytest.raises(ValueError):
        FFConfig(remote_keep=0)
    with pytest.raises(ValueError):
        FFConfig(barrier_timeout=0.0)


def test_barrier_timeout_flag_parses():
    cfg = FFConfig.from_args(["--barrier-timeout", "5.5"])
    assert cfg.barrier_timeout == 5.5
    assert FFConfig.from_args([]).barrier_timeout == 30.0


def test_serving_cli_flags_parse():
    cfg = FFConfig.from_args([
        "--serving-mode", "static", "--kv-page-size", "8",
        "--kv-pool-blocks", "65", "--serving-slots", "16",
    ])
    assert cfg.serving_mode == "static"
    assert cfg.kv_page_size == 8
    assert cfg.kv_pool_blocks == 65
    assert cfg.serving_slots == 16
    # defaults: continuous with auto-sized pool
    base = FFConfig.from_args([])
    assert base.serving_mode == "continuous"
    assert base.kv_page_size == 16
    assert base.kv_pool_blocks == 0
    assert base.serving_slots == 8


def test_serving_config_validated():
    with pytest.raises(ValueError):
        FFConfig(serving_mode="bogus")
    with pytest.raises(ValueError):
        FFConfig(kv_page_size=0)
    with pytest.raises(ValueError):
        FFConfig(kv_pool_blocks=-1)
    with pytest.raises(ValueError):
        FFConfig(serving_slots=0)
    with pytest.raises(ValueError):
        FFConfig(prefill_chunk=-1)


def test_prefix_cache_cli_flags_parse():
    cfg = FFConfig.from_args(["--prefill-chunk", "16",
                              "--no-prefix-cache"])
    assert cfg.prefill_chunk == 16
    assert cfg.prefix_cache is False
    base = FFConfig.from_args([])
    assert base.prefill_chunk == 8      # chunked prefill on by default
    assert base.prefix_cache is True    # sharing on by default
    assert FFConfig.from_args(["--prefill-chunk", "0"]).prefill_chunk == 0


def test_serving_front_cli_flags_parse():
    cfg = FFConfig.from_args([
        "--serving-replicas", "3", "--serving-step-timeout", "2.5",
        "--serving-max-restarts", "5", "--request-retry-limit", "4",
    ])
    assert cfg.serving_replicas == 3
    assert cfg.serving_step_timeout == 2.5
    assert cfg.serving_max_restarts == 5
    assert cfg.request_retry_limit == 4
    base = FFConfig.from_args([])
    assert base.serving_replicas == 1
    assert base.serving_step_timeout == 0.0  # decode watchdog off
    assert base.serving_max_restarts == 3
    assert base.request_retry_limit == 2


def test_serving_front_config_validated():
    with pytest.raises(ValueError):
        FFConfig(serving_replicas=0)
    with pytest.raises(ValueError):
        FFConfig(serving_step_timeout=-1.0)
    with pytest.raises(ValueError):
        FFConfig(serving_max_restarts=-1)
    with pytest.raises(ValueError):
        FFConfig(request_retry_limit=-1)


def test_store_cli_flags_parse(monkeypatch):
    cfg = FFConfig.from_args([
        "--strategy-store", "/tmp/fleet_store",
        "--compilation-cache", "/tmp/xla",
    ])
    assert cfg.strategy_store == "/tmp/fleet_store"
    assert cfg.resolve_store_dir() == "/tmp/fleet_store"
    assert cfg.compilation_cache == "/tmp/xla"
    # bare --compilation-cache ties the XLA cache to the store root
    auto = FFConfig.from_args(["--strategy-store", "/tmp/s",
                               "--compilation-cache"])
    assert auto.compilation_cache == "auto"
    # --no-strategy-store opts out even when the fleet env var is set
    monkeypatch.setenv("FLEXFLOW_TPU_STORE_DIR", "/tmp/fleet_store")
    off = FFConfig.from_args(["--no-strategy-store"])
    assert off.strategy_store == "none"
    assert off.resolve_store_dir() is None
    # defaults: no store unless the env var names one
    base = FFConfig.from_args([])
    assert base.strategy_store is None
    assert base.compilation_cache is None
    assert base.resolve_store_dir() == "/tmp/fleet_store"  # env fallback
    monkeypatch.delenv("FLEXFLOW_TPU_STORE_DIR")
    assert base.resolve_store_dir() is None


def test_store_config_validated():
    with pytest.raises(ValueError):
        FFConfig(compilation_cache="")
    with pytest.raises(ValueError):
        FFConfig(compilation_cache="   ")
    # None disables, paths and "auto" are fine
    FFConfig(compilation_cache=None)
    FFConfig(compilation_cache="auto")
    FFConfig(compilation_cache="/tmp/xla")


def test_resilience_config_validated():
    with pytest.raises(ValueError):
        FFConfig(nan_policy="bogus")
    with pytest.raises(ValueError):
        FFConfig(checkpoint_every=-1)
    with pytest.raises(ValueError):
        FFConfig(checkpoint_keep=0)
    with pytest.raises(ValueError):
        FFConfig(max_restarts=-2)
    with pytest.raises(ValueError):
        FFConfig(retry_backoff=-0.1)
    with pytest.raises(ValueError):
        FFConfig(step_timeout=-1.0)


def test_remat_matches_nonremat_numerics_and_inserts_checkpoint(devices8):
    """--remat wraps pure segments in jax.checkpoint: identical math,
    recomputed backward (TPU-native HBM/FLOPs trade)."""
    import jax

    def build(remat):
        cfg = FFConfig(batch_size=8, remat=remat)
        ff = _mlp_relu(cfg)
        ff.compile(optimizer=SGDOptimizer(lr=0.05), devices=devices8[:1],
                   seed=3)
        return ff

    ff_a, ff_b = build(False), build(True)
    x = np.random.RandomState(0).randn(8, 16).astype(np.float32)
    y = np.random.RandomState(1).randint(0, 4, (8,))
    la = [float(ff_a.train_step({"x": x}, y)["loss"]) for _ in range(4)]
    lb = [float(ff_b.train_step({"x": x}, y)["loss"]) for _ in range(4)]
    np.testing.assert_allclose(la, lb, rtol=1e-5, atol=1e-6)

    # the remat step's jaxpr actually carries checkpoint/remat regions
    ex = ff_b.executor
    xx, yy = ff_b._device_put_batch({"x": x}, y)
    jaxpr = str(jax.make_jaxpr(ex.build_step())(
        ff_b._weights, ff_b._opt_state, ff_b._state, xx, yy,
        jax.random.key(0),
    ))
    assert "remat" in jaxpr
    assert ex._remat_plan is not None
    assert any(pure for _, _, _, pure in ex._remat_plan)
    assert ff_a.executor._remat_plan is None


def test_topology_cli_flags_parse():
    cfg = FFConfig.from_args([
        "--slices", "2", "--dcn-bandwidth", "5e9",
        "--dcn-latency", "2e-5", "--slice-topology", "2,2",
    ])
    assert cfg.slices == 2
    assert cfg.dcn_bandwidth == pytest.approx(5e9)
    assert cfg.dcn_latency == pytest.approx(2e-5)
    assert cfg.slice_topology == "2,2"
    # defaults: 1 slice = exactly the flat pre-topology behavior
    d = FFConfig.from_args([])
    assert d.slices == 1 and d.slice_topology is None
    assert d.dcn_bandwidth == pytest.approx(25e9)
    assert d.dcn_latency == pytest.approx(10e-6)


def test_topology_config_validated():
    with pytest.raises(ValueError):
        FFConfig(slices=0)
    with pytest.raises(ValueError):
        FFConfig(dcn_bandwidth=0.0)
    with pytest.raises(ValueError):
        FFConfig(dcn_latency=-1e-6)
    with pytest.raises(ValueError):
        FFConfig(slice_topology="zero,4")
    FFConfig(slices=2, slice_topology="4x4")  # valid hierarchy config


def test_slices_selects_hierarchy_machine_model():
    from flexflow_tpu.sim.machine_model import make_machine_model
    from flexflow_tpu.topology.hierarchy import SliceHierarchy

    m = make_machine_model(FFConfig(slices=2, dcn_bandwidth=3e9), 8)
    assert isinstance(m, SliceHierarchy)
    assert m.slices == 2 and m.dcn_bw == pytest.approx(3e9)
    assert m.num_devices() == 8
    flat = make_machine_model(FFConfig(), 8)
    assert not isinstance(flat, SliceHierarchy)


def test_serving_tp_cli_flags_parse():
    cfg = FFConfig.from_args(
        ["--serving-tp", "4", "--serving-chip-budget", "16"])
    assert cfg.serving_tp == 4
    assert cfg.serving_chip_budget == 16
    d = FFConfig.from_args([])
    assert d.serving_tp == 1 and d.serving_chip_budget == 0


def test_serving_tp_config_validated():
    with pytest.raises(ValueError):
        FFConfig(serving_tp=0)
    with pytest.raises(ValueError):
        FFConfig(serving_chip_budget=-1)
    FFConfig(serving_tp=2, serving_chip_budget=8)  # valid


def test_resolve_serving_tp_rejects_bad_degrees():
    """--serving-tp misconfigurations must fail at BUILD time with a
    ConfigError naming the flag, never surface as a mid-compile shape
    error."""
    from flexflow_tpu.config import ConfigError, resolve_serving_tp

    assert resolve_serving_tp(1) == 1
    assert resolve_serving_tp(2, num_heads=4, visible_devices=8) == 2
    with pytest.raises(ConfigError, match="must be >= 1"):
        resolve_serving_tp(0)
    with pytest.raises(ConfigError, match="does not divide"):
        resolve_serving_tp(3, num_heads=4, visible_devices=8)
    with pytest.raises(ConfigError, match="exceeds the 2 visible"):
        resolve_serving_tp(4, num_heads=4, visible_devices=2)


def test_spec_decode_cli_flags_parse():
    cfg = FFConfig.from_args(["--spec-decode", "ngram", "--spec-k", "6"])
    assert cfg.spec_decode == "ngram"
    assert cfg.spec_k == 6
    cfg = FFConfig.from_args(["--spec-decode", "draft"])
    assert cfg.spec_decode == "draft" and cfg.spec_k == 4
    base = FFConfig.from_args([])
    assert base.spec_decode == "off"  # speculation is opt-in
    assert base.spec_k == 4


def test_spec_decode_config_validated():
    with pytest.raises(ValueError, match="spec_decode"):
        FFConfig(spec_decode="lookahead")
    with pytest.raises(ValueError, match="spec_k"):
        FFConfig(spec_k=0)
    assert FFConfig(spec_decode="ngram", spec_k=8) is not None


def test_resolve_spec_decode_rejects_bad_combos():
    """--spec-decode misconfigurations must fail at BUILD time with a
    ConfigError naming the flag:
    unknown modes, a draft budget under 1, and — because verification
    accepts the longest GREEDY-matching prefix, meaningless across
    beam hypotheses — any combination with beam search."""
    from flexflow_tpu.config import ConfigError, resolve_spec_decode

    assert resolve_spec_decode("off", 4) == "off"
    assert resolve_spec_decode("ngram", 1) == "ngram"
    assert resolve_spec_decode("draft", 4, beam_size=1) == "draft"
    # off tolerates any k/beam — nothing speculative runs
    assert resolve_spec_decode("off", 0, beam_size=4) == "off"
    with pytest.raises(ConfigError, match="--spec-decode must be one"):
        resolve_spec_decode("medusa", 4)
    with pytest.raises(ConfigError, match="--spec-k must be >= 1"):
        resolve_spec_decode("ngram", 0)
    with pytest.raises(ConfigError, match="beam"):
        resolve_spec_decode("ngram", 4, beam_size=4)
    with pytest.raises(ConfigError, match="beam"):
        resolve_spec_decode("draft", 4, beam_size=2)


def test_disagg_cli_flags_parse():
    cfg = FFConfig.from_args([
        "--serving-roles", "prefill=1,decode=2",
        "--kv-transfer", "blob",
        "--migration-cost-cap", "2.5",
        "--autoscale-predictive",
    ])
    assert cfg.serving_roles == "prefill=1,decode=2"
    assert cfg.kv_transfer == "blob"
    assert cfg.migration_cost_cap == 2.5
    assert cfg.autoscale_predictive is True
    base = FFConfig.from_args([])
    assert base.serving_roles == ""  # colocated fleet
    assert base.kv_transfer == "inproc"
    assert base.migration_cost_cap == 1.0
    assert base.autoscale_predictive is False


def test_disagg_config_validated():
    with pytest.raises(ValueError, match="decode-capable"):
        FFConfig(serving_roles="prefill=2")
    with pytest.raises(ValueError, match="unknown role"):
        FFConfig(serving_roles="verify=1")
    with pytest.raises(ValueError, match="kv_transfer"):
        FFConfig(kv_transfer="ftp")
    with pytest.raises(ValueError, match="cost"):
        FFConfig(migration_cost_cap=0.0)
    # a valid roles spec constructs fine
    assert FFConfig(serving_roles="prefill=1,decode=1") is not None
