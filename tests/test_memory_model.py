"""Liveness/remat-aware memory model (sim/simulator.py per_device_memory).

The r02 model summed every tensor ever produced and ignored --remat, so
memory_search optimized a systematically inflated objective (VERDICT
weak #6).  These tests pin the new semantics:

  * modeled training memory tracks XLA's own accounting
    (compiled.memory_analysis()) within a small factor;
  * --remat strictly reduces both the modeled number and XLA's temp
    allocation;
  * a strategy the inflated model rejected against a budget is now
    accepted by memory_search (the done-criterion case).
"""
import numpy as np
import pytest

from flexflow_tpu import FFConfig, FFModel, LossType, SGDOptimizer
from flexflow_tpu.sim.machine_model import TpuPodModel
from flexflow_tpu.sim.simulator import OpCostModel, Simulator


def _mlp(batch=32, width=256, layers=6, remat=False):
    """Residual MLP: each block is a multi-op single-tensor segment
    (the residual edge forbids interior cuts), with standalone ReLU
    ElementUnary ops — the shapes that distinguish liveness/remat
    accounting from the old sum-of-everything."""
    ff = FFModel(FFConfig(batch_size=batch, num_devices=1, remat=remat))
    x = ff.create_tensor([batch, width], name="input")
    t = x
    for i in range(layers):
        h = ff.dense(t, width * 2, name=f"up{i}")
        h = ff.relu(h, name=f"act{i}")
        h = ff.dense(h, width, name=f"down{i}")
        t = ff.add(t, h, name=f"res{i}")
    t = ff.dense(t, 8, name="head")
    ff.softmax(t)
    return ff


def _xla_train_bytes(ff):
    """XLA's own accounting for the jitted train step: temp (live
    activations + workspace) + donated args (weights/opt state)."""
    import jax

    rng = np.random.RandomState(0)
    x = rng.randn(*ff.layers.source_ops()[0].outputs[0].shape.logical_shape
                  ).astype(np.float32)
    y = rng.randint(0, 8, x.shape[0]).astype(np.int32)
    step = ff.executor._step_fn
    lowered = step.lower(
        ff._weights, ff._opt_state, ff._state, {"input": x}, y,
        jax.random.key(0),
    )
    ma = lowered.compile().memory_analysis()
    return ma.temp_size_in_bytes + ma.argument_size_in_bytes


def test_training_memory_tracks_xla(devices8):
    ff = _mlp()
    ff.compile(optimizer=SGDOptimizer(lr=0.1, momentum=0.9),
               loss_type=LossType.SPARSE_CATEGORICAL_CROSSENTROPY,
               devices=devices8[:1])
    machine = TpuPodModel(topology=(1,))
    sim = Simulator(machine, OpCostModel(machine), optimizer_slots=1)
    modeled = sim.per_device_memory(ff.operators, training=True)
    actual = _xla_train_bytes(ff)
    # same order of magnitude, both directions (the model has no view
    # of XLA's exact residual choices, but must not be 2x+ inflated)
    assert 0.4 * actual < modeled < 2.0 * actual, (modeled, actual)


def test_remat_reduces_modeled_and_actual(devices8):
    machine = TpuPodModel(topology=(1,))
    sim = Simulator(machine, OpCostModel(machine), optimizer_slots=1)

    ff_plain = _mlp(batch=64, width=512, layers=4)
    ff_plain.compile(optimizer=SGDOptimizer(lr=0.1),
                     loss_type=LossType.SPARSE_CATEGORICAL_CROSSENTROPY,
                     devices=devices8[:1])
    ff_remat = _mlp(batch=64, width=512, layers=4, remat=True)
    ff_remat.compile(optimizer=SGDOptimizer(lr=0.1),
                     loss_type=LossType.SPARSE_CATEGORICAL_CROSSENTROPY,
                     devices=devices8[:1])

    m_plain = sim.per_device_memory(ff_plain.operators, training=True)
    m_remat = sim.per_device_memory(ff_remat.operators, training=True,
                                    remat=True)
    assert m_remat < m_plain

    import jax

    def lowered_step(ff):
        rng = np.random.RandomState(0)
        x = rng.randn(64, 512).astype(np.float32)  # noqa: F841
        y = rng.randint(0, 8, 64).astype(np.int32)
        return ff.executor._step_fn.lower(
            ff._weights, ff._opt_state, ff._state, {"input": x}, y,
            jax.random.key(0),
        )

    # the checkpointed step must actually recompute: optimization
    # barriers present and, where its segments keep nothing, more
    # matmuls than the plain step (this part of the lowering is
    # backend-independent).  Since PR 37 a segment keeps its matrix
    # products: the barriers stay, the second matmuls go.
    plain_txt = lowered_step(ff_plain).as_text()
    kept_txt = lowered_step(ff_remat).as_text()
    assert kept_txt.count("optimization_barrier") > 0
    assert (kept_txt.count("stablehlo.dot")
            == plain_txt.count("stablehlo.dot"))
    ff_remat.executor.remat_keep = "none"
    remat_txt = lowered_step(ff_remat).as_text()
    assert remat_txt.count("optimization_barrier") > 0
    assert (remat_txt.count("stablehlo.dot")
            > plain_txt.count("stablehlo.dot"))

    if jax.devices()[0].platform == "cpu":
        pytest.skip(
            "XLA:CPU buffer assignment reports identical "
            "temp_size_in_bytes with and without jax.checkpoint (the "
            "recompute + barriers ARE in the lowered module — asserted "
            "above — but the CPU scheduler's accounting doesn't "
            "reflect the residual savings); the temp-bytes reduction "
            "is only observable on accelerator backends"
        )

    def temp_bytes(ff):
        return (lowered_step(ff).compile()
                .memory_analysis().temp_size_in_bytes)

    assert temp_bytes(ff_remat) < temp_bytes(ff_plain)


def test_inference_liveness_below_sum(devices8):
    ff = _mlp()
    ff.compile(optimizer=SGDOptimizer(lr=0.1),
               loss_type=LossType.SPARSE_CATEGORICAL_CROSSENTROPY,
               devices=devices8[:1])
    machine = TpuPodModel(topology=(1,))
    sim = Simulator(machine, OpCostModel(machine))
    g = ff.operators
    inf = sim.per_device_memory(g, training=False)
    everything = sum(
        t.shape.shard_bytes() for op in g.ops for t in op.outputs
    ) + sum(w.shape.shard_bytes() for op in g.ops for w in op.weights)
    # liveness peak must beat the sum-of-all-tensors accounting
    assert inf < everything


def test_memory_search_accepts_previously_rejected(devices8):
    """A budget between the new (accurate) and old (inflated) numbers:
    the inflated model pushed memory_search into a degraded strategy,
    the liveness model keeps the fast one."""
    from flexflow_tpu.pcg.unity import UnitySearch

    ff = _mlp(batch=64, width=256, layers=6)
    ff.compile(optimizer=SGDOptimizer(lr=0.1),
               loss_type=LossType.SPARSE_CATEGORICAL_CROSSENTROPY,
               devices=devices8[:1])
    machine = TpuPodModel(topology=(8,))
    cm = OpCostModel(machine)
    sim = Simulator(machine, cm, optimizer_slots=2)
    g = ff.layers

    # what the unconstrained search would pick, and its footprints
    free = UnitySearch(g, 8, machine, cm, budget=64).optimize()
    assert free is not None
    new_model_bytes = sim.per_device_memory(g, training=True)
    old_model_bytes = int(
        (2 + 2) * sum(w.shape.shard_bytes() for op in g.ops
                      for w in op.weights)
        + sum(t.shape.shard_bytes() for op in g.ops for t in op.outputs)
    )
    assert new_model_bytes < old_model_bytes
    budget = (new_model_bytes + old_model_bytes) // 2

    search = UnitySearch(g, 8, machine, cm, budget=64,
                         memory_budget=budget)
    chosen = search.optimize_with_memory()
    assert chosen is not None
    # fits under the budget per the accurate model — the old model
    # would have judged this same graph over budget and forced lambda
    # escalation
    assert search._strategy_memory(chosen) <= budget
