"""The lfm2_moe language model (gated short convolutions beside a
grouped-query attention layer, dense then sigmoid-routed experts under
a dense or a grouped product, RMSNorm, a next-token loss) against its
plain float32 reference (benchmarks/families/lfm2_moe.py) on seeded
weights, at a toy size on the CPU: the whole model's logits and its
FIRST-STEP GRADIENT through `FFModel.compile` and `train_step`, under
both products and with and without remat; the routed counts a step
reports; the configuration file.  Every op alone:
tests/test_lfm2_moe_ops.py.  The seed's weights, the batch and the
reference's side are made once (`_family`'s module fixtures).

Tolerances.  The program and the reference compute the same float32
arithmetic in another order, so they differ by rounding only: 2e-5 for
logits, and a relative L2 error of 2e-5 for a group of gradients (the
toy configuration's own limits; a bf16 run reads 1e-3 and more).
"""
import jax
import numpy as np
import pytest
from _family import (batch, close, compiled, config,  # noqa: F401 (fixtures)
                     first_step_equals_the_reference, reference, seeded)

from benchmarks.families import lfm2_moe as fam
from flexflow_tpu import FFConfig, FFModel
from flexflow_tpu.config import ConfigError
from flexflow_tpu.obs import trace
from flexflow_tpu.ops import routed_experts as rx

CFG = config("toy-lfm2.json")
D = fam.dims(CFG)
SEED = 13
B, S = 2, 16
LOGIT_TOL, GROUP_TOL = 2e-5, 2e-5


@pytest.fixture
def grouped(monkeypatch):
    """Steer the toy shapes onto the grouped product (the choice is by
    shape; a test moves the threshold, the program has no option)."""
    monkeypatch.setattr(rx, "GROUPED_MIN_ROWS_PER_EXPERT", 1)



def model(seeded, remat=CFG["assumed"]["remat"]):
    return compiled(fam, dict(CFG, assumed=dict(CFG["assumed"], remat=remat)),
                    seeded["program"], B, S)


# -- 1. the whole model: logits and the first-step gradient ---------------------
@pytest.mark.parametrize("product", ["dense", "grouped"])
def test_logits_equal_the_reference(product, request, seeded, batch,
                                    reference):
    if product == "grouped":
        request.getfixturevalue("grouped")
    close(model(seeded).forward(batch[0]), reference["logits"], LOGIT_TOL)


@pytest.mark.parametrize("product", ["dense", "grouped"])
@pytest.mark.parametrize("remat", [False, True])
def test_first_step_gradient_equals_the_reference_by_group(
        product, remat, request, seeded, batch, reference):
    if product == "grouped":
        request.getfixturevalue("grouped")
    ff = model(seeded, remat)
    plans = {op.product_plan() for op in ff.executor.routed_expert_ops}
    assert plans == {product}
    first_step_equals_the_reference(fam, CFG, ff, batch, reference,
                                    GROUP_TOL)


def test_routed_layers_are_rematerialised_and_their_counts_still_leave(
        seeded, batch):
    """A segment that holds a routed-expert layer is checkpointed (its
    state entries are counters the forward pass only writes), and the
    step returns the same counts with and without `remat`."""
    counts = {}
    for remat in (False, True):
        ff = model(seeded, remat)
        plan = ff.executor._remat_plan
        if remat:
            moe = [pure for seg, _, _, pure in plan
                   if any(op.name.startswith("moe_") for op in seg)]
            assert moe and all(moe)
        else:
            assert plan is None
        ff.train_step(*batch)
        counts[remat] = {k: np.asarray(v["moe_stats"])
                         for k, v in ff._state.items() if "moe_stats" in v}
    assert sorted(counts[True]) == ["moe_1", "moe_2"]
    for name in counts[True]:
        assert np.array_equal(counts[True][name], counts[False][name])
        assert counts[True][name][0] > 0 and counts[True][name][1] == 0


# -- 6. spans and counters --------------------------------------------------------
def test_train_step_reports_the_routed_counts_without_a_wait(
        grouped, seeded, batch):
    ff = model(seeded)
    built = [r for r in trace.spans() if r.name == "build_step_fns"][-1]
    assert built.args["expert_grouped_ops"] == 2
    assert built.args["expert_dense_ops"] == 0
    before = len([r for r in trace.spans() if r.name == "train_step.moe"])
    inputs, labels = batch
    for _ in range(4):
        jax.block_until_ready(ff.train_step(inputs, labels)["loss"])
    spans = [r for r in trace.spans() if r.name == "train_step.moe"][before:]
    # a step reports an EARLIER step's counts: none for the first
    assert 1 <= len(spans) <= 3
    steps = [r for r in trace.spans() if r.name == "train_step"]
    by_id = {r.span_id: r for r in steps}
    # the step that compiled says what its grouped products run on: off
    # the TPU the ragged dot (the kernel path: test_grouped_kernel.py)
    compiled_at = [r for r in steps[-4:] if r.args["first"]]
    assert [(r.args["experts_product"], r.args["experts_tiling"])
            for r in compiled_at] == [("ragged", "")]
    assert not any("experts_product" in r.args for r in steps[-4:]
                   if not r.args["first"])
    for r in spans:
        assert set(r.args) == {"step", "moe_pairs", "moe_dropped",
                               "moe_max_rows", "moe_hit",
                               "moe_rows_computed", "moe_overflow"}
        assert r.args["moe_overflow"] == 0
        assert r.args["step"] < by_id[r.parent_id].args["step"]
        assert r.args["moe_dropped"] == 0 and r.args["moe_pairs"] > 0
        assert r.args["moe_pairs"] <= r.args["moe_rows_computed"] \
            <= r.args["moe_pairs"] + 7 * r.args["moe_hit"]
    reg = ff.telemetry.metrics
    assert reg.counter("train/moe_steps").value == len(spans)
    assert reg.counter("train/moe_pairs").value == sum(
        r.args["moe_pairs"] for r in spans)


def test_the_dense_product_reports_its_static_rows(seeded, batch):
    ff = model(seeded)
    inputs, labels = batch
    for _ in range(3):
        jax.block_until_ready(ff.train_step(inputs, labels)["loss"])
    last = [r for r in trace.spans() if r.name == "train_step.moe"][-1]
    assert not any("experts_product" in r.args for r in trace.spans()[-40:]
                   if r.name == "train_step")  # no grouped product
    # two routed layers x every held expert over every row
    assert last.args["moe_rows_computed"] == 2 * D["held"] * B * S


def test_a_model_without_routed_layers_emits_no_moe_span():
    from flexflow_tpu.models.transformer import build_bert

    ff = FFModel(FFConfig(batch_size=2, num_devices=1))
    build_bert(ff, batch_size=2, seq_length=8, hidden_size=16, num_layers=1,
               num_heads=2, intermediate_size=32, vocab_size=32,
               num_classes=2, from_token_ids=True)
    ff.compile(devices=jax.devices()[:1])
    before = trace.next_span_id()
    m = ff.train_step({"input": np.zeros((2, 8), np.int32)},
                      np.zeros((2,), np.int32))
    assert "__moe__" not in m
    new = [r for r in trace.spans() if r.span_id > before]
    assert not [r for r in new if r.name == "train_step.moe"]
    built = [r for r in trace.spans() if r.name == "build_step_fns"][-1]
    assert "expert_grouped_ops" not in built.args


def test_the_seeded_choosing_bias_evens_the_experts_loads():
    """`make_weights` moves each routed layer's choosing bias until the
    router sends every expert the same number of pairs on its seeded
    calibration sequences (what the published family's balancing rule
    does while it trains): the step's time follows the routed rows, and
    without it the held experts' share moves by seed."""
    from benchmarks import reference as ref

    cfg = dict(CFG, max_position_embeddings=256)
    key = jax.random.fold_in(ref.seed_key(SEED), 2 ** 20)
    ids = jax.random.randint(key, (fam.CALIBRATION_SEQUENCES, 256), 0,
                             D["v"])

    chosen_by = jax.jit(lambda w, row: fam.forward(w, row, cfg, "bfloat16")[1])

    def max_over_mean(w):
        loads = 0
        for row in ids:
            chosen = chosen_by(w, row)
            loads = loads + np.stack([np.bincount(
                np.asarray(c).ravel(), minlength=D["total"]) for c in chosen])
        return (loads.max(axis=1) / loads.mean(axis=1)).max()

    even = jax.tree.map(np.asarray, fam.make_weights(cfg, SEED, "program"))
    drawn = {op: dict(leaves) for op, leaves in even.items()}
    for op, leaves in drawn.items():
        if "router_bias" in leaves:  # as drawn: N(0, 0.02), not moved
            leaves["router_bias"] = 0.02 * np.asarray(jax.random.normal(
                jax.random.key(len(op)), leaves["router_bias"].shape))
    assert max_over_mean(even) < 1.06 < 1.2 < max_over_mean(drawn)
    # both layouts hold the same bias
    grouped = fam.make_weights(cfg, SEED, "reference")
    for op, leaves in grouped["choosing_bias"].items():
        assert np.array_equal(leaves["router_bias"], even[op]["router_bias"])


# -- 7. the configuration file, and what the family does not carry ----------------
def test_the_configuration_file_counts_602_202_368_parameters():
    cfg = config("lfm2-8b-a1b-ep4-train.json")
    assert fam.parameter_count(cfg) == 602_202_368 \
        == cfg["deployment"]["parameters"]
    ff = fam.build_model(cfg, 1, 8, 1)  # the graph alone: no weight is made
    assert sum(int(np.prod(w.shape.logical_shape))
               for op in ff.layers.topo_order()
               for w in op.weights[:getattr(
                   op, "num_trainable_weights", lambda: len(op.weights))()]
               ) == 602_202_368
    per_token = fam.macs_per_token(cfg)
    assert round(sum(per_token.values()) / 1e5) == 2603  # 260.3 M
    d = cfg["deployment"]
    assert (d["chips_sharing_a_layer"], d["layers_a_host"], d["chips"]) \
        == (4, 6, 16)
    assert cfg["layer_types"] == cfg["published"]["layer_types"][:6]


def test_serving_the_family_is_a_config_error_by_name():
    from flexflow_tpu.decoding import decoder_recipe

    ff = fam.build_model(CFG, 1, 8, 1)
    with pytest.raises(ConfigError, match="lfm2_moe"):
        decoder_recipe(ff)
    with pytest.raises(ConfigError, match="layer_types"):
        fam.build_model(dict(CFG, layer_types=["conv", "window"]), 1, 8, 1)
