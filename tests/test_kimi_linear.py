"""The kimi_linear language model (Kimi Delta Attention: the delta rule
with a decay per channel, run a chunk at a time; a latent-attention
layer without positions and without a query bottleneck; dense then
sigmoid-routed experts beside a shared one) against its plain float32
reference (benchmarks/families/kimi_linear.py, whose recurrence runs a
position at a time) on seeded weights, at a toy size on the CPU: the
whole model's logits and its FIRST-STEP GRADIENT through
`FFModel.compile` and `train_step`, and what the builder and the
configuration file say.  The chunked rule against the scan:
tests/test_kimi_linear_rule.py; each op alone, the share test and the
flash kernels at the published widths: tests/test_kimi_linear_ops.py.
The seed's weights, the batch and the reference's side are made once
(`_family`'s module fixtures).

Tolerances as tests/test_lfm2_moe.py: the same float32 arithmetic in
another order.
"""
import jax
import numpy as np
import pytest
from _family import (batch, close, compiled, config,  # noqa: F401 (fixtures)
                     first_step_equals_the_reference, reference, seeded)

from benchmarks.families import kimi_linear as fam
from flexflow_tpu import FFConfig, FFModel
from flexflow_tpu.config import ConfigError
from flexflow_tpu.models.kimi_linear import build_kimi_linear, layer_kinds
from flexflow_tpu.obs import trace
from flexflow_tpu.ops import kimi_delta_attention as kda_op
from flexflow_tpu.ops.pallas import gated_delta_rule as gdr

CFG = config("toy-kimi-linear.json")
SEED = 13
B, S = 2, 16
LOGIT_TOL, GROUP_TOL = 2e-5, 2e-5


def test_builder_reads_the_layer_pattern_from_linear_attn_config():
    assert layer_kinds(CFG["linear_attn_config"], 4) == [
        "kda", "kda", "mla", "kda"]
    published = config("kimi-linear-ep32-train.json")
    assert layer_kinds(published["published"]["linear_attn_config"], 27) == (
        ["kda", "kda", "kda", "mla"] * 6 + ["kda", "kda", "mla"])
    assert layer_kinds(published["linear_attn_config"], 5) == [
        "kda", "kda", "kda", "mla", "kda"]
    with pytest.raises(ConfigError, match="each of layers"):
        layer_kinds({"kda_layers": [1, 2], "full_attn_layers": [2]}, 3)
    ff = FFModel(FFConfig(batch_size=1, num_devices=1))
    with pytest.raises(ConfigError, match="topk_group"):
        build_kimi_linear(ff, 1, 8, **{**{k: CFG[k] for k in (
            "hidden_size", "num_hidden_layers", "linear_attn_config")},
            "num_expert_group": 8, "topk_group": 4})


def test_logits_equal_the_reference(seeded, batch, reference):
    ff = compiled(fam, CFG, seeded["program"], B, S)
    close(ff.forward(batch[0]), reference["logits"], LOGIT_TOL)


@pytest.mark.parametrize("remat", [False, True])
def test_first_step_gradient_equals_the_reference_by_group(
        remat, seeded, batch, reference):
    cfg = dict(CFG, assumed=dict(CFG["assumed"], remat=remat))
    before = trace.next_span_id()
    ff = compiled(fam, cfg, seeded["program"], B, S)
    first_step_equals_the_reference(fam, CFG, ff, batch, reference,
                                    GROUP_TOL)
    # the step the program built says which chunk its cores took
    built = [r for r in trace.spans()
             if r.span_id > before and r.name == "build_step_fns"]
    assert built and built[-1].args["kda_chunk_tokens"] == 16
    assert built[-1].args["kda_kernel_ops"] == 0  # the CPU's plan
    assert ff.executor.remat_segments == (8 if remat else 0) or remat


def test_build_step_fns_counts_the_chunk_kernels_and_their_chunk(
        monkeypatch):
    """The toy model at heads of 128 and rows of one chunk: with a
    TPU's answer from `pick_recurrence` the `build_step_fns` span
    counts the three KDA layers' kernels and reports their chunk; the
    CPU's plan reports the same chunk and no kernel."""
    cfg = dict(CFG, linear_attn_config=dict(
        CFG["linear_attn_config"], head_dim=128, num_heads=2))

    def built():
        before = trace.next_span_id()
        ff = fam.build_model(cfg, 1, 64, 1)
        fam.compile_model(ff, cfg, jax.devices()[:1])
        return [r.args for r in trace.spans() if r.span_id > before
                and r.name == "build_step_fns"][-1]

    args = built()
    assert (args["kda_chunk_tokens"], args["kda_kernel_ops"]) == (64, 0)
    monkeypatch.setattr(  # the ops ask `pick_recurrence` as a TPU would
        kda_op, "pick_recurrence",
        lambda backend, *a: gdr.pick_recurrence("tpu", *a))
    k_args = built()
    assert (k_args["kda_chunk_tokens"], k_args["kda_kernel_ops"]) == (64, 3)
    assert k_args["remat_segments"] == args["remat_segments"] > 0


def test_a_twin_of_the_trainer_graph_is_refused_by_name():
    from flexflow_tpu.decoding import decoder_recipe

    with pytest.raises(ConfigError, match="kimi_linear is built for "
                                          "training only"):
        decoder_recipe(fam.build_model(CFG, 1, 8, 1))


def test_parameter_count_of_the_published_cut_is_the_deployments():
    cfg = config("kimi-linear-ep32-train.json")
    assert fam.parameter_count(cfg) == cfg["deployment"]["parameters"] \
        == 602_434_432
    shapes = fam.op_shapes(cfg)
    count = {op: sum(int(np.prod(s)) for s in leaves.values())
             for op, leaves in shapes.items()}
    assert count["kda_0"] == 39_514_272 and count["mla_3"] == 29_114_880
    assert count["mlp_0"] == 63_700_992
    assert shapes["kda_0"]["f_b_proj"] == (128, 4096)  # a decay a channel
    assert shapes["mla_3"]["wq"] == (2304, 32, 192)    # no bottleneck

