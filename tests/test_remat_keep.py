"""What a checkpointed segment keeps under `remat` (PR 37): the outputs
of its matrix products and the values its ops tagged (`remat_keep`: the
grouped products, the flash forward's output and row statistics), and
nothing else; the step falls back to keeping nothing when the compiled
program does not fit the device.  CPU, toy sizes: counts, shapes and
gradients, no time.
"""
import dataclasses
import functools
import io
import re
import types
from contextlib import redirect_stdout

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from _family import config

from benchmarks import check
from benchmarks.families import lfm2_moe as fam
from flexflow_tpu import executor as executor_mod
from flexflow_tpu.executor import _REMAT_POLICIES, _RematStep, remat_kept
from flexflow_tpu.obs import trace
from flexflow_tpu.ops import routed_experts as rx
from flexflow_tpu.ops.pallas import flash_attention as fa

CFG = config("toy-lfm2.json")
B, S, SEED = 2, 16, 13
LEVELS = list(_REMAT_POLICIES)


@pytest.fixture(autouse=True)
def grouped(monkeypatch):
    """The toy's routed layers take the grouped product (the choice is
    by shape; a test moves the threshold)."""
    monkeypatch.setattr(rx, "GROUPED_MIN_ROWS_PER_EXPERT", 1)


@functools.cache
def seeded():
    """The seed's weights, made once, as host copies (a train step
    donates what `set_weights` was given)."""
    return jax.tree.map(np.asarray, fam.make_weights(CFG, SEED, "program"))


def build(remat=True, keep=None, strategy=None):
    """A compiled toy LFM2 (conv, attention, conv; a dense MLP and two
    routed layers) whose attention core is the flash branch's jnp twin."""
    from flexflow_tpu import AdamOptimizer, LossType

    cfg = dict(CFG, assumed=dict(CFG["assumed"], remat=remat))
    ff = fam.build_model(cfg, B, S, 1)
    ff.config.flash_min_seq = 1
    o = cfg["optimizer"]
    ff.compile(optimizer=AdamOptimizer(alpha=o["alpha"], beta1=o["beta1"],
                                       beta2=o["beta2"], weight_decay=0.0,
                                       epsilon=o["epsilon"]),
               loss_type=LossType.SPARSE_CATEGORICAL_CROSSENTROPY,
               metrics=(), devices=jax.devices()[:1], strategy=strategy)
    ff.set_weights(seeded())
    if keep is not None:
        ff.executor.remat_keep = keep
    return ff


def batch():
    return fam.make_batch(CFG, B, S, np.random.default_rng(5))


def step_args(ff):
    inputs, labels = ff._device_put_batch(*batch())
    return (ff._weights, ff._opt_state, ff._state, inputs, labels,
            jax.random.key(0))


def spans_named(name):
    return [s for s in trace.spans() if s.name == name]


def stepped(ff):
    """`ff` after two steps: Adam's first moment of the first (its
    gradient, scaled), by group, and what the spans said."""
    built = spans_named("build_step_fns")[-1].args
    ff.train_step(*batch())
    moments = fam.to_reference_layout(
        jax.tree.map(np.asarray, ff._opt_state["m"]))
    ff.train_step(*batch())
    first, second = (s.args for s in spans_named("train_step")[-2:])
    return types.SimpleNamespace(ff=ff, moments=moments, built=built,
                                 first=first, second=second)


@functools.cache
def toy(remat=True, keep=None):
    """Traced, never stepped: the differentiated step's jaxpr and what
    its checkpointed segments hold."""
    ff = build(remat, keep)
    jaxpr = ff._step_fn.trace(*step_args(ff)).jaxpr.jaxpr
    plan = ff.executor._remat_plan
    return types.SimpleNamespace(
        ff=ff, jaxpr=jaxpr, kept=plan and remat_kept(jaxpr, plan))


@functools.cache
def toy_stepped(remat=True, keep=None):
    return stepped(build(remat, keep))


def count(jaxpr, name: str, shape=None) -> int:
    """Equations of primitive `name` (with an output of `shape`, where
    that is given), sub-jaxprs (both branches of a `cond`, a backward
    `checkpoint`) included."""
    n = 0
    for eqn in jaxpr.eqns:
        n += eqn.primitive.name == name and (
            shape is None or any(v.aval.shape == shape for v in eqn.outvars))
        for sub in jax.core.jaxprs_in_params(eqn.params):
            n += count(sub, name, shape)
    return n


def segment_of(ff, op_name: str) -> int:
    return next(i for i, (seg, *_) in enumerate(ff.executor._remat_plan)
                if any(op.name == op_name for op in seg))


def same_gradient(a, b):
    stats = check.group_rel_l2(a, b, fam.GROUPS)
    assert set(stats) == set(fam.GROUPS) and len(stats) == 8
    assert max(stats.values()) <= 1e-7, stats


# -- 1. what a segment holds ---------------------------------------------------
D = fam.dims(CFG)
T = B * S
# the grouped product's usual buffers: 1.5 x the pairs an even router sends
M_USUAL = -(-int(rx.GROUPED_SLACK * T * D["k"] * D["held"] / D["total"])
            // rx.GROUPED_ROW_TILE) * rx.GROUPED_ROW_TILE


@pytest.mark.parametrize("op_name, want", [
    # in_proj's product; out_proj's is not needed by the backward pass
    ("conv_0", [(B, S, 3 * D["e"])]),
    # gate and up; neither the norm's output nor silu(gate) * up
    ("mlp_0", [(B, S, D["f"])] * 2),
    # q, k, v projections [b, s, heads, d], the flash forward's output
    # [b * h, s, d] and its row statistics [b * h, s]
    ("attn_1", [(B, S, D["heads"], D["d"]), (B, S, D["kv"], D["d"]),
                (B, S, D["kv"], D["d"]),
                (B * D["heads"], S, D["d"]), (B * D["heads"], S)]),
    # the router's product and the three grouped products (usual buffers)
    ("moe_1", [(T, D["total"]), (M_USUAL, D["fe"]), (M_USUAL, D["fe"]),
               (M_USUAL, D["e"])]),
])
def test_a_segment_keeps_its_products_and_named_values_only(op_name, want):
    t = toy()
    got = sorted(a.shape for a in t.kept[segment_of(t.ff, op_name)])
    assert got == sorted(want)


def test_keeping_nothing_holds_boundaries_only():
    kept = toy(keep="none").kept
    assert len(kept) == len(toy().kept) > 0 and not any(kept.values())


@pytest.mark.parametrize("tiling", ["online", "one_tile"])
def test_the_flash_forward_names_its_output_and_row_statistics(
        tiling, monkeypatch):
    """`print_saved_residuals` of the attention core under the
    executor's policy, through both tilings' custom_vjp (the long rows'
    jnp twin; the one-tile rule around stand-ins for its kernels, which
    the interpreter cannot run under `checkpoint`): `o` and the row
    statistics are saved, so the forward is not run again for them, and
    no [s, s] value is."""
    b, s, h, d = 1, 256, 2, 64
    q, k, v = (jax.random.normal(key, (b, s, h, d), jnp.float32)
               for key in jax.random.split(jax.random.key(0), 3))
    core = fa.mha_flash
    if tiling == "one_tile":
        monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
        monkeypatch.setattr(fa, "_one_tile_fwd", lambda q, k, v, **kw: (
            jnp.tanh(q + k + v), jnp.sum(q * k, axis=-1)))
        monkeypatch.setattr(fa, "_one_tile_bwd", lambda q, k, v, out, lse,
                            dout, **kw: (dout * out, dout, dout))
        core = fa.flash_mha
    out = io.StringIO()
    with redirect_stdout(out):
        jax.ad_checkpoint.print_saved_residuals(
            jax.checkpoint(lambda *qkv: jnp.sum(core(*qkv, 0.125, True)),
                           policy=_REMAT_POLICIES["products"]), q, k, v)
    # q, k, v are the arguments here; besides them exactly two values
    # (jax prints a float one by the `reduce_precision` it puts on a
    # residual's producer, not by its name)
    inner = [line for line in out.getvalue().splitlines()
             if "from the argument" not in line]
    assert len(inner) == 2, out.getvalue()
    assert any("named 'remat_kept'" in line for line in inner)
    assert not any(f"{s},{s}]" in line for line in inner), out.getvalue()


# -- 2. the same gradient, fewer products in the backward pass -----------------
@pytest.mark.parametrize("other", [dict(keep="none"), dict(remat=False)])
def test_gradients_equal_keep_nothing_and_remat_off_for_all_eight_groups(
        other):
    same_gradient(toy_stepped().moments, toy_stepped(**other).moments)


def in_the_backward_pass(jaxpr, name, shape=None):
    """`count` over the segments' backward `checkpoint` equations: what
    a segment computes again and its gradient."""
    return sum(count(eqn.params["jaxpr"], name, shape)
               for eqn in jaxpr.eqns if eqn.params.get("differentiated"))


def test_the_backward_pass_runs_no_forward_product_or_kernel_again():
    """A routed layer has 3 grouped products forward and 6 backward, at
    either of two sizes behind a `cond` in each rule of its
    `custom_vjp`: 3 + 3 forward; backward 6 on the usual buffers, whose
    products the segment keeps, and 3 again + 6 on the every-pair ones,
    which keep nothing.  Keeping nothing, the segment's backward pass
    also runs the forward rule's `cond` again for the usual buffers'
    products (3 + 0: the every-pair branch hands the backward rule
    nothing); keeping products it does not.  The attention forward (the twin's `log` of the row
    sums marks it) is not in the segment's backward equation (keeping
    nothing, the one the step runs is there: the first pass has no use
    for its `log`)."""
    kept, nothing = toy().jaxpr, toy(keep="none").jaxpr
    routed = len(toy().ff.executor.routed_expert_ops)
    assert routed == 2
    assert count(nothing, "ragged_dot_general") == routed * (6 + 3 + 15)
    assert count(kept, "ragged_dot_general") == routed * (6 + 15)
    assert in_the_backward_pass(nothing, "log") == 1
    assert in_the_backward_pass(kept, "log") == 0
    assert count(kept, "log") == count(nothing, "log") == 2  # and the loss's
    assert in_the_backward_pass(nothing, "ragged_dot_general") == routed * (
        3 + 15)
    assert in_the_backward_pass(kept, "ragged_dot_general") == routed * 15


def test_a_routed_segment_computes_no_combine_again():
    """The gathers of [t, e] rows in a routed segment's backward pass
    are the input gradient's, k a size (`_sum_of_slots`); the forward
    combine's (k a size more: the step's forward pass has them) are
    not there at either level of keeping: nothing of the backward rule
    reads the combined output."""
    k, rows = D["k"], (T, D["e"])
    routed = len(toy().ff.executor.routed_expert_ops)
    for level in (toy(), toy(keep="none")):
        assert in_the_backward_pass(level.jaxpr, "gather",
                                    rows) == routed * 2 * k
        assert count(level.jaxpr, "gather", rows) == routed * 4 * k


def test_moe_overflow_counts_the_layers_that_took_every_pair(monkeypatch):
    """`train_step.moe` says how many routed layers of a step overflowed
    their usual buffers: 0 on the toy's steps, every routed layer once
    the usual buffers are cut to one row tile; the gradient is the
    same either way."""
    def overflow_of(t):
        moe = [s for s in spans_named("train_step.moe")
               if s.parent_id in {p.span_id for p in
                                  spans_named("train_step")[-3:]}]
        assert moe and all(s.args["moe_dropped"] == 0 for s in moe)
        return {s.args["moe_overflow"] for s in moe}, \
            t.ff.telemetry.metrics.counter("train/moe_overflow").value

    def three_steps(ff):
        t = stepped(ff)
        jax.block_until_ready(ff.train_step(*batch())["loss"])
        ff.train_step(*batch())  # reports an earlier step's counts
        return t

    fits = three_steps(build())
    assert overflow_of(fits) == ({0}, 0)
    monkeypatch.setattr(rx, "GROUPED_SLACK", 1e-3)
    over = three_steps(build())
    routed = len(over.ff.executor.routed_expert_ops)
    layers, total = overflow_of(over)
    assert layers == {routed} and total >= routed
    same_gradient(over.moments, fits.moments)
    # the usual buffers of that step: one row tile
    kept = remat_kept(over.ff._step_fn.trace(*step_args(over.ff)).jaxpr.jaxpr,
                      over.ff.executor._remat_plan)
    assert (rx.GROUPED_ROW_TILE, D["e"]) in [
        a.shape for a in kept[segment_of(over.ff, "moe_1")]]


# -- 3. the fit ----------------------------------------------------------------
def test_a_step_that_fits_keeps_products_and_says_so():
    t = toy_stepped()
    assert isinstance(t.ff._step_fn, _RematStep)
    assert t.built["remat_segments"] == t.ff.executor.remat_segments > 0
    assert t.built["remat_keep"] == "products"
    assert t.first["first"] == 1 and t.first["remat_keep"] == "products"
    assert t.first["remat_kept_bytes"] == t.ff._step_fn.kept_bytes == sum(
        a.size * a.dtype.itemsize for v in toy().kept.values() for a in v)
    assert t.second["first"] == 0 and "remat_keep" not in t.second


@pytest.mark.parametrize("how", ["the_compiler_counts_too_much",
                                 "xla_refuses_the_program"])
def test_a_step_that_does_not_fit_is_lowered_keeping_nothing(
        how, monkeypatch):
    ff = build()
    if how == "the_compiler_counts_too_much":
        # a device smaller than what the compiler says the step holds
        monkeypatch.setattr(executor_mod, "_device_memory_limit",
                            lambda mesh: 1)
    else:
        real = jax.stages.Lowered.compile

        def refuse(lowered, *a, **kw):
            if ff.executor.remat_keep == "products":
                raise jax.errors.JaxRuntimeError(
                    "RESOURCE_EXHAUSTED: XLA:TPU compile permanent error. "
                    "Ran out of memory in memory space hbm.")
            return real(lowered, *a, **kw)

        monkeypatch.setattr(jax.stages.Lowered, "compile", refuse)
    t = stepped(ff)
    assert t.built["remat_keep"] == "products"  # what it was built to keep
    assert ff._step_fn.keep == "none" and ff._step_fn.kept_bytes == 0
    assert t.first["remat_keep"] == "none"
    assert t.first["remat_kept_bytes"] == 0
    # today's program, jax.checkpoint without a policy: its gradient to
    # the bit, and the forward's grouped products run again
    want = toy_stepped(keep="none").moments
    assert jax.tree.all(jax.tree.map(np.array_equal, t.moments, want))
    jaxpr = ff._step_fn.trace(*step_args(ff)).jaxpr.jaxpr
    assert count(jaxpr, "ragged_dot_general") == 2 * (6 + 3 + 15)


def test_another_refusal_is_not_taken_for_a_full_device(monkeypatch):
    def broken(lowered, *a, **kw):
        raise jax.errors.JaxRuntimeError("INTERNAL: something else")

    ff = build()
    monkeypatch.setattr(jax.stages.Lowered, "compile", broken)
    with pytest.raises(jax.errors.JaxRuntimeError, match="something else"):
        ff.train_step(*batch())


# -- 4. who else runs this code ------------------------------------------------
def bert_step_text():
    from flexflow_tpu import AdamOptimizer, FFConfig, FFModel, LossType
    from flexflow_tpu.models.transformer import build_bert

    ff = FFModel(FFConfig(batch_size=2, num_devices=1, flash_min_seq=1))
    build_bert(ff, batch_size=2, seq_length=16, hidden_size=32,
               num_layers=2, num_heads=2, intermediate_size=64,
               vocab_size=64, num_classes=2, from_token_ids=True)
    ff.compile(optimizer=AdamOptimizer(alpha=1e-4),
               loss_type=LossType.SPARSE_CATEGORICAL_CROSSENTROPY,
               metrics=(), devices=jax.devices()[:1])
    built = spans_named("build_step_fns")[-1].args
    inputs, labels = ff._device_put_batch(
        {"input": np.zeros((2, 16), np.int32)}, np.zeros((2,), np.int32))
    text = ff._step_fn.lower(ff._weights, ff._opt_state, ff._state, inputs,
                             labels, jax.random.key(0)).as_text()
    # jitted helpers are numbered by a process-wide counter
    return ff, built, re.sub(r"(@\w+?)_\d+\b", r"\1", text)


def test_a_step_without_remat_is_the_plain_jit_it_was(monkeypatch):
    """BERT's step, no `remat`: `build_step` returns the `jax.jit` it
    always did, the span counts 0 segments, and the ops' tags lower to
    nothing: the program's text is the text with the tags taken out."""
    ff, built, text = bert_step_text()
    assert type(ff._step_fn) is type(jax.jit(lambda: 0))
    assert built["attn_dense_ops"] == 2  # the flash branch's jnp twin
    assert built["remat_segments"] == 0 and "remat_keep" not in built
    assert "remat_kept" not in text and "optimization_barrier" not in text
    monkeypatch.setattr(fa, "remat_keep", lambda x: x)
    assert bert_step_text()[2] == text


def test_a_searched_plan_checkpoints_only_the_segments_it_names():
    from flexflow_tpu.strategy import data_parallel_strategy

    every = toy().ff
    named = sorted(segment_of(every, name) for name in ("mlp_0", "moe_1"))
    assert set(named) < {i for i, (*_, pure) in enumerate(
        every.executor._remat_plan) if pure}
    ff = build(remat=False, strategy=dataclasses.replace(
        data_parallel_strategy(1), remat=named))
    assert ff.executor.remat_segments == 2
    jaxpr = ff._step_fn.trace(*step_args(ff)).jaxpr.jaxpr
    assert sum(bool(e.params.get("differentiated")) for e in jaxpr.eqns) == 2
    kept = remat_kept(jaxpr, ff.executor._remat_plan)
    assert sorted(kept) == named
    assert {i: sorted(a.shape for a in v) for i, v in kept.items()} == {
        i: sorted(a.shape for a in toy().kept[i]) for i in named}
    t = stepped(ff)
    assert t.first["remat_keep"] == "products"
    same_gradient(t.moments, toy_stepped(remat=False).moments)
