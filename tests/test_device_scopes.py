"""The device-scope grammar (flexflow_tpu/obs/scopes.py): every step
program the benchmark's families build names its instructions by op
kind, op name and part, and `parse` reads those names back.  Each family
is the benchmark's own toy configuration, lowered and compiled on the
CPU; the strings come from the compiled HLO's `op_name` metadata."""
import json
import os
import re

import jax
import numpy as np
import pytest
from _family import config

from benchmarks.run import load_module
from flexflow_tpu import (AdamOptimizer, FFConfig, FFModel, LossType,
                          SGDOptimizer)
from flexflow_tpu.obs import scopes
from flexflow_tpu.obs.scopes import (BACKWARD, FORWARD, MIXED, NOT_OPS,
                                     RECOMPUTE, ROUTED_PARTS, Scope,
                                     element, parse)

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
#: instructions whose time a table has to be able to lay at a layer
HEAVY = re.compile(r" (dot|ragged-dot|convolution|custom-call)\(")
INSTRUCTION = re.compile(r"^\s*(?:ROOT )?%?[\w.\-]+ = .*$", re.M)
OP_NAME = re.compile(r'op_name="([^"]*)"')


def op_names(compiled, only=None):
    """The `op_name` of every instruction of the compiled program (of
    those matching ``only``); an instruction without one gives ""."""
    out = []
    for line in INSTRUCTION.findall(compiled.as_text()):
        if " parameter(" in line or " constant(" in line \
                or " get-tuple-element(" in line or " tuple(" in line \
                or " bitcast(" in line:
            continue
        if only is None or only.search(line):
            m = OP_NAME.search(line)
            out.append(m.group(1) if m else "")
    return out


def toy(name):
    cfg = config(name + ".json")
    return cfg, load_module("families", cfg["family"])


def train_programs(config, traffic):
    cfg, fam = toy(config)
    with open(os.path.join(ROOT, "benchmarks", "traffic",
                           traffic + ".json")) as f:
        t = json.load(f)
    devices = jax.devices()[:1]
    ff = fam.build_model(cfg, t["batch_per_chip"], t["seq"], 1)
    fam.compile_model(ff, cfg, devices)
    inputs, labels = fam.make_batch(cfg, t["batch_per_chip"], t["seq"],
                                    np.random.default_rng(0))
    put_inputs, put_labels = ff._device_put_batch(inputs, labels)
    step = ff._step_fn.lower(ff._weights, ff._opt_state, ff._state,
                             put_inputs, put_labels, jax.random.key(0))
    return ff, {"step": step.compile()}


def serve_programs(config):
    from flexflow_tpu.serving.scheduler import PagedKVDecodeModel

    cfg, fam = toy(config)
    ff = fam.build_server(cfg, jax.devices()[:1])
    if ff._weights is None:  # a `defer_weights` compile
        ff.set_weights(fam.make_weights(cfg, 0, "program"))
    c = ff.config
    model = PagedKVDecodeModel(
        ff, batch_slots=c.serving_slots, page_size=c.kv_page_size,
        num_blocks=c.kv_pool_blocks or None, devices=jax.devices()[:1],
        prefill_chunk=c.prefill_chunk, prefix_cache=False)
    b, chunk = model.batch_slots, model.prefill_chunk
    table = np.zeros((b, model.max_blocks_per_seq), np.int32)
    rows = model._row_tokens(np.ones((b,), np.int32))
    zeros = np.zeros((b,), np.int32)
    w, st = model.ffd._weights, model._state
    return model.ffd, {
        "step": model._step_fn.lower(w, st, zeros, zeros, table,
                                     *rows).compile(),
        "prefill": model._prefill_fn.lower(
            w, st, np.zeros((b, chunk), np.int32), zeros, table,
            *model._row_tokens(np.ones((b,), np.int32),
                               model.prefill_passes == 1)).compile(),
    }


FAMILIES = {
    "bert": lambda: train_programs("toy-bert", "toy-train"),
    "gpt": lambda: serve_programs("toy-gpt2"),
    "kimi_k2": lambda: serve_programs("toy-kimi"),
    "qwen3_next": lambda: serve_programs("toy-qwen3-next"),
    "lfm2_moe": lambda: train_programs("toy-lfm2", "toy-lfm2-train"),
}
_built = {}


def programs(family):
    if family not in _built:
        _built[family] = FAMILIES[family]()
    return _built[family]


def kinds_of(ff):
    return {type(op).__name__ for op in ff.operators.topo_order()}


@pytest.mark.parametrize("family", sorted(FAMILIES))
def test_every_product_and_kernel_lies_under_a_known_kind(family):
    ff, compiled = programs(family)
    known = kinds_of(ff) | set(NOT_OPS)
    for program, exe in compiled.items():
        heavy = op_names(exe, HEAVY)
        assert heavy, f"{family}/{program}: no product in the program?"
        for name in heavy:
            got = parse(name)
            assert got.kind in known, (family, program, name, got)
            assert got.program == program, (name, got)


@pytest.mark.parametrize("family", ["kimi_k2", "qwen3_next", "lfm2_moe"])
def test_every_routed_expert_instruction_lies_under_a_part(family):
    _, compiled = programs(family)
    allowed = set(ROUTED_PARTS) | {scopes.CAST_WEIGHTS}
    seen = set()
    for exe in compiled.values():
        for name in op_names(exe):
            got = parse(name)
            if got.kind == "RoutedExperts":
                assert got.part in allowed, (family, name, got)
                seen.add(got.part)
    assert {"route", "dispatch", "products", "combine"} <= seen, seen


@pytest.mark.parametrize("family,phases", [
    ("lfm2_moe", {FORWARD, BACKWARD, RECOMPUTE}),   # `remat` on
    ("bert", {FORWARD, BACKWARD}),
    ("kimi_k2", {FORWARD}),
])
def test_phases_are_jaxs_own(family, phases):
    ff, compiled = programs(family)
    if RECOMPUTE in phases:
        assert ff.executor.remat_segments > 0
    seen = {parse(n).phase for exe in compiled.values()
            for n in op_names(exe) if parse(n).kind in kinds_of(ff)}
    assert seen == phases


@pytest.mark.parametrize("family,kind,parts", [
    ("bert", "MultiHeadAttention", {"proj", "core", "out"}),
    ("gpt", "MultiHeadAttention", {"proj", "paged_read", "out"}),
    ("kimi_k2", "MLAttention", {"proj", "paged_read", "out"}),
    ("qwen3_next", "GatedDeltaNet", {"proj", "conv", "recurrence", "out"}),
    ("qwen3_next", "MultiHeadAttention", {"proj", "paged_read", "out"}),
    ("lfm2_moe", "ShortConv", {"proj", "conv", "out"}),
    ("lfm2_moe", "MultiHeadAttention", {"proj", "core", "out"}),
])
def test_mixer_parts(family, kind, parts):
    _, compiled = programs(family)
    seen = {parse(n).part for exe in compiled.values()
            for n in op_names(exe) if parse(n).kind == kind}
    assert seen - {scopes.CAST_WEIGHTS} == parts


def _mlp(cfg, optimizer):
    ff = FFModel(cfg)
    x = ff.create_tensor((cfg.batch_size, 64), name="input")
    ff.dense(ff.relu(ff.dense(x, 128)), 10)
    ff.compile(optimizer=optimizer,
               loss_type=LossType.SPARSE_CATEGORICAL_CROSSENTROPY)
    rng = np.random.default_rng(0)
    put = ff._device_put_batch(
        {"input": rng.normal(size=(cfg.batch_size, 64)).astype(np.float32)},
        rng.integers(0, 10, size=(cfg.batch_size,)).astype(np.int32))
    return ff, ff._step_fn.lower(ff._weights, ff._opt_state, ff._state,
                                 *put, jax.random.key(0)).compile()


@pytest.mark.parametrize("optimizer,zero_stage", [
    ("sgd", 0), ("adam", 0), ("adam", 1), ("adam", 2), ("adam", 3),
    ("sgd", 2)])
def test_optimizer_scope_holds_the_update(optimizer, zero_stage):
    """Everything that reads an optimizer slot or a gradient to write a
    weight is named `optimizer`: found by the update's own constants."""
    opt = (SGDOptimizer(lr=0.125, momentum=0.5) if optimizer == "sgd"
           else AdamOptimizer(alpha=0.125))
    cfg = FFConfig(batch_size=16, num_devices=8 if zero_stage else 1,
                   zero_stage=zero_stage)
    ff, exe = _mlp(cfg, opt)
    names = op_names(exe)
    under = [n for n in names if parse(n).kind == scopes.OPTIMIZER]
    assert under and all(parse(n).program == "step" for n in under)
    # the update's arithmetic appears nowhere else: a weight leaves the
    # step through an instruction named `optimizer` (or a copy of one)
    text = exe.as_text()
    root = next(line for line in text.splitlines()
                if line.lstrip().startswith("ROOT")
                and "ENTRY" not in line and " tuple(" in line
                and text.index(line) > text.index("ENTRY"))
    assert root
    produced = {}
    for line in INSTRUCTION.findall(text[text.index("ENTRY"):]):
        m = re.match(r"\s*(?:ROOT )?%?([\w.\-]+) = ", line)
        o = OP_NAME.search(line)
        produced[m.group(1)] = o.group(1) if o else ""
    operands = re.findall(r"%?([\w.\-]+)(?:,|\))", root.split(" tuple(")[1])
    n_weights = len(jax.tree.leaves(ff._weights))
    kinds = [parse(produced.get(o, "")).kind for o in operands[:n_weights]]
    assert kinds.count(scopes.OPTIMIZER) == n_weights, (operands, kinds)


LISTED = [
    ("jit(step)/jvp({d0})/products/dot_general",
     Scope("step", FORWARD, "Dense", "d0", "products")),
    ("jit(step)/transpose(jvp({d0}))/products/dot_general",
     Scope("step", BACKWARD, "Dense", "d0", "products")),
    ("jit(step)/transpose(jvp(jvp()))/checkpoint/{d1}/products/dot_general",
     Scope("step", BACKWARD, "Dense", "d1", "products")),
    ("jit(step)/transpose(jvp(jvp()))/checkpoint/rematted_computation/{d1}/tanh",
     Scope("step", RECOMPUTE, "Dense", "d1", None)),
    ("jit(step)/jvp({loss})/reduce_sum",
     Scope("step", FORWARD, "loss", None, None)),
    ("jit(step)/{optimizer}/sub",
     Scope("step", FORWARD, "optimizer", None, None)),
    ("jit(step)/transpose(jvp({loss}))/mul;"
     "jit(step)/transpose(jvp({loss}))/broadcast_in_dim",
     Scope("step", BACKWARD, "loss", None, None)),
    # two layers, one kind and part: the name goes, the place stays
    ("jit(step)/jvp({d0})/products/mul;jit(step)/jvp({d1})/products/add",
     Scope("step", FORWARD, "Dense", None, "products")),
    # two origins that disagree: its own row
    ("jit(step)/transpose(jvp({d0}))/products/dot_general;"
     "jit(step)/{optimizer}/sub", Scope("step", None, MIXED, None, None)),
    # the recorded v5e trace's bare names, with the xplane's trailing ":"
    ("jit(prefill)/while/body/closed_call/attn_0/bse,ehd->bshd/dot_general:",
     Scope("prefill", FORWARD, None, None, None)),
    ("jit(prefill)/while/body/closed_call/{attn}/proj/bse,ehd->bshd/"
     "dot_general:",
     Scope("prefill", FORWARD, "MultiHeadAttention", "attn_0", "proj")),
    ("jit(step)/{attn}/paged_read/jit(_take)/gather:",
     Scope("step", FORWARD, "MultiHeadAttention", "attn_0", "paged_read")),
    ("copy-start.7", Scope(None, FORWARD, None, None, None)),
    # an instruction the compiler made from an argument: the op's name
    ("state['attn_3']['k_cache']:",
     Scope(None, None, None, "attn_3", scopes.ARG_LAYOUT)),
    ("args[0]['tok_embed']['weight']:",
     Scope(None, None, None, "tok_embed", scopes.ARG_LAYOUT)),
    # `lax.ragged_dot` under the name XLA's TPU pipeline gives it
    ("ragged-dot-none:", Scope(None, None, "RoutedExperts", None, "products")),
    ("", Scope(None, FORWARD, None, None, None)),
]


@pytest.mark.parametrize("template,want", LISTED,
                         ids=[str(i) for i in range(len(LISTED))])
def test_parse_round_trip(template, want):
    made = template.format(
        d0=element("Dense", "d0"), d1=element("Dense", "d1"),
        loss=element("loss"), optimizer=element("optimizer"),
        attn=element("MultiHeadAttention", "attn_0"))
    assert parse(made) == want


def test_one_place_emits_what_xla_renames():
    """`RENAMED_BY_XLA` names the ONE op and part that emit a primitive
    XLA renames: `lax.ragged_dot` / `ragged_dot_general`, and since
    PR 50 the kernels of `ops/pallas/grouped_matmul.py` that take their
    place on a TPU and keep their scope path, are entered from
    `ops/routed_experts.py`'s three `grouped_matmul*` functions alone,
    all under `RoutedExperts | products`."""
    import subprocess

    assert scopes.RENAMED_BY_XLA == {
        "ragged-dot": ("RoutedExperts", "products")}
    found = subprocess.run(
        ["grep", "-rlE", r"ragged_dot(_general)?\(|import grouped_matmul", "--include=*.py",
         os.path.join(ROOT, "flexflow_tpu")],
        capture_output=True, text=True).stdout.split()
    assert [os.path.relpath(f, ROOT) for f in found] == [
        "flexflow_tpu/ops/routed_experts.py"]
    names = op_names(programs("lfm2_moe")[1]["step"])
    assert all(parse(n).kind != "RoutedExperts" or parse(n).program == "step"
               for n in names)


def test_element_refuses_what_the_grammar_lacks():
    with pytest.raises(ValueError):
        element("sampling")
    assert element("proj") == "proj"
