"""The benchmark that is run reads the program's host spans by NAME
(`benchmarks/readers/<metric>.py`, listed in `BENCHMARK.json`'s
`per_layer`).  One case a `program_span` / `program_counter` metric whose
reader names a span or a span argument literally: every such name is one
`flexflow_tpu/` emits (`span("...", arg=...)`, `.set(arg=...)`).  A
rename of `sched.iteration` or of `kv_blocks_live` then fails the cases
of exactly the metrics that read it, here, instead of turning a ledger
column to null on the chip.  Nothing under `benchmarks/` is imported or
changed: its files are parsed."""
import ast
import json
import os

import pytest

ROOT = os.path.abspath(os.path.join(os.path.dirname(__file__), ".."))
BENCH = os.path.join(ROOT, "benchmarks")
SPAN_TAKERS = ("named", "compile_child_seconds")  # (records, "<span>")


def _tree(path):
    with open(path) as f:
        return ast.parse(f.read())


def _callee(call):
    f = call.func
    return f.attr if isinstance(f, ast.Attribute) else getattr(f, "id", None)


def _strs(nodes):
    return [n.value for n in nodes
            if isinstance(n, ast.Constant) and isinstance(n.value, str)]


def _is_args(node):
    return isinstance(node, ast.Attribute) and node.attr == "args"


def names_read(node, helpers):
    """(span names, span-name suffixes, span args) that `node`'s code
    names literally, following calls into `helpers` ({function name:
    its FunctionDef} of the `benchmarks` modules the file imports)."""
    spans, suffixes, args = set(), set(), set()
    for n in ast.walk(node):
        if isinstance(n, ast.Call):
            callee = _callee(n)
            if callee in SPAN_TAKERS:
                spans.update(_strs(n.args[1:2]))
            elif callee == "dispatch_args":
                args.update(_strs(n.args[1:]))
            elif callee == "endswith" and isinstance(n.func, ast.Attribute) \
                    and getattr(n.func.value, "attr", None) == "name":
                suffixes.update(_strs(n.args[:1]))
            elif callee == "get" and _is_args(n.func.value):
                args.update(_strs(n.args[:1]))
            if callee in helpers:
                for got, more in zip((spans, suffixes, args),
                                     names_read(helpers[callee], {})):
                    got.update(more)
        elif isinstance(n, ast.Subscript) and _is_args(n.value):
            args.update(_strs([n.slice]))
    return spans, suffixes, args


def reader_names(metric):
    """What the reader of `metric` names, or None where it has no reader
    file (the driver's own counter) or names no span."""
    parts = metric.split(".")
    for k in range(len(parts), 0, -1):  # benchmarks/run.py load_module
        path = os.path.join(BENCH, "readers", ".".join(parts[:k]) + ".py")
        if os.path.isfile(path):
            break
    else:
        return None
    tree, helpers = _tree(path), {}
    for imp in ast.walk(tree):
        if isinstance(imp, ast.ImportFrom) and imp.module \
                and imp.module.split(".")[0] == "benchmarks":
            for alias in imp.names:
                for mod in (f"{imp.module}.{alias.name}", imp.module):
                    src = os.path.join(ROOT, *mod.split(".")) + ".py"
                    if os.path.isfile(src):
                        helpers.update({
                            f.name: f for f in ast.walk(_tree(src))
                            if isinstance(f, ast.FunctionDef)})
                        break
    found = names_read(tree, helpers)
    return found if any(found) else None


with open(os.path.join(ROOT, "BENCHMARK.json")) as _f:
    CASES = {m["name"]: reader_names(m["name"])
             for m in json.load(_f)["per_layer"]
             if m["source"] in ("program_span", "program_counter")}
CASES = {name: found for name, found in CASES.items() if found}


@pytest.fixture(scope="module")
def emitted():
    """(span names, span args) the package's source can emit: the first
    argument and the keywords of every `span(...)`, the keywords of every
    `.set(...)`, and, for args handed over as `**dict`, the string keys
    of dict literals in the modules that open spans and in the op
    modules that count a dispatch's args for the scheduler to set
    (`Op.dispatch_group_of`, ISSUE 59); `f"moe_{k}"` over `MOE_STATS`
    and `MOE_ZERO_STATS`, spelled out, and the `eva_*` counts, which
    `ops/eva_attention.py eva_row_counts` names."""
    from flexflow_tpu.ops.eva_attention import eva_row_counts
    from flexflow_tpu.ops.routed_experts import MOE_STATS, MOE_ZERO_STATS

    spans = set()
    args = {f"moe_{k}" for k in MOE_STATS + MOE_ZERO_STATS} | set(
        eva_row_counts(4, 2, 2, 1, [0], [1]))
    for dirpath, _, files in os.walk(os.path.join(ROOT, "flexflow_tpu")):
        for fn in files:
            if not fn.endswith(".py"):
                continue
            tree = _tree(os.path.join(dirpath, fn))
            opened = set()
            for n in ast.walk(tree):
                if isinstance(n, ast.Call) and _callee(n) in ("span", "set"):
                    if _callee(n) == "span":
                        opened.update(_strs(n.args[:1]))
                    args.update(k.arg for k in n.keywords if k.arg)
            spans |= opened
            if opened or any(isinstance(n, ast.FunctionDef)
                             and n.name == "dispatch_group_of"
                             for n in ast.walk(tree)):
                args.update(k for n in ast.walk(tree)
                            if isinstance(n, ast.Dict)
                            for k in _strs(n.keys))
    return spans, args


def test_the_contract_has_its_cases():
    assert len(CASES) >= 12, sorted(CASES)
    # the scan sees each way a reader names things
    assert "sched.iteration" in CASES["sched.self_ms.capacity"][0]
    assert ".dispatch" in CASES["sched.self_ms.capacity"][1]
    assert "rows" in CASES["decode.rows.capacity"][2]
    assert "kv_blocks_live" in CASES["kv.read_share.capacity"][2]
    assert "sched.decode.dispatch" in CASES["kv.read_share.capacity"][0]
    assert {"compile", "init_weights"} \
        <= CASES["compile.init_weights_inner_s"][0]


@pytest.mark.parametrize("metric", sorted(CASES))
def test_reader_names_only_what_the_program_emits(metric, emitted):
    spans, suffixes, args = CASES[metric]
    have_spans, have_args = emitted
    assert spans <= have_spans, \
        f"{metric}: no span(...) under flexflow_tpu/ is called " \
        f"{sorted(spans - have_spans)}"
    for suffix in suffixes:
        assert any(s.endswith(suffix) for s in have_spans), (metric, suffix)
    assert args <= have_args, \
        f"{metric}: no span under flexflow_tpu/ carries " \
        f"{sorted(args - have_args)}"
