"""Device scopes: the names the program gives its device work.

Every instruction of every step program is traced under
`jax.named_scope`s built here, and nowhere else, so that the
`op_name` XLA keeps in an instruction's metadata (the `tf_op` stat of a
profiler trace's `XLA Ops` events) says which part of the program the
instruction belongs to.  `named_scope` runs at trace time only: the
compiled program is the same with or without it, and nothing switches
it off.

The grammar (docs/OBSERVABILITY.md "Device scopes"):

* a PCG op: `op_scope(op)` = ONE element, `<Kind>:<name>`
  (`MultiHeadAttention:attn_3`; the kind is the op's class), set in
  `GraphExecutor._exec_op`;
* a part inside an op: `scope(<part>)`, one of `PARTS`, only where a
  question of the records needs it (`RoutedExperts`, the attention ops,
  `GatedDeltaNet`, `Mamba2Mixer`, `KimiDeltaAttention`, `ShortConv`,
  `EvaAttention`;
  `cast_weights`
  where the executor casts an op's weight to the compute precision);
* what is not an op: `scope(<one of NOT_OPS>)`: `loss`, `optimizer`,
  `metrics`, `logits` (the sink's way out of the graph: its cast to
  float32, a decode step's last position), `feed` (a serving step
  program's own arithmetic on positions and block tables);
* the phase is jax's own (`jvp(..)`, `transpose(..)`,
  `checkpoint/rematted_computation`): the program adds nothing for it.

`parse(op_name)` is the one reader of it.
"""
from __future__ import annotations

import re
from collections import namedtuple

import jax

LOSS, OPTIMIZER, METRICS, LOGITS = "loss", "optimizer", "metrics", "logits"
#: what a serving step program computes of its own inputs before the
#: graph runs (a chunk's positions, the scratch routing of its pads)
FEED = "feed"
#: scope elements outside every PCG op
NOT_OPS = (LOSS, OPTIMIZER, METRICS, LOGITS, FEED)

CAST_WEIGHTS = "cast_weights"
#: parts of a routed-expert layer (`shared` is its shared expert, `zero`
#: the identity experts' term: each row times its identity picks' weights)
ROUTED_PARTS = ("route", "dispatch", "products", "shared", "combine", "zero")
#: parts of an attention op (`core` XOR `paged_read` XOR `window_read`,
#: a window layer's read of its per-slot ring), of a delta-net
#: layer (`KimiDeltaAttention`: `gate` its decays and step sizes,
#: `core` the delta rule, `norm_gate` the gated head norm) and of a
#: short convolution; of `EvaAttention`: `summarise` the pooling of the
#: chunks a step completes, `state_write` the step's keys and values
#: into the window; of `MLAttention` with an indexer: `index_proj` the
#: indexer's queries, key and head weights, `index_scores` its scores
#: against the row's index keys, `topk` the picks, `selected_read` the
#: picked latents' gather and the attention over them (`state_write`
#: there: the step's latents and index keys into their pools)
MIXER_PARTS = ("proj", "core", "paged_read", "conv", "recurrence", "out",
               "gate", "norm_gate", "summarise", "state_write",
               "window_read", "index_proj", "index_scores", "topk",
               "selected_read")
PARTS = ROUTED_PARTS + MIXER_PARTS + (CAST_WEIGHTS,)
#: `parse`'s part for an instruction the compiler made from one of the
#: step program's ARGUMENTS (the layout copy of a weight or of a paged
#: pool): XLA names it by the argument's path, `state['attn_0']['k_cache']`,
#: whose second-to-last key is the op's name.  No scope sets it.
ARG_LAYOUT = "arg_layout"

#: primitives that XLA's TPU pipeline rewrites into a call of its own
#: and NAMES ITSELF, dropping the scope path (`lax.ragged_dot` becomes a
#: Mosaic call whose `op_name` is `ragged-dot-none`): the prefix of that
#: name -> the (kind, part) of the one place in the program that emits
#: the primitive (`ops/routed_experts.py`: the three `grouped_matmul*`
#: functions, wherever `pick_grouped_tiling` leaves them the ragged dot;
#: the Pallas kernel they take on a TPU since PR 50 keeps its scope
#: path like any other call).  Its phase is lost with the path.
RENAMED_BY_XLA = {"ragged-dot": ("RoutedExperts", "products")}

FORWARD, BACKWARD, RECOMPUTE = "forward", "backward", "recompute"
#: `parse`'s kind for an instruction fused from differently placed origins
MIXED = "mixed"

Scope = namedtuple("Scope", "program phase kind name part")

_OP_ELEMENT = re.compile(r"^([A-Za-z_]\w*):([\w.\-]+)$")
_PROGRAM = re.compile(r"^jit\(([^()]*)\)")
_ARG_PATH = re.compile(r"^\w+(?:\[[^\]]*\])*\['([\w.\-]+)'\]\['[\w.\-]+'\]$")


def element(kind: str, name: str = None) -> str:
    """The scope element of a PCG op (`kind`, `name`), or of a part or a
    non-op (`kind` alone, which must be one the grammar has)."""
    if name is not None:
        return f"{kind}:{name}"
    if kind not in PARTS and kind not in NOT_OPS:
        raise ValueError(f"{kind!r} is no part and no non-op scope")
    return kind


def scope(kind: str, name: str = None):
    """`jax.named_scope` of `element(kind, name)`."""
    return jax.named_scope(element(kind, name))


def op_scope(op):
    """The scope of one PCG op: its class and its name in one element."""
    return scope(type(op).__name__, op.name)


def _parse_one(op_name: str) -> Scope:
    for prefix, (kind, part) in RENAMED_BY_XLA.items():
        if op_name.startswith(prefix):
            return Scope(None, None, kind, None, part)
    arg = _ARG_PATH.match(op_name)
    if arg:  # the op's name without its kind: a reader looks that up
        return Scope(None, None, None, arg.group(1), ARG_LAYOUT)
    m = _PROGRAM.match(op_name)
    # "a/t(u(b/c))/d" -> elements a b c d, transforms t u
    tokens = re.split(r"([/()])", op_name)
    elements = [t for t, nxt in zip(tokens[::2], tokens[1::2] + [""])
                if t and nxt != "("]
    transforms = {t for t, nxt in zip(tokens[::2], tokens[1::2]) if nxt == "("}
    if "rematted_computation" in elements:
        phase = RECOMPUTE
    else:
        phase = BACKWARD if "transpose" in transforms else FORWARD
    kind = name = part = None
    for e in elements[1 if m else 0:]:
        if kind is None:
            op = _OP_ELEMENT.match(e)
            if op:
                kind, name = op.groups()
            elif e in NOT_OPS:
                kind = e
        elif e in PARTS:
            part = e  # the innermost
    return Scope(m.group(1) if m else None, phase, kind, name, part)


def parse(op_name: str) -> Scope:
    """(program, phase, kind, name, part) of an instruction's `op_name`
    (an xplane event's `tf_op`, whose trailing `:` is dropped).  What
    the string does not say is None: a bare `jit(step)/attn_0/..` has no
    kind; a name XLA gave itself to a primitive it rewrote
    (`RENAMED_BY_XLA`) has kind and part and nothing else; an argument's
    path (`weights['attn_0']['wo']`) has the op's name and the part
    `ARG_LAYOUT`, and leaves the kind to whoever knows the graph.  XLA joins
    the origins of a fused instruction with `;`: where they agree on
    program, phase, kind and part that is the answer, where they do not
    the kind is `MIXED`."""
    found = [_parse_one(s) for s in op_name.rstrip(":").split(";") if s]
    if not found:
        return Scope(None, FORWARD, None, None, None)
    first = found[0]
    if all(f == first for f in found):
        return first
    if all(f._replace(name=None) == first._replace(name=None)
           for f in found):
        return first._replace(name=None)
    programs = {f.program for f in found}
    return Scope(first.program if len(programs) == 1 else None,
                 first.phase if len({f.phase for f in found}) == 1 else None,
                 MIXED, None, None)
