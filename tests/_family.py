"""What the family files (test_kimi_k2, test_qwen3_next, test_ouro,
test_lfm2_moe*, test_kimi_linear*, test_remat_keep), the delta rule's
kernel files and the serving files share: the configuration loader, the
one `close`, `op_alone` (an op's forward and gradient against its
family's plain reference), a family's whole model against its reference,
and the toy GPT that six serving files train.

What a file of tests compares it COMPILES (`jax.jit`), once: run op by
op, every primitive of every shape is an XLA program of its own, and
compiling those was three quarters of such a test's time (PR 46).  Two
exceptions, each kept where its own seconds said so (`jit=False`): a
Pallas kernel under the interpreter is a program already, and a tile
function whose sub-tiles share one small program a primitive compiles
slower as one unrolled program than eagerly.
"""
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks import check
from flexflow_tpu import FFConfig, FFModel, LossType, SGDOptimizer

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
OP_TOL = 1e-5


def config(name):
    with open(os.path.join(ROOT, "benchmarks", "configs", name)) as f:
        return json.load(f)


def close(got, want, tol=OP_TOL):
    """Equal within `tol` of `want`'s largest magnitude."""
    got, want = np.asarray(got, np.float32), np.asarray(want, np.float32)
    assert got.shape == want.shape
    err, scale = float(np.max(np.abs(got - want))), float(np.max(np.abs(want)))
    assert err <= tol * scale, (err, scale)


def value_and_gradient(f, probe, x, w, jit=True):
    """(f(x, w), the gradient by x and by w of sum(f(x, w) * probe)), as
    one program."""
    def scalar(x, w):
        out = f(x, w)
        return jnp.sum(out * probe), out

    run = jax.value_and_grad(scalar, argnums=(0, 1), has_aux=True)
    (_, out), grads = (jax.jit(run) if jit else run)(x, w)
    return out, grads


def probed(rule, jit=True):
    """For a recurrence `rule(S, q, k, v, g, beta) -> (state, o)`:
    ({name: operand}, probe_o, probe_s) -> ((the probes' sum over both,
    (state, o)), its gradient by operand), one program a shape."""
    def scalar(args, probe_o, probe_s):
        state, o = rule(*(args[n] for n in "S q k v g beta".split()))
        return jnp.sum(o * probe_o) + jnp.sum(state * probe_s), (state, o)

    run = jax.value_and_grad(scalar, has_aux=True)
    return jax.jit(run) if jit else run


def reference_side(reference, leaves, embed, seq=16, batch=2, seed=13):
    """A seeded case for `op_alone` and the reference's side of it:
    (x [batch, seq, embed], {leaf: weight}, probe, (value, gradient) of
    `reference(row [seq, embed], {leaf})` over the rows).  A function of
    its arguments alone, so cases that differ in the PROGRAM's plan
    compute it once."""
    keys = jax.random.split(jax.random.key(seed), len(leaves) + 2)
    w = {n: 0.3 * jax.random.normal(k, shape)
         + (1.0 if "norm" in n or n == "gamma" else 0.0)
         for k, (n, shape) in zip(keys, leaves.items())}
    x = jax.random.normal(keys[-1], (batch, seq, embed))
    probe = jax.random.normal(keys[-2], (batch, seq, embed))

    def plain(x, w):
        with jax.default_matmul_precision("highest"):
            return jnp.stack([reference(row, w) for row in x])

    return x, w, probe, value_and_gradient(plain, probe, x, w)


def op_alone(build, case, inputs=1, positions=False, prepare=None,
             grad_tol=OP_TOL, leaf_tol=(), chooses=(), jit=True):
    """The op `build(ff, x, positions)` makes against `case`
    (`reference_side`'s answer): its `forward`'s output and the
    gradients of the input and of every leaf (within `grad_tol`, but the
    leaves `leaf_tol` names their own; a leaf in `chooses` only chooses,
    and takes no gradient either way).  The op's weights past the
    case's leaves are its counters."""
    x, w, probe, (want, want_grads) = case
    batch, seq, embed = x.shape
    ff = FFModel(FFConfig(batch_size=batch, num_devices=1))
    x_t = ff.create_tensor([batch, seq, embed], name="x")
    pos_t = ff.create_tensor([batch, seq], dtype="int32", name="positions") \
        if positions else None
    op = build(ff, x_t, pos_t).owner_op
    if prepare:
        prepare(op)
    names = list(w)  # (jax hands a dict back with its keys sorted)
    assert [s.name for s in op.weight_specs[:len(w)]] == names
    state = [jnp.zeros(s.shape.logical_shape, jnp.int32)
             for s in op.weight_specs[len(w):]]
    pos = [jnp.tile(jnp.arange(seq, dtype=jnp.int32), (batch, 1))] \
        if positions else []

    def program(x, w):
        return op.forward([x] * inputs + pos, [w[n] for n in names] + state,
                          training=True)[0]

    got, got_grads = value_and_gradient(program, probe, x, w, jit)
    close(got, want)
    close(got_grads[0], want_grads[0], grad_tol)
    for n in names:
        if n in chooses:
            assert not np.any(np.asarray(got_grads[1][n]))
        else:
            close(got_grads[1][n], want_grads[1][n],
                  dict(leaf_tol).get(n, grad_tol))
    return op


# -- a family's whole model against its reference ---------------------------
def model_reference(fam, cfg, seeded, batch):
    """The float32 reference on `batch`: its logits and its loss over
    the rows, one program, and its gradient by group."""
    inputs, labels = batch
    ids, labels = jnp.asarray(inputs["input"]), jnp.asarray(labels)

    @jax.jit
    def rows(w):
        with jax.default_matmul_precision("highest"):
            return (jnp.stack([fam.logits_fn(w, row, cfg) for row in ids]),
                    jnp.stack([fam.sequence_loss(w, row, lab, cfg, "float32")
                               for row, lab in zip(ids, labels)]))

    logits, losses = rows(seeded["program"])
    return dict(logits=logits, loss=np.mean(np.asarray(losses, np.float64)),
                grads=fam.reference_grads(seeded["reference"], ids, labels))


@pytest.fixture(scope="module")
def seeded(request):
    """The requesting module's `fam`, `CFG`, `SEED`: the seed's weights
    in both layouts (the makers are programs of seconds even at a toy
    size)."""
    m = request.module
    return {layout: m.fam.make_weights(m.CFG, m.SEED, layout)
            for layout in ("program", "reference")}


@pytest.fixture(scope="module")
def batch(request):
    m = request.module
    return m.fam.make_batch(m.CFG, m.B, m.S, np.random.default_rng(5))


@pytest.fixture(scope="module")
def reference(request, seeded, batch):
    return model_reference(request.module.fam, request.module.CFG, seeded,
                           batch)


def compiled(fam, cfg, weights, batch, seq):
    """The family's trainer, compiled on one device, at `weights`."""
    ff = fam.build_model(cfg, batch, seq, 1)
    fam.compile_model(ff, cfg, jax.devices()[:1])
    ff.set_weights(jax.tree.map(np.asarray, weights))
    return ff


def first_step_equals_the_reference(fam, cfg, ff, batch, reference, tol):
    """One `train_step` of a new `ff`: its loss, and its gradient by
    group (Adam's first moment after one step from zero is
    (1 - beta1) g; the bias that only chooses takes none, in no group)
    against `model_reference`'s."""
    loss = float(ff.train_step(*batch)["loss"])
    scale = 1.0 / (1.0 - cfg["optimizer"]["beta1"])
    got = fam.to_reference_layout(jax.tree.map(
        lambda m: np.asarray(m, np.float32) * scale, ff._opt_state["m"]))
    stats = check.group_rel_l2(got, reference["grads"], fam.GROUPS)
    assert set(stats) == set(fam.GROUPS) == {
        k[len("grad."):] for k in cfg["tolerance"]}
    assert max(stats.values()) <= tol, stats
    assert not any(np.any(v["router_bias"])
                   for v in got["choosing_bias"].values())
    assert abs(loss - reference["loss"]) <= 1e-5 * reference["loss"]


# -- what the served families' files share ---------------------------------------
def trained_gpt(devices, batch, seq, vocab, steps=40):
    """(a toy GPT trained `steps` steps on arithmetic sequences mod
    `vocab`, so that greedy decoding has something to say; the ids it
    was trained on)."""
    from flexflow_tpu.models.transformer import build_gpt

    ff = FFModel(FFConfig(batch_size=batch, num_devices=1))
    build_gpt(ff, batch_size=batch, seq_length=seq, hidden_size=32,
              num_layers=2, num_heads=4, intermediate_size=64,
              vocab_size=vocab)
    ff.compile(optimizer=SGDOptimizer(lr=0.5),
               loss_type=LossType.SPARSE_CATEGORICAL_CROSSENTROPY,
               devices=devices[:1])
    rng = np.random.RandomState(0)
    start = rng.randint(0, vocab, (batch, 1))
    step = rng.randint(1, 6, (batch, 1))
    seq_ids = (start + step * np.arange(seq + 1)) % vocab
    ids = seq_ids[:, :-1].astype(np.int32)
    labels = seq_ids[:, 1:].astype(np.int32)
    pos = np.broadcast_to(np.arange(seq, dtype=np.int32), (batch, seq)).copy()
    for _ in range(steps):
        ff.train_step({"input": ids, "positions": pos}, labels)
    return ff, ids


#: the five families on the one-pass prefill program and their toy
#: configurations (`tests/test_pass_decode.py`, `tests/test_lookahead.py`)
PASS_FAMILIES = {"kimi_k2": "toy-kimi.json",
                 "qwen3_next": "toy-qwen3-next.json", "ouro": "toy-ouro.json",
                 "longcat_flash": "toy-longcat-flash.json",
                 "evabyte": "toy-evabyte.json"}


def reader_ctx(make):
    """(a benchmark reader's context whose traced stretch holds the
    spans `make()` makes, the lines the reader said)."""
    import time
    import types

    t0 = time.monotonic()
    make()
    said = []
    return types.SimpleNamespace(
        _trace_t0=t0, trace_window_s=time.monotonic() - t0,
        out=said.append), said


class Recorder:
    """Wraps a scheduler so that every sampling dispatch's logits (a
    decode step's, and a one-pass prefill's at each row's last real
    token) and what `also(model, row)` adds are kept beside (request,
    position) of the row they belong to (`pass_also` where a pass's
    rows have something else to add).  Taken where the scheduler
    settles a dispatch's rows (`_settle_rows`: right behind its fetch,
    whether the dispatch was fetched at once or left in flight)."""

    def __init__(self, sched, also=lambda model, i: (), pass_also=None):
        self.sched, self.rows, model = sched, [], sched.model
        settle = sched._settle_rows

        def recorded(flight, logits):
            add = (pass_also or also) if flight.program == "prefill" else also
            for i, live, start, n in flight.rows:
                if sched._slots[i] is live:
                    self.rows.append((live.req, start + n - 1,
                                      logits[i].copy(), *add(model, i)))
            settle(flight, logits)

        sched._settle_rows = recorded


def padded(tokens, to=32):
    """`tokens` right-padded with zeros to a whole `to` (a causal
    reference's position sees nothing after it), so that sequences of
    several lengths share one program a layer."""
    ids = np.zeros(-(-len(tokens) // to) * to, np.int32)
    ids[:len(tokens)] = tokens
    return jnp.asarray(ids)


def equations(jaxpr, into_kernels=True):
    """Every equation of `jaxpr`, sub-jaxprs (a `cond`'s branches, a
    `custom_vjp`'s rules once differentiated, jitted calls, checkpoints)
    included; a `pallas_call`'s own body only with `into_kernels`."""
    for eqn in jaxpr.eqns:
        yield eqn
        if into_kernels or eqn.primitive.name != "pallas_call":
            for inner in jax.core.jaxprs_in_params(eqn.params):
                yield from equations(inner, into_kernels)


def engine_factory(ff, kernel, devices):
    """A front's replica factory with the paged read asked for by name
    (a front built from the config always asks for "auto"): the kernel
    under the interpreter against the gather."""
    from flexflow_tpu.serving.scheduler import PagedKVDecodeModel

    def factory(replica_id, survivors=None):
        return PagedKVDecodeModel(
            ff, batch_slots=2, page_size=4, num_blocks=12,
            devices=devices, paged_kernel=kernel,
            prefill_chunk=4 if kernel == "pallas" else 0)
    return factory


def weights_equal(a, b):
    fa, fb = jax.tree.leaves(a), jax.tree.leaves(b)
    assert len(fa) == len(fb)
    for x, y in zip(fa, fb):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))
