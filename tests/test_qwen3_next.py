"""The qwen3_next language model (Gated-DeltaNet layers whose state
lives in per-slot arrays beside a paged grouped-query cache, a share of
softmax-routed experts with a gated shared expert, zero-centred
RMSNorm) against its plain float32 reference
(benchmarks/families/qwen3_next.py) on seeded weights, at a toy size on
the CPU, comparing LOGITS.

Tolerances.  The program and the reference compute the same float32
arithmetic in another order (a chunk's scan from a carried state
against one scan over the sequence, one batched product over experts
against one expert at a time, grouped heads against repeated ones), so
they differ by rounding only: 1e-5 of the compared tensor's largest
magnitude for one op, 2e-5 for logits that went through every layer.
"""
import contextlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from _family import Recorder, close, config, padded

from benchmarks import reference as ref
from benchmarks.families import qwen3_next as fam
from flexflow_tpu import FFConfig, FFModel
from flexflow_tpu.config import ConfigError
from flexflow_tpu.models.qwen3_next import build_qwen3_next

CFG = config("toy-qwen3-next.json")
D = fam.dims(CFG)
SEED = 11
KEY = ref.seed_key(SEED)
OP_TOL, LOGIT_TOL = 1e-5, 2e-5
LINEAR, FULL = 0, D.interval - 1  # a layer of each kind


def holder(cfg=CFG, **ffconfig):
    """The served model's holder with the seed's weights set."""
    dep = cfg["deployment"]
    ffconfig.setdefault("prefix_cache", False)
    ff = FFModel(FFConfig(
        batch_size=1, num_devices=1, compute_dtype=cfg["precision"],
        serving_slots=dep["serving_slots"], kv_page_size=dep["kv_page_size"],
        kv_pool_blocks=dep["kv_pool_blocks"], **ffconfig))
    build_qwen3_next(ff, 1, cfg["n_positions"], **fam.published(cfg))
    ff.compile(devices=jax.devices()[:1], defer_weights=True)
    ff.set_weights(fam.make_weights(cfg, SEED, "program"))
    return ff


def reference_logits(tokens):
    return np.asarray(fam.logits_fn(
        fam.make_weights(CFG, SEED, "reference"), padded(tokens),
        "float32"))[:len(tokens)]


# -- 1. each op alone ----------------------------------------------------------
def holder_graph_op(name, cfg=CFG):
    ff = FFModel(FFConfig(batch_size=1, num_devices=1))
    build_qwen3_next(ff, 1, 8, **fam.published(cfg))
    return next(op for op in ff.layers.topo_order() if op.name == name)


def one_op_model(build):
    """A model of input -> one op: (ff, the op's name)."""
    ff = FFModel(FFConfig(batch_size=2, num_devices=1))
    x = ff.create_tensor([2, 12, D.e], name="x")
    out = build(ff, x)
    ff.compile(devices=jax.devices()[:1], defer_weights=True)
    return ff, out.owner_op.name


def attention_fields():
    """(embed_dim, num_heads, the other fields by name) of the family's
    full-attention op, as `ff.multihead_attention` takes them."""
    p = holder_graph_op(f"attn_{FULL}").params
    return p.embed_dim, p.num_heads, {
        f: getattr(p, f) for f in p.__dataclass_fields__
        if f not in ("embed_dim", "num_heads", "use_bias")}


def _attention(ff, x):
    embed, heads, kw = attention_fields()
    return ff.multihead_attention(x, x, x, embed, heads, name="op", **kw)


OPS = {
    "rms_norm": (
        lambda ff, x: ff.rms_norm(x, D.eps, name="op", zero_centered=True),
        "norm", LINEAR, lambda x, w: fam.rms(x, w["gamma"], D.eps)),
    "attention": (
        _attention, "attn", FULL,
        lambda x, w: fam.attention(x, w, D, lambda v: v)),
    "gated_delta_net": (
        lambda ff, x: ff.gated_delta_net(
            x, holder_graph_op(f"gdn_{LINEAR}").params, name="op"),
        "gdn", LINEAR, lambda x, w: fam.delta_net(x, w, D, lambda v: v)),
    "routed_experts": (
        lambda ff, x: ff.routed_experts(
            x, holder_graph_op("moe_1").params, name="op"),
        "moe", 1, None),
}


@pytest.mark.parametrize("name", sorted(OPS))
def test_op_alone_matches_the_reference(name):
    build, kind, layer, want_fn = OPS[name]
    ff, op_name = one_op_model(build)
    w = jax.tree.map(np.asarray, fam.make_op(
        KEY, layer, d=D, kind=kind, dtype=jnp.dtype("float32")))
    ff.set_weights({op_name: w})
    x = np.asarray(jax.random.normal(jax.random.key(3), (2, 12, D.e)))
    got = np.asarray(ff.forward({"x": x}))
    with jax.default_matmul_precision("highest"):
        if name == "routed_experts":
            want = [sum(fam.experts(jnp.asarray(row), KEY, layer, D,
                                    lambda v: v)) for row in x]
        else:
            want = [want_fn(jnp.asarray(row), w) for row in x]
    close(got, np.stack(want), OP_TOL)


# -- 2. prefill in chunks, then decode, through ServingFront's scheduler ---------



@contextlib.contextmanager
def forced_kernel():
    """Every `GatedDeltaNet` with per-slot state plans the Pallas kernel
    inside, as on a TPU at published widths: the toy's 8 x 8 heads then
    run it under the interpreter.  Builds and first dispatches (the
    traces) have to happen inside."""
    from flexflow_tpu.ops.gated_delta_net import GatedDeltaNet

    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(GatedDeltaNet, "recurrence_plan",
                   lambda op, s: "kernel" if op._slot_state else "plain")
        yield


def serve_toy():
    """One scheduler over the toy model: a long prompt prefilled in
    chunks alone, then three prompts at once (every slot busy, so the
    prefill dispatches carry decode-phase riders), then a request into
    a slot that an earlier one used: (recorded rows, handles, stats)."""
    from flexflow_tpu.serving.scheduler import ContinuousScheduler

    sched = ContinuousScheduler.from_trained(
        holder(), batch_slots=3, page_size=4, num_blocks=40,
        prefill_chunk=4, prefix_cache=False, devices=jax.devices()[:1])
    rec = Recorder(sched)
    try:
        rng = np.random.default_rng(5)
        prompts = [rng.integers(1, D.v, n).tolist() for n in (16, 15, 9, 5)]
        handles = [sched.generate_async(prompts[0], 6, 0.0)]
        handles[0].wait(300)
        handles += [sched.generate_async(p, 5, 0.0) for p in prompts[:3]]
        for h in handles[1:]:
            h.wait(300)
        handles.append(sched.generate_async(prompts[3], 4, 0.0))
        handles[-1].wait(300)
        stats = sched.stats()
    finally:
        sched.close(10)
    return rec.rows, handles, stats


@pytest.fixture(scope="module")
def served_plain():
    return serve_toy()


@pytest.fixture(scope="module")
def served_kernel():
    with forced_kernel():
        return serve_toy()


@pytest.fixture(params=["plain", "kernel"])
def served(request):
    """The served toy on either recurrence (the plain scan is what a
    CPU picks; the kernel is forced, interpreted)."""
    return request.getfixturevalue(f"served_{request.param}")


def test_served_logits_equal_the_reference_full_forward(served):
    rows, handles, _ = served
    want = {id(h): reference_logits(h.result) for h in handles}
    assert len(rows) >= 25
    for req, pos, logits in rows:
        close(logits, want[id(req)][pos], LOGIT_TOL)


def test_a_reused_slot_serves_what_a_fresh_server_serves(served):
    """The first request ran alone on a fresh server in slot 0; the
    second is the same prompt in the same slot after it: the same
    tokens, and logits equal to the reference's (above), which a state
    left behind by the first tenant would not give."""
    _, handles, stats = served
    assert handles[0].result[:-1] == handles[1].result
    assert stats["requests_done"] == 5 and stats["prefill_steps"] > 0
    assert stats["prefill_passes"] == 1


def test_scheduler_counts_rows_advanced_against_rows_touched(
        served, request):
    """`rows_touched` is what the built path touches: every slot under
    the plain recurrence, the advanced rows where the twin says the
    kernel is in."""
    _, _, stats = served
    r = stats["rstate"]
    for program in ("decode", "prefill"):  # (the sums are by program)
        n, live, touched = (r[f"{program}_{k}"] for k in (
            "dispatches", "rstate_rows_live", "rstate_rows_touched"))
        assert 3 * n >= live > 0
        if request.node.callspec.params["served"] == "kernel":
            assert touched == live
        else:
            assert touched == 3 * n
    assert 3 * r["decode_dispatches"] > r["decode_rstate_rows_live"]
    # 6 linear layers x (4 heads x 8 x 8 + a tail of 3 x 64) float32, 3 slots
    assert r["state_bytes"] == 6 * (4 * 8 * 8 + 3 * 64) * 4 * 3
    assert r["state_bytes"] == fam.rstate_row_bytes(CFG) * 3
    assert r["layers"] == 6
    assert stats["prefix_cache"]["hits"] == 0


@pytest.mark.parametrize("plan", ["plain", "kernel"])
def test_front_reports_rstate_and_the_dispatch_spans_carry_it(plan):
    from flexflow_tpu.obs.trace import next_span_id, spans
    from flexflow_tpu.serving import build_front

    first = next_span_id()
    with forced_kernel() if plan == "kernel" else contextlib.nullcontext():
        front = build_front(holder(prefill_chunk=4))
        try:
            front.generate(list(range(1, 14)), 3, 0.0)
            replicas = front.stats()["replicas"]
        finally:
            front.close()
    slots = CFG["deployment"]["serving_slots"]
    # one request: a dispatch advances one row; the kernel touches that
    # row, the plain recurrence every slot
    touched = 1 if plan == "kernel" else slots
    for r in replicas:
        for program in ("decode", "prefill"):
            n = r["rstate"][f"{program}_dispatches"]
            assert n > 0
            assert r["rstate"][f"{program}_rstate_rows_touched"] == touched * n
            assert r["rstate"][f"{program}_rstate_rows_live"] == n
    mine = [r for r in spans() if r.span_id > first]
    twin = next(r for r in mine if r.name == "serve.build_twin")
    assert twin.args["rstate_bytes"] == fam.rstate_row_bytes(CFG) * slots
    linear = D.L - D.full_layers
    assert (twin.args["gdn_kernel_ops"], twin.args["gdn_plain_ops"]) == (
        (linear, 0) if plan == "kernel" else (0, linear))
    for name in ("sched.decode.dispatch", "sched.prefill.dispatch"):
        got = [r.args for r in mine if r.name == name]
        assert got and all(a["rstate_rows_live"] == 1
                           and a["rstate_rows_touched"] == touched
                           for a in got), name
    decode = next(r.args for r in mine if r.name == "sched.decode.dispatch")
    assert {"moe_pairs", "moe_hit", "kv_blocks_live"} <= set(decode)


def test_twin_asks_each_step_length_for_its_recurrence(monkeypatch):
    """`rstate_rows_touched` is counted per program: a chunk too long for
    the kernel keeps the scan (every slot) while the decode step skips
    idle rows."""
    from flexflow_tpu.ops.gated_delta_net import GatedDeltaNet
    from flexflow_tpu.serving.scheduler import PagedKVDecodeModel

    monkeypatch.setattr(
        GatedDeltaNet, "recurrence_plan",
        lambda op, s: "kernel" if op._slot_state and s == 1 else "plain")
    model = PagedKVDecodeModel(holder(), batch_slots=3, page_size=4,
                               num_blocks=40, prefill_chunk=4,
                               prefix_cache=False,
                               devices=jax.devices()[:1])
    at, two = [5, 9, 0], [1, 1, 0]  # two of three rows advance
    assert model.dispatch_counts(at, two, 1) == {
        "rstate": {"rstate_rows_live": 2, "rstate_rows_touched": 2}}
    assert model.dispatch_counts(at, two, 4) == {
        "rstate": {"rstate_rows_live": 2, "rstate_rows_touched": 3}}
    linear = D.L - D.full_layers
    assert model.groups["rstate"].geometry == {
        "gdn_kernel_ops": 0, "gdn_plain_ops": linear, "layers": linear,
        "state_bytes": model.rstate_bytes}


# -- 2b. the twin's chunk pass against C single steps ------------------------------
CHUNK, PAGE, SLOTS = 4, 4, 4
#: row -> (position its chunk starts at, tokens of the chunk it really
#: has); `rider` is a decode-phase row of a prefill dispatch: an
#: all-zero table row at position 0 that advances by nothing
ROWS = {"whole_chunk": (0, 4), "crosses_a_page": (6, 4), "short": (3, 2),
        "rider": (5, 0)}


@pytest.fixture(scope="module", params=["plain", "kernel"])
def twin(request):
    """The twin's programs on either recurrence; "kernel" makes THIS
    twin's delta-net ops plan the Pallas kernel (interpreted here), in
    the seq-1 step and in the pass."""
    from flexflow_tpu.decoding import (build_paged_decode_step,
                                       build_paged_prefill_pass,
                                       build_slot_state_reset, make_decoder)
    from flexflow_tpu.fftype import OperatorType

    ffd = make_decoder(holder(), batch_size=SLOTS, kv_page_size=PAGE,
                       kv_num_blocks=1 + SLOTS * D.p // PAGE,
                       devices=jax.devices()[:1])
    if request.param == "kernel":
        for op in ffd.operators.topo_order():
            if op.op_type == OperatorType.GATED_DELTA_NET:
                op.recurrence_plan = lambda s: "kernel"
    fns = {"step": build_paged_decode_step(ffd),
           "pass": build_paged_prefill_pass(ffd, CHUNK),
           "reset": build_slot_state_reset(ffd)}
    btab = np.arange(1, 1 + SLOTS * D.p // PAGE,
                     dtype=np.int32).reshape(SLOTS, -1)

    def run(name, state, *args):
        state = jax.tree.map(jnp.copy, state)  # the programs donate it
        if name == "reset":
            return fns[name](state, jnp.int32(args[0]))
        return fns[name](ffd._weights, state,
                         *(jnp.asarray(a, jnp.int32) for a in args))

    return ffd, run, btab


def _rstate(state):
    return {(op, k): np.asarray(e[k], np.float32)
            for op, e in state.items()
            for k in ("conv_state", "rec_state") if k in e}


@pytest.fixture(scope="module")
def chunk_pair(twin):
    """Rows at different positions fed one chunk by C seq-1 steps and by
    the pass from the SAME state, then one decode step each."""
    ffd, run, btab = twin
    starts = np.array([s for s, _ in ROWS.values()], np.int32)
    counts = np.array([c for _, c in ROWS.values()], np.int32)
    tokens = np.random.default_rng(17).integers(
        1, D.v, (SLOTS, int(starts.max()) + CHUNK + 1)).astype(np.int32)
    state = ffd._state
    for t in range(int(starts.max())):  # each row's history, a token a step
        live = starts > t
        _, state = run("step", state, np.where(live, tokens[:, t], 0),
                       np.where(live, t, 0),
                       np.where(live[:, None], btab, 0), live)
    cols = starts[:, None] + np.arange(CHUNK + 1)
    feed = np.take_along_axis(tokens, cols, axis=1)
    # riders of a prefill dispatch sit on scratch
    table = np.where((counts > 0)[:, None], btab, 0)
    stepped, last = state, np.zeros((SLOTS, D.v), np.float32)
    for j in range(CHUNK):
        live = counts > j
        logits, stepped = run("step", stepped, np.where(live, feed[:, j], 0),
                              np.where(live, starts + j, 0),
                              np.where(live[:, None], table, 0), live)
        last[counts == j + 1] = np.asarray(logits, np.float32)[counts == j + 1]
    at_last, passed = run("pass", state, feed[:, :CHUNK], starts, table,
                          counts)
    # (behind the rows' logits ride the routed layers' counts: a row)
    assert at_last.shape == (SLOTS + 1, D.v)
    at_last = at_last[:SLOTS]
    after = {}
    for name, st, own in (("steps", stepped, last),
                          ("pass", passed, np.asarray(at_last, np.float32))):
        nxt = np.take_along_axis(feed, counts[:, None], axis=1)[:, 0]
        logits, _ = run("step", st, nxt, starts + counts, btab,
                        np.ones(SLOTS))
        after[name] = (st, np.asarray(logits, np.float32), own)
    return tokens, state, after


@pytest.mark.parametrize("row", sorted(ROWS))
def test_chunk_in_one_pass_equals_seq1_stepped_over_the_chunk(chunk_pair, row):
    """Conv tail and delta-rule matrix of the row after the chunk, the
    next decode step's logits, those logits against the reference's
    full forward of the row's tokens, and the logits the pass itself
    returns against the seq-1 step at the row's last real token."""
    tokens, _, after = chunk_pair
    i = list(ROWS).index(row)
    end = sum(ROWS[row])
    steps, one = _rstate(after["steps"][0]), _rstate(after["pass"][0])
    assert len(one) == 2 * (D.L - D.full_layers)
    for k in one:
        close(one[k][i], steps[k][i], OP_TOL)
    close(after["pass"][1][i], after["steps"][1][i], LOGIT_TOL)
    close(after["pass"][1][i], reference_logits(tokens[i, :end + 1])[end],
          LOGIT_TOL)
    if ROWS[row][1]:  # the pass's own logits: the row's last real token
        close(after["pass"][2][i], after["steps"][2][i], LOGIT_TOL)


def test_rows_that_do_not_advance_keep_their_state_to_the_byte(chunk_pair):
    """The rider's state after the pass is the state before it, and the
    short row's is untouched by the chunk's trailing pads (it equals the
    stepped one above, which never saw them)."""
    _, before, after = chunk_pair
    i = list(ROWS).index("rider")
    was, now = _rstate(before), _rstate(after["pass"][0])
    for k in now:
        assert np.array_equal(now[k][i], was[k][i]), k
        assert np.abs(was[k][i]).max() > 0  # it had a history


def test_reset_zeroes_one_slot_and_leaves_the_rest_and_the_pools(
        twin, chunk_pair):
    _, run, _ = twin
    _, before, _ = chunk_pair
    after = run("reset", before, 1)
    was, now = _rstate(before), _rstate(after)
    for k in now:
        assert not now[k][1].any() and np.abs(was[k][1]).max() > 0
        assert np.array_equal(np.delete(now[k], 1, 0),
                              np.delete(was[k], 1, 0))
    for op, e in after.items():
        for k in ("k_cache", "v_cache"):
            if k in e:
                assert np.array_equal(np.asarray(e[k]),
                                      np.asarray(before[op][k]))


def test_twin_programs_hold_the_recurrence_their_ops_plan(twin, request):
    """One call of the jitted kernel wrapper a delta-net layer (ONE
    `pallas_call` body between them) in the step and in the pass when
    the ops plan the kernel, none on the plain path."""
    from flexflow_tpu.decoding import (build_paged_decode_step,
                                       build_paged_prefill_pass)

    ffd, _, btab = twin
    want = (D.L - D.full_layers
            if request.node.callspec.params["twin"] == "kernel" else 0)
    zeros = jnp.zeros(SLOTS, jnp.int32)
    for fn, tokens in ((build_paged_decode_step(ffd), zeros),
                       (build_paged_prefill_pass(ffd, CHUNK),
                        jnp.zeros((SLOTS, CHUNK), jnp.int32))):
        text = str(jax.make_jaxpr(fn)(ffd._weights, ffd._state, tokens,
                                      zeros, jnp.asarray(btab), zeros))
        assert text.count("name=_rule") == want
        assert text.count("pallas_call") == min(want, 1)


def test_state_predicates_keep_pages_and_slot_state_apart(twin):
    from flexflow_tpu.decoding import cache_entries, slot_state_entries

    ffd, _, _ = twin
    pools, slots = cache_entries(ffd), slot_state_entries(ffd)
    assert set(pools) == {f"attn_{i}" for i in range(D.L) if D.is_full(i)}
    assert set(slots) == {f"gdn_{i}" for i in range(D.L)
                          if not D.is_full(i)}
    assert all(v == ("k_cache", "v_cache") for v in pools.values())
    assert all(v == ("conv_state", "rec_state") for v in slots.values())
    st = ffd._state
    assert st[f"attn_{FULL}"]["k_cache"].shape == (
        1 + SLOTS * D.p // PAGE, PAGE, D.kvh, D.hd)
    assert st[f"gdn_{LINEAR}"]["rec_state"].shape == (SLOTS, D.hv, D.dk, D.dv)
    assert st[f"gdn_{LINEAR}"]["rec_state"].dtype == jnp.float32
    assert st[f"gdn_{LINEAR}"]["conv_state"].shape == (
        SLOTS, D.K - 1, D.conv_dim)


def test_state_sizes_at_published_widths():
    """From the ops' specs with the catalog's widths (no array is made):
    12.58 MB of delta-rule state and 4,096 B of keys and values a token."""
    cfg = config("qwen3-next-ep4-serve.json")
    ff = FFModel(FFConfig(batch_size=64, num_devices=1))
    build_qwen3_next(ff, 64, 1, **fam.published(cfg), decode_max_seq=4096,
                     kv_page_size=16, kv_num_blocks=16385)
    ops = {op.name: op for op in ff.layers.topo_order()}

    def state(op):
        return {s.name: s.shape.logical_shape
                for s in op.weight_specs[op.num_trainable_weights():]}

    assert state(ops["gdn_0"])["rec_state"] == (64, 32, 128, 128)
    assert state(ops["gdn_0"])["conv_state"] == (64, 3, 8192)
    assert state(ops["attn_3"])["k_cache"] == (16385, 16, 2, 256)
    assert fam.rstate_row_bytes(cfg) == 6 * (32 * 128 * 128 * 4
                                             + 3 * 8192 * 2)
    assert fam.latent_block_bytes(cfg) == 16 * 4096
    c = fam.parameter_counts(fam.dims(cfg))
    total = (c["attention"] + c["delta_net"] + c["norms"] + c["router"]
             + c["shared"] + c["held_experts"] * c["one_expert"]
             + c["table"] + c["head"])
    assert abs(total / 1e6 - 3667) < 1.0


# -- 3. the share test -----------------------------------------------------------
def test_every_share_of_an_expert_layer_adds_up_to_the_uncut_layer():
    """The routed parts that all `total / held` shares give (experts
    0-3, 4-7, 8-11, 12-15), with the gated shared expert counted once,
    are the uncut reference's whole layer: through the PROGRAM's op for
    each share, against the reference given every expert."""
    x = np.asarray(jax.random.normal(jax.random.key(7), (2, 12, D.e)))
    with jax.default_matmul_precision("highest"):
        whole = np.stack([sum(fam.experts(
            jnp.asarray(row), KEY, 1, D, lambda v: v, held=(0, D.total)))
            for row in x])
        shared = np.stack([fam.experts(
            jnp.asarray(row), KEY, 1, D, lambda v: v, held=(0, 0))[1]
            for row in x])
    total = np.zeros_like(whole)
    for first in range(0, D.total, D.held):
        cfg = dict(CFG, deployment=dict(CFG["deployment"],
                                        first_held_expert=first))
        ff, name = one_op_model(lambda ff, x: ff.routed_experts(
            x, holder_graph_op("moe_1", cfg).params, name="op"))
        ff.set_weights({name: jax.tree.map(np.asarray, fam.make_op(
            KEY, 1, d=fam.dims(cfg), kind="moe",
            dtype=jnp.dtype("float32")))})
        total += np.asarray(ff.forward({"x": x})) - shared
    close(total + shared, whole, OP_TOL)


def test_softmax_router_normalises_over_all_chosen_held_or_not():
    from flexflow_tpu.ops.routed_experts import route

    p = holder_graph_op("moe_1").params
    assert p.scoring == "softmax" and p.shared_expert_gate
    h = jax.random.normal(jax.random.key(2), (5, D.e))
    router = fam.leaf(KEY, "moe", "router", (D.e, D.total), 1)
    chosen, w = route(h, router, jnp.zeros(D.total), p)
    with jax.default_matmul_precision("highest"):
        want = np.asarray(fam.routing(h, router, D))
    assert chosen.shape == (5, D.k)
    close(np.sum(w, axis=-1), np.ones(5), 1e-6)
    close(w, np.take_along_axis(want, np.asarray(chosen), axis=1), 1e-6)


# -- 4. what the family does not carry, by name -----------------------------------
def _front(**ffconfig):
    from flexflow_tpu.serving import build_front

    return build_front(holder(**ffconfig))


def _prefix_cache_by_default():
    # FFConfig.prefix_cache defaults to True
    ff = holder(prefix_cache=True)
    from flexflow_tpu.serving import build_front

    return build_front(ff)


def _beam():
    from flexflow_tpu.decoding import gpt_beam_search_cached, make_decoder

    ffd = make_decoder(holder(), batch_size=2, kv_page_size=4,
                       kv_num_blocks=40, devices=jax.devices()[:1])
    return gpt_beam_search_cached(ffd, [[1, 2, 3]], 2, beam_size=2)


def _dense_cache():
    from flexflow_tpu.decoding import make_decoder

    return make_decoder(holder(), batch_size=2, devices=jax.devices()[:1])


NOT_CARRIED = {
    "prefix_cache": _prefix_cache_by_default,
    "speculative": lambda: _front(spec_decode="ngram"),
    "handoff": lambda: _front(serving_handoff=True),
    "tensor_parallel": lambda: _front(serving_tp=2),
    "beam_search": _beam,
    "dense_cache": _dense_cache,
}


@pytest.mark.parametrize("feature", sorted(NOT_CARRIED))
def test_feature_not_carried_is_a_config_error_by_name(feature):
    with pytest.raises(ConfigError) as err:
        NOT_CARRIED[feature]()
    assert "qwen3_next does not carry" in str(err.value)
    assert feature in str(err.value)


def test_grouped_heads_refuse_the_reads_that_keep_one_head_count():
    """The per-position read and the dense cache keep one head count;
    the one-view read takes grouped heads under the gather AND, since
    PR 55, under the Pallas kernel (this family's recipe still asks for
    the gather: it does not carry `pallas_read`)."""
    from flexflow_tpu.ops.op import ShapeError

    embed, heads, kw = attention_fields()

    def build(**over):
        ff = FFModel(FFConfig(batch_size=2, num_devices=1))
        x = ff.create_tensor([2, 1, D.e], name="x")
        return ff.multihead_attention(
            x, x, x, embed, heads, name="op", decode_max_seq=16,
            **{"kv_page_size": 4, "kv_num_blocks": 9, **kw, **over})

    for bad in (dict(paged_read_once=False),
                dict(kv_page_size=0, kv_num_blocks=0)):
        with pytest.raises(ShapeError, match="grouped-query heads"):
            build(**bad)
    assert build(kv_kernel="pallas").owner_op._kv_kernel == "pallas"
    assert "pallas_read" not in holder().decoder_recipe.carries


# -- 5. the ops that were adapted keep what GPT and Kimi lower -----------------------
def test_defaults_of_the_adapted_ops_are_off():
    from flexflow_tpu.ops.attention import MultiHeadAttentionParams
    from flexflow_tpu.ops.norm import RMSNormParams
    from flexflow_tpu.ops.routed_experts import RoutedExpertsParams

    a = MultiHeadAttentionParams(64, 4)
    assert (a.kv_heads, a.group, a.qk_norm, a.rotary_dim, a.output_gate,
            a.paged_read_once) == (4, 1, False, 0, False, False)
    r = RoutedExpertsParams(8, 4, 0, 2, 16)
    assert (r.scoring, r.shared_expert_gate) == ("sigmoid", False)
    assert RMSNormParams().zero_centered is False


def test_gated_delta_net_flops_count_the_recurrence():
    op = holder_graph_op(f"gdn_{LINEAR}")
    b, s, _ = op.inputs[0].shape.logical_shape
    rec = 7.0 * D.hv * D.dk * D.dv * b * s
    assert op.flops() > rec > 0
    moe = holder_graph_op("moe_1")
    t = b * s
    assert moe.flops() == t * D.e * (
        2.0 * D.total + 6.0 * D.held * D.f + 6.0 * D.f_shared + 2.0)
