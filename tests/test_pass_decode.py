"""The step plan in which a row past its prompt takes its token inside
the chunked-prefill pass (ISSUE 52; serving/scheduler.py
`plan_chunk_rows`, decoding.py `build_paged_prefill_pass`), for each of
the five families on the pass at its toy configuration, on the CPU.

* the plan as a pure function of the slots;
* the pass against the seq-1 step from ONE state, for rows of one token
  a position short of a page's end, of an EVA window's end and of
  `max_seq`, beside a row that feeds a whole chunk: the logits the pass
  returns, and the next decode step's after it (what the pads left);
* a server on the fused plan against a server on the pair-by-pair plan
  (the parent's: a pass of the feeding rows, then a decode dispatch) on
  a seeded mix of one-token and feeding rows: every sampled row's
  logits, the greedy tokens, the pool's invariants after every
  dispatch, and what `decode_rows`, `tokens`, `rows` and
  `pass_decode_tokens` count;
* GPT keeps the scan: no logits, no `decode_rows`, the pair;
* the reader of `decode.in_pass_share.capacity` on a recorded ring;
* what the pass reports beside its logits (ISSUE 53): the routed
  layers' counts over its REAL tokens alone (a rider's row counts what
  the decode step counts for that row, pad columns and idle slots
  nothing), on the sampling pass's span and in `stats()["moe"]` by
  program, with the decode step's program left as it was; and what
  `model.enqueue` / `model.fetch` say they moved.

Tolerance: the pass and the step compute the same float32 arithmetic
over other shapes, 2e-5 of the logits' largest magnitude (the family
files' `LOGIT_TOL`).
"""
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from _family import PASS_FAMILIES, Recorder, close, config, reader_ctx

from benchmarks.run import load_module
from flexflow_tpu.obs.trace import next_span_id, span, spans
from flexflow_tpu.serving.scheduler import (ContinuousScheduler,
                                            PagedKVDecodeModel,
                                            plan_chunk_rows)

FAMILIES = PASS_FAMILIES
SEED, CHUNK, LOGIT_TOL = 11, 4, 2e-5


@pytest.fixture(scope="module", params=sorted(FAMILIES))
def served_model(request):
    """(family name, its toy server's holder with the seed's weights)."""
    cfg = config(FAMILIES[request.param])
    fam = load_module("families", cfg["family"])
    ff = fam.build_server(cfg, jax.devices()[:1])
    ff.set_weights(fam.make_weights(cfg, SEED, "program"))
    return request.param, cfg, ff


# -- 1. the plan ---------------------------------------------------------------
def row(feed_len, pos):
    return types.SimpleNamespace(feed=list(range(feed_len)), pos=pos)


PLANS = {
    # (feed length, position) a slot -> fed a slot, sampled and not
    "one_feeding_one_decoding": ([(10, 2), (3, 7), None], [4, 1, 0], [4, 0, 0]),
    "the_last_chunk_holds_the_last_token": ([(6, 3)], [3], [2]),
    "only_the_last_token_left_beside_a_feeder": (
        [(6, 5), (9, 0)], [1, 4], [0, 4]),
    "two_left": ([(6, 4)], [2], [1]),
    "nobody_feeding": ([(6, 5), (3, 8), None], None, None),
    "idle": ([None, None], None, None),
}


@pytest.mark.parametrize("case", sorted(PLANS))
@pytest.mark.parametrize("sampled", [True, False])
def test_plan_is_every_live_row_when_the_pass_samples(case, sampled):
    slots, fused, pair = PLANS[case]
    slots = [s and row(*s) for s in slots]
    want = fused if sampled else pair
    plan = plan_chunk_rows(slots, CHUNK, sampled)
    if want is None:
        assert plan == []  # the plain decode step runs
        return
    fed = [0] * len(slots)
    for i, live, n in plan:
        assert live is slots[i]
        fed[i] = n
    assert fed == want
    # a row that is not sampled never takes its feed's last token
    if not sampled:
        assert all(live.pos + n < len(live.feed) for _, live, n in plan)


# -- 2. the pass against the step, from one state ------------------------------------
#: row -> (position of its first token, tokens of the chunk it has);
#: pages are 4 positions (an EVA chunk too), the EVA window 16, max_seq 64
ROWS = {"short_of_a_page": (3, 1), "short_of_a_window": (15, 1),
        "short_of_max_seq": (62, 1), "feeding": (5, CHUNK)}


@pytest.fixture(scope="module")
def pass_and_steps(served_model):
    """Four rows stepped to their positions a token at a time, then
    from that ONE state the pass and the seq-1 steps over the same
    tokens, and one decode step of every row after either."""
    name, cfg, ff = served_model
    c = ff.config
    model = PagedKVDecodeModel(
        ff, batch_slots=len(ROWS), page_size=c.kv_page_size,
        num_blocks=1 + len(ROWS) * cfg["n_positions"] // c.kv_page_size,
        prefill_chunk=CHUNK, prefix_cache=False, devices=jax.devices()[:1])
    assert model.max_seq == 64 and model.prefill_passes == 1
    assert model.page_size in (4, 16)
    if "eva" in model.groups:
        eva = model.groups["eva"].geometry
        assert eva["window"] == 16 and eva["chunk"] == 4
    starts = np.array([s for s, _ in ROWS.values()], np.int32)
    fed = np.array([n for _, n in ROWS.values()], np.int32)
    slots, width = len(ROWS), model.max_blocks_per_seq
    btab = np.arange(1, 1 + slots * width, dtype=np.int32).reshape(slots, -1)
    tokens = np.random.default_rng(17).integers(
        1, cfg["vocab_size"], (slots, 64)).astype(np.int32)

    def step(live, at):
        """The rows `live` a token each at positions `at`."""
        rows = (live.astype(np.int32),) if model.has_slot_state else ()
        tok = np.take_along_axis(tokens, np.minimum(at, 63)[:, None], 1)[:, 0]
        return model.step(np.where(live, tok, 0), np.where(live, at, 0),
                          np.where(live[:, None], btab, 0), *rows)

    for t in range(int(starts.max())):
        step(starts > t, np.full(slots, t, np.int32))
    base = model._state
    out = {}
    # the pass: every row at its own position and table
    model._state = jax.tree.map(jnp.copy, base)
    feed = np.take_along_axis(tokens, np.minimum(
        starts[:, None] + np.arange(CHUNK), 63), 1)
    feed[np.arange(CHUNK) >= fed[:, None]] = 0
    own = model.prefill_step(feed, starts, btab, fed)
    out["pass"] = (own, step(np.ones(slots, bool), starts + fed))
    # the steps: a token a row a dispatch
    model._state = jax.tree.map(jnp.copy, base)
    own = np.zeros_like(own)
    for j in range(CHUNK):
        logits = step(fed > j, starts + j)
        own[fed == j + 1] = logits[fed == j + 1]
    out["steps"] = (own, step(np.ones(slots, bool), starts + fed))
    return name, out


@pytest.mark.parametrize("which", sorted(ROWS))
def test_pass_returns_the_steps_logits_and_leaves_the_steps_state(
        pass_and_steps, which):
    """Logits at the row's last real token, and the decode step behind
    it: a one-token row's 3 pad columns (past a page's end, a window's
    end, `max_seq`) changed nothing a later step reads."""
    _, out = pass_and_steps
    i = list(ROWS).index(which)
    assert np.abs(out["steps"][0][i]).max() > 0
    close(out["pass"][0][i], out["steps"][0][i], LOGIT_TOL)
    close(out["pass"][1][i], out["steps"][1][i], LOGIT_TOL)


# -- 3. a server on the fused plan against one on the pair-by-pair plan ----------
#: (prompt length, new tokens): a prompt of one token (a rider from its
#: first dispatch), prompts of chunks and a remainder, of whole chunks,
#: and more requests than slots (a slot is refilled while others decode)
REQUESTS = ((1, 12), (9, 6), (18, 5), (30, 4), (8, 7), (2, 9), (13, 3))


def serve(ff, cfg, pairwise):
    """One scheduler over REQUESTS, all at once, invariants checked
    after every dispatch.  `pairwise`: the parent's plan driven by hand
    (the pass's logits dropped, its riders on scratch, a decode
    dispatch behind every pass)."""
    c = ff.config
    sched = ContinuousScheduler.from_trained(
        ff, batch_slots=3, page_size=c.kv_page_size,
        num_blocks=c.kv_pool_blocks or None, prefill_chunk=CHUNK,
        prefix_cache=c.prefix_cache, check_invariants=True,
        devices=jax.devices()[:1])
    assert sched._pass_samples
    fed_log, beside_log = [], []
    rec = Recorder(sched)

    def logged(inner):
        def prefill(tok, slen, btab, *fed, **beside):
            # (the pair plan hands a twin without per-slot state no
            # `row_tokens`, and nobody reads that pass's logits)
            fed = fed or (np.ones(len(slen), np.int32),)
            fed_log.append(fed[0].copy())
            beside_log.append(set(beside))
            return inner(tok, slen, btab, *fed, **beside)
        return prefill

    # the fused plan leaves its passes in flight (`launch_prefill`); the
    # pair plan is the parent's loop too: every dispatch fetched at once
    if pairwise:
        sched.model.prefill_step = logged(sched.model.prefill_step)
        sched._synchronous = lambda: "family"
    else:
        sched.model.launch_prefill = logged(sched.model.launch_prefill)
    sched._pass_samples = not pairwise
    first = next_span_id()
    try:
        rng = np.random.default_rng(5)
        handles = [sched.generate_async(
            rng.integers(1, cfg["vocab_size"], n).tolist(), new, 0.0)
            for n, new in REQUESTS]
        for h in handles:
            h.wait(300)
        stats = sched.stats()
    finally:
        sched.close(10)
    mine = [r for r in spans() if r.span_id > first]
    sampled = {}
    for req, pos, logits, *_ in rec.rows:
        if pos >= len(req.prompt) - 1:
            key = (handles.index(req), pos)
            assert key not in sampled  # a position is sampled once
            sampled[key] = logits
    return dict(handles=handles, stats=stats, sampled=sampled, fed=fed_log,
                beside=beside_log, spans=mine,
                passes=[r for r in mine if r.name == "sched.prefill.dispatch"],
                steps=[r for r in mine if r.name == "sched.decode.dispatch"])


@pytest.fixture(scope="module")
def plans(served_model):
    name, cfg, ff = served_model
    return name, {p: serve(ff, cfg, pairwise=(p == "pair"))
                  for p in ("fused", "pair")}


def test_fused_plan_samples_what_the_pair_plan_samples(plans):
    _, by = plans
    fused, pair = by["fused"], by["pair"]
    assert sorted(fused["sampled"]) == sorted(pair["sampled"])
    assert len(fused["sampled"]) == sum(new for _, new in REQUESTS)
    for key, logits in fused["sampled"].items():
        close(logits, pair["sampled"][key], LOGIT_TOL)
    for a, b, (n, new) in zip(fused["handles"], pair["handles"], REQUESTS):
        assert a.result == b.result and len(a.result) == n + new


def test_while_a_row_feeds_an_iteration_is_the_pass_alone(plans):
    _, by = plans
    fused, pair = by["fused"], by["pair"]
    # no decode dispatch carries a row that is still feeding...
    assert fused["steps"] and pair["steps"]
    assert all(r.args["feeding"] == 0 for r in fused["steps"])
    assert any(r.args["feeding"] > 0 for r in pair["steps"])
    # ...and the pair plan pays a decode dispatch behind every pass
    assert len(fused["passes"]) + len(fused["steps"]) \
        < len(pair["passes"]) + len(pair["steps"])
    assert fused["stats"]["steps"] == len(fused["passes"]) + len(fused["steps"])
    assert fused["stats"]["prefill_steps"] == len(fused["passes"])


def test_span_args_and_stats_count_what_was_sampled(plans):
    _, by = plans
    fused, pair = by["fused"], by["pair"]
    total = sum(new for _, new in REQUESTS)
    in_pass = sum(r.args["decode_rows"] for r in fused["passes"])
    in_step = sum(r.args["rows"] for r in fused["steps"])
    assert in_pass > 0 and in_pass + in_step == total
    assert fused["stats"]["pass_decode_tokens"] == in_pass
    assert fused["stats"]["tokens_generated"] == total
    assert len(fused["fed"]) == len(fused["passes"])
    # a pass left in flight says which rows take their token from the
    # device's ids (`take_prev`); the pair plan's call is the scan's:
    # it returns at once and takes no such argument
    assert all(b == {"take_prev"} for b in fused["beside"])
    assert not any(pair["beside"])
    assert all("kv_blocks_live" in r.args
               for r in fused["passes"] + pair["passes"])
    for r, fed in zip(fused["passes"], fused["fed"]):
        assert r.args["tokens"] == fed.sum()
        assert r.args["rows"] == (fed > 0).sum()
        assert r.args["decode_rows"] <= r.args["rows"] <= 3
        assert r.args["capacity"] == 3 * CHUNK and r.args["passes"] == 1
    # the riders' tokens count: more real tokens a pass than prompt
    # tokens alone
    prompt = sum(n for n, _ in REQUESTS)
    assert sum(r.args["tokens"] for r in fused["passes"]) > \
        sum(r.args["tokens"] for r in pair["passes"])
    assert sum(r.args["tokens"] for r in pair["passes"]) < prompt
    # the parent's plan: no such arg, nothing sampled from a pass
    assert all("decode_rows" not in r.args for r in pair["passes"])
    assert pair["stats"]["pass_decode_tokens"] == 0


def test_a_family_with_layer_state_reports_the_riders_rows(plans):
    """`rstate_rows_live` / the EVA counters of a pass cover the rows
    that advanced by one token too."""
    name, by = plans
    fused = by["fused"]
    if name == "qwen3_next":
        assert [r.args["rstate_rows_live"] for r in fused["passes"]] == \
            [r.args["rows"] for r in fused["passes"]]
    elif name == "evabyte":
        assert all(r.args["eva_rows_window"] >= r.args["tokens"]
                   for r in fused["passes"])
    elif name == "ouro":
        loop = fused["stats"]["loop"]
        assert loop["exit_rows"] == in_rows(fused)
        assert sum(loop["exit_mass"]) == pytest.approx(loop["exit_rows"],
                                                       rel=1e-5)
    else:
        assert "rstate_rows_live" not in fused["passes"][0].args


def in_rows(run):
    """Live rows over the dispatches that returned an exit pdf."""
    return (sum(r.args["rows"] for r in run["passes"])
            + sum(r.args["rows"] + r.args["feeding"] for r in run["steps"]))


# -- 4. GPT keeps the scan and the pair -----------------------------------------------
def test_gpt_scans_its_chunk_returns_no_logits_and_pairs_its_dispatches():
    from flexflow_tpu import FFConfig, FFModel, LossType, SGDOptimizer
    from flexflow_tpu.models.transformer import build_gpt

    ff = FFModel(FFConfig(batch_size=1, num_devices=1))
    build_gpt(ff, 1, 32, hidden_size=32, num_layers=2, num_heads=2,
              intermediate_size=64, vocab_size=50)
    ff.compile(optimizer=SGDOptimizer(lr=0.01),
               loss_type=LossType.SPARSE_CATEGORICAL_CROSSENTROPY,
               devices=jax.devices()[:1])
    assert ff.decoder_recipe.head == ()
    sched = ContinuousScheduler.from_trained(
        ff, batch_slots=2, page_size=4, num_blocks=17, prefill_chunk=CHUNK,
        check_invariants=True, devices=jax.devices()[:1])
    returned = []
    inner = sched.model.prefill_step

    def prefill(*args):
        assert len(args) == 3  # no row_tokens: the scan's signature
        returned.append(inner(*args))
        return returned[-1]

    sched.model.prefill_step = prefill
    first = next_span_id()
    try:
        assert not sched._pass_samples
        a = sched.generate_async([7], 9, 0.0)  # a rider from the start
        b = sched.generate_async(list(range(1, 15)), 3, 0.0)
        assert len(a.wait(120)) == 10 and len(b.wait(120)) == 17
        stats = sched.stats()
    finally:
        sched.close(10)
    mine = [r for r in spans() if r.span_id > first]
    passes = [r for r in mine if r.name == "sched.prefill.dispatch"]
    steps = [r for r in mine if r.name == "sched.decode.dispatch"]
    assert returned and all(r is None for r in returned)
    assert stats["pass_decode_tokens"] == 0 and stats["prefill_passes"] == CHUNK
    assert all("decode_rows" not in r.args for r in passes)
    # b's 14 tokens: chunks never past its 13th, riders not counted
    assert [r.args["tokens"] for r in passes] == [4, 4, 3]
    assert all(r.args["rows"] == 1 for r in passes)
    # a decode dispatch behind every chunk, b still feeding in it
    # until its last prompt token is the one it is fed
    assert sum(r.args["feeding"] > 0 for r in steps) == len(passes) - 1
    assert stats["steps"] == len(steps)


def test_a_family_on_the_pass_names_its_head():
    import dataclasses

    from flexflow_tpu.config import ConfigError
    from flexflow_tpu.decoding import build_paged_prefill_pass, make_decoder

    cfg = config(FAMILIES["kimi_k2"])
    fam = load_module("families", cfg["family"])
    ff = fam.build_server(cfg, jax.devices()[:1])
    ff.set_weights(fam.make_weights(cfg, SEED, "program"))
    assert ff.decoder_recipe.head == ("final_norm", "lm_head")
    ffd = make_decoder(ff, batch_size=2, kv_page_size=4, kv_num_blocks=9,
                       devices=jax.devices()[:1])
    ffd.decoder_recipe = dataclasses.replace(ffd.decoder_recipe, head=())
    with pytest.raises(ConfigError, match="names no `head` ops"):
        build_paged_prefill_pass(ffd, CHUNK)


# -- 5. the reader ----------------------------------------------------------------------
def dispatches(passes, steps):
    def make():
        for args in passes:
            with span("sched.prefill.dispatch", **args):
                pass
        for rows in steps:
            with span("sched.decode.dispatch", rows=rows, feeding=0, slots=4):
                pass
    return make


READINGS = {
    # a ring without `decode_rows` (the parent, or the scan): 0
    "parent": (([dict(rows=2, tokens=8)] * 3, [3, 4]), 0.0),
    # 5 + 3 rows sampled from passes, 2 from a decode dispatch
    "fused": (([dict(rows=4, tokens=9, decode_rows=5),
                dict(rows=4, tokens=7, decode_rows=3)], [2]), 80.0),
    "all_in_passes": (([dict(rows=3, tokens=3, decode_rows=3)], []), 100.0),
    "nothing_dispatched": (([], []), None),
    "nothing_sampled": (([dict(rows=1, tokens=4, decode_rows=0)], []), None),
}


@pytest.mark.parametrize("case", sorted(READINGS))
def test_in_pass_share_reader_on_a_recorded_ring(case):
    made, want = READINGS[case]
    reader = load_module("readers", "decode.in_pass_share.capacity")
    ctx, said = reader_ctx(dispatches(*made))
    got = reader.read(ctx, {"name": "decode.in_pass_share.capacity"})
    if want is None:
        assert got is None and not said
    else:
        assert got == pytest.approx(want) and len(said) == 1


def test_in_pass_share_reader_without_a_traced_stretch():
    reader = load_module("readers", "decode.in_pass_share.capacity")
    ctx = types.SimpleNamespace(_trace_t0=None, trace_window_s=None,
                                out=lambda s: None)
    assert reader.read(ctx, {}) is None


# -- 6. what the pass reports beside its logits (ISSUE 53) -------------------------
ROUTED = {"kimi_k2": (), "qwen3_next": (), "longcat_flash": ("zero_picks",
                                                            "real_min",
                                                            "real_max")}
MOE = ("pairs", "dropped", "max_rows", "hit")
SLOTS = 3


@pytest.fixture(scope="module")
def counting(served_model):
    """A routed family's twin at position 0 and `count(program, tokens,
    fed)`: the routed layers' counts (`moe_last`) of ONE dispatch from
    that state: the pass over `tokens [slots, CHUNK]` with `fed` real
    tokens a row, or the decode step over `tokens [slots]`."""
    name, cfg, ff = served_model
    if name not in ROUTED:
        pytest.skip(f"{name} has no routed layer")
    c = ff.config
    model = PagedKVDecodeModel(
        ff, batch_slots=SLOTS, page_size=c.kv_page_size,
        num_blocks=1 + SLOTS * cfg["n_positions"] // c.kv_page_size,
        prefill_chunk=CHUNK, prefix_cache=False, devices=jax.devices()[:1])
    assert model._moe_ops and bool(model._moe_zero_ops) == bool(ROUTED[name])
    base = model._state
    width = model.max_blocks_per_seq
    btab = np.arange(1, 1 + SLOTS * width, dtype=np.int32).reshape(SLOTS, -1)
    zeros = np.zeros(SLOTS, np.int32)

    def count(program, tokens, fed):
        model._state = jax.tree.map(jnp.copy, base)
        model.moe_last = None
        tokens, fed = np.asarray(tokens, np.int32), np.asarray(fed, np.int32)
        if program == "pass":
            logits = model.prefill_step(tokens, zeros, btab, fed)
            assert logits.shape == (SLOTS, model.vocab)
        else:
            model.step(tokens, zeros, btab,
                       *((fed,) if model.has_slot_state else ()))
        return dict(model.moe_last)

    return name, cfg, model, count


def chunk_of(cfg, seed):
    return np.random.default_rng(seed).integers(
        1, cfg["vocab_size"], (SLOTS, CHUNK)).astype(np.int32)


def test_a_riders_row_counts_what_the_decode_step_counts_for_it(counting):
    """One token at column 0, `row_tokens` 1, the other slots idle: the
    pass counts that row.  The decode step counts every slot's row, so
    it is given the same token in every slot (a row picks an expert at
    most once: `max_rows` and the sums are the slots' multiple)."""
    name, cfg, _, count = counting
    tokens = chunk_of(cfg, 3)
    tokens[:, 0] = tokens[0, 0]
    rider = count("pass", tokens, [1, 0, 0])
    step = count("step", tokens[:, 0], [1, 1, 1])
    assert rider["pairs"] > 0 and rider["dropped"] == 0
    for k in ("pairs", "max_rows", *ROUTED[name][:1]):
        assert step[k] == SLOTS * rider[k], k
    for k in ("hit", "dropped", *ROUTED[name][1:]):
        assert step[k] == rider[k], k
    # and whichever slot holds the rider
    assert count("pass", tokens, [0, 0, 1]) == rider


def test_pad_columns_and_idle_slots_count_nothing(counting):
    name, cfg, model, count = counting
    tokens, fed = chunk_of(cfg, 5), [3, 1, 0]
    want = count("pass", tokens, fed)
    other = chunk_of(cfg, 7)
    real = np.arange(CHUNK) < np.asarray(fed)[:, None]
    assert count("pass", np.where(real, tokens, other), fed) == want
    # the rows' counts add up (each row has its own pages)
    a, b = count("pass", tokens, [3, 0, 0]), count("pass", tokens, [0, 1, 0])
    assert want["pairs"] == a["pairs"] + b["pairs"]
    if ROUTED[name]:
        assert want["zero_picks"] == a["zero_picks"] + b["zero_picks"]
        assert want["real_min"] == min(a["real_min"], b["real_min"])
        assert want["real_max"] == max(a["real_max"], b["real_max"])
        top_k, layers = cfg["moe_topk"], len(model._moe_zero_ops)
        assert 0 <= want["zero_picks"] <= 4 * top_k * layers
    # every column real: what the pass counted before it masked
    whole = count("pass", tokens, [CHUNK] * SLOTS)
    assert whole["pairs"] > want["pairs"] >= want["hit"] > 0
    # nobody real: nothing
    idle = count("pass", tokens, [0, 0, 0])
    assert [idle[k] for k in MOE] == [0, 0, 0, 0]
    assert idle.get("zero_picks", 0) == 0


def test_decode_step_never_sees_the_mask(counting, monkeypatch):
    """The mask reaches the routed layers from the pass's closure: the
    decode step's program is the one a `forward` without the argument
    traces, and takes no `row_tokens` for it."""
    from flexflow_tpu.ops.routed_experts import RoutedExperts

    _, _, model, _ = counting
    args = (model.ffd._weights, model._state, np.zeros(SLOTS, np.int32),
            np.zeros(SLOTS, np.int32),
            np.zeros((SLOTS, model.max_blocks_per_seq), np.int32),
            *model._row_tokens(np.ones(SLOTS, np.int32)))
    text = model._step_fn.lower(*args).as_text()
    assert len(jax.tree.leaves(args)) == len(jax.tree.leaves(
        (model.ffd._weights, model._state))) + 3 + model.has_slot_state \
        + 2 * model.keeps_ids  # (the last dispatch's ids, `take_prev`)
    inner, seen = RoutedExperts.forward, []

    def forward(self, inputs, weights, *, training=False, rng=None, **kw):
        seen.append(set(kw))
        return inner(self, inputs, weights, training=training, rng=rng)

    monkeypatch.setattr(RoutedExperts, "forward", forward)
    from flexflow_tpu.decoding import (build_paged_decode_step,
                                       build_paged_prefill_pass)

    assert build_paged_decode_step(model.ffd).lower(*args).as_text() == text
    assert seen and not any(seen)
    del seen[:]
    jax.make_jaxpr(build_paged_prefill_pass(model.ffd, CHUNK))(
        *args[:2], np.zeros((SLOTS, CHUNK), np.int32), *args[3:5],
        np.ones(SLOTS, np.int32))
    assert seen and all(kw == {"count_rows"} for kw in seen)


def test_sampling_pass_span_carries_the_counts_and_stats_sum_by_program(
        plans):
    name, by = plans
    fused, pair = by["fused"], by["pair"]
    moe = fused["stats"].get("moe")
    args = {f"moe_{k}" for k in MOE + ROUTED.get(name, ())}
    if name not in ROUTED:
        assert moe is None
        assert not any(k.startswith("moe_") for r in fused["passes"]
                       for k in r.args)
        return
    for r in fused["passes"]:
        assert args <= set(r.args) and r.args["slots"] == 3
        assert r.args["moe_dropped"] == 0
        assert 0 < r.args["moe_hit"] <= r.args["moe_pairs"]
    for r in fused["steps"]:
        assert args <= set(r.args)
    # the pair plan's passes sample nothing and fetch no counts
    assert not any("moe_pairs" in r.args or "slots" in r.args
                   for r in pair["passes"])
    assert moe["prefill_dispatches"] == len(fused["passes"])
    assert moe["dispatches"] == len(fused["steps"])
    for k in ("pairs", "max_rows", "hit", "dropped"):
        assert moe[f"prefill_{k}"] == sum(
            r.args[f"moe_{k}"] for r in fused["passes"])
        assert moe[k] == sum(r.args[f"moe_{k}"] for r in fused["steps"])
    if ROUTED[name]:
        assert moe["prefill_real_min"] == min(
            r.args["moe_real_min"] for r in fused["passes"])
        assert moe["real_max"] == max(
            r.args["moe_real_max"] for r in fused["steps"])


WAITS = ("model.fetch", "model.fetch_behind")


def test_enqueue_and_fetch_say_what_they_moved(plans):
    """`program`, `arg_leaves`, `host_bytes` on `model.enqueue` and
    `program`, `bytes` on the wait for it, the same on every call of
    one program.  The k-th wait is for the k-th enqueue of a sampled
    program: `model.fetch` right behind it on the synchronous loop (the
    pair plan's), `model.fetch_behind` with exactly one more enqueue in
    between where the dispatch was left in flight (ISSUE 54)."""
    name, by = plans
    for plan, run in by.items():
        calls = [r for r in run["spans"]
                 if r.name in ("model.enqueue", *WAITS)]
        moved = {}
        for r in calls:
            static = {k: v for k, v in r.args.items() if k != "first"}
            assert moved.setdefault((r.name, r.args["program"]),
                                    static) == static
        programs = {p for _, p in moved}
        assert {"step", "prefill"} <= programs <= {
            "step", "prefill", "reset_slot_state"}
        for (span_name, program), static in moved.items():
            if span_name in WAITS:
                assert set(static) == {"program", "bytes"}
                assert static["bytes"] >= 3 * 4  # a logit a slot at least
            else:
                assert set(static) == {"program", "arg_leaves", "host_bytes"}
                assert static["arg_leaves"] > 3
        # tokens [3, C], positions, row_tokens and the table against
        # tokens [3], positions and the table: the pass copies in more
        assert moved["model.enqueue", "prefill"]["host_bytes"] > \
            moved["model.enqueue", "step"]["host_bytes"] >= 3 * 4 * 3
        assert moved["model.enqueue", "prefill"]["arg_leaves"] >= \
            moved["model.enqueue", "step"]["arg_leaves"]
        sampled = [r for r in calls if r.args["program"] in ("step", "prefill")]
        enqueues = [r for r in sampled if r.name == "model.enqueue"]
        waits = [r for r in sampled if r.name in WAITS]
        assert [r.args["program"] for r in enqueues] == \
            [r.args["program"] for r in waits]
        for k, (enq, wait) in enumerate(zip(enqueues, waits)):
            assert enq.t_end <= wait.t_start
            between = sum(enq.t_end <= e.t_start <= wait.t_start
                          for e in enqueues[k + 1:k + 3])
            assert between == (wait.name == "model.fetch_behind")
        behind = sum(r.name == "model.fetch_behind" for r in waits)
        if plan == "pair":
            assert behind == 0
        else:
            # the stretch ends once: every other wait is behind a launch
            assert behind == run["stats"]["dispatches_ahead"] > len(waits) / 2
            assert run["stats"]["overrun_tokens"] == 0
