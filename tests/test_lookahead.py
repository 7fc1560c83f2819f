"""One dispatch of lookahead (ISSUE 54; serving/scheduler.py `_fly`,
`_advance_rows` / `_settle_rows`, decoding.py `prev_ids` / `take_prev` /
`ids`), for each of the five families on the one-pass program at its
toy configuration, on the CPU.

* the loop that leaves a dispatch in flight serves, token for token,
  what the synchronous loop serves (forced by patching `_synchronous`,
  the rule that decides, never by an option): over admissions, finishes
  by length in the middle of a stretch and refilled slots; with an
  `eos_id` that rows hit (one dropped token a row: `overrun_tokens`,
  the pool whole and its blocks reusable); with a request sampled at a
  temperature arriving in a greedy stretch (the flight is settled, its
  RNG stream draws as in the synchronous loop);
* the plan over advanced-but-unsettled slots is the plan over the
  settled ones;
* the programs: `ids` is `np.argmax` of the returned rows, ties
  included; `take_prev` all zero is the program without the arguments,
  to the bit; `take_prev` set feeds the device's id;
* the spans: one dispatch span a dispatch with `ahead`, the counts only
  a fetch brings on the record of the dispatch they describe, nothing
  compiled a second time;
* the reader of `sched.ahead_share.capacity` on a recorded ring.
"""
import time
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from _family import PASS_FAMILIES, config, reader_ctx

from benchmarks.run import load_module
from flexflow_tpu.decoding import _greedy_ids
from flexflow_tpu.obs.trace import next_span_id, span, spans
from flexflow_tpu.serving.scheduler import (ContinuousScheduler,
                                            advanced_slots,
                                            plan_chunk_rows)

FAMILIES = PASS_FAMILIES
ROUTED = ("kimi_k2", "qwen3_next", "longcat_flash")
SEED, CHUNK, SLOTS = 11, 4, 3
#: (prompt length, new tokens): more requests than slots, a prompt of
#: one token, prompts of chunks and a remainder, short and long replies
REQUESTS = ((1, 12), (9, 6), (18, 5), (30, 4), (8, 7), (2, 9), (13, 3))
#: the request sampled at a temperature: when it arrives (after that
#: many settled dispatches of the phase), its prompt, reply, seed
LATE = dict(after=6, prompt=5, new=4, temperature=0.8, seed=1234)


@pytest.fixture(scope="module", params=sorted(FAMILIES))
def served(request):
    """(family, configuration, ONE scheduler over its toy server,
    invariants checked after every dispatch) for all of a family's
    phases: a phase's loop is chosen by patching the rule."""
    cfg = config(FAMILIES[request.param])
    fam = load_module("families", cfg["family"])
    ff = fam.build_server(cfg, jax.devices()[:1])
    ff.set_weights(fam.make_weights(cfg, SEED, "program"))
    c = ff.config
    sched = ContinuousScheduler.from_trained(
        ff, batch_slots=SLOTS, page_size=c.kv_page_size,
        num_blocks=c.kv_pool_blocks or None, prefill_chunk=CHUNK,
        prefix_cache=False, check_invariants=True,
        devices=jax.devices()[:1])
    assert sched._pass_samples and sched.model.keeps_ids
    yield request.param, cfg, sched
    sched.close(10)


def phase(sched, cfg, *, ahead, eos=-1, late=False):
    """REQUESTS through `sched`, all at once, on the loop that leaves
    dispatches in flight (`ahead`) or on the synchronous one; what was
    served, the phase's spans and what its counters moved by."""
    assert all(s is None for s in sched._slots) and not sched._flights
    sched.eos_id = eos
    if ahead:
        sched.__dict__.pop("_synchronous", None)
    else:
        sched._synchronous = lambda: "family"
    settle, settled, handles = sched._settle_rows, [], []
    rng = np.random.default_rng(5)
    prompts = [rng.integers(1, cfg["vocab_size"], n).tolist()
               for n, _ in REQUESTS]
    late_prompt = rng.integers(1, cfg["vocab_size"], LATE["prompt"]).tolist()

    def counted(flight, logits):
        # (on the worker: the span and the counts the fetch just brought)
        settled.append((flight.dispatch.span_id,
                        dict(sched.model.moe_last or {}),
                        sched.model.exit_last))
        settle(flight, logits)
        if late and len(settled) == LATE["after"]:
            handles.append(sched.generate_async(
                late_prompt, LATE["new"], LATE["temperature"],
                seed=LATE["seed"]))

    sched._settle_rows = counted
    before, first = sched.stats(), next_span_id()
    try:
        handles[:0] = [sched.generate_async(p, new, 0.0)
                       for p, (_, new) in zip(prompts, REQUESTS)]
        for h in list(handles):
            h.wait(300)
        for h in handles:  # (the late one is appended meanwhile)
            h.wait(300)
        deadline = time.monotonic() + 30
        while sched._flights and time.monotonic() < deadline:
            time.sleep(0.01)  # an overrun flight is dropped a turn later
    finally:
        sched._settle_rows = settle
    after = sched.stats()
    moved = {k: after[k] - before[k] for k in (
        "dispatches_ahead", "overrun_tokens", "tokens_generated", "steps")}
    moved["drains"] = {k: after["lookahead_drains"][k]
                       - before["lookahead_drains"][k]
                       for k in after["lookahead_drains"]}
    # (programs compiled so far: one a family, whichever loop ran)
    moved["compiled"] = (sched.model._step_fn._cache_size(),
                         sched.model._prefill_fn._cache_size())
    mine = [r for r in spans() if r.span_id > first]
    return dict(results=[h.result for h in handles], prompts=prompts,
                moved=moved, settled=settled, spans=mine,
                sampling=[r for r in mine if r.name == "sched.decode.dispatch"
                          or (r.name == "sched.prefill.dispatch"
                              and "decode_rows" in r.args)])


@pytest.fixture(scope="module")
def greedy(served):
    _, cfg, sched = served
    return {loop: phase(sched, cfg, ahead=(loop == "ahead"))
            for loop in ("sync", "ahead")}


# -- a. the same tokens ---------------------------------------------------------------
def test_lookahead_serves_what_the_synchronous_loop_serves(greedy):
    sync, ahead = greedy["sync"], greedy["ahead"]
    assert ahead["results"] == sync["results"]
    for got, prompt, (n, new) in zip(ahead["results"], ahead["prompts"],
                                     REQUESTS):
        assert got[:n] == prompt and len(got) == n + new
    total = sum(new for _, new in REQUESTS)
    assert ahead["moved"]["tokens_generated"] == total
    assert sync["moved"]["tokens_generated"] == total


def test_the_rule_decides_which_loop_ran(greedy):
    sync, ahead = greedy["sync"], greedy["ahead"]
    assert sync["moved"]["dispatches_ahead"] == 0
    assert sync["moved"]["drains"]["family"] == len(sync["sampling"])
    assert all(r.args["ahead"] == 0 for r in sync["sampling"])
    # every dispatch but a stretch's first was enqueued behind an
    # unfetched one; finishes by length end no stretch while rows live
    n = len(ahead["sampling"])
    assert ahead["moved"]["dispatches_ahead"] == sum(
        r.args["ahead"] for r in ahead["sampling"]) >= n - 4
    assert not any(ahead["moved"]["drains"].values())
    assert ahead["moved"]["overrun_tokens"] == 0
    # a slot freed by length is refilled one iteration later: at most
    # one more dispatch a refill than the synchronous loop's
    assert len(sync["sampling"]) <= n <= len(sync["sampling"]) + len(REQUESTS)


# -- b. an EOS is found out one dispatch late ---------------------------------------
@pytest.fixture(scope="module")
def with_eos(served, greedy):
    """Both loops again with an `eos_id` that cuts replies short: a
    token some reply holds before its last, so its row was a row of the
    next dispatch when the host found out."""
    _, cfg, sched = served
    replies = [r[n:] for r, (n, _) in zip(greedy["sync"]["results"], REQUESTS)]
    eos = next(t for reply in replies if len(reply) > 3 for t in reply[1:-2])
    runs = {loop: phase(sched, cfg, ahead=(loop == "ahead"), eos=eos)
            for loop in ("sync", "ahead")}
    sched.eos_id = -1
    return eos, replies, runs


def test_eos_costs_one_dropped_token_a_row_and_nothing_else(served, with_eos):
    _, cfg, sched = served
    eos, replies, runs = with_eos
    sync, ahead = runs["sync"], runs["ahead"]
    assert ahead["results"] == sync["results"]
    cut = 0
    for got, reply, (n, _) in zip(ahead["results"], replies, REQUESTS):
        want = reply[:reply.index(eos) + 1] if eos in reply else reply
        assert got[n:] == want
        cut += len(want) < len(reply)
    assert cut >= 1
    # a row that ended on EOS before its budget had ridden one more
    # dispatch (unless the stretch ended with it): its token is dropped
    assert 1 <= ahead["moved"]["overrun_tokens"] <= cut
    assert sync["moved"]["overrun_tokens"] == 0
    assert ahead["moved"]["tokens_generated"] == \
        sync["moved"]["tokens_generated"] == sum(
            len(r) - n for r, (n, _) in zip(ahead["results"], REQUESTS))
    # the pool is whole (its sweep also ran after every dispatch) and
    # every block is back: the next phase is served from them
    sched.pool.check_invariants()
    assert sched.pool.reserved_blocks == 0 and sched.pool.used_blocks == 0
    assert not sched.pool.live_sequences()
    again = phase(sched, cfg, ahead=True)
    assert [r[n:] for r, (n, _) in zip(again["results"], REQUESTS)] == replies


# -- c. a request sampled at a temperature arrives in a greedy stretch ----------
def test_a_sampled_request_settles_the_flight_and_draws_as_before(
        served, greedy):
    _, cfg, sched = served
    runs = {loop: phase(sched, cfg, ahead=(loop == "ahead"), late=True)
            for loop in ("sync", "ahead")}
    sync, ahead = runs["sync"], runs["ahead"]
    assert len(ahead["results"]) == len(REQUESTS) + 1
    assert ahead["results"] == sync["results"]
    # the greedy rows were not moved by their sampled neighbour
    assert ahead["results"][:-1] == greedy["sync"]["results"]
    assert len(ahead["results"][-1]) == LATE["prompt"] + LATE["new"]
    drains = ahead["moved"]["drains"]
    # while it lived every dispatch was fetched at once; before and
    # after it the loop ran ahead
    assert LATE["new"] <= drains["temperature"] < len(ahead["sampling"])
    assert not any(v for k, v in drains.items() if k != "temperature")
    flags = [r.args["ahead"] for r in ahead["sampling"]]
    assert flags[1] == 1 and flags.count(0) >= LATE["new"]
    assert flags.count(1) == ahead["moved"]["dispatches_ahead"] >= LATE["after"]


# -- d. the plan over advanced slots ----------------------------------------------------
def live(feed, pos, generated, unsettled, max_new):
    return types.SimpleNamespace(feed=list(range(feed)), pos=pos,
                                 generated=[7] * generated,
                                 unsettled=unsettled, max_new=max_new)


#: slot -> (feed, position after the flight's advance, tokens generated
#: before it, whether the flight owes the row a token, budget)
ADVANCED = {
    "a_feeder_and_a_row_owed_its_token": [(10, 6, 0, 0, 5), (3, 4, 1, 1, 5)],
    "the_feed_ended_in_the_flight": [(6, 6, 0, 1, 4), (9, 4, 0, 0, 4)],
    "its_last_token_is_in_flight": [(3, 6, 3, 1, 4), (12, 4, 0, 0, 4)],
    "its_only_token_is_in_flight": [(5, 5, 0, 1, 1), (12, 8, 0, 0, 2)],
    "nobody_feeding": [(3, 5, 2, 1, 9), None],
    "everybody_leaving": [(3, 4, 1, 1, 2), (2, 2, 0, 1, 1)],
}


@pytest.mark.parametrize("case", sorted(ADVANCED))
def test_plan_on_advanced_slots_is_the_plan_on_settled_ones(case):
    rows = ADVANCED[case]
    unsettled = [r and live(*r) for r in rows]
    # settled: the token is on the host; a row at its budget is finished
    settled = [r and (None if r[2] + r[3] >= r[4]
                      else live(r[0], r[1], r[2] + r[3], 0, r[4]))
               for r in rows]
    ahead = plan_chunk_rows(advanced_slots(unsettled), CHUNK, True)
    want = plan_chunk_rows(advanced_slots(settled), CHUNK, True)
    assert advanced_slots(settled) == settled
    assert [(i, n) for i, _, n in ahead] == [(i, n) for i, _, n in want]
    assert all(row is unsettled[i] for i, row, _ in ahead)
    # a row whose last token is in flight is no row of the next plan
    for i, r in enumerate(rows):
        if r and r[2] + r[3] >= r[4]:
            assert i not in [j for j, _, _ in ahead]


# -- e. the programs ------------------------------------------------------------------------
def test_greedy_ids_take_the_first_maximum_as_the_host_does():
    rng = np.random.default_rng(3)
    logits = rng.standard_normal((6, 40)).astype(np.float32)
    logits[0, [5, 17]] = logits[0].max() + 1.0      # a tie: the first wins
    logits[1, :] = 0.25                             # every column equal
    logits[2, [39, 3]] = 9.0
    logits[3, 0] = logits[3, 39] = 4.0
    for dtype in (jnp.float32, jnp.bfloat16):
        on_device = jnp.asarray(logits, dtype)
        ids = np.asarray(_greedy_ids(on_device))
        host = np.asarray(on_device).astype(np.float32)
        assert ids.dtype == np.int32
        assert ids.tolist() == np.argmax(host, axis=-1).tolist()
    assert ids[:4].tolist() == [5, 0, 3, 0]


@pytest.fixture(scope="module")
def programs(served):
    """The twin's two sampling programs called from ONE state a few
    tokens in, with and without the lookahead's arguments."""
    _, cfg, sched = served
    model = sched.model
    width = model.max_blocks_per_seq
    btab = np.arange(1, 1 + SLOTS * width, dtype=np.int32).reshape(SLOTS, -1)
    rng = np.random.default_rng(23)
    feed = rng.integers(1, cfg["vocab_size"], (SLOTS, CHUNK)).astype(np.int32)
    zeros, ones = np.zeros(SLOTS, np.int32), np.ones(SLOTS, np.int32)
    # (the programs are donated their state: the server's own is kept
    # aside, the calls here work on copies)
    saved = model._state, model._ids
    model._state = jax.tree.map(jnp.copy, saved[0])
    model.prefill_step(feed, zeros, btab, ones * CHUNK)
    base = model._state

    def call(fn, tokens, positions, fed, *ids):
        out = fn(model.ffd._weights, jax.tree.map(jnp.copy, base), tokens,
                 positions, btab, fed, *ids)
        return [np.asarray(x) for i, x in enumerate(out) if i != 1]

    yield model, call, feed, ones
    model._state, model._ids = saved


@pytest.mark.parametrize("program", ["step", "prefill"])
def test_programs_return_the_argmax_and_take_prev_feeds_it(programs, program):
    model, call, feed, ones = programs
    at = ones * CHUNK
    if program == "step":
        fn, tokens = model._step_fn, feed[:, 0]
        fed = ones if model.has_slot_state else None
    else:
        fn, tokens, fed = model._prefill_fn, feed, np.array([1, 3, 2], np.int32)
    other = np.array([9, 8, 7], np.int32)
    # without the arguments: the parent's program
    plain = call(fn, tokens, at, fed)
    kept = call(fn, tokens, at, fed, other, 0 * ones)
    assert len(kept) == len(plain) + 1
    logits, ids = kept[0][:SLOTS], kept[-1]
    # `take_prev` all zero: the parent's logits to the bit (the routed
    # layers' counts ride behind a program's rows when it keeps ids)
    assert np.array_equal(logits, plain[0][:SLOTS])
    assert ids.dtype == np.int32 and ids.shape == (SLOTS,)
    assert ids.tolist() == np.argmax(
        logits.astype(np.float32), axis=-1).tolist()
    # rows 0 and 2 take column 0 from the device's ids
    take = np.array([1, 0, 1], np.int32)
    if program == "step":
        by_host = np.where(take > 0, other, tokens)
    else:
        by_host = tokens.copy()
        by_host[:, 0] = np.where(take > 0, other, tokens[:, 0])
    fed_by_device = call(fn, tokens, at, fed, other, take)
    fed_by_host = call(fn, by_host, at, fed, other, 0 * ones)
    for a, b in zip(fed_by_device, fed_by_host):
        assert np.array_equal(a, b)
    assert not np.array_equal(fed_by_device[0][0], logits[0])
    assert np.array_equal(fed_by_device[0][1], logits[1])


# -- f. the spans -------------------------------------------------------------------------------
def test_one_dispatch_span_a_dispatch_with_its_own_counts(served, greedy):
    name = served[0]
    for loop, run in greedy.items():
        by_id = {r.span_id: r for r in run["sampling"]}
        # every settled dispatch has ONE span, settled once, in order
        ids = [sid for sid, _, _ in run["settled"]]
        assert ids == sorted(by_id) and len(set(ids)) == len(ids)
        for sid, moe, exit_pdf in run["settled"]:
            args = by_id[sid].args
            assert args["ahead"] in (0, 1) and args["slots"] == SLOTS
            assert "kv_blocks_live" in args
            # what the fetch of THIS dispatch brought is on its record
            if name in ROUTED:
                assert moe and {f"moe_{k}": v for k, v in moe.items()}.items() \
                    <= args.items()
                assert 0 < args["moe_hit"] <= args["moe_pairs"]
            else:
                assert not any(k.startswith("moe_") for k in args)
            if name == "ouro":
                assert exit_pdf is not None and "exit_mass_0" in args
        waits = [r for r in run["spans"]
                 if r.name in ("model.fetch", "model.fetch_behind")]
        assert len(waits) == len(run["settled"])
        behind = [r for r in waits if r.name == "model.fetch_behind"]
        assert len(behind) == run["moved"]["dispatches_ahead"]
        assert bool(behind) == (loop == "ahead")
        # one program a family, whichever loop ran: nothing compiled
        # a second time (the first ids are placed as the programs'
        # own)
        assert run["moved"]["compiled"] == (1, 1)


# -- g. the reader -------------------------------------------------------------------------------
def ring_of(passes, steps):
    def make():
        for args in passes:
            with span("sched.prefill.dispatch", rows=2, tokens=5, **args):
                pass
        for args in steps:
            with span("sched.decode.dispatch", rows=2, feeding=0, slots=4,
                      **args):
                pass
    return make


READINGS = {
    # the parent: no span says `ahead`
    "parent": (([dict(decode_rows=2)] * 3, [{}]), None),
    # GPT: the scan's spans sample nothing, its steps are fetched at once
    "the_scan": (([dict(ahead=0)] * 2, [dict(ahead=0)] * 2), 0.0),
    "a_stretch": (([dict(decode_rows=2, ahead=0)]
                   + [dict(decode_rows=2, ahead=1)] * 6,
                   [dict(ahead=1)]), 87.5),
    "drained_now_and_then": (([dict(decode_rows=1, ahead=1),
                               dict(decode_rows=1, ahead=0)] * 2, []), 50.0),
    "nothing_dispatched": (([], []), None),
}


@pytest.mark.parametrize("case", sorted(READINGS))
def test_ahead_share_reader_on_a_recorded_ring(case):
    made, want = READINGS[case]
    reader = load_module("readers", "sched.ahead_share.capacity")
    ctx, said = reader_ctx(ring_of(*made))
    got = reader.read(ctx, {"name": "sched.ahead_share.capacity"})
    if want is None:
        assert got is None and not said
    else:
        assert got == pytest.approx(want) and len(said) == 1


def test_ahead_share_reader_without_a_traced_stretch():
    reader = load_module("readers", "sched.ahead_share.capacity")
    ctx = types.SimpleNamespace(_trace_t0=None, trace_window_s=None,
                                out=lambda s: None)
    assert reader.read(ctx, {}) is None


def test_ahead_share_is_a_registered_metric_of_the_serving_cells():
    import json
    import os

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        bench = json.load(f)
    (metric,) = [m for m in bench["per_layer"]
                 if m["name"] == "sched.ahead_share.capacity"]
    assert metric["moves"] == "serve_tokens_per_s"
    assert metric["workloads"] == [
        w["name"] for w in bench["workloads"] if "-serve." in w["name"]]
