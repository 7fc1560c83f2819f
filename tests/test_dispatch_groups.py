"""The seam between the mixers and the serving tier (ISSUE 59;
`Op.dispatch_group`, `Op.dispatch_group_of`, docs/SERVING.md "What a
mixer with serving state declares"): an op names a group, the ops of a
name tell the paged twin their geometry and count a dispatch's args from
host-owned lengths, and `serving/` knows the names it is handed.

* for each toy twin that exists (EVA layers, window layers, selected
  keys, recurrent layers, and kimi_k2 with no group), one front and one
  seeded scenario whose admission order is fixed (the worker is parked
  while the requests queue): the twin's `dispatch_counts` is what the
  group's ops answer, every dispatch span carries exactly those keys,
  the sums by program are the ones the parent's engine counted for the
  same scenario (recorded once on commit be3d049), and `stats()` and the
  replica's `stats()` carry the group;
* an op class defined HERE that names a group of its own, served with no
  edit under `serving/`: its args reach the spans, `serve.build_twin`,
  the gauges and `stats()`; and the scheduler's half over a bare fake
  model.
"""
import threading

import jax
import numpy as np
import pytest
from _family import config
from test_continuous_scheduler import FakeStepModel

from benchmarks.run import load_module
from flexflow_tpu import FFConfig, FFModel
from flexflow_tpu.obs.metrics import MetricsRegistry
from flexflow_tpu.obs.trace import next_span_id, spans
from flexflow_tpu.ops.attention import MultiHeadAttention
from flexflow_tpu.ops.op import DispatchGroup
from flexflow_tpu.serving import ContinuousScheduler, build_front

SEED = 11
#: (prompt length, new tokens) of the scenario's requests, in admission
#: order; the toys have 4 slots, so two wait for a slot
REQUESTS = ((19, 5), (3, 9), (11, 2), (30, 6), (1, 4), (24, 7))
DISPATCHES = ("sched.prefill.dispatch", "sched.decode.dispatch")
#: toy -> (its group, `serve.build_twin`'s args of it, the dispatch args
#: summed by program over the scenario AS THE PARENT COUNTED THEM)
TOYS = {
    "toy-evabyte.json": ("eva", {"eva_state_bytes": 73728}, {
        "prefill_dispatches": 9, "prefill_eva_rows_read": 2304,
        "prefill_eva_rows_summary": 224, "prefill_eva_rows_window": 1338,
        "prefill_eva_summaries_written": 44, "decode_dispatches": 10,
        "decode_eva_rows_read": 2560, "decode_eva_rows_summary": 120,
        "decode_eva_rows_window": 336, "decode_eva_summaries_written": 8}),
    "toy-laguna.json": ("swa", {"swa_state_bytes": 36864}, {
        "prefill_dispatches": 14, "prefill_swa_rows_live": 927,
        "prefill_swa_rows_read": 2016, "decode_dispatches": 8,
        "decode_swa_rows_live": 252, "decode_swa_rows_read": 1152}),
    "toy-glm52.json": ("dsa", {}, {
        "prefill_dispatches": 14, "prefill_dsa_keys_live": 1259,
        "prefill_dsa_keys_read": 3584, "prefill_dsa_keys_scored": 2518,
        "prefill_dsa_keys_selected": 674, "prefill_dsa_rows_past_topk": 23,
        "prefill_index_blocks_live": 288, "decode_dispatches": 8,
        "decode_dsa_keys_live": 266, "decode_dsa_keys_read": 256,
        "decode_dsa_keys_scored": 532, "decode_dsa_keys_selected": 84,
        "decode_dsa_rows_past_topk": 10, "decode_index_blocks_live": 140}),
    "toy-qwen3-next.json": ("rstate", {
        "rstate_bytes": 43008, "gdn_kernel_ops": 0, "gdn_plain_ops": 6}, {
        "prefill_dispatches": 7, "prefill_rstate_rows_live": 20,
        "prefill_rstate_rows_touched": 28, "decode_dispatches": 10,
        "decode_rstate_rows_live": 21, "decode_rstate_rows_touched": 40}),
    "toy-kimi.json": (None, {}, {}),
}
#: what a group's arg is called: no other arg of a dispatch span is
PREFIXES = ("eva_", "swa_", "dsa_", "rstate_", "index_blocks", "mine_")


def run_scenario(front, vocab, positions):
    """`REQUESTS` through the front's one engine, all queued before the
    worker sees the first: (the scheduler's stats, the replica's)."""
    sched = front.replicas[0].scheduler
    rng = np.random.default_rng(7)
    gate, parked = threading.Event(), threading.Event()
    sched.run_on_worker(lambda: (parked.set(), gate.wait(120)))
    assert parked.wait(120)
    handles = []
    for plen, new in REQUESTS:
        plen = min(plen, positions - new - 1)
        handles.append(sched.generate_async(
            rng.integers(1, vocab, plen).tolist(), new, 0.0))
    gate.set()
    for h, (plen, new) in zip(handles, REQUESTS):
        assert len(h.wait(600)) == min(plen, positions - new - 1) + new
    return sched.stats(), front.stats()["replicas"][0]


@pytest.fixture(scope="module", params=sorted(TOYS))
def served(request):
    """(toy, its twin, {span name: [args]}, stats, replica stats)."""
    cfg = config(request.param)
    fam = load_module("families", cfg["family"])
    ff = fam.build_server(cfg, jax.devices()[:1])
    ff.set_weights(fam.make_weights(cfg, SEED, "program"))
    first = next_span_id()
    front = build_front(ff)
    try:
        twin = front.replicas[0].scheduler.model._model
        stats, replica = run_scenario(front, cfg["vocab_size"],
                                      cfg["n_positions"])
    finally:
        front.close()
    mine = [r for r in spans() if r.span_id > first]
    args = {name: [dict(r.args) for r in mine if r.name == name]
            for name in DISPATCHES + ("serve.build_twin",)}
    return request.param, twin, args, stats, replica


def test_twin_counts_are_what_the_groups_ops_answer(served):
    toy, twin, *_ = served
    group = TOYS[toy][0]
    assert list(twin.groups) == ([group] if group else [])
    slots = twin.batch_slots
    at = np.arange(slots) * 7 % twin.max_seq
    for chunk in (1, twin.prefill_chunk):
        n = np.minimum(np.arange(slots) % 3 * chunk, chunk)  # (row 0 idle)
        got = twin.dispatch_counts(at, n, chunk)
        if group is None:
            assert got == {}
            continue
        ops = [op for op in twin.ffd.operators.topo_order()
               if op.dispatch_group() == group]
        geometry = twin.groups[group].geometry
        assert geometry["layers"] == len(ops) > 0
        told = type(ops[0]).dispatch_group_of(
            ops, family="toy", batch_slots=slots, page_size=twin.page_size,
            max_seq=twin.max_seq, prefill_chunk=twin.prefill_chunk,
            state_bytes=geometry["state_bytes"])
        assert got == {group: told.counts(at, n, chunk)}
        assert all(isinstance(v, int) for v in got[group].values())
        assert dict(told.geometry, layers=len(ops),
                    state_bytes=geometry["state_bytes"]) == geometry
        assert geometry["state_bytes"] == sum(
            int(twin._state[op.name][k].nbytes)
            for op in ops for k in op.slot_state_entries())


def test_every_dispatch_span_carries_exactly_the_groups_keys(served):
    toy, twin, args, *_ = served
    keys = {k for rows in twin.dispatch_counts(
        [0] * twin.batch_slots, [1] * twin.batch_slots, 1).values()
        for k in rows}
    assert bool(keys) == (TOYS[toy][0] is not None)
    for name in DISPATCHES:
        assert args[name]
        for a in args[name]:
            assert {k for k in a if k.startswith(PREFIXES)} == keys, name
    (built,) = args["serve.build_twin"]
    assert {k: v for k, v in built.items()
            if k not in ("replica", "slots", "pool_blocks")
            and not k.startswith("loop_")} == TOYS[toy][1]


def test_sums_by_program_are_the_parents_counts(served):
    toy, _, args, stats, _ = served
    group, _, want = TOYS[toy]
    got = {}
    for name in DISPATCHES:
        program = name.split(".")[1]
        for a in args[name]:
            for k, v in a.items():
                if k.startswith(PREFIXES):
                    got[f"{program}_{k}"] = got.get(f"{program}_{k}", 0) + v
    if group is None:
        assert got == {}
        return
    got.update({f"{name.split('.')[1]}_dispatches": len(args[name])
                for name in DISPATCHES})
    assert got == want
    assert {k: stats[group][k] for k in want} == want


def test_stats_and_the_replicas_stats_carry_the_group(served):
    toy, twin, _, stats, replica = served
    group, _, want = TOYS[toy]
    names = {"eva", "swa", "dsa", "rstate"}
    assert names & set(stats) == names & set(replica) == (
        {group} if group else set())
    if group:
        assert replica[group] == stats[group] == dict(
            want, **twin.groups[group].geometry)
    assert ("moe" in stats) == ("moe" in replica)


# -- a group of the test's own ---------------------------------------------------------
class CountingAttention(MultiHeadAttention):
    """The attention op, telling the serving tier something of its own
    under a name no file of `serving/` holds."""

    def dispatch_group(self):
        return "mine" if self._paged() else None

    @classmethod
    def dispatch_group_of(cls, ops, *, batch_slots, prefill_chunk, **twin):
        heads, n = ops[0].params.num_heads, len(ops)

        def counts(positions, counts, chunk):
            return {"mine_tokens": n * int(np.sum(counts)),
                    "mine_first": int(np.min(positions)),
                    "mine_capacity": n * batch_slots * chunk}

        return DispatchGroup(
            geometry={"heads": heads}, counts=counts,
            build_args={"mine_chunk": prefill_chunk},
            gauges={"mine_heads": heads})


def test_an_op_of_the_tests_own_is_served_with_no_edit_under_serving(
        monkeypatch):
    from flexflow_tpu.models.transformer import build_gpt

    monkeypatch.setattr("flexflow_tpu.model.MultiHeadAttention",
                        CountingAttention)
    ff = FFModel(FFConfig(batch_size=1, num_devices=1, serving_slots=2,
                          kv_page_size=4, prefill_chunk=4))
    build_gpt(ff, batch_size=1, seq_length=32, hidden_size=32, num_layers=2,
              num_heads=4, intermediate_size=64, vocab_size=17)
    ff.compile(devices=jax.devices()[:1])
    registry, first = MetricsRegistry(), next_span_id()
    front = build_front(ff, registry=registry)
    try:
        out = front.generate(list(range(1, 11)), 3, 0.0)
        (replica,) = front.stats()["replicas"]
    finally:
        front.close()
    assert len(out) == 13
    mine = [r for r in spans() if r.span_id > first]
    built = next(r.args for r in mine if r.name == "serve.build_twin")
    assert built["mine_chunk"] == 4
    assert registry.gauge("serving/mine_heads").value == 4
    prefill, decode = ([r.args for r in mine if r.name == name]
                       for name in DISPATCHES)
    # GPT's scan feeds the prompt in chunks of 4, the step what is left:
    # the 12 tokens before the last, once a layer
    assert [a["mine_tokens"] for a in prefill] == [2 * 4, 2 * 4]
    assert [a["mine_first"] for a in prefill] == [0, 0]  # (the idle row)
    assert all(a["mine_capacity"] == 2 * 2 * 4 for a in prefill)
    assert len(decode) == 4 and all(
        a["mine_tokens"] == 2 and a["mine_capacity"] == 4 for a in decode)
    assert replica["mine"] == {
        "heads": 4, "layers": 2, "state_bytes": 0,
        "prefill_dispatches": 2, "decode_dispatches": 4,
        **{f"{program}_{k}": sum(a[k] for a in rows)
           for program, rows in (("prefill", prefill), ("decode", decode))
           for k in ("mine_tokens", "mine_first", "mine_capacity")}}


class FakeGroupedModel(FakeStepModel):
    """The scheduler's tests' bare host model (the next token is the
    input's successor), handing the scheduler two groups."""

    def __init__(self):
        super().__init__(prefill_chunk=4)
        self.groups = {
            "one": DispatchGroup(geometry={"width": 3, "layers": 1,
                                           "state_bytes": 0},
                                 counts=None, gauges={"one_width": 3}),
            "two": DispatchGroup(geometry={"layers": 2, "state_bytes": 8},
                                 counts=None)}
        self.asked = []

    def dispatch_counts(self, positions, counts, chunk):
        self.asked.append(chunk)
        live = int(np.count_nonzero(counts))
        return {"one": {"one_rows": live},
                "two": {"two_tokens": int(np.sum(counts)), "two_chunk": chunk}}


def test_the_scheduler_notes_whatever_groups_its_model_hands_it():
    model, registry, first = FakeGroupedModel(), MetricsRegistry(), \
        next_span_id()
    sched = ContinuousScheduler(model, registry=registry)
    try:
        assert sched.generate([1, 2, 3, 4, 5, 6], 3, 0.0) == list(range(1, 10))
        stats = sched.stats()
    finally:
        sched.close()
    prefill, decode = ([r.args for r in spans()
                        if r.span_id > first and r.name == name]
                       for name in DISPATCHES)
    # the scan feeds one chunk, the step the 4 tokens before the last
    assert [a["two_tokens"] for a in prefill] == [4]
    assert all(a["two_chunk"] == 4 and a["one_rows"] == 1 for a in prefill)
    assert len(decode) == 4 and all(
        a["two_chunk"] == 1 and a["two_tokens"] == 1 for a in decode)
    assert model.asked == [4, 1, 1, 1, 1]
    assert stats["one"] == {"width": 3, "layers": 1, "state_bytes": 0,
                            "prefill_dispatches": 1, "prefill_one_rows": 1,
                            "decode_dispatches": 4, "decode_one_rows": 4}
    assert stats["two"]["prefill_two_tokens"] == 4
    assert stats["two"]["decode_two_chunk"] == 4
    assert registry.gauge("serving/one_width").value == 3


def test_a_model_without_groups_is_asked_nothing():
    model = FakeGroupedModel()
    model.groups = {}
    sched = ContinuousScheduler(model)
    try:
        assert sched.generate([1, 2, 3], 2, 0.0) == [1, 2, 3, 4, 5]
        stats = sched.stats()
    finally:
        sched.close()
    assert model.asked == [] and not {"one", "two"} & set(stats)
