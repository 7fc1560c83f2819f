"""A region of the graph that runs N times over one copy of its weights
(`FFModel.repeat`, pcg `LoopRegion`), and the looped `ouro` language
model built on it, against the plain float32 reference
(benchmarks/families/ouro.py) on seeded weights, at a toy size on the
CPU, comparing LOGITS and gradients.

Tolerances.  The program and the reference compute the same float32
arithmetic in another order (a scan's body against a Python loop, a
chunk attended through the paged pool against one full causal pass), so
they differ by rounding only: 2e-5 of the compared tensor's largest
magnitude for logits that went through every layer of every pass, 1e-4
for a gradient (a sum over the passes of products of such values).
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from _family import Recorder, close, config, padded

from benchmarks.families import ouro as fam
from flexflow_tpu import FFConfig, FFModel, SGDOptimizer
from flexflow_tpu.config import ConfigError
from flexflow_tpu.models.ouro import build_ouro

CFG = config("toy-ouro.json")
D = fam.dims(CFG)
SEED = 11
LOGIT_TOL, GRAD_TOL = 2e-5, 1e-4
HELD = fam.held_weights(CFG, SEED)


def program_weights():
    """The held weights under the program's names, as host copies (a
    train step donates what `set_weights` was given)."""
    return jax.tree.map(np.array, fam.to_program_layout(HELD))


@jax.jit
def forward(ids):
    return fam.forward(HELD, ids, d=D)


def reference(ids):
    """(logits [s, vocab], exit pdf [T, s]) of the plain reference, one
    program for several lengths (`padded`)."""
    logits, pdf = forward(padded(ids))
    return logits[:len(ids)], pdf[:, :len(ids)]


def trainer(batch=2, seq=12, **ffconfig):
    ff = FFModel(FFConfig(batch_size=batch, num_devices=1, **ffconfig))
    build_ouro(ff, batch, seq, **fam.published(CFG))
    return ff


def holder(**ffconfig):
    """The served model's holder with the seed's weights set."""
    dep = CFG["deployment"]
    ff = FFModel(FFConfig(
        batch_size=1, num_devices=1, compute_dtype=CFG["precision"],
        serving_slots=dep["serving_slots"], kv_page_size=dep["kv_page_size"],
        kv_pool_blocks=dep["kv_pool_blocks"], **ffconfig))
    build_ouro(ff, 1, CFG["n_positions"], **fam.published(CFG))
    ff.compile(devices=jax.devices()[:1], defer_weights=True)
    ff.set_weights(fam.make_weights(CFG, SEED, "program"))
    return ff


# -- 1. the graph says it, the weights exist once ----------------------------------
def test_the_region_is_in_the_graph_and_its_weights_exist_once():
    ff = trainer()
    ff.compile(devices=jax.devices()[:1])
    (region,) = ff.operators.regions
    assert region.times == D.T and region.passes_op == "ut_loop_passes"
    assert len(region.op_names) == 8 * D.L + 1  # the layers + final_norm
    assert ff.operators.sink_op().name == "lm_head"
    # one leaf a published parameter: the reference's count
    leaves = jax.tree.leaves(ff._weights)
    assert sum(x.size for x in leaves) == fam.parameters(CFG)
    abstract = ff.executor.abstract_weights()
    assert ({(op, k) for op, e in abstract.items() for k in e}
            == {(op, k) for op, e in ff._weights.items() for k in e})
    # set_weights round-trips the reference's tree
    given = program_weights()
    ff.set_weights(given)
    back = ff.get_weights()
    for op, entries in given.items():
        for k, v in entries.items():
            np.testing.assert_array_equal(np.asarray(v), back[op][k])
    assert ff.executor.loop_counts == {
        "loop_regions": 1, "loop_steps": D.T, "loop_ops": 8 * D.L + 1}


def test_full_forward_logits_equal_the_reference():
    ff = trainer()
    ff.compile(devices=jax.devices()[:1])
    ff.set_weights(program_weights())
    ids = np.random.default_rng(0).integers(0, D.v, (2, 12)).astype(np.int32)
    got = np.asarray(ff.forward({"input": ids}))
    for b in range(2):
        close(got[b], reference(ids[b])[0], LOGIT_TOL)


def test_gradient_through_the_region_is_the_sum_over_the_passes():
    """One SGD step at lr 1 without momentum moves every weight by its
    gradient: the program's (a scan with the weights closed over)
    against `jax.grad` of the reference's Python loop, in which layer
    i's leaves are read once a pass."""
    ff = trainer(batch=2, seq=10)
    ff.compile(optimizer=SGDOptimizer(lr=1.0), devices=jax.devices()[:1])
    before = program_weights()
    ff.set_weights(program_weights())
    rng = np.random.default_rng(1)
    ids = rng.integers(0, D.v, (2, 10)).astype(np.int32)
    labels = rng.integers(0, D.v, (2, 10)).astype(np.int32)
    ff.train_step({"input": ids}, labels)
    after = ff.get_weights()

    def loss(w):
        nll = []
        for b in range(2):
            logits, _ = fam.forward(w, jnp.asarray(ids[b]), d=D)
            logp = jax.nn.log_softmax(logits, axis=-1)
            nll.append(-jnp.take_along_axis(
                logp, jnp.asarray(labels[b])[:, None], axis=-1))
        return jnp.mean(jnp.stack(nll))

    want = fam.to_program_layout(jax.jit(jax.grad(loss))(HELD))
    seen = 0
    for op, entries in want.items():
        for k, g in entries.items():
            moved = np.asarray(before[op][k]) - after[op][k]
            if op == "early_exit_gate":  # read, never in the loss
                assert not np.any(moved) and not np.any(np.asarray(g))
                continue
            close(moved, g, GRAD_TOL)
            seen += 1
    assert seen == 11 * D.L + 3


def test_a_graph_without_a_region_lowers_to_the_text_it_lowered_to(monkeypatch):
    """The executor's region dispatch adds nothing to a flat graph: its
    forward lowers to the same text as under the parent's `_exec_op`
    (an op's scope, then its body)."""
    from flexflow_tpu.executor import GraphExecutor
    from flexflow_tpu.models.transformer import build_gpt
    from flexflow_tpu.obs import scopes

    def lowered():
        ff = FFModel(FFConfig(batch_size=2, num_devices=1))
        build_gpt(ff, batch_size=2, seq_length=8, hidden_size=16,
                  num_layers=2, num_heads=2, vocab_size=32, max_positions=8)
        ff.compile(devices=jax.devices()[:1])
        assert ff.operators.regions == [] and not ff.executor._loop_of
        ids = np.zeros((2, 8), np.int32)
        text = ff._fwd_fn.lower(
            ff._weights, ff._state,
            {"input": ids, "positions": ids}).as_text()
        # (op guids differ between two builds and nothing else does)
        return text

    now = lowered()

    def parent_exec_op(self, op, env, ctx):
        with scopes.op_scope(op):
            self._exec_op_traced(op, env, ctx)

    monkeypatch.setattr(GraphExecutor, "_exec_op", parent_exec_op)
    assert lowered() == now


def test_a_scope_inside_the_region_still_reads_kind_and_name():
    """`while/body` stands in an instruction's path before the op's
    scope element; `scopes.parse` finds the op all the same."""
    from flexflow_tpu.obs import scopes

    import re

    ff = trainer(batch=1, seq=4)
    ff.compile(devices=jax.devices()[:1])
    text = ff._fwd_fn.lower(
        ff._weights, ff._state,
        {"input": np.zeros((1, 4), np.int32)}).compile().as_text()
    # the `op_name` of a compiled instruction: what a device profile's
    # events carry
    paths = set(re.findall(r'op_name="([^"]*)"', text))
    inside = [p for p in paths if "/while/body/" in p]
    assert inside
    found = {(s.kind, s.name, s.part) for s in map(scopes.parse, inside)}
    assert ("MultiHeadAttention", "attn_1", "core") in found
    assert ("GatedMLP", "mlp_0", None) in found
    assert ("RMSNorm", "final_norm", None) in found
    # what stays unplaced is the loop's own bookkeeping (its counter)
    assert all(":" not in p for p in inside if scopes.parse(p).kind is None)


# -- 2. what a region refuses, by name ------------------------------------------------
def test_an_exit_threshold_that_would_be_acted_on_is_refused():
    ff = FFModel(FFConfig(batch_size=1, num_devices=1))
    kw = dict(fam.published(CFG), early_exit_threshold=0.5)
    with pytest.raises(ConfigError, match="early_exit_threshold 0.5"):
        build_ouro(ff, 1, 8, **kw)


def test_per_slot_state_inside_a_region_is_refused():
    from flexflow_tpu.ops.gated_delta_net import GatedDeltaNetParams

    ff = FFModel(FFConfig(batch_size=2, num_devices=1))
    x = ff.create_tensor([2, 1, 16], name="x")
    with pytest.raises(ConfigError, match="per-slot state inside a region"):
        with ff.repeat(x, 2, name="r") as loop:
            t = ff.gated_delta_net(
                x, GatedDeltaNetParams(
                    embed_dim=16, num_k_heads=2, num_v_heads=2,
                    head_k_dim=8, head_v_dim=8, conv_kernel=2),
                name="gdn", slot_state=True)
            loop.carry(t)


def test_a_region_reads_one_tensor_and_hands_on_its_shape():
    ff = FFModel(FFConfig(batch_size=2, num_devices=1))
    x = ff.create_tensor([2, 4, 16], name="x")
    y = ff.create_tensor([2, 4, 16], name="y")
    with pytest.raises(ConfigError, match="ONE tensor in"):
        with ff.repeat(x, 2, name="a") as loop:
            loop.carry(ff.add(ff.rms_norm(x, name="n"), y, name="s"))
    with pytest.raises(ConfigError, match="is not shaped like"):
        with ff.repeat(x, 2, name="b") as loop:
            loop.carry(ff.dense(x, 8, name="narrow"))
    with pytest.raises(ConfigError, match="do not nest"):
        with ff.repeat(x, 2, name="c"):
            with ff.repeat(x, 2, name="d"):
                pass


def looped_mlp(ff, times, width=16):
    x = ff.create_tensor([4, 8, width], name="x")
    t = x
    with ff.repeat(x, times, name="loop") as loop:
        for i in range(4):
            t = ff.add(t, ff.dense(ff.rms_norm(t, name=f"n{i}"), width,
                                   name=f"d{i}"), name=f"r{i}")
        loop.carry(t)
    return ff.dense(t, 8, name="head")


@pytest.mark.parametrize("feature,ffconfig,strategy", [
    ("pipeline blocks", {}, "pipeline"),
    ("remat", {"remat": True}, None),
    ("--fusion", {"perform_fusion": True}, None),
    ("the strategy search", {"search_budget": 4,
                             "only_data_parallel": False}, "search"),
])
def test_a_region_refuses_what_cannot_take_one_by_name(feature, ffconfig,
                                                       strategy):
    from flexflow_tpu.strategy import data_parallel_strategy

    ff = FFModel(FFConfig(batch_size=4, num_devices=1, **ffconfig))
    looped_mlp(ff, 3)
    given = None
    if strategy == "pipeline":
        given = data_parallel_strategy(1)
        given.pipeline = {"num_stages": 2, "num_microbatches": 2}
    elif strategy is None:
        given = data_parallel_strategy(1)
    with pytest.raises(ConfigError) as e:
        ff.compile(strategy=given, devices=jax.devices()[:1])
    assert "region 'loop'" in str(e.value) and feature in str(e.value)


# -- 3. what a region costs ------------------------------------------------------------
def test_the_simulator_prices_a_region_at_its_passes():
    """A looped graph's predicted forward time is N times the time of
    the same ops run once (what is outside the region counted once),
    and the per-op table's FLOPs likewise."""
    from flexflow_tpu.sim.machine_model import make_machine_model
    from flexflow_tpu.sim.simulator import Simulator

    def forward_times(times):
        ff = FFModel(FFConfig(batch_size=4, num_devices=1))
        looped_mlp(ff, times, width=64)
        ff.compile(devices=jax.devices()[:1])
        sim = Simulator(make_machine_model(ff.config, 1))
        res = sim.simulate(ff.operators, ff.strategy.mesh_axes,
                           training=False)
        inside = set(ff.operators.regions[0].op_names)
        return (sum(v for k, v in res.breakdown.items() if k in inside),
                sum(v for k, v in res.breakdown.items() if k not in inside),
                res.compute_time)

    in1, out1, total1 = forward_times(1)
    in3, out3, total3 = forward_times(3)
    assert in1 > 0 and out1 > 0
    assert in3 == pytest.approx(3 * in1, rel=1e-9)
    assert out3 == pytest.approx(out1, rel=1e-9)
    assert total3 == pytest.approx(total1 + 2 * in1, rel=1e-6)

    from flexflow_tpu.profiler import profile_operators

    ff = FFModel(FFConfig(batch_size=4, num_devices=1))
    looped_mlp(ff, 3, width=64)
    ff.compile(devices=jax.devices()[:1])
    rows = {r["name"]: r for r in profile_operators(ff, warmup=0, repeats=1)}
    ops = {op.name: op for op in ff.operators.topo_order()}
    assert rows["d0"]["flops"] == 3 * ops["d0"].flops() > 0
    assert rows["head"]["flops"] == ops["head"].flops()
    assert "loop_passes" not in rows


# -- 4. served: a plane a pass under one block table ---------------------------------------



def serve_toy(paged_kernel):
    """One scheduler over the toy model: a long prompt prefilled in
    chunks alone, then three prompts at once (every slot busy, so the
    prefill dispatches carry decode-phase riders), then a prompt that
    shares a long prefix with the first (a page hit):
    (recorded rows, handles, stats)."""
    from flexflow_tpu.obs import trace
    from flexflow_tpu.serving.scheduler import ContinuousScheduler

    sched = ContinuousScheduler.from_trained(
        holder(), batch_slots=3, page_size=4, num_blocks=40,
        prefill_chunk=4, paged_kernel=paged_kernel,
        devices=jax.devices()[:1])
    rec = Recorder(sched, lambda model, i: (model.exit_last[i].copy(),))
    since = trace.next_span_id()
    try:
        rng = np.random.default_rng(5)
        prompts = [rng.integers(1, D.v, n).tolist() for n in (16, 15, 9, 5)]
        handles = [sched.generate_async(prompts[0], 6, 0.0)]
        handles[0].wait(300)
        handles += [sched.generate_async(p, 5, 0.0) for p in prompts[1:]]
        for h in handles[1:]:
            h.wait(300)
        handles.append(sched.generate_async(
            prompts[0][:13] + prompts[3], 4, 0.0))
        handles[-1].wait(300)
        stats = sched.stats()
    finally:
        sched.close(10)
    return rec.rows, handles, stats, [
        r for r in trace.spans() if r.span_id > since]


@pytest.fixture(scope="module")
def served_gather():
    return serve_toy("gather")


@pytest.fixture(scope="module")
def served_pallas():
    return serve_toy("pallas")  # the kernel, interpreted


@pytest.fixture(params=["gather", "pallas"])
def served(request):
    return request.getfixturevalue(f"served_{request.param}")


def test_served_logits_equal_the_reference_full_forward(served):
    """Prefill in chunks of 4 through the paged pool, then decode: at
    every decode dispatch every live row's logits against the
    reference's full forward over what the request ended as, and the
    step program's exit pdf against the reference's."""
    rows, handles, stats, _ = served
    want = {id(h): reference(h.result) for h in handles}
    assert len(rows) >= 25
    for req, pos, logits, pdf in rows:
        ref_logits, ref_pdf = want[id(req)]
        close(logits, ref_logits[pos], LOGIT_TOL)
        np.testing.assert_allclose(pdf, np.asarray(ref_pdf)[:, pos],
                                   atol=2e-5)
        assert pdf.sum() == pytest.approx(1.0, abs=1e-5)
    assert stats["prefill_steps"] > 0 and stats["prefill_passes"] == 1
    assert stats["requests_done"] == 5
    assert stats["prefix_cache"]["hit_tokens"] >= 12  # the shared pages


def test_dispatch_spans_and_stats_carry_the_loop(served_gather):
    _, _, stats, spans = served_gather
    decode = [r for r in spans if r.name == "sched.decode.dispatch"]
    prefill = [r for r in spans if r.name == "sched.prefill.dispatch"]
    assert decode and prefill
    assert all(r.args["loop_steps"] == D.T for r in decode + prefill)
    for r in decode:
        mass = [r.args[f"exit_mass_{t}"] for t in range(D.T)]
        assert sum(mass) == pytest.approx(1.0, abs=1e-5)
        assert f"exit_mass_{D.T}" not in r.args
    loop = stats["loop"]
    assert loop["loop_regions"] == 1 and loop["loop_steps"] == D.T
    assert loop["decode_weight_passes"] == D.T * loop["decode_dispatches"]
    assert loop["decode_dispatches"] == len(decode)
    assert loop["prefill_weight_passes"] == D.T * len(prefill)
    assert sum(loop["exit_mass"]) == pytest.approx(loop["exit_rows"],
                                                   rel=1e-5)
    # a block of the table is a page in every plane of every layer
    itemsize = 4
    assert stats["kv_pool"]["bytes_per_token"] == (
        D.T * D.L * 2 * D.h * D.hd * itemsize)
    twin = [r for r in spans if r.name == "serve.build_twin"]
    assert not twin  # (from_trained builds no front; see the front's test)


def test_the_planes_of_a_layer_differ_and_a_copied_block_copies_them_all():
    from flexflow_tpu.serving.scheduler import PagedKVDecodeModel

    model = PagedKVDecodeModel(
        holder(), batch_slots=2, page_size=4, num_blocks=10,
        prefill_chunk=4, devices=jax.devices()[:1])
    assert model.loop == {"loop_regions": 1, "loop_steps": D.T,
                          "loop_ops": 8 * D.L + 1}
    nb = model.num_blocks
    pool = model._state["attn_0"]["k_cache"]
    assert pool.shape == (D.T * nb, 4, D.h, D.hd)
    table = np.zeros((2, model.max_blocks_per_seq), np.int32)
    table[0, :2] = [3, 5]
    table[1, :2] = [4, 6]
    tokens = np.array([[7, 8, 9, 10], [11, 12, 13, 14]], np.int32)
    model.prefill_step(tokens, np.zeros(2, np.int32), table,
                       np.full(2, 4, np.int32))
    for name in ("attn_0", "attn_1"):
        for entry in ("k_cache", "v_cache"):
            pool = np.asarray(model._state[name][entry])
            planes = [pool[t * nb + 3] for t in range(D.T)]
            assert all(np.abs(p).max() > 0 for p in planes)
            for a in range(D.T):
                for b in range(a + 1, D.T):
                    assert np.abs(planes[a] - planes[b]).max() > 1e-4
            # nothing was written outside the rows' blocks and scratch
            untouched = [r for r in range(pool.shape[0])
                         if r % nb not in (0, 3, 4)]
            assert not pool[untouched].any()
    exported = model.export_block(3)
    assert exported["attn_1/v_cache"].shape == (D.T, 4, D.h, D.hd)
    model.copy_block(3, 8)
    for name in ("attn_0", "attn_1"):
        pool = np.asarray(model._state[name]["k_cache"])
        for t in range(D.T):
            np.testing.assert_array_equal(pool[t * nb + 8], pool[t * nb + 3])
    model.import_block(9, exported)
    pool = np.asarray(model._state["attn_1"]["v_cache"])
    np.testing.assert_array_equal(
        pool[9 + nb * np.arange(D.T)], exported["attn_1/v_cache"])
    assert model.kv_block_bytes == D.T * D.L * 2 * 4 * D.h * D.hd * 4


def test_front_stats_and_the_twin_span_say_the_loop():
    from flexflow_tpu.obs import trace
    from flexflow_tpu.serving import build_front

    since = trace.next_span_id()
    front = build_front(holder())
    try:
        h = front.generate_async(list(range(1, 11)), 3, 0.0)
        h.wait(300)
        replica = front.stats()["replicas"][0]
        loop = replica["loop"]
    finally:
        front.close(10)
    assert replica["pass_decode_tokens"] == 1 < replica["tokens_generated"]
    # the prompt's last chunk holds its last token: the first of the
    # three tokens is sampled from that pass, the others from two steps
    assert loop["loop_steps"] == D.T and loop["decode_dispatches"] == 2
    assert loop["exit_rows"] == 2 + loop["prefill_dispatches"] >= 3
    assert len(loop["exit_mass"]) == D.T
    (twin,) = [r for r in trace.spans()
               if r.span_id > since and r.name == "serve.build_twin"]
    assert twin.args["loop_regions"] == 1
    assert twin.args["loop_steps"] == D.T
    assert twin.args["loop_ops"] == 8 * D.L + 1
