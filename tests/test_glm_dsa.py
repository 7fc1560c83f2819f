"""The glm_moe_dsa language model (latent attention over the keys a
learned indexer picks, picks reused by the layers above, a share of
sigmoid-routed experts) against its plain float32 reference
(benchmarks/families/glm_dsa.py) on seeded weights, at a toy size on the
CPU: `index_topk` 8 against contexts of 40 and more, so selection bites.

Tolerances.  Program and reference compute the same float32 arithmetic
in another order (absorbed attention over a gathered selection against
expanded keys under a mask, `lax.top_k` against a stable sort), so they
differ by rounding only, as `test_kimi_k2.py` argues: 1e-5 of the
compared tensor's largest magnitude for one op, 2e-5 for logits that
went through every layer.  The SETS of picks are compared exactly; a
key on the other side of the threshold moves a logit by a hundred
times the tolerance (`test_the_mechanism_controls_...`).
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from _family import Recorder, close, config

from benchmarks import reference as ref
from benchmarks.families import glm_dsa as fam
from flexflow_tpu import FFConfig, FFModel
from flexflow_tpu.models.glm_dsa import build_glm_dsa, published_layer_types
from flexflow_tpu.obs.trace import next_span_id, spans

CFG = config("toy-glm52.json")
D = fam.dims(CFG)
SEED = 11
KEY = ref.seed_key(SEED)
OP_TOL, LOGIT_TOL = 1e-5, 2e-5


def holder(cfg=CFG, seq=None, **ffconfig):
    """The served model's holder with the seed's weights set."""
    dep = cfg["deployment"]
    ff = FFModel(FFConfig(
        batch_size=1, num_devices=1, compute_dtype=cfg["precision"],
        serving_slots=dep["serving_slots"], kv_page_size=dep["kv_page_size"],
        kv_pool_blocks=dep["kv_pool_blocks"],
        prefill_chunk=dep["prefill_chunk"], **ffconfig))
    build_glm_dsa(ff, 1, seq or cfg["n_positions"], **fam.published(cfg))
    ff.compile(devices=jax.devices()[:1], defer_weights=True)
    ff.set_weights(fam.make_weights(cfg, SEED, "program"))
    return ff


def reference_logits(tokens, cfg=CFG, selection="dsa"):
    return np.asarray(fam.logits_fn(
        fam.make_weights(cfg, SEED, "reference"), tokens, "float32",
        selection))


def graph_op(name, cfg=CFG, **cache):
    ff = FFModel(FFConfig(batch_size=1, num_devices=1))
    build_glm_dsa(ff, 1, 8, **fam.published(cfg), **cache)
    return next(op for op in ff.layers.topo_order() if op.name == name)


def sets_of(picks):
    """picks [s, k] (positions, -1 = none) -> [set of positions] a
    query."""
    return [set(int(i) for i in row if i >= 0) for row in np.asarray(picks)]


# -- 1. the op alone: the picks as SETS, the output -------------------------------
@pytest.fixture(scope="module")
def full_op_case():
    """A `full` op's stateless forward on 48 positions beside the
    reference's attention on the same weights: (out, picks, reference
    out, reference keep [s, s])."""
    op = graph_op("attn_0")
    w = fam.make_leaves(KEY, D, "attn_full", 0)
    x = jax.random.normal(jax.random.key(1), (1, 48, D.e))
    pos = jnp.arange(48, dtype=jnp.int32)[None]
    out, picks = jax.jit(lambda x, w: op.forward(
        [x, pos], [w[s.name] for s in op.weight_specs]))(x, w)

    @jax.jit
    def plain(x, w):
        with jax.default_matmul_precision("highest"):
            h = jnp.zeros((D.p, D.e)).at[:48].set(x[0])
            return fam.attention(h, 48, jnp.zeros((D.p, D.p), bool), w, D,
                                 lambda v: v, True)

    want, keep = plain(x, w)
    return (np.asarray(out)[0], np.asarray(picks)[0],
            np.asarray(want)[:48], np.asarray(keep)[:48, :48])


def test_full_op_picks_the_references_sets_exactly(full_op_case):
    _, picks, _, keep = full_op_case
    got = sets_of(picks)
    for t in range(48):
        assert got[t] == set(np.flatnonzero(keep[t]).tolist()), t
        assert len(got[t]) == min(t + 1, D.topk)
    # selection bites: the picks are not the last index_topk keys
    assert any(got[t] != set(range(t - D.topk + 1, t + 1))
               for t in range(D.topk, 48))


def test_full_op_output_is_the_references_over_the_picked_keys(full_op_case):
    out, _, want, _ = full_op_case
    close(out, want, OP_TOL)


def test_shared_op_has_no_indexer_weights_and_follows_the_picks_it_is_handed():
    """(c): a `shared` layer owns no indexer leaf and no index pool; its
    output is the reference's attention under the handed picks, and
    moves when other picks are handed."""
    op = graph_op("attn_1", decode_max_seq=64, kv_page_size=4,
                  kv_num_blocks=65)
    names = [s.name for s in op.weight_specs]
    assert not [n for n in names if "idx" in n or n == "index_cache"]
    assert op.cache_entries() == ("latent_cache",)
    full = graph_op("attn_0", decode_max_seq=64, kv_page_size=4,
                    kv_num_blocks=65)
    assert full.cache_entries() == ("latent_cache", "index_cache")
    assert [s.name for s in full.weight_specs[7:12]] == [
        "wq_idx", "wk_idx", "k_idx_norm", "k_idx_bias", "w_idx"]

    op = graph_op("attn_1")  # stateless
    w = fam.make_leaves(KEY, D, "attn", 1)
    x = jax.random.normal(jax.random.key(2), (1, 40, D.e))
    pos = jnp.arange(40, dtype=jnp.int32)[None]
    rng = np.random.default_rng(0)

    def some_picks():
        rows = [rng.permutation(t + 1)[:D.topk] for t in range(40)]
        return np.stack([np.pad(r, (0, D.topk - len(r)), constant_values=-1)
                         for r in rows]).astype(np.int32)

    def program(picks):
        return np.asarray(op.forward(
            [x, pos, jnp.asarray(picks)[None]],
            [w[s.name] for s in op.weight_specs])[0])[0]

    def plain(picks):
        keep = np.zeros((D.p, D.p), bool)
        for t, s in enumerate(sets_of(picks)):
            keep[t, list(s)] = True
        with jax.default_matmul_precision("highest"):
            h = jnp.zeros((D.p, D.e)).at[:40].set(x[0])
            return np.asarray(fam.attention(
                h, 40, jnp.asarray(keep), w, D, lambda v: v, False)[0])[:40]

    one, other = some_picks(), some_picks()
    close(program(one), plain(one), OP_TOL)
    close(program(other), plain(other), OP_TOL)
    assert np.max(np.abs(program(one) - program(other))) \
        > 1e3 * OP_TOL * np.max(np.abs(program(one)))


def test_published_layer_types_are_reproduced_from_the_scalar_keys():
    """GLM-5.2's `indexer_types` / `mlp_layer_types` (the catalog's
    lists, copied whole into the cell's configuration) from
    `index_topk_freq`, `index_skip_topk_offset`, `first_k_dense_replace`:
    21 full, 57 shared; 3 dense, 75 sparse."""
    real = config("glm52-ep16-serve.json")
    roles, mlps = published_layer_types(
        real["published"]["num_hidden_layers"],
        real["first_k_dense_replace"], real["index_topk_freq"],
        real["index_skip_topk_offset"])
    assert roles == real["indexer_types"] and mlps == real["mlp_layer_types"]
    assert (roles.count("full"), roles.count("shared")) == (21, 57)
    assert (mlps.count("dense"), mlps.count("sparse")) == (3, 75)
    assert [i for i, r in enumerate(roles) if r == "full"][:5] == [
        0, 1, 2, 6, 10]
    # the cell's stretch: published layers 2-6
    kw = fam.published(real)
    assert kw["indexer_types"] == ["full", "shared", "shared", "shared",
                                   "full"]
    assert kw["mlp_layer_types"] == ["dense"] + ["sparse"] * 4
    # the configuration's arithmetic, from the shapes: 7.76 GB in bf16
    assert fam.total_parameters(fam.dims(real)) == 3_881_517_056
    assert fam.latent_block_bytes(real) == 16 * (5 * 640 + 2 * 128) * 2
    toy = published_layer_types(6, CFG["first_k_dense_replace"],
                                CFG["index_topk_freq"],
                                CFG["index_skip_topk_offset"])
    assert list(toy) == [CFG["indexer_types"], CFG["mlp_layer_types"]]


# -- 2. the whole model: one-shot forward, then the served path -------------------
def test_one_shot_forward_equals_the_reference_and_leaves_both_controls():
    """The stateless graph (picks as a mask on the einsum core) against
    the reference, and the reference's two controls of the mechanism
    against it: dense attention and the wrong layer's picks both move
    the logits by orders more than the tolerance."""
    ff = holder(seq=48)
    toks = np.random.default_rng(3).integers(1, D.v, 48)
    got = np.asarray(ff.forward({
        "input": toks[None].astype(np.int32),
        "positions": np.arange(48, dtype=np.int32)[None]}))[0]
    want = reference_logits(toks)
    close(got, want, LOGIT_TOL)
    scale = np.max(np.abs(want))
    for selection in ("dense", "above"):
        other = reference_logits(toks, selection=selection)
        # equal while every causal key is picked, far apart after
        close(other[:D.topk], want[:D.topk], LOGIT_TOL)
        assert np.max(np.abs(other - want)) > 0.1 * scale, selection


@pytest.mark.parametrize("length", [70, 200, 256])
def test_the_references_walk_does_not_depend_on_its_blocks(length,
                                                           monkeypatch):
    """The reference walks a sequence in blocks of queries whose keys
    widen by steps, its experts over the rows that chose them a few at
    a time: the logits and the picks are those of one block over every
    key and every row (256 positions, so that the small blocks are
    many: 4 queries, of which the first two blocks pick every causal
    key unsorted, keys by 48, 8 rows an expert)."""
    cfg = dict(CFG, n_positions=256, max_position_embeddings=256)
    toks = np.random.default_rng(5).integers(1, D.v, length)
    padded = jnp.zeros(256, jnp.int32).at[:length].set(jnp.asarray(toks))
    got = {}
    for name, sizes in (("whole", (256, 256, 256, 256)),
                        ("blocks", (4, 48, 8, 32))):
        for key, size in zip(("QUERIES_AT_ONCE", "KEYS_STEP", "EXPERT_ROWS",
                              "ROWS_AT_ONCE"), sizes):
            monkeypatch.setattr(fam, key, size)
        fam.layer_fn.clear_cache()  # the sizes are read when it traces
        w = fam.make_weights(cfg, SEED, "reference")
        with jax.default_matmul_precision("highest"):
            made = fam.walk(w, padded, length, "float32")[1]
        got[name] = (np.asarray(fam.logits_fn(w, toks, "float32")),
                     {i: np.asarray(m)[:length] for i, m in made.items()})
    fam.layer_fn.clear_cache()
    close(got["blocks"][0], got["whole"][0], LOGIT_TOL)
    for i, picks in got["whole"][1].items():
        assert np.array_equal(got["blocks"][1][i], picks), i
        assert picks.sum(axis=1).max() == min(length, D.topk)


@pytest.fixture(scope="module")
def served():
    """One scheduler over the toy model and a scenario with chunked
    prefill of prompts well past `index_topk`, a full-prompt prefix hit
    (copy-on-write), a partial hit and a slot reused after a finished
    request: (recorded rows, handles, scheduler stats with the
    replica's own under `replica`, dispatch spans).  The engine is the
    one replica of a front, so what the replica forwards is seen."""
    from flexflow_tpu.serving.front import ServingFront
    from flexflow_tpu.serving.scheduler import PagedKVDecodeModel

    ff = holder()
    front = ServingFront(lambda replica_id, survivors=None: PagedKVDecodeModel(
        ff, batch_slots=3, page_size=4, num_blocks=60, prefill_chunk=4,
        prefix_cache=True, devices=jax.devices()[:1]), 1)
    sched = front.replicas[0].scheduler
    rec = Recorder(sched)
    first = next_span_id()
    try:
        rng = np.random.default_rng(5)
        a = rng.integers(1, D.v, 40).tolist()  # ten full pages
        b = a[:24] + rng.integers(1, D.v, 19).tolist()
        c = rng.integers(1, D.v, 45).tolist()
        handles = [sched.generate_async(a, 6, 0.0)]
        handles[0].wait(120)
        # a again (every block cached: the tail block is copied before
        # the first write), b (shares six blocks), c: three slots at once
        handles += [sched.generate_async(p, 5, 0.0) for p in (a, b, c)]
        for h in handles[1:]:
            h.wait(120)
        handles.append(sched.generate_async(c[:5], 4, 0.0))  # a slot again
        handles[-1].wait(120)
        stats = dict(sched.stats(), replica=front.stats()["replicas"][0])
    finally:
        front.close()
    mine = [r for r in spans() if r.span_id > first and r.name in (
        "sched.prefill.dispatch", "sched.decode.dispatch")]
    return rec.rows, handles, stats, mine


def test_served_logits_equal_the_reference_full_forward(served):
    """(d): chunked prefill and decode through both pools, by logits."""
    rows, handles, _, _ = served
    want = {id(h): reference_logits(h.result) for h in handles}
    assert len(rows) >= 25
    assert max(pos for _, pos, _ in rows) >= 45  # far past index_topk
    for req, pos, logits in rows:
        close(logits, want[id(req)][pos], LOGIT_TOL)


def test_scenario_ran_one_pass_hit_the_prefix_cache_and_copied_a_block(served):
    """A prefix hit serves the index keys with the latents (the hit
    requests' logits are compared above; here: the hits happened)."""
    _, handles, stats, _ = served
    assert stats["prefill_chunk"] == 4 and stats["prefill_passes"] == 1
    assert handles[1].prefix_hit_tokens >= 36      # the full-prompt hit
    assert handles[2].prefix_hit_tokens == 24      # the shared six pages
    assert stats["prefix_cache"]["cow_copies"] >= 1


def test_dispatch_spans_carry_the_selections_counters(served):
    """(g): from host-owned lengths, real tokens only: a prefill pass
    of n tokens from position p reads `min(t + 1, index_topk)` keys a
    query a layer where a dense read would attend `t + 1`."""
    from flexflow_tpu.ops.mla import selection_counts

    _, _, stats, mine = served
    assert mine and all("dsa_keys_selected" in r.args for r in mine)
    full = D.full_layers
    for r in mine:
        a = r.args
        assert a["dsa_keys_scored"] == full * a["dsa_keys_live"]
        assert a["dsa_keys_selected"] <= a["dsa_keys_live"]
        assert a["index_blocks_live"] % full == 0
        # the keys a layer's read touches under the CPU tier's plan: the
        # decode step gathers 3 slots' picks, a chunk of 4 takes the view
        # over 3 slots' tables of 64
        assert a["dsa_keys_read"] == (
            3 * 64 if r.name == "sched.prefill.dispatch" else 3 * D.topk)
    first = next(r.args for r in mine if r.name == "sched.prefill.dispatch")
    # request a alone: 4 tokens from position 0, all under index_topk
    assert first["tokens"] == 4 and first["dsa_keys_live"] == 10 \
        and first["dsa_keys_selected"] == 10 \
        and first["dsa_rows_past_topk"] == 0 \
        and first["index_blocks_live"] == full
    assert selection_counts(8, [0, 6, 40, 3], [4, 4, 1, 0]) == {
        "keys_live": 10 + (7 + 8 + 9 + 10) + 41,
        "keys_selected": 10 + (7 + 8 + 8 + 8) + 8, "rows_past_topk": 2}
    dsa = stats["dsa"]
    assert dsa["topk"] == D.topk and dsa["layers"] == D.L \
        and dsa["full_layers"] == full
    # the sums, by program; and the replica forwards them (ISSUE 59)
    assert stats["replica"]["dsa"] == dsa
    for program in ("prefill", "decode"):
        args = [r.args for r in mine if r.name == f"sched.{program}.dispatch"]
        assert dsa[f"{program}_dispatches"] == len(args) > 0
        for k in ("dsa_keys_selected", "dsa_keys_read"):
            assert dsa[f"{program}_{k}"] == sum(a[k] for a in args)
        assert dsa[f"{program}_dsa_keys_selected"] \
            < dsa[f"{program}_dsa_keys_live"]
        assert dsa[f"{program}_dsa_rows_past_topk"] > 0


@pytest.mark.parametrize("plan,chunk,want", [
    ("walk", 16, (6 + 16) + (40 + 16) + 64),   # live keys, held to the table
    ("walk", 1, 7 + 41 + 61),
    ("view", 16, 4 * 64),                       # every slot's table
    ("gather", 1, 4 * 1 * 8),                   # every slot's picks
    ("gather", 16, 4 * 16 * 8)])
def test_keys_read_follow_the_plan_in_force(plan, chunk, want):
    """`dsa_keys_read` of a dispatch: what a layer's read touches under
    the plan its program's shape takes, the idle slot nothing under the
    walk."""
    import types

    from flexflow_tpu.ops.mla import MLAttention

    ops = [types.SimpleNamespace(
        params=types.SimpleNamespace(index_topk=8, indexer=role),
        selected_plan=lambda s, n: plan)
        for role in ("full", "shared", "full", "shared")]
    told = MLAttention.dispatch_group_of(
        ops, family="toy", batch_slots=4, page_size=4, max_seq=64,
        prefill_chunk=chunk, state_bytes=0)
    assert told.geometry == {"topk": 8, "full_layers": 2}
    rows = told.counts([6, 40, 60, 0], [min(chunk, 4)] * 3 + [0], chunk)
    assert rows["dsa_keys_read"] == want
    assert rows["dsa_keys_scored"] == 2 * rows["dsa_keys_live"]


# -- 3. where selection is the identity, and the pad contract ----------------------
def test_up_to_index_topk_keys_the_op_is_the_indexerless_op():
    """(b): with no more keys in reach than `index_topk` a `full` op
    traces the op without an indexer on the same weights: the same
    output (bit for bit: the same program), no index pool, and picks
    that are the causal keys themselves."""
    from flexflow_tpu.ops.mla import MLAttention

    cache = dict(decode_max_seq=8, kv_page_size=4, kv_num_blocks=9)
    full = graph_op("attn_0", **cache)             # 8 keys, index_topk 8
    assert not full.reads_selection()
    assert full.cache_entries() == ("latent_cache",)
    assert full.pool_width() == full.params.latent_width
    plain = MLAttention(
        dataclasses.replace(full.params, indexer="", index_topk=0,
                            index_n_heads=0, index_head_dim=0),
        full.inputs, name="plain", **cache)
    w = fam.make_leaves(KEY, D, "attn_full", 0)
    x = jax.random.normal(jax.random.key(4), (2, 3, D.e))
    pos = jnp.asarray([[2, 3, 4], [0, 1, 2]], jnp.int32)
    state = [jnp.zeros((9, 4, D.rk + D.dr)),
             jnp.asarray([[1, 2], [3, 4]], jnp.int32), pos[:, 0]]

    def run(op):
        names = [s.name for s in op.weight_specs[:op.num_trainable_weights()]]
        return op.forward([x, pos], [w[n] for n in names] + state)

    out, picks, pool, *_ = run(full)
    want, want_pool, *_ = run(plain)
    assert np.array_equal(np.asarray(out), np.asarray(want))
    assert np.array_equal(np.asarray(pool), np.asarray(want_pool))
    assert sets_of(np.asarray(picks)[0]) == [set(range(t + 1))
                                             for t in (2, 3, 4)]
    # and the whole model under a table no wider than index_topk builds
    # no index pool and counts no selection
    wide = dict(CFG, index_topk=64)
    assert not fam.dims(wide).selects
    assert fam.latent_block_bytes(wide) == 4 * D.L * (D.rk + D.dr) * 4


SLOTS, PAGE, CHUNK = 4, 4, 4


@pytest.fixture(scope="module")
def twin():
    """The paged seq-1 twin with its decode step and the one-pass
    prefill program (state is donated: every call gets a copy)."""
    from flexflow_tpu.decoding import (build_paged_decode_step,
                                       build_paged_prefill_pass,
                                       make_decoder)

    ffd = make_decoder(holder(), batch_size=SLOTS, kv_page_size=PAGE,
                       kv_num_blocks=1 + SLOTS * D.p // PAGE,
                       devices=jax.devices()[:1])
    fns = {"step": build_paged_decode_step(ffd),
           "pass": build_paged_prefill_pass(ffd, CHUNK)}
    btab = np.arange(1, 1 + SLOTS * D.p // PAGE,
                     dtype=np.int32).reshape(SLOTS, -1)

    def run(name, state, tokens, positions, table, fed=None):
        out = fns[name](ffd._weights, jax.tree.map(jnp.copy, state),
                        jnp.asarray(tokens, jnp.int32),
                        jnp.asarray(positions, jnp.int32),
                        jnp.asarray(table, jnp.int32),
                        *((jnp.asarray(fed, jnp.int32),)
                          if name == "pass" else ()))
        return out[1]

    return ffd, run, btab


def _pools(state):
    return {(op, k): np.asarray(v, np.float32)
            for op, e in state.items() for k, v in e.items()
            if k in ("latent_cache", "index_cache")}


def test_pass_keeps_the_pad_contract_in_both_pools(twin):
    """(e): rows within CHUNK of max_seq and two riders: positions >=
    max_seq and the riders' tokens go to scratch, in the latent pools
    of all four layers AND the index pools of the two full ones: every
    block outside the fed rows' own frontier is byte-unchanged, and the
    frontier holds what a seq-1 step writes there."""
    ffd, run, btab = twin
    rng = np.random.default_rng(23)
    state = {op: {k: (jnp.asarray(rng.standard_normal(v.shape), v.dtype)
                      if k in ("latent_cache", "index_cache") else v)
                  for k, v in e.items()} for op, e in ffd._state.items()}
    starts = np.array([D.p - 2, D.p - 3, 0, 0], np.int32)
    table = btab.copy()
    table[2:] = 0
    feed = rng.integers(1, D.v, (SLOTS, CHUNK)).astype(np.int32)
    was = _pools(state)
    assert sorted(k for _, k in was) == ["index_cache"] * D.full_layers \
        + ["latent_cache"] * D.L
    one = _pools(run("pass", state, feed, starts, table,
                     fed=[CHUNK, CHUNK, 0, 0]))
    stepped = state
    for j in range(3):  # the in-range positions, a token a step
        live = starts + j < D.p
        stepped = run("step", stepped, np.where(live, feed[:, j], 0),
                      np.where(live, starts + j, 0),
                      np.where(live[:, None], table, 0))
    stepped = _pools(stepped)
    frontier = np.zeros(was["attn_0", "latent_cache"].shape[:2], bool)
    for i in (0, 1):
        for pos in range(starts[i], D.p):
            frontier[table[i, pos // PAGE], pos % PAGE] = True
    assert frontier.sum() == 2 + 3
    for key in one:
        rest = ~frontier
        rest[0] = False                                  # scratch
        assert np.array_equal(one[key][rest], was[key][rest]), key
        close(one[key][frontier], stepped[key][frontier], OP_TOL)
        assert not np.allclose(one[key][frontier], was[key][frontier])


def test_both_formulations_of_the_selected_read_agree_and_the_plan_is_by_shape():
    """The gather by selection and the masked view over the table's
    width are one read; which a step takes comes from its shapes."""
    op = graph_op("attn_1", decode_max_seq=64, kv_page_size=4,
                  kv_num_blocks=65)
    assert op.reads_selection() and op.pool_width() == 128  # 24 -> a tile
    # the toy's table of 64: the decode step gathers, a chunk of 4 takes
    # the view; under a table long enough every step gathers
    assert op.selected_plan(1, 64) == "gather"
    assert op.selected_plan(4, 64) == "view"
    assert op.selected_plan(4, 4096) == "gather"
    p = op.params
    assert [p.index_topk, op.selected_plan(1, 12800),
            op.selected_plan(2, 12800)] == [8, "gather", "gather"]
    rng = np.random.default_rng(1)
    b, s, h = 2, 3, p.num_heads
    pool = jnp.asarray(rng.standard_normal((65, 4, 128)), jnp.float32)
    btab = jnp.asarray(1 + rng.permutation(64)[:32].reshape(2, 16), jnp.int32)
    picks = np.full((b, s, p.index_topk), -1, np.int32)
    for i in range(b):
        for j in range(s):
            m = int(rng.integers(1, p.index_topk + 1))
            picks[i, j, :m] = rng.permutation(64)[:m]
    args = (jnp.asarray(rng.standard_normal((b, s, h, p.qk_nope_head_dim)),
                        jnp.float32),
            jnp.asarray(rng.standard_normal((b, s, h, p.qk_rope_head_dim)),
                        jnp.float32),
            jnp.asarray(rng.standard_normal(
                (p.kv_lora_rank, h, p.qk_nope_head_dim + p.v_head_dim)),
                jnp.float32), pool, btab, jnp.asarray(picks))
    with jax.default_matmul_precision("highest"):
        close(op._attend_masked_view(*args), op._attend_selected(*args),
              OP_TOL)
