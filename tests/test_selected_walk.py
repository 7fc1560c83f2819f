"""The selected read in place (`ops/pallas/selected_attention.py`, PR 58)
under the Pallas interpreter, against BOTH XLA formulations of the same
read (`MLAttention._attend_masked_view`, `_attend_selected`), and
`selected_plan`'s table.

One launch holds every row that matters: incoming lengths of 0, 1, 15,
16, 17, a tile less one, a tile, a tile and one, a chunk whose last
token opens a page, and the table's width; picks are random sets of
`min(t + 1, index_topk)` causal keys in random order behind `-1`s, so a
row under `index_topk` picks the causal positions themselves.  float32
operands: the walk's online softmax sums in another order than the
oracles' one softmax, so they agree to rounding (1e-5 of the largest
magnitude, `test_glm_dsa.py`'s tolerance for one op).
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from _family import close, config

from benchmarks.families import glm_dsa as fam
from flexflow_tpu import FFConfig, FFModel
from flexflow_tpu.models.glm_dsa import build_glm_dsa
from flexflow_tpu.ops.pallas import selected_attention as sa

CFG = dict(config("toy-glm52.json"), index_topk=24)
PAGE, WIDTH = 16, 10           # a table of 160 positions
N_KEYS = PAGE * WIDTH
OP_TOL = 1e-5


def graph_op(name, **cache):
    ff = FFModel(FFConfig(batch_size=1, num_devices=1))
    build_glm_dsa(ff, 1, 8, **fam.published(CFG), **cache)
    return next(op for op in ff.layers.topo_order() if op.name == name)


@pytest.fixture(scope="module")
def shared_op():
    return graph_op("attn_1", decode_max_seq=N_KEYS, kv_page_size=PAGE,
                    kv_num_blocks=1 + 12 * WIDTH)


def lengths(chunk, pages):
    """The rows' incoming lengths of the one launch."""
    tile = pages * PAGE
    return np.array([0, 1, 15, 16, 17, tile - 1, tile, tile + 1,
                     3 * PAGE - chunk + 1,  # the last token opens a page
                     N_KEYS - chunk], np.int32)


def case(op, chunk, pages, seed=0, most=None):
    """(q_nope, q_rope, wkv_b, pool, btab, slen, picks) for one launch:
    every row on blocks of its own, in random order; a query picks
    `most` keys at most (default: `index_topk`)."""
    p = op.params
    rng = np.random.default_rng(seed)
    slen = lengths(chunk, pages)
    b, h = len(slen), p.num_heads
    btab = 1 + rng.permutation(b * WIDTH).reshape(b, WIDTH).astype(np.int32)
    pool = rng.standard_normal((1 + 12 * WIDTH, PAGE, op.pool_width()))
    pool[..., p.latent_width:] = 0.0
    picks = np.full((b, chunk, p.index_topk), -1, np.int32)
    for i in range(b):
        for j in range(chunk):
            at = slen[i] + j
            m = min(at + 1, most or p.index_topk)
            picks[i, j, rng.permutation(p.index_topk)[:m]] = \
                rng.permutation(at + 1)[:m]
    f32 = lambda *shape: jnp.asarray(  # noqa: E731
        rng.standard_normal(shape), jnp.float32)
    return (f32(b, chunk, h, p.qk_nope_head_dim),
            f32(b, chunk, h, p.qk_rope_head_dim),
            f32(p.kv_lora_rank, h, p.qk_nope_head_dim + p.v_head_dim),
            jnp.asarray(pool, jnp.float32), jnp.asarray(btab),
            jnp.asarray(slen), jnp.asarray(picks))


def walk(op, args, pages, monkeypatch):
    monkeypatch.setattr(sa, "pages_per_tile", lambda page: pages)
    with jax.default_matmul_precision("highest"):
        return np.asarray(op._attend_walk(*args))


def oracles(op, args):
    q_nope, q_rope, wkv_b, pool, btab, _, picks = args
    with jax.default_matmul_precision("highest"):
        return [np.asarray(f(q_nope, q_rope, wkv_b, pool, btab, picks))
                for f in (op._attend_masked_view, op._attend_selected)]


@pytest.mark.parametrize("pages", [2, 4])
@pytest.mark.parametrize("chunk", [1, 16])
def test_walk_equals_both_formulations_in_one_launch(shared_op, chunk,
                                                     pages, monkeypatch):
    args = case(shared_op, chunk, pages)
    picks = np.asarray(args[-1])
    assert (picks == -1).any() and (picks[-1] >= 0).all()
    assert set(picks[0, 0][picks[0, 0] >= 0]) == {0}  # under index_topk
    got = walk(shared_op, args, pages, monkeypatch)
    view, gather = oracles(shared_op, args)
    close(got, view, OP_TOL)
    close(got, gather, OP_TOL)


@pytest.mark.parametrize("chunk", [1, 16])
def test_what_no_query_picks_reaches_no_context(shared_op, chunk,
                                                monkeypatch):
    """NaN in every pool row that no query of its batch row picks, live
    or past the row's length or in a page the table does not name, and
    in the spare lanes of none: the walk's contexts are the clean
    pool's to the bit (a masked pair's probability is exactly 0, and an
    unpicked key's value row is cleared before the product)."""
    args = case(shared_op, chunk, 2, seed=3, most=4)
    q_nope, q_rope, wkv_b, pool, btab, slen, picks = args
    want = walk(shared_op, args, 2, monkeypatch)
    spoiled = np.full(pool.shape, np.nan, np.float32)
    clean, table = np.asarray(pool), np.asarray(btab)
    unpicked_live = 0
    for i, row in enumerate(np.asarray(picks)):
        named = np.unique(row[row >= 0])
        spoiled[table[i, named // PAGE], named % PAGE] = \
            clean[table[i, named // PAGE], named % PAGE]
        unpicked_live += int(slen[i]) + chunk - len(named)
    assert unpicked_live > 100
    got = walk(shared_op, (q_nope, q_rope, wkv_b, jnp.asarray(spoiled),
                           btab, slen, picks), 2, monkeypatch)
    assert np.isfinite(got).all()
    np.testing.assert_array_equal(got, want)


@pytest.mark.parametrize("fold", [16, 32])
def test_rows_a_fold_do_not_change_the_read(shared_op, fold, monkeypatch):
    """A fold scores whole heads of the chunk's queries, `rows_per_fold`
    rows at a time: 1 and 2 of the toy's 4 heads equal all of them."""
    args = case(shared_op, 16, 4, seed=5)
    want = walk(shared_op, args, 4, monkeypatch)
    monkeypatch.setattr(sa, "ROWS_PER_FOLD", fold)
    np.testing.assert_allclose(walk(shared_op, args, 4, monkeypatch), want,
                               rtol=0, atol=1e-6)
    with pytest.raises(ValueError, match="whole heads"):
        sa.selected_latent_attention(
            jnp.zeros((1, 16, 4, 128)), args[3], args[4][:1], args[5][:1],
            jnp.zeros((1, 16, N_KEYS), bool), 1.0, 16, rows_per_fold=24)


@pytest.mark.parametrize("chunk", [1, 16])
def test_a_full_ops_picks_do_not_depend_on_the_read(chunk, monkeypatch):
    """A `full` op scores, picks and hands its picks on before any read:
    under the walk they are the masked view's bit for bit, the pools
    too, and the outputs agree to rounding."""
    from flexflow_tpu.ops.mla import MLAttention

    op = graph_op("attn_0", decode_max_seq=N_KEYS, kv_page_size=PAGE,
                  kv_num_blocks=1 + 3 * WIDTH)
    assert op.cache_entries() == ("latent_cache", "index_cache")
    w = fam.make_leaves(fam.ref.seed_key(11), fam.dims(CFG), "attn_full", 0)
    rng = np.random.default_rng(7)
    b = 3
    slen = jnp.asarray([0, 40, N_KEYS - chunk], jnp.int32)
    x = jnp.asarray(rng.standard_normal((b, chunk, op.params.embed_dim)),
                    jnp.float32)
    pos = slen[:, None] + jnp.arange(chunk, dtype=jnp.int32)
    state = [jnp.asarray(rng.standard_normal((1 + 3 * WIDTH, PAGE, width)),
                         jnp.float32).at[..., live:].set(0.0)
             for width, live in ((op.pool_width(), op.params.latent_width),
                                 (op.params.index_head_dim,) * 2)]
    state += [jnp.asarray(1 + np.arange(b * WIDTH).reshape(b, WIDTH),
                          jnp.int32), slen]
    names = [s.name for s in op.weight_specs[:op.num_trainable_weights()]]
    got = {}
    for plan in ("view", "walk"):
        monkeypatch.setattr(MLAttention, "selected_plan",
                            lambda self, s, n, plan=plan: plan)
        with jax.default_matmul_precision("highest"):
            got[plan] = [np.asarray(t) for t in op.forward(
                [x, pos], [w[n] for n in names] + state)]
    out, picks, pool, index_pool = got["walk"][:4]
    assert picks.shape == (b, chunk, op.params.index_topk)
    assert (picks[1] >= 0).all() and (picks[0, 0] == -1).sum() == 23
    np.testing.assert_array_equal(picks, got["view"][1])
    np.testing.assert_array_equal(pool, got["view"][2])
    np.testing.assert_array_equal(index_pool, got["view"][3])
    close(out, got["view"][0], OP_TOL)


@pytest.mark.parametrize("s,n,backend,want", [
    (1, 12800, "tpu", "walk"),      # cell 12's decode step
    (16, 12800, "tpu", "walk"),     # and its pass
    (8, 12800, "tpu", "walk"),
    (1, 32768, "tpu", "gather"),    # tables long enough gather again
    (16, 49152, "tpu", "walk"),
    (16, 65536, "tpu", "gather"),
    (4, 12800, "tpu", "view"),      # no whole sublane tile of queries
    (1, 12800, "cpu", "gather"),    # the CPU tier, as before the walk
    (16, 12800, "cpu", "view"),
    (16, 65536, "cpu", "gather"),
])
def test_selected_plan_by_its_table(s, n, backend, want, monkeypatch):
    """From the step's shapes and the backend alone, at cell 12's
    geometry (2,048 picks, pages of 16 rows 640 wide)."""
    real = dict(config("glm52-ep16-serve.json"))
    ff = FFModel(FFConfig(batch_size=1, num_devices=1))
    build_glm_dsa(ff, 1, 8, **fam.published(real), decode_max_seq=12800,
                  kv_page_size=16, kv_num_blocks=801)
    op = next(o for o in ff.layers.topo_order() if o.name == "attn_1")
    assert (op.params.index_topk, op.pool_width()) == (2048, 640)
    monkeypatch.setattr(jax, "default_backend", lambda: backend)
    assert op.selected_plan(s, n) == want


def test_the_walk_needs_whole_tiles():
    assert sa.walk_fits(16, 16, 640) and sa.walk_fits(1, 16, 640)
    assert not sa.walk_fits(16, 16, 576)   # a row of no whole lane tiles
    assert not sa.walk_fits(16, 4, 640)    # a page under a sublane tile
    assert not sa.walk_fits(4, 16, 640)
    assert sa.pages_per_tile(16) == 32
