"""Fused Pallas PagedAttention (ops/pallas/paged_attention.py) vs the
gather oracle (ops/attention.py `_attend_decode_paged`, the reference
formulation): interpret-mode parity across page sizes, partial tail
blocks, scratch rows, CoW-shared prefix blocks and the seq-C chunk
twin; the build-time ConfigError gate for pallas-less runtimes; the
jaxpr assertion that the kernel-path decode step materializes NO dense
[slots, decode_max_seq] K/V view; and scheduler-level greedy
token-identity between the gather and the kernel asked for by name on the
shared-prefix smoke workload (docs/SERVING.md "Fused paged
attention"); and, since PR 28, the kernel as the TPU's default: parity
and pool bytes at the benchmark cell's own shapes, the default resolved
by backend, one lowering a step program whatever the lengths, and the
read share on the dispatch spans."""
from types import SimpleNamespace

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from _family import trained_gpt

from flexflow_tpu.config import ConfigError, FFConfig
from flexflow_tpu.ops.pallas import paged_attention as pk

V, S, B = 32, 16, 4


# -- kernel-level parity (interpret mode; no model compiles) ----------

def _gather_oracle(qh, k_pool, v_pool, btab, slen, scale):
    """The read math of ops/attention._attend_decode_paged, verbatim:
    dense per-row gather + per-position masked softmax."""
    b, s, h, _ = qh.shape
    page = k_pool.shape[1]
    n = btab.shape[1] * page
    key_pos = jnp.arange(n, dtype=jnp.int32)
    pos = slen.reshape(b).astype(jnp.int32)
    ctxs = []
    for j in range(s):
        pj = pos + j
        kv_k = jnp.take(k_pool, btab, axis=0).reshape(b, n, h, -1)
        kv_v = jnp.take(v_pool, btab, axis=0).reshape(b, n, h, -1)
        scores = jnp.einsum("bqhd,bkhd->bhqk", qh[:, j:j + 1],
                            kv_k.astype(qh.dtype)) * scale
        mask = key_pos[None, :] <= pj[:, None]
        scores = jnp.where(mask[:, None, None, :], scores,
                           jnp.finfo(scores.dtype).min)
        probs = jax.nn.softmax(scores, axis=-1)
        ctxs.append(jnp.einsum("bhqk,bkhd->bqhd", probs,
                               kv_v.astype(qh.dtype)))
    return ctxs[0] if s == 1 else jnp.concatenate(ctxs, axis=1)


def _random_case(rng, b, s, h, d, page, table_width, extra_blocks=0):
    """Pools + per-row tables with PARTIAL TAIL positions and one
    SCRATCH row (slot 0: seq_len 0, table all zeros — the idle-slot
    shape).  Every live row gets distinct non-contiguous blocks."""
    nb = 1 + (b * table_width) + extra_blocks
    k_pool = jnp.asarray(rng.randn(nb, page, h, d), jnp.float32)
    v_pool = jnp.asarray(rng.randn(nb, page, h, d), jnp.float32)
    qh = jnp.asarray(rng.randn(b, s, h, d), jnp.float32)
    perm = rng.permutation(np.arange(1, nb))[:b * table_width]
    btab = perm.reshape(b, table_width).astype(np.int32)
    btab[0] = 0  # scratch row
    # partial tails on purpose: positions NOT page-aligned, and the
    # chunk must fit inside the table for every row
    top = table_width * page - s
    slen = np.array([0] + [1 + rng.randint(top - 1)
                           for _ in range(b - 1)], np.int32)
    return qh, k_pool, v_pool, jnp.asarray(btab), jnp.asarray(slen)


@pytest.mark.parametrize("page", [4, 8])
@pytest.mark.parametrize("chunk", [1, 4])
def test_kernel_parity_vs_gather_oracle(page, chunk):
    """fp32-tolerance parity of the fused kernel against the gather
    read math — page sizes {4, 8}, partial tail blocks, a scratch row,
    both the seq-1 decode twin and the seq-C chunk twin."""
    rng = np.random.RandomState(7 * page + chunk)
    qh, kp, vp, btab, slen = _random_case(
        rng, b=5, s=chunk, h=3, d=16, page=page, table_width=4)
    scale = 1.0 / np.sqrt(16)
    got = pk.paged_attention(qh, kp, vp, btab, slen, scale,
                             interpret=True)
    want = _gather_oracle(qh, kp, vp, btab, slen, scale)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-6, atol=2e-6)


def test_kernel_parity_cow_shared_prefix_blocks():
    """Two rows whose tables map the SAME physical blocks (the prefix
    cache's CoW sharing shape) read identically to the oracle — the
    kernel must stream a shared page once per row without caring who
    else references it."""
    rng = np.random.RandomState(11)
    qh, kp, vp, btab, slen = _random_case(
        rng, b=4, s=1, h=2, d=8, page=4, table_width=4)
    btab = np.asarray(btab).copy()
    btab[2, :2] = btab[1, :2]  # rows 1 and 2 share their first 2 blocks
    btab[3, 0] = btab[1, 0]    # row 3 shares one
    slen = jnp.asarray([0, 9, 10, 5], jnp.int32)
    btab = jnp.asarray(btab)
    got = pk.paged_attention(qh, kp, vp, btab, slen, 0.25,
                             interpret=True)
    want = _gather_oracle(qh, kp, vp, btab, slen, 0.25)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-6, atol=2e-6)


def test_kernel_decode_and_chunk_twins_agree():
    """The seq-C chunk twin over a freshly scattered chunk equals C
    seq-1 decode calls at successive positions (the host-side twin
    relationship build_paged_chunk_step documents)."""
    rng = np.random.RandomState(3)
    C, page, tw = 4, 4, 4
    qh, kp, vp, btab, slen = _random_case(
        rng, b=3, s=C, h=2, d=8, page=page, table_width=tw)
    scale = 0.3
    chunk_out = np.asarray(pk.paged_chunk_attention(
        qh, kp, vp, btab, slen, scale, interpret=True))
    for j in range(C):
        one = np.asarray(pk.paged_decode_attention(
            qh[:, j:j + 1], kp, vp, btab, slen + j, scale,
            interpret=True))
        np.testing.assert_allclose(chunk_out[:, j:j + 1], one,
                                   rtol=2e-6, atol=2e-6)


# -- grouped query heads: G query heads read one key/value head's pages ---------

class _GroupedAttn:
    """`MultiHeadAttention`'s one-view paged step (`paged_read_once`)
    without a graph around it: the gather oracle of a grouped layer and
    the same writes under the Pallas read."""
    from flexflow_tpu.ops.attention import MultiHeadAttention as _M

    _attend_decode_paged_once = _M._attend_decode_paged_once
    _paged_kernel_read = _M._paged_kernel_read

    def __init__(self, kernel, heads, kv_heads, page, head_major=False):
        self.params = SimpleNamespace(num_heads=heads, kv_heads=kv_heads,
                                      group=heads // kv_heads,
                                      kv_head_major=head_major)
        self._kv_page_size, self._kv_kernel = page, kernel
        self.shard = SimpleNamespace(channel=1)


@pytest.mark.parametrize("head_major", [False, True],
                         ids=["page_major", "head_major"])
@pytest.mark.parametrize("chunk", [1, 5, 8])
@pytest.mark.parametrize("group", [2, 6])
def test_grouped_heads_match_the_one_view_gather(group, chunk, head_major):
    """`q [b, s, kv_heads * G, d]` against the pool `[nb, page,
    kv_heads, d]` (or head-major, `[nb, kv_heads, page, d]`, folded a
    key/value head at a time): query head g reads key/value head `g //
    G`.  The whole step (the chunk's writes, then the read) under the
    kernel equals `_attend_decode_paged_once`'s gather, decode and
    chunk, and leaves the same pools."""
    kv_heads, d, page, tw = 2, 16, 4, 5
    rng = np.random.RandomState(31 * group + chunk)
    _, kp, vp, btab, slen = _random_case(
        rng, b=4, s=chunk, h=kv_heads, d=d, page=page, table_width=tw)
    if head_major:
        kp, vp = (x.transpose(0, 2, 1, 3) for x in (kp, vp))
    qh = jnp.asarray(rng.randn(4, chunk, kv_heads * group, d), jnp.float32)
    kh, vh = (jnp.asarray(rng.randn(4, chunk, kv_heads, d), jnp.float32)
              for _ in range(2))
    scale = 1.0 / np.sqrt(d)
    got, gk, gv = _GroupedAttn(
        "pallas", kv_heads * group, kv_heads, page, head_major
    )._attend_decode_paged_once(qh, kh, vh, kp, vp, btab, slen, scale)
    want, wk, wv = _GroupedAttn(
        "gather", kv_heads * group, kv_heads, page, head_major
    )._attend_decode_paged_once(qh, kh, vh, kp, vp, btab, slen, scale)
    assert got.shape == (4, chunk, kv_heads * group, d)
    live = np.asarray(slen) > 0  # (row 0 is the scratch row)
    np.testing.assert_allclose(np.asarray(got)[live], np.asarray(want)[live],
                               rtol=2e-6, atol=2e-6)
    np.testing.assert_array_equal(np.asarray(gk), np.asarray(wk))
    np.testing.assert_array_equal(np.asarray(gv), np.asarray(wv))
    # the heads of a group differ (their queries do), the groups read
    # different pages' heads
    assert not np.allclose(np.asarray(got)[1, 0, 0], np.asarray(got)[1, 0, 1])


@pytest.fixture(scope="module")
def grouped_oracle():
    """`_gather_oracle` over a head-major pool under G query heads a
    key/value head, jitted: query head g reads key/value head g // G."""
    @jax.jit
    def oracle(qh, kp, vp, btab, slen, scale):
        group = qh.shape[2] // kp.shape[1]
        kp, vp = (jnp.repeat(x.transpose(0, 2, 1, 3), group, axis=2)
                  for x in (kp, vp))
        return _gather_oracle(qh, kp, vp, btab, slen, scale)
    return oracle


def _walk_case(group, chunk, tile, page=8, tw=9, kv_heads=8, d=16):
    """A head-major launch whose rows hold, in live pages, 1 on scratch
    (parked: zero table, position 0), 1, N - 1, N, N + 1 and the whole
    table for a tile of N = `tile` pages, each ending on its page's last
    slot; and two rows whose chunk's LAST token alone stands on a new
    page (the first of a tile, and the one after)."""
    rng = np.random.RandomState(97 * group + 7 * chunk + tile)
    live = [1, 1, max(tile - 1, 1), tile, tile + 1, tw]
    slen = [0] + [n * page - chunk for n in live[1:]]
    slen += [n * page - chunk + 1 for n in (tile, tile + 1)]
    slen = np.maximum(np.asarray(slen, np.int32), 0)
    b = len(slen)
    nb = 1 + b * tw
    kp, vp = (jnp.asarray(rng.randn(nb, kv_heads, page, d), jnp.float32)
              for _ in "kv")
    qh = jnp.asarray(rng.randn(b, chunk, kv_heads * group, d), jnp.float32)
    btab = rng.permutation(np.arange(1, nb)).reshape(b, tw).astype(np.int32)
    btab[0] = 0
    want = live + [tile + 1, tile + 2]
    np.testing.assert_array_equal(pk._live_block_count(
        slen, chunk, page, tw), want)
    return qh, kp, vp, jnp.asarray(btab), jnp.asarray(slen)


@pytest.mark.parametrize("tile", [2, 4])
@pytest.mark.parametrize("chunk", [1, 5])
@pytest.mark.parametrize("group", [6, 8])
def test_head_major_walk_follows_each_rows_length(group, chunk, tile,
                                                  grouped_oracle):
    """The head-major launch walks a row's live pages N at a time
    inside the kernel, a trip count a row: rows of every length around
    a tile's edge in ONE launch read what the gather reads, float32 to
    2e-6, with 8 key/value heads under 48 and 64 query heads' ratios
    and N forced to 2 and 4 (a column past a row's live pages repeats
    its last live block and is masked by position)."""
    qh, kp, vp, btab, slen = _walk_case(group, chunk, tile)
    scale = 0.25
    got = pk.paged_attention(qh, kp, vp, btab, slen, scale, interpret=True,
                             pages_per_step=tile, head_major=True)
    want = grouped_oracle(qh, kp, vp, btab, slen, scale)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-6, atol=2e-6)


def test_head_major_walk_reads_no_page_past_a_rows_length():
    """What stands in the pool past a row's live pages, NaN included,
    never reaches its context: the walk's trip count is the row's
    length, and its last tile's spare columns repeat a live block."""
    qh, kp, vp, btab, slen = _walk_case(6, 5, 4)
    want = pk.paged_attention(qh, kp, vp, btab, slen, 0.25, interpret=True,
                              pages_per_step=4, head_major=True)
    live = np.asarray(pk._live_block_count(np.asarray(slen), 5, 8, 9))
    dead = np.concatenate([np.asarray(btab)[i, n:]
                           for i, n in enumerate(live) if i])
    spoiled = [x.at[dead].set(jnp.nan) for x in (kp, vp)]
    got = pk.paged_attention(qh, *spoiled, btab, slen, 0.25, interpret=True,
                             pages_per_step=4, head_major=True)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    assert np.isfinite(np.asarray(got)).all()


@pytest.mark.parametrize("page_bytes,want", [
    (2 * 16 * 8 * 128 * 2, 32),  # laguna-xs2-ep8-serve's pool: 512 keys
    (2 * 16 * 8 * 128 * 4, 16),  # the same pool in float32
    (2 * 8 * 8 * 16 * 4, 256),   # the tests': more than their tables hold
    (8 << 20, 1)])
def test_pages_a_tile_follow_the_page(page_bytes, want):
    """N is a function of the launch's shapes: the K and V tiles, two
    of each in flight, within 4 MiB of VMEM."""
    assert pk.pages_per_tile(page_bytes) == want


def test_query_heads_must_be_a_multiple_of_the_pools():
    rng = np.random.RandomState(2)
    _, kp, vp, btab, slen = _random_case(rng, b=2, s=1, h=2, d=8, page=4,
                                         table_width=2)
    with pytest.raises(ValueError, match="no multiple"):
        pk.paged_attention(jnp.zeros((2, 1, 3, 8)), kp, vp, btab, slen, 1.0,
                           interpret=True)


# -- the fold at the widths of both cells that run it (16 heads, page
# 16, a bf16 pool: gpt2-medium-serve d = 64 under a table 64 wide,
# ouro-2.6b-serve d = 128 under one 20 wide), fewer rows than either ----

WIDTHS = [(64, 64), (128, 20)]


def _wide_case(d, tw, chunk, q_dtype=jnp.bfloat16):
    """`_random_case` at a cell's widths over a bf16 pool: a scratch
    row, partial tails, and row 1 placed so that its LAST page is live
    for the chunk's last query and wholly in the future of its first."""
    rng = np.random.RandomState(d + tw + chunk)
    qh, kp, vp, btab, slen = _random_case(
        rng, b=5, s=chunk, h=16, d=d, page=16, table_width=tw)
    # queries a bf16 holds, whatever carries them: the oracle then sees
    # the kernel's own operands
    qh = qh.astype(jnp.bfloat16).astype(q_dtype)
    kp, vp = kp.astype(jnp.bfloat16), vp.astype(jnp.bfloat16)
    slen = np.asarray(slen).copy()
    slen[1] = 3 * 16 - min(3, chunk - 1)
    return qh, kp, vp, btab, jnp.asarray(slen)


def _f32_oracle(qh, kp, vp, btab, slen, scale):
    return np.asarray(_gather_oracle(
        *(x.astype(jnp.float32) for x in (qh, kp, vp)), btab, slen, scale))


@pytest.mark.parametrize("q_dtype,tol", [(jnp.bfloat16, 1e-2),
                                         (jnp.float32, 2e-6)])
@pytest.mark.parametrize("chunk", [1, 8])
@pytest.mark.parametrize("d,tw", WIDTHS)
def test_fold_at_cell_widths_matches_f32_oracle(d, tw, chunk, q_dtype, tol):
    """The merged-rows fold (one product for a page's scores of all 16
    heads, one for its context) against the gather math in float32 at
    both cells' widths, one query a row and a chunk of 8.  With bf16
    queries the context is rounded to bf16 on the way out (1e-2); with
    the same queries carried in float32 it is not, and 2e-6 shows that
    the probabilities reach the second product unrounded: rounded to
    bf16 they would miss by ~4e-3."""
    qh, kp, vp, btab, slen = _wide_case(d, tw, chunk, q_dtype)
    scale = 1.0 / np.sqrt(d)
    got = pk.paged_attention(qh, kp, vp, btab, slen, scale, interpret=True)
    assert got.dtype == q_dtype
    want = _f32_oracle(qh, kp, vp, btab, slen, scale)
    np.testing.assert_allclose(np.asarray(got, np.float32), want,
                               rtol=tol, atol=tol)


@pytest.mark.parametrize("chunk", [1, 8])
@pytest.mark.parametrize("d,tw", WIDTHS)
def test_fold_keeps_heads_apart(d, tw, chunk):
    """A page's scores come out of ONE product over all its heads, so
    an entry pairs every query head with every key head; only matching
    heads may survive the mask.  Whatever the OTHER heads' keys hold
    (NaN, +-1e30, noise) and whatever finite values ride beside them
    (+-1e30 included), a head's context stays equal to the bit: a
    foreign key's score is selected away before the softmax, a foreign
    value is multiplied by an exact zero.  (A NaN or infinite VALUE is
    not finite times zero; the pool holds only what the model's own
    projections wrote, and a row with one is lost in the output
    projection over all heads whichever way it is read.)"""
    qh, kp, vp, btab, slen = _wide_case(d, tw, chunk)
    scale = 1.0 / np.sqrt(d)
    want = np.asarray(pk.paged_attention(
        qh, kp, vp, btab, slen, scale, interpret=True), np.float32)
    rng = np.random.RandomState(chunk)
    head = 5
    others = np.arange(16) != head

    def spoil(pool, fills):
        pool = np.asarray(pool, np.float32).copy()
        pick = rng.randint(len(fills), size=pool[:, :, others].shape)
        pool[:, :, others] = np.asarray(fills, np.float32)[pick]
        return jnp.asarray(pool, jnp.bfloat16)

    got = np.asarray(pk.paged_attention(
        qh, spoil(kp, [np.nan, 1e30, -1e30, 7.0]),
        spoil(vp, [1e30, -1e30, -3.0, 0.5]), btab, slen, scale,
        interpret=True), np.float32)
    np.testing.assert_array_equal(got[:, :, head], want[:, :, head])
    assert np.isfinite(want).all()


# -- the serving cell's own shapes (gpt2-medium-serve: 16 slots, table
# width 64, page 16, 16 heads x 64, bf16 pool of 513 blocks) ----------

CELL = dict(b=16, tw=64, page=16, h=16, d=64, nb=513)


def _cell_case(s):
    """Queries, this step's k/v, pools, tables and positions at the
    cell's shapes.  Rows: 0-3 idle, parked on scratch (zero table,
    position 0); 4 a live row of length 0; 5 a partial tail page; 6 a
    page boundary; 7 the last position a step of `s` can start from
    without pads (max_seq - s); 8 (s > 1 only) a chunk whose pads run
    past the table; the rest mixed lengths."""
    c = CELL
    rng = np.random.RandomState(28 + s)
    n = c["tw"] * c["page"]

    def rand(*shape):
        return jnp.asarray(rng.randn(*shape), jnp.bfloat16)

    kp, vp = (rand(c["nb"], c["page"], c["h"], c["d"]) for _ in "kv")
    qh, kh, vh = (rand(c["b"], s, c["h"], c["d"]) for _ in "qkv")
    slen = np.zeros(c["b"], np.int32)
    slen[5:8] = 37, 16 * 9, n - s
    slen[8] = n - 1 if s == 1 else n - s + 3
    slen[9:] = rng.randint(1, n - s, c["b"] - 9)
    free = iter(rng.permutation(np.arange(1, c["nb"])))
    btab = np.zeros((c["b"], c["tw"]), np.int32)
    for i in range(4, c["b"]):
        # the pool holds half the dense footprint: give a row the
        # blocks its positions need, as the scheduler would
        need = min((slen[i] + s - 1) // c["page"] + 1, c["tw"])
        if i >= 9:
            need = min(need, 24)
            slen[i] = min(slen[i], need * c["page"] - s)
        btab[i, :need] = [next(free) for _ in range(need)]
    return qh, kh, vh, kp, vp, jnp.asarray(btab), jnp.asarray(slen)


class _Attn:
    """`MultiHeadAttention`'s paged step without a graph around it."""
    from flexflow_tpu.ops.attention import MultiHeadAttention as _M

    _attend_decode_paged = _M._attend_decode_paged
    _attend_decode_paged_kernel = _M._attend_decode_paged_kernel
    _paged_kernel_read = _M._paged_kernel_read

    def __init__(self, kernel):
        self.params = SimpleNamespace(num_heads=CELL["h"])
        self._kv_page_size, self._kv_kernel = CELL["page"], kernel
        self.shard = SimpleNamespace(channel=1)


@pytest.mark.parametrize("s", [1, 8])
def test_step_at_cell_shapes_matches_oracle_and_pool_bytes(s):
    """The whole paged step (scatter, then read) at the serving cell's
    shapes: the kernel's context equals the gather oracle's to bf16
    rounding on every row a scheduler would look at, and the pools
    come out byte-identical: the read side changed, the writes did
    not.  (With s = 8 row 8's pads run past the table: the kernel
    path routes them to scratch block 0, the oracle's are dropped, so
    block 0 is left out there.)"""
    qh, kh, vh, kp, vp, btab, slen = _cell_case(s)
    scale = 1.0 / np.sqrt(CELL["d"])
    got, gk, gv = _Attn("pallas")._attend_decode_paged(
        qh, kh, vh, kp, vp, btab, slen, scale)
    # the oracle's softmax runs in the operands' bf16; judge both
    # against the same math in float32
    f32 = [x.astype(jnp.float32) for x in (qh, kh, vh, kp, vp)]
    want, wk, wv = _Attn("gather")._attend_decode_paged(
        *f32, btab, slen, scale)
    n = CELL["tw"] * CELL["page"]
    real = np.asarray(slen)[:, None] + np.arange(s)[None, :] < n
    np.testing.assert_allclose(
        np.asarray(got, np.float32)[real], np.asarray(want)[real],
        rtol=1e-2, atol=1e-2)
    first = 1 if s > 1 else 0
    for a, b in ((gk, wk), (gv, wv)):
        np.testing.assert_array_equal(
            np.asarray(a, np.float32)[first:],
            np.asarray(b.astype(jnp.bfloat16), np.float32)[first:])
    # the row of length 0 attends its own first token only
    np.testing.assert_allclose(
        np.asarray(got, np.float32)[4, 0],
        np.asarray(vh, np.float32)[4, 0], rtol=1e-2, atol=1e-2)


@pytest.mark.parametrize("pages", [1, 3, 8, 64])
def test_pages_per_step_is_not_semantic(pages):
    """How many pages a grid program folds only shapes the grid: any
    value (dividing the table width or not) reads the same context."""
    rng = np.random.RandomState(5)
    qh, kp, vp, btab, slen = _random_case(
        rng, b=4, s=2, h=2, d=8, page=4, table_width=7)
    want = _gather_oracle(qh, kp, vp, btab, slen, 0.3)
    got = pk.paged_attention(qh, kp, vp, btab, slen, 0.3,
                             interpret=True, pages_per_step=pages)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-6, atol=2e-6)


@pytest.mark.parametrize("tw,page_bytes,chunk,want", [
    (20, 2 * 16 * 16 * 128 * 2, 8, 20),  # ouro-2.6b-serve's prefill program
    (20, 2 * 16 * 16 * 128 * 2, 1, 8),   # its decode step: stepped
    (64, 2 * 16 * 16 * 64 * 2, 8, 8),    # a wide table (gpt2-medium-serve's)
    (24, 2 * 16 * 16 * 128 * 2, 4, 24),  # the widest taken whole
    (25, 2 * 16 * 16 * 128 * 2, 4, 8),
    (20, 2 * 16 * 16 * 128 * 4, 8, 8),   # float32 pages: over the VMEM bound
    (4, 2 * 4 * 2 * 8 * 4, 4, 4), (4, 2 * 4 * 2 * 8 * 4, 1, 4)])
def test_pages_a_program_follow_the_launch(tw, page_bytes, chunk, want):
    """The grid's choice is a function of the launch's shapes: a narrow
    table whose pages fit in VMEM is one program a row under a chunk of
    queries."""
    assert pk.pages_per_program(tw, page_bytes, chunk) == want


def test_blocks_read_scales_with_live_tokens():
    """The host telemetry twin of the kernel's traffic discipline:
    per-step blocks follow live tokens, not the table width — the
    bench leg's 'KV bytes read' signal."""
    page, tw = 4, 8
    seq_lens = np.array([0, 5, 12, 0])
    live = np.array([False, True, True, True])
    # idle rows cost 0 (their scratch fetch is an elided repeat); pos 5
    # -> 2 blocks; pos 12 -> 4 blocks; live pos 0 -> 1 block
    assert pk.blocks_read(seq_lens, live, 1, page, tw) == 0 + 2 + 4 + 1
    # dense equivalent is ALWAYS slots * table width
    assert len(seq_lens) * tw == 32
    # widening the table does not change what live rows read
    assert pk.blocks_read(seq_lens, live, 1, page, 64) == 7
    # a chunk reaches chunk-1 positions further
    assert pk.blocks_read(np.array([3]), np.array([True]), 4, page, tw) \
        == 2
    # ...but never past the table
    assert pk.blocks_read(np.array([30]), np.array([True]), 4, page, tw) \
        == tw
    # a scanned program is so many seq-1 reads a row: the same sum
    assert pk.scan_blocks_read(seq_lens, live, page, tw) == 7
    assert pk.scan_blocks_read(seq_lens, [0, 3, 2, 0], page, tw) == sum(
        pk.blocks_read(seq_lens + j, np.array([0, 3, 2, 0]) > j, 1,
                       page, tw) for j in range(3))
    assert pk.scan_blocks_read(seq_lens, np.zeros(4, int), page, tw) == 0


# -- the one place that picks the read ---------------------------------

GPT_CARRIES = frozenset({"paged", "pallas_read"})


def pick(asked="auto", *, backend="cpu", have_kernel=True,
         carries=GPT_CARRIES):
    from flexflow_tpu.serving.scheduler import pick_paged_read

    return pick_paged_read(asked, backend=backend, have_kernel=have_kernel,
                           carries=carries, family="fam")


def test_selecting_kernel_without_pallas_is_config_error():
    """A pallas-less jax refuses the kernel asked for by name at engine
    BUILD time with a ConfigError naming the fix, never a deep
    ImportError mid-compile; nothing it was not asked for needs it."""
    assert pick("gather") == "gather"
    assert pick("pallas") == "pallas"  # asked by name: interpreted here
    with pytest.raises(ConfigError, match="needs jax.experimental.pallas"):
        pick("pallas", have_kernel=False)
    assert pick("gather", have_kernel=False) == "gather"
    assert pick("auto", backend="tpu", have_kernel=False) == "gather"


def test_unknown_formulation_given_to_the_engine_is_config_error():
    """No flag and no config field carry the choice any more: the one
    value a caller can give is the engine's `paged_kernel=`, and an
    unknown one is refused before any graph is built."""
    from flexflow_tpu.serving.scheduler import PagedKVDecodeModel

    assert not hasattr(FFConfig.from_args([]), "paged_kernel")
    with pytest.raises(ConfigError, match="paged_kernel must be one of"):
        pick("fused")
    ff = _untrained_gpt()
    with pytest.raises(ConfigError, match="paged_kernel must be one of"):
        PagedKVDecodeModel(ff, batch_slots=2, page_size=4,
                           paged_kernel="fused")


@pytest.mark.parametrize("backend,want", [("cpu", "gather"),
                                          ("gpu", "gather"),
                                          ("tpu", "pallas")])
def test_default_formulation_follows_the_backend(backend, want):
    """`auto` is resolved from what the code can observe, the backend
    and the family: the in-place read where Mosaic compiles it and the
    attention op has it, the gather where the kernel would only be
    interpreted.  A value asked for by name is honoured on every
    backend or refused, never replaced."""
    assert pick("auto", backend=backend) == want
    for explicit in ("gather", "pallas"):
        assert pick(explicit, backend=backend) == explicit
    # a family without the kernel: auto never picks it, by name refused
    latent = frozenset({"paged", "prefill_pass"})
    assert pick("auto", backend=backend, carries=latent) == "gather"
    with pytest.raises(ConfigError, match="fam does not carry pallas_read"):
        pick("pallas", backend=backend, carries=latent)


def _untrained_gpt():
    from flexflow_tpu import FFModel
    from flexflow_tpu.models.transformer import build_gpt

    ff = FFModel(FFConfig(batch_size=2, num_devices=1))
    build_gpt(ff, batch_size=2, seq_length=8, hidden_size=16,
              num_layers=1, num_heads=2, intermediate_size=32,
              vocab_size=16)
    return ff


def test_dense_cache_rejects_kernel_selection():
    """kv_kernel='pallas' without a paged pool has no block table to
    stream through — refused loudly at make_decoder time."""
    from flexflow_tpu.decoding import make_decoder

    with pytest.raises(ValueError, match="kv_page_size"):
        make_decoder(_untrained_gpt(), kv_kernel="pallas")
    # the twin takes a formulation already decided, as the ops do
    with pytest.raises(ValueError, match="'gather' or 'pallas'"):
        make_decoder(_untrained_gpt(), kv_page_size=4, kv_kernel="auto")


# -- model-level: the compiled decode step ----------------------------

@pytest.fixture(scope="module")
def trained(devices8):
    return trained_gpt(devices8, B, S, V, steps=30)


def _collect_avals(jaxpr, acc):
    """Every intermediate aval in `jaxpr`, recursing into sub-jaxprs
    (pjit bodies, scan bodies, the pallas kernel jaxpr, ...)."""
    from jax.extend.core import ClosedJaxpr, Jaxpr

    def subs(val):
        if isinstance(val, ClosedJaxpr):
            yield val.jaxpr
        elif isinstance(val, Jaxpr):
            yield val
        elif isinstance(val, (list, tuple)):
            for v in val:
                yield from subs(v)

    for eqn in jaxpr.eqns:
        for v in eqn.outvars:
            acc.append(v.aval)
        for val in eqn.params.values():
            for sub in subs(val):
                _collect_avals(sub, acc)
    return acc


def _decode_step_avals(ff, devices8, kv_kernel):
    from flexflow_tpu.decoding import (build_paged_decode_step,
                                       make_decoder)

    page = 4
    nb = 1 + B * (S // page)
    paged = make_decoder(ff, devices=devices8[:1], kv_page_size=page,
                             kv_num_blocks=nb, kv_kernel=kv_kernel)
    step = build_paged_decode_step(paged)
    btab = np.arange(1, nb, dtype=np.int32).reshape(B, S // page)
    args = (paged._weights, paged._state, jnp.zeros(B, jnp.int32),
            jnp.zeros(B, jnp.int32), jnp.asarray(btab))
    jaxpr = jax.make_jaxpr(lambda *a: step(*a))(*args)
    return _collect_avals(jaxpr.jaxpr, []), paged, step, btab


def test_jaxpr_kernel_step_has_no_dense_gather(trained, devices8):
    """THE traffic assertion: the kernel-path decode step's jaxpr
    contains NO [slots, decode_max_seq, heads, head_dim] intermediate
    — the dense K/V view the gather oracle materializes every step is
    structurally absent, not just optimized away."""
    ff, _ = trained
    dense_view = (B, S, 4, 8)  # [slots, decode_max_seq, heads, head_dim]

    gather_avals, _, _, _ = _decode_step_avals(ff, devices8, "gather")
    assert any(getattr(a, "shape", None) == dense_view
               for a in gather_avals), \
        "oracle sanity: the gather formulation must materialize the view"

    kernel_avals, _, _, _ = _decode_step_avals(ff, devices8, "pallas")
    offenders = [a for a in kernel_avals
                 if getattr(a, "shape", None) == dense_view]
    assert not offenders, (
        f"kernel decode step materializes dense K/V views: {offenders}")


def test_kernel_decode_step_matches_gather_through_model(trained,
                                                        devices8):
    """End-to-end fp32 parity of the compiled kernel-path decode step
    against the gather oracle, and identical greedy argmax over a full
    sequence (the property the scheduler-level identity test rides)."""
    ff, ids = trained
    _, g_paged, g_step, btab = _decode_step_avals(ff, devices8, "gather")
    _, k_paged, k_step, _ = _decode_step_avals(ff, devices8, "pallas")
    g_state, k_state = g_paged._state, k_paged._state
    for t in range(S - 1):
        toks = jnp.asarray(ids[:, t])
        slens = jnp.asarray(np.full(B, t, np.int32))
        bt = jnp.asarray(btab)
        g_logits, g_state = g_step(g_paged._weights, g_state, toks,
                                   slens, bt)
        k_logits, k_state = k_step(k_paged._weights, k_state, toks,
                                   slens, bt)
        g, k = np.asarray(g_logits), np.asarray(k_logits)
        np.testing.assert_allclose(k, g, rtol=2e-5, atol=2e-5)
        np.testing.assert_array_equal(k.argmax(-1), g.argmax(-1))
    # the kernel only replaces the READ side: the first attention
    # layer's pool bytes (whose k/v inputs are pure embeddings,
    # identical between formulations) must match BIT FOR BIT — deeper
    # layers legitimately drift at fp tolerance, because their k/v
    # inputs ride the previous layer's attention output
    for key in ("k_cache", "v_cache"):
        np.testing.assert_array_equal(
            np.asarray(g_state["attn_0"][key]),
            np.asarray(k_state["attn_0"][key]),
            err_msg=f"attn_0.{key} write bytes diverged — the kernel "
                    "path must scatter exactly like the oracle")
        np.testing.assert_allclose(
            np.asarray(k_state["attn_1"][key]),
            np.asarray(g_state["attn_1"][key]), rtol=2e-4, atol=2e-6)


def test_kernel_chunk_twin_matches_gather_chunk_twin(trained, devices8):
    """The seq-C chunk twin under the kernel (one fused dispatch per
    layer) matches the gather chunk twin to fp tolerance, and a chunk
    whose trailing PAD positions run past the position table never
    corrupts a real block — the kernel scatter carries the same
    scratch-routing clamp build_paged_prefill_step pins."""
    from flexflow_tpu.decoding import (build_paged_chunk_step,
                                       make_decoder)

    ff, ids = trained
    page, C = 4, 4
    max_blocks = S // page
    nb = 1 + B * max_blocks
    btab = np.arange(1, nb, dtype=np.int32).reshape(B, max_blocks)

    def twin(kv_kernel):
        m = make_decoder(ff, devices=devices8[:1], kv_page_size=page,
                             kv_num_blocks=nb, step_tokens=C,
                             kv_kernel=kv_kernel)
        return m, build_paged_chunk_step(m)

    g_twin, g_step = twin("gather")
    k_twin, k_step = twin("pallas")
    g_state, k_state = g_twin._state, k_twin._state
    for start in (0, C):  # two full chunks: positions 0..7
        toks = jnp.asarray(ids[:, start:start + C])
        pos = jnp.asarray(np.full(B, start, np.int32))
        bt = jnp.asarray(btab)
        g_logits, g_state = g_step(g_twin._weights, g_state, toks, pos, bt)
        k_logits, k_state = k_step(k_twin._weights, k_state, toks, pos, bt)
        np.testing.assert_allclose(np.asarray(k_logits),
                                   np.asarray(g_logits),
                                   rtol=2e-4, atol=2e-5)
    # pad overflow: a chunk at S-2 puts positions S, S+1 past the
    # table — the kernel path must not let those writes clamp onto the
    # row's last real block (key slot S-1 stays byte-stable)
    before = {key: np.asarray(k_state["attn_0"][key]).copy()
              for key in ("k_cache", "v_cache")}
    toks = jnp.asarray(ids[:, :C])
    pos = jnp.asarray(np.full(B, S - 2, np.int32))
    _, k_state = k_step(k_twin._weights, k_state, toks, pos,
                        jnp.asarray(btab))
    for key in ("k_cache", "v_cache"):
        after = np.asarray(k_state["attn_0"][key])
        for i in range(B):
            for t in range(S - 2):  # every position before the chunk
                blk, off = btab[i, t // page], t % page
                np.testing.assert_array_equal(
                    after[blk, off], before[key][blk, off],
                    err_msg=f"attn_0.{key} row {i} position {t} "
                            "corrupted by a pad write")


# -- scheduler-level: the serving smoke workload ----------------------

def test_scheduler_greedy_token_identical_gather_vs_kernel(trained,
                                                           devices8):
    """Acceptance: greedy completions on the shared-prefix smoke
    workload are token-identical under the kernel asked for by name vs the
    gather oracle (prefix cache + chunked prefill ON in both), and the
    kernel's per-step KV reads actually undercut the dense-gather
    equivalent."""
    from flexflow_tpu.serving import ContinuousScheduler

    ff, _ = trained

    def run(paged_kernel):
        sched = ContinuousScheduler.from_trained(
            ff, batch_slots=B, page_size=4, devices=devices8[:1],
            prefix_cache=True, prefill_chunk=4,
            paged_kernel=paged_kernel, check_invariants=True)
        try:
            rng = np.random.RandomState(9)
            prefix = rng.randint(0, V, 8).tolist()  # 2 full pages
            prompts = [prefix]
            prompts += [prefix
                        + rng.randint(0, V, rng.randint(1, 5)).tolist()
                        for _ in range(6)]
            prompts.append(prefix)  # full-prompt COW rehit
            mnts = [int(rng.randint(2, 7)) for _ in prompts]
            handles = [sched.generate_async(p, m)
                       for p, m in zip(prompts, mnts)]
            got = [h.wait(120.0) for h in handles]
            sched.pool.check_invariants()
            return got, sched.stats()
        finally:
            sched.close()

    want, g_stats = run("gather")
    got, k_stats = run("pallas")
    assert got == want
    assert g_stats["paged_kernel"]["formulation"] == "gather"
    assert g_stats["paged_kernel"]["blocks_read"] == 0
    kk = k_stats["paged_kernel"]
    assert kk["formulation"] == "pallas"
    # reads happened, and they undercut the dense-gather equivalent
    assert 0 < kk["blocks_read"] < kk["dense_blocks_equiv"]
    assert kk["bytes_read"] > 0
    assert kk["dense_bytes_avoided"] > 0


@pytest.mark.parametrize("paged_kernel", ["auto", "pallas"])
def test_engine_lowers_each_step_program_once(trained, devices8,
                                              paged_kernel):
    """One `jit_step` and one `jit_prefill` an engine, whatever the mix
    of lengths: the block table and the positions are data, so after
    the first dispatch of each nothing is lowered again.  Every
    dispatch span says how many KV blocks its attention read against
    the dense view's, and the replica's stats carry the sums."""
    import jax.monitoring as mon

    from flexflow_tpu.obs.trace import spans
    from flexflow_tpu.serving import ServingFront

    ff, _ = trained
    lowered, watching = [], [True]

    def on_duration(event, secs, fun_name=None, **_):
        if watching[0] and event.endswith("jaxpr_to_mlir_module_duration"):
            lowered.append(fun_name)

    mon.register_event_duration_secs_listener(on_duration)
    from flexflow_tpu.serving.scheduler import PagedKVDecodeModel

    def factory(replica_id, survivors=None):
        return PagedKVDecodeModel(
            ff, batch_slots=B, page_size=4, prefill_chunk=4,
            devices=devices8[:1], paged_kernel=paged_kernel)

    t_start = spans()[-1].t_end if spans() else 0.0
    try:
        front = ServingFront(factory, 1)
        try:
            rng = np.random.RandomState(2)
            front.generate_async(rng.randint(0, V, 9).tolist(), 2).wait(120)
            warm = list(lowered)
            handles = [front.generate_async(
                rng.randint(0, V, n).tolist(), m)
                for n, m in ((1, 3), (6, 2), (11, 4), (3, 5), (13, 2),
                             (2, 1), (10, 3))]
            for h in handles:
                h.wait(120)
            stats = front.stats()["replicas"][0]["paged_kernel"]
        finally:
            front.close(30.0)
    finally:
        watching[0] = False
    # (the module names the profiler shows as jit_step, jit_prefill)
    assert warm.count("jit(step)") == 1
    assert warm.count("jit(prefill)") == 1
    assert lowered[len(warm):] == []
    want = "gather" if paged_kernel == "auto" else "pallas"  # CPU here
    assert stats["formulation"] == want
    dispatches = [r for r in spans() if r.t_start > t_start and r.name in
                  ("sched.decode.dispatch", "sched.prefill.dispatch")]
    assert {r.name for r in dispatches} == {"sched.decode.dispatch",
                                            "sched.prefill.dispatch"}
    read = sum(r.args["kv_blocks_read"] for r in dispatches)
    dense = sum(r.args["kv_blocks_dense"] for r in dispatches)
    assert all(0 <= r.args["kv_blocks_read"] <= r.args["kv_blocks_dense"]
               for r in dispatches)
    if want == "pallas":
        assert 0 < read < dense
        assert (stats["blocks_read"], stats["dense_blocks_equiv"]) \
            == (read, dense)
    else:  # the gather reads the whole dense view
        assert read == dense and stats["blocks_read"] == 0
