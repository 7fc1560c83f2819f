"""The grouped-matmul kernel under the routed experts' three products
(`ops/routed_experts.py`: `grouped_matmul`, `grouped_matmul_into_lhs`,
`grouped_matmul_into_rhs` on `ops/pallas/grouped_matmul.py`), run by the
Pallas interpreter on the CPU at toy shapes: each product against the
ragged dot inside the groups, the tails' contract, `_grouped`'s value
and gradient through the kernel against the dense product's, the rows
the counter says the kernel multiplies, and `pick_grouped_tiling` as a
pure function of the shapes.

The interpreter fills what a kernel does not write with NaN, which is
what the chip's unwritten rows may hold: every comparison here reads
the rows the contract defines and nothing else.  Tolerance: the same
float32 sums in another order, `_family.OP_TOL`.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from _family import close
from jax.experimental.pallas.ops.tpu.megablox.gmm import make_group_metadata
from test_lfm2_moe_ops import grouped_case

from flexflow_tpu.ops import routed_experts as rx
from flexflow_tpu.ops.pallas import grouped_matmul as kernels

TOY_TILING = (8, 4, 4)


@pytest.fixture
def kernel(monkeypatch):
    """Steer every product whose rows the toy row tile divides onto the
    kernel (the choice is by shape and backend; a test replaces the
    picker, the program has no option)."""
    def pick(m, k, n, groups, rows_a_group, backend="", into_rhs=False):
        return TOY_TILING if m % TOY_TILING[0] == 0 else None

    monkeypatch.setattr(rx, "pick_grouped_tiling", pick)


# -- 1. each product against the ragged dot --------------------------------------
#: name: (m, k, n, sizes)
PRODUCT_CASES = {
    "an_empty_group": (40, 16, 8, [9, 0, 12]),
    "fewer_rows_than_slots": (40, 16, 8, [5, 7, 4]),       # the usual size
    "every_slot_in_a_group": (40, 16, 8, [16, 8, 16]),     # the overflow's
    "several_k_and_n_tiles": (40, 24, 16, [9, 3, 12]),
    "runs_that_share_every_row_tile": (24, 8, 8, [3, 3, 3]),
}


@pytest.mark.parametrize("case", sorted(PRODUCT_CASES))
@pytest.mark.parametrize("form", ["grouped_matmul",
                                  "grouped_matmul_into_lhs",
                                  "grouped_matmul_into_rhs"])
def test_the_kernel_equals_the_ragged_dot_inside_the_groups(
        form, case, kernel, monkeypatch):
    """With NaN in every row past the last group: no form reads one,
    the first two equal the ragged dot in the groups' rows (what they
    leave after them is not compared: not defined), and the third
    equals it everywhere, an empty group's slice being zero."""
    m, k, n, sizes = PRODUCT_CASES[case]
    keys = jax.random.split(jax.random.key(4), 3)
    count, groups = sum(sizes), len(sizes)
    live = (jnp.arange(m) < count)[:, None]
    lhs = jnp.where(live, jax.random.normal(keys[0], (m, k)), jnp.nan)
    ct = jnp.where(live, jax.random.normal(keys[1], (m, n)), jnp.nan)
    rhs = jax.random.normal(keys[2], (groups, k, n))
    sizes = jnp.asarray(sizes, jnp.int32)
    args = {"grouped_matmul": (lhs, rhs), "grouped_matmul_into_lhs": (ct, rhs),
            "grouped_matmul_into_rhs": (lhs, ct)}[form]
    got = getattr(rx, form)(*args, sizes, 8.0)
    monkeypatch.setattr(rx, "pick_grouped_tiling", lambda *a, **kw: None)
    want = getattr(rx, form)(*args, sizes, 8.0)
    if form == "grouped_matmul_into_rhs":
        close(got, want)
        for g in np.flatnonzero(np.asarray(sizes) == 0):
            assert not np.any(np.asarray(got[g]))
    else:
        assert np.all(np.isfinite(np.asarray(got[:count])))
        close(got[:count], want[:count])


@pytest.mark.parametrize("form", ["grouped_matmul",
                                  "grouped_matmul_into_lhs",
                                  "grouped_matmul_into_rhs"])
def test_the_kernel_refuses_a_tiling_that_does_not_divide(form, kernel):
    """Widths are whole tiles: the picker hands the kernel divisors of
    k and n only (below), and a k or n that the tile does not divide is
    an error, never a masked remainder."""
    m, k, n = 40, 10, 6
    rhs = jnp.zeros((m, n) if form.endswith("rhs") else (3, k, n))
    lhs = jnp.zeros((m, n if form.endswith("lhs") else k))
    with pytest.raises(ValueError, match="does not divide"):
        getattr(rx, form)(lhs, rhs, jnp.array([9, 3, 12], jnp.int32), 8.0)


# -- 2. the layer through the kernel ----------------------------------------------
@pytest.mark.parametrize("size", ["usual", "overflow", "one_size"])
@pytest.mark.parametrize("load", ["light", "even",
                                  "every_pair_on_one_expert"])
def test_grouped_through_the_kernel_equals_the_dense_product(
        load, size, kernel):
    """`_grouped`'s value and its hand-written gradient, every grouped
    product of either size on the kernel (whose rows past the last
    group are NaN here), against autodiff of the dense product; the
    usual buffers are whole row tiles, and the counter counts a row
    tile a visit."""
    (h, landed_on, w, ws, expected), overflows = grouped_case(load, size)
    n = ws[0].shape[0]
    probe = jax.random.normal(jax.random.key(3), h.shape)
    landed = jax.nn.one_hot(landed_on, n, dtype=jnp.float32)

    def dense(h, w, *ws):
        out = rx.dense_experts(h, jnp.einsum("tkx,tk->tx", landed, w), *ws)
        return jnp.sum(probe * out), out

    def grouped(h, w, *ws):
        out, counts = rx.grouped_experts(h, landed_on, w, *ws, expected)
        return jnp.sum(probe * out), (out, counts)

    want, dense_out = jax.grad(dense, argnums=range(5), has_aux=True)(
        h, w, *ws)
    got, (out, counts) = jax.grad(grouped, argnums=range(5), has_aux=True)(
        h, w, *ws)
    close(out, dense_out)
    for g, d in zip(got, want):
        close(g, d)
    assert int(counts[1]) == overflows
    sizes = np.bincount(np.asarray(landed_on).ravel(), minlength=n + 1)[:n]
    tm = TOY_TILING[0]
    ends = np.cumsum(sizes)
    visits = np.where(sizes > 0, -(-ends // tm) - (ends - sizes) // tm, 0)
    assert int(counts[0]) == tm * visits.sum() >= sizes.sum()


def test_the_usual_buffers_are_whole_row_tiles(kernel, monkeypatch):
    """`grouped_experts` rounds the usual buffers up to the row tile of
    the kernel it was given, so the kernel's `m % tm == 0` holds at
    both sizes."""
    seen = []
    grouped = rx._grouped
    monkeypatch.setattr(rx, "_grouped",
                        lambda usual, *a: seen.append(usual) or
                        grouped(usual, *a))
    (h, landed_on, w, ws, _), _ = grouped_case("even", "usual")
    for expected in (3.0, 10.0, 17.0, 48.0):
        rx.grouped_experts(h, landed_on, w, *ws, expected)
    assert [m for m, _ in seen] == [8, 16, 32, 48]
    assert all(rows == expected / 3 for (_, rows), expected in zip(
        seen, (3.0, 10.0, 17.0, 48.0)))


# -- 3. what the counter counts ---------------------------------------------------
@pytest.mark.parametrize("sizes", [
    [1024] * 8, [1000, 1100, 900, 1200, 800, 1024, 1024, 1144],
    [0, 5, 0, 300, 0, 0, 700, 1], [0] * 8, [4096, 0, 0, 0, 0, 0, 0, 0],
    [256, 256, 256, 256, 256, 256, 256, 255]])
@pytest.mark.parametrize("tm", [128, 256, 512])
def test_rows_multiplied_are_the_kernels_row_tile_visits(sizes, tm):
    """`rows_multiplied` = tm x the grid steps megablox's own metadata
    gives the forward kernel (`num_tiles`: a tile two runs share is
    visited for each; an empty group is not visited)."""
    sizes = jnp.asarray(sizes, jnp.int32)
    _, visits = make_group_metadata(
        group_sizes=sizes, m=8192, tm=tm, start_group=jnp.int32(0),
        num_nonzero_groups=sizes.shape[0], visit_empty_groups=False)
    assert int(rx.rows_multiplied(sizes, tm)) == tm * int(visits)
    # and the kernels' own list of steps is megablox's, step for step
    for empty_groups in (False, True):
        (offsets, group_ids, m_tile_ids), count = make_group_metadata(
            group_sizes=sizes, m=8192, tm=tm, start_group=jnp.int32(0),
            num_nonzero_groups=sizes.shape[0],
            visit_empty_groups=empty_groups)
        got = kernels.visits(sizes, m=8192, tm=tm, empty_groups=empty_groups)
        n = int(count)
        assert int(got[3]) == n and np.array_equal(got[0], offsets)
        assert np.array_equal(got[1][:n], group_ids[:n])
        live = np.asarray(sizes)[np.asarray(group_ids[:n])] > 0
        assert np.array_equal(np.asarray(got[2][:n])[live],
                              np.asarray(m_tile_ids[:n])[live])
        assert 0 <= int(jnp.min(got[2])) and int(jnp.max(got[2])) < 8192 // tm
    ragged = int(rx.rows_multiplied(sizes))
    assert int(jnp.sum(sizes)) <= ragged <= int(jnp.sum(sizes)) + 7 * 8


# -- 4. which kernel and tiling, by shape ---------------------------------------
#: a routed layer of the two training cells: (hidden, expert width,
#: held, rows a group expects, every-pair slots)
CELL_LAYERS = {"cell6_lfm2": (2048, 1792, 8, 1024.0, 32768),
               "cell8_kimi": (2304, 1024, 8, 256.0, 65536)}
#: what the v5e chose for them (`scripts/expert_product_probe.py
#: --sweep`, PR 50): (k, n, into_rhs) -> tiling
CHOSEN = {
    "cell6_lfm2": {(2048, 1792, False): (128, 2048, 896),
                   (1792, 2048, False): (128, 1792, 1024),
                   (2048, 1792, True): (128, 2048, 896),
                   (1792, 2048, True): (128, 1792, 1024)},
    "cell8_kimi": {(2304, 1024, False): (128, 2304, 1024),
                   (1024, 2304, False): (128, 1024, 2304),
                   (2304, 1024, True): (128, 2304, 512),
                   (1024, 2304, True): (128, 1024, 1152)},
}


@pytest.mark.parametrize("size", ["usual", "every_pair"])
@pytest.mark.parametrize("product", range(4))
@pytest.mark.parametrize("cell", sorted(CELL_LAYERS))
def test_pick_grouped_tiling_by_shape(cell, product, size):
    """The two training cells' products answer `kernel` on a TPU, at
    the usual and at the every-pair size, with the tiling the chip
    chose: the contracted width whole, an output width that divides n,
    a row tile that divides m; any other backend keeps the ragged
    dot."""
    e, f, held, rows, m_all = CELL_LAYERS[cell]
    (k, n, into_rhs), want = list(CHOSEN[cell].items())[product]
    m_usual = int(rx.GROUPED_SLACK * rows * held)
    m = m_usual if size == "usual" else m_all
    got = rx.pick_grouped_tiling(m, k, n, held, rows, "tpu", into_rhs)
    assert got == want
    tm, tk, tn = got
    assert m % tm == 0 and k % tk == 0 and n % tn == 0 and tn % 128 == 0
    for backend in ("cpu", "gpu", ""):
        assert rx.pick_grouped_tiling(m, k, n, held, rows, backend,
                                      into_rhs) is None


@pytest.mark.parametrize("m,k,n,rows,into_rhs,want", [
    (12288, 2048, 1792, 127.0, False, None),   # too few rows a group
    (12288, 2000, 1792, 1024.0, False, None),  # k no whole lane tiles
    (12288, 2048, 1800, 1024.0, True, None),   # n no whole lane tiles
    (12200, 2048, 1792, 1024.0, False, None),  # no row tile divides m
    (12288 + 128, 2048, 1792, 1024.0, False, (128, 2048, 896)),
    (4096, 2048, 1792, 128.0, False, (128, 2048, 896)),  # short runs
    (8192, 7168, 2048, 1024.0, False, (128, 1024, 2048)),  # k too wide
    (8192, 7168, 2048, 1024.0, True, (128, 1792, 1024)),   # to keep whole
    (1024, 128, 128, 128.0, False, (128, 128, 128)),
])
def test_pick_grouped_tiling_at_the_edges(m, k, n, rows, into_rhs, want):
    assert rx.pick_grouped_tiling(m, k, n, 8, rows, "tpu", into_rhs) == want


@pytest.mark.parametrize("rows,top_k,held,total", [
    (8192, 4, 8, 32), (8192, 8, 8, 256), (8000, 4, 8, 32), (4104, 4, 8, 32),
    (1000, 6, 4, 16), (96, 2, 2, 4)])
def test_the_row_tile_divides_both_buffer_sizes(rows, top_k, held, total,
                                                monkeypatch):
    """Whatever the step's rows: `grouped_slots` rounds the usual
    buffers to the row tile of the every-pair size, so a product of
    either size is handed an m its own row tile divides, or the ragged
    dot."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    m_all, pairs = rows * top_k, rows * top_k * held / total
    m_usual, tile_usual, tile_all = rx.grouped_slots(
        m_all, pairs, 2048, 1792, held)
    assert m_usual <= m_all and m_usual >= min(m_all, int(1.5 * pairs))
    for m, tile in ((m_usual, tile_usual), (m_all, tile_all)):
        for k, n, into_rhs in CHOSEN["cell6_lfm2"]:
            tiling = rx.pick_grouped_tiling(m, k, n, held, pairs / held,
                                            "tpu", into_rhs)
            assert (tiling is None) == (tile is None)
            assert tiling is None or m % tiling[0] == 0
    if m_all % 128 == 0 and pairs / held >= 128:
        assert tile_all and tile_usual and m_usual % tile_all == 0


def test_the_op_names_what_its_grouped_products_run_on(monkeypatch):
    """`RoutedExperts.grouped_product_plan`, which the `first=1`
    `train_step` span carries as `experts_product` / `experts_tiling`:
    cell 6's layer answers the ragged dot here and, on a TPU, the
    kernel with its distinct tilings."""
    from flexflow_tpu import FFConfig, FFModel

    ff = FFModel(FFConfig(batch_size=2, num_devices=1))
    op = ff.routed_experts(
        ff.create_tensor([2, 4096, 2048], name="x"),
        rx.RoutedExpertsParams(experts_total=32, experts_held=8,
                               first_held=0, top_k=4, expert_hidden=1792),
        name="op").owner_op
    assert op.product_plan() == "grouped"
    assert op.grouped_product_plan() == ("ragged", "")
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert op.grouped_product_plan() == (
        "kernel", "128x2048x896+128x1792x1024")
