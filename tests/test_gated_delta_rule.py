"""The gated delta rule's Pallas kernel (ops/pallas/gated_delta_rule.py)
under the interpreter against the plain `delta_rule_scan`, the live-row
list it walks, and the function that picks between kernel and scan.

Tolerance: the same float32 operations in the same order, the two
reductions over dk summed in another order: 1e-5 of the compared
tensor's largest magnitude, the op's own (tests/test_qwen3_next.py).
The kernel at the serving cell's widths is compiled for a v5e in
tests/test_tpu_bringup.py; served end to end at a toy size in
tests/test_qwen3_next.py.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from flexflow_tpu.ops.gated_delta_net import delta_rule_scan as scanned
from flexflow_tpu.ops.pallas import gated_delta_rule as gdr

OP_TOL = 1e-5
HEADS, DK, DV = 4, 128, 128


def inputs(counts, s, seed=0, h=HEADS, dk=DK, dv=DV):
    """A step as `GatedDeltaNet.forward` hands it over: non-zero state,
    unit keys, scaled unit queries, `g = beta = 0` past a row's count."""
    counts = np.asarray(counts)
    b, r = len(counts), np.random.default_rng(seed)
    k = r.normal(size=(b, s, h, dk))
    k /= np.linalg.norm(k, axis=-1, keepdims=True)
    q = r.normal(size=(b, s, h, dk))
    q /= np.linalg.norm(q, axis=-1, keepdims=True) * dk ** 0.5
    real = (np.arange(s)[None, :] < counts[:, None])[..., None]
    g = np.where(real, -r.uniform(0, 2, (b, s, h)), 0.0)
    beta = np.where(real, r.uniform(0, 1, (b, s, h)), 0.0)
    arrs = (r.normal(size=(b, h, dk, dv)), q, k,
            r.normal(size=(b, s, h, dv)), g, beta)
    return ([jnp.asarray(a, jnp.float32) for a in arrs]
            + [jnp.asarray(counts, jnp.int32)])


def close(got, want, tol=OP_TOL):
    got, want = np.asarray(got), np.asarray(want)
    assert got.shape == want.shape
    assert float(np.max(np.abs(got - want))) <= tol * float(
        np.max(np.abs(want)))


#: step length -> counts of one batch: none, some and all of the step
BATCHES = {1: [1, 0, 1, 0, 0, 1], 8: [8, 0, 3, 0, 8, 1]}


@pytest.mark.parametrize("heads_block", [None, 1, 2])
@pytest.mark.parametrize("s", sorted(BATCHES))
def test_kernel_equals_the_scanned_step(s, heads_block):
    counts = np.array(BATCHES[s])
    xs = inputs(counts, s, seed=s)
    S_want, o_want = scanned(*xs[:6])
    S, o = gdr.gated_delta_rule(*xs, interpret=True,
                                heads_block=heads_block)
    live = counts > 0
    close(np.asarray(S)[live], np.asarray(S_want)[live])
    # the real positions' outputs (a pad's is computed and thrown away
    # on both paths)
    real = np.arange(s)[None, :] < counts[:, None]
    close(np.asarray(o)[real], np.asarray(o_want)[real])


@pytest.mark.parametrize("s", sorted(BATCHES))
def test_rows_that_do_not_advance_are_their_input_to_the_byte(s):
    counts = np.array(BATCHES[s])
    xs = inputs(counts, s, seed=10 + s)
    before = np.asarray(xs[0])
    S, o = map(np.asarray, gdr.gated_delta_rule(*xs, interpret=True))
    idle = counts == 0
    assert idle.any() and np.abs(before[idle]).min() > 0
    assert np.array_equal(S[idle], before[idle])
    assert not o[idle].any()
    assert not np.array_equal(S[~idle], before[~idle])


def test_a_short_rows_trailing_pads_leave_its_state_where_it_was():
    """Count 3 of 8: the state after the step is the state after its
    first three positions alone."""
    xs = inputs([3, 8], 8, seed=5)
    S8, _ = gdr.gated_delta_rule(*xs, interpret=True)
    S, q, k, v, g, beta, count = xs
    S3, _ = gdr.gated_delta_rule(
        S, *(t[:, :3] for t in (q, k, v, g, beta)), count, interpret=True)
    assert np.array_equal(np.asarray(S8)[0], np.asarray(S3)[0])


@pytest.mark.parametrize("s", [1, 8])
def test_no_live_row_returns_the_input(s):
    xs = inputs([0, 0, 0], s, seed=3)
    S, o = gdr.gated_delta_rule(*xs, interpret=True)
    assert np.array_equal(np.asarray(S), np.asarray(xs[0]))
    assert not np.asarray(o).any()


def test_state_stays_float32_and_shapes_are_the_ops():
    xs = inputs([2, 0], 2)
    S, o = gdr.gated_delta_rule(*xs, interpret=True)
    assert S.dtype == o.dtype == jnp.float32
    assert S.shape == (2, HEADS, DK, DV) and o.shape == (2, 2, HEADS, DV)


@pytest.mark.parametrize("count, rows, n", [
    ([0, 2, 0, 1], [1, 3, 3, 3], 2),
    ([1, 1, 1], [0, 1, 2], 3),
    ([0, 0, 0], [2, 2, 2], 0),
    ([0, 0, 5], [2, 2, 2], 1),
])
def test_live_rows_compacts_and_repeats_the_last(count, rows, n):
    got_rows, got_n = gdr.live_rows(jnp.asarray(count, jnp.int32))
    assert got_rows.tolist() == rows and got_n.tolist() == [n]
    assert got_rows.dtype == got_n.dtype == jnp.int32


@pytest.mark.parametrize("heads, s, want", [
    (32, 1, 32), (32, 8, 16), (32, 16, 8), (4, 4, 4), (6, 8, 6),
    (12, 16, 6), (5, 16, 5), (7, 16, 7), (9, 16, 3),
])
def test_heads_per_block_divides_the_heads_inside_one_lane_tile(
        heads, s, want):
    hb = gdr.heads_per_block(heads, s)
    assert hb == want and heads % hb == 0 and hb * s <= 128


def test_heads_per_block_keeps_the_state_block_inside_default_vmem():
    """A block of state is at most 2 MB (in, out, double-buffered: 8 of
    the 16 MiB a kernel gets without asking; asking for more hung the
    served programs on the chip, PR 35)."""
    assert gdr.heads_per_block(32, 1, 128 * 128 * 4) == 32
    assert gdr.heads_per_block(32, 1, 256 * 128 * 4) == 16
    assert gdr.heads_per_block(32, 1, 256 * 256 * 4) == 8
    assert gdr.heads_per_block(32, 8, 256 * 256 * 4) == 8


#: (backend, slot state, head_k_dim, head_v_dim, step tokens) -> path
PICKS = [
    (("tpu", True, 128, 128, 1), "kernel"),    # the cell's decode step
    (("tpu", True, 128, 128, 8), "kernel"),    # its prefill chunk
    (("tpu", True, 256, 128, 4), "kernel"),
    (("tpu", True, 128, 128, gdr.MAX_STEP_TOKENS), "kernel"),
    (("tpu", True, 128, 128, gdr.MAX_STEP_TOKENS + 1), "plain"),
    (("tpu", True, 128, 128, 512), "plain"),
    # stateless takes the chunked rule, on every backend and at every
    # width: what a trainer differentiates
    (("tpu", False, 128, 128, 1), "chunked"),
    (("tpu", False, 128, 128, 8192), "chunked"),
    (("cpu", False, 128, 128, 8), "chunked"),
    (("cpu", False, 8, 8, 16), "chunked"),
    # a CPU (and anything that is not a TPU) keeps the plain path
    (("cpu", True, 128, 128, 1), "plain"),
    (("cpu", True, 128, 128, 8), "plain"),
    (("gpu", True, 128, 128, 8), "plain"),
    # head dims that are not whole 128-lane tiles (the toy model's 8)
    (("tpu", True, 8, 8, 4), "plain"),
    (("tpu", True, 64, 128, 1), "plain"),
    (("tpu", True, 128, 192, 1), "plain"),
]


@pytest.mark.parametrize("args, want", PICKS,
                         ids=["-".join(map(str, a)) for a, _ in PICKS])
def test_pick_recurrence_is_a_pure_function_of_what_it_is_given(
        args, want, monkeypatch):
    assert gdr.pick_recurrence(*args) == want
    # the process's own backend is not read
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert gdr.pick_recurrence(*args) == want


def test_pick_recurrence_without_pallas_is_plain(monkeypatch):
    monkeypatch.setattr(gdr, "_HAVE_PALLAS", False)
    assert gdr.pick_recurrence("tpu", True, 128, 128, 1) == "plain"


def test_kernel_never_interpreted_on_tpu(monkeypatch):
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    with pytest.raises(ValueError, match="must run compiled"):
        gdr.gated_delta_rule(*inputs([1], 1), interpret=True)
