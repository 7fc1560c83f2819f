"""One chip's share of a routed-expert layer, with its shared expert.

The layer is TOLD WHICH EXPERTS IT HOLDS: `experts_total` is the
router's published width, `experts_held` of them live here, starting
at `first_held`.  It routes every token over all `experts_total` in
float32 (scores by the params' `scoring` rule: `sigmoid` of each logit,
or `softmax` over all the logits; the top `top_k` of score + bias, the
chosen scores normalised over ALL of the chosen where `norm_topk_prob`
says so, and scaled), and adds

    sum over experts chosen AND held here of w_e E_e(h)  +  g E_shared(h)

with `g = 1`, or `sigmoid(w_sg . h)` where `shared_expert_gate` is set.

Experts that live on other chips add nothing: on one chip the layer
runs without its exchange, and nothing stands in for the absent chips.
`experts_held == experts_total` is the whole layer.

IDENTITY ("zero-compute") EXPERTS.  `zero_experts` of them stand behind
the `experts_total` real ones: the router (and its choosing bias) is
`experts_total + zero_experts` wide, `top_k` picks fall anywhere in
that width, and a pick `j >= experts_total` has no weights and no home:
it adds `w_j h`, the token's own row times its routing weight, on the
chip that holds the token (what every chip computes for its own rows,
like a shared expert: no exchange).  So a token costs a VARYING number
of expert products (`top_k` less its identity picks).  Neither product
sees an identity pick: the dense one's `combine` has no column for it,
the grouped one sorts it with "lives elsewhere", and the pairs an even
router sends here are counted over the router's whole width.  The term
is added under the scope part `zero` and counted in the state entry
`moe_zero` (`MOE_ZERO_STATS`; the entry exists only where
`zero_experts > 0`, so a layer without them lowers what it always did).

No token is dropped at any load, by either of the two products, of
which `pick_expert_product` chooses one from the step's shapes:

* "dense": every held expert is applied to every row of the step and
  the result combined with the routing weights (zero where an expert
  was not chosen): a capacity of the step's rows, which at a decode
  step's few rows costs what any static-shape dispatch costs, the read
  of the held experts' weights.  What the serving steps take.
* "grouped": the step's (token, expert) pairs are sorted by expert,
  those on held experts first; each held expert multiplies its own run
  of rows (`grouped_matmul`: one grouped product a weight, whose groups
  are the runs; on a TPU a grouped-matmul Pallas kernel at a tiling
  `pick_grouped_tiling` takes from the shapes, elsewhere the ragged
  dot) and the results go back to their tokens weighted by the
  routing.  The buffers are static (`GROUPED_SLACK` x the pairs an even
  router sends here; every pair of the step when there are more), the
  product visits the held runs only.  What a training step's thousands
  of rows take: dense would multiply `experts_total / top_k` times what
  the routing asked for.  Forward and backward are written out
  (`_grouped`, one `custom_vjp`): slot-sized buffers, one gather in and
  k out a pass, no mask pass (a select inside the two sums over a
  token's slots drops the pairs not held), and only the usual buffers'
  three products kept for the backward pass.  A step that OVERFLOWS the
  usual buffers is as right and as dropless as any other and pays for
  it twice: its forward runs every pair's rows (`top_k x rows`, 2.7 x
  the usual buffers in the LFM2 cell) and its backward runs that
  forward again, because the every-pair size keeps nothing
  (`moe_overflow` counts such layers; 18.7 against 12.0 ms a layer on
  the v5e, PERF.md).

`moe_stats` counts what a dispatch with a smaller capacity would have
to get right: routed pairs that landed on held experts, pairs the
combine left out (0), the rows of the fullest held expert, and the held
experts that received a row.  The grouped product also counts the rows
it multiplied and whether the step overflowed (`moe_rows_computed`
[2]: `rows_multiplied`, the padding of the product's row tiles
included: under the kernel every visit of a row tile, so a tile that
two experts' runs share counts twice; 1 where the layer took the
every-pair size); the dense product's rows are the static
`rows x experts_held`.
"""
from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp

from ..fftype import DataType, OperatorType
from ..initializer import DEFAULT_WEIGHT_INIT, ZeroInitializer
from ..obs.scopes import scope
from ..tensor import ParallelDim, ParallelTensorShape
from .dense import gated_mlp
from .op import Op, ShapeError, WeightSpec, remat_keep
from .pallas import grouped_matmul as kernels

#: order of the counters in the `moe_stats` state entry
MOE_STATS = ("pairs", "dropped", "max_rows", "hit")
#: order of the counters in the `moe_zero` state entry of a layer with
#: identity experts: the identity picks of the step's rows, and the
#: least and the most REAL picks (on any chip's experts) a row made
MOE_ZERO_STATS = ("zero_picks", "real_min", "real_max")


@dataclasses.dataclass(frozen=True)
class RoutedExpertsParams:
    experts_total: int
    experts_held: int
    first_held: int
    top_k: int
    expert_hidden: int
    shared_hidden: int = 0  # 0: no shared expert
    routed_scaling_factor: float = 1.0
    norm_topk_prob: bool = True
    dtype: DataType = DataType.FLOAT
    scoring: str = "sigmoid"  # or "softmax", over all experts_total
    shared_expert_gate: bool = False  # shared expert times sigmoid(w . h)
    norm_eps: float = 1e-20  # added to the chosen scores' sum
    #: identity experts behind the `experts_total` real ones: router
    #: outputs that add `w h` and hold no weights (module docstring)
    zero_experts: int = 0

    @property
    def router_width(self) -> int:
        """Outputs of the router: the real experts of every chip and the
        identity experts."""
        return self.experts_total + self.zero_experts


def route(h, router, bias, p: RoutedExpertsParams):
    """h [t, e] -> (chosen expert ids [t, k], their weights [t, k]),
    all in float32 at full matmul precision: scores by `p.scoring` over
    the router's whole width (identity experts included: an id `>=
    p.experts_total` is one), the bias only chooses, the normaliser
    (where `p.norm_topk_prob`) runs over all k chosen."""
    logits = jnp.matmul(
        h.astype(jnp.float32), router.astype(jnp.float32),
        precision=jax.lax.Precision.HIGHEST)
    scores = (jax.nn.softmax(logits, axis=-1) if p.scoring == "softmax"
              else jax.nn.sigmoid(logits))
    _, chosen = jax.lax.top_k(scores + bias.astype(jnp.float32), p.top_k)
    w = jnp.take_along_axis(scores, chosen, axis=-1)
    if p.norm_topk_prob:
        w = w / (jnp.sum(w, axis=-1, keepdims=True) + p.norm_eps)
    return chosen, w * p.routed_scaling_factor


#: routed rows a held expert has to expect before the grouped product
#: pays for its sort and its gathers: the rows of one pass of the
#: matrix unit
GROUPED_MIN_ROWS_PER_EXPERT = 128
#: rows the ragged product is counted to multiply at a time (a sublane
#: tile): a held expert's run of n rows counts `ceil(n / 8)` tiles
GROUPED_ROW_TILE = 8
#: the grouped product's usual buffers hold this many times the pairs an
#: even router sends to the held experts; a step with more takes the
#: buffers that hold every pair
GROUPED_SLACK = 1.5


def pick_expert_product(rows: int, experts_held: int, experts_total: int,
                        top_k: int, backend: str = "") -> str:
    """"dense" or "grouped" for a step of `rows` rows; `experts_total`
    is the router's WHOLE width (identity experts included: a pick that
    falls on one reaches no expert).  A pure function
    of its arguments; the backend does not enter (both products are
    plain XLA programs), it is taken so that a caller never has to know
    that.  Dense multiplies `rows x experts_held` rows; grouped about
    `rows x top_k x experts_held / experts_total` and pays a sort and
    four gathers of every pair: worth it once a held expert expects
    `GROUPED_MIN_ROWS_PER_EXPERT` rows, which no serving step's few
    hundred rows give it."""
    del backend
    if experts_held < 2:
        return "dense"
    expected = rows * top_k / experts_total  # routed rows an expert
    return ("grouped" if expected >= GROUPED_MIN_ROWS_PER_EXPERT
            else "dense")


def dense_experts(h, combine, w_gate, w_up, w_down):
    """The dense product: every held expert (axis x) over every row of
    h [t, e], combined with `combine` [t, held], the routing weights
    (zero where an expert was not chosen) -> [t, e]."""
    with scope("products"):
        gate = jnp.einsum("te,xef->xtf", h, w_gate)
        up = jnp.einsum("te,xef->xtf", h, w_up)
        y = jnp.einsum("xtf,xfe->xte", jax.nn.silu(gate) * up, w_down)
    with scope("combine"):
        return jnp.einsum("xte,tx->te", y, combine.astype(y.dtype))


#: rows of the grouped-matmul kernel's row tile: the matrix unit's own
#: (`pick_grouped_tiling` has the chip's readings of 128 / 256 / 512)
GROUPED_KERNEL_ROW_TILE = 128
#: bytes of its 16 MiB of fast memory the forward kernel's tiles may
#: take by `pick_grouped_tiling`'s count (double-buffered operand and
#: result tiles and the float32 accumulator); Mosaic's own temporaries
#: take the rest: of 84 tilings compiled for the v5e none that counts
#: under 14.5 MiB was refused ((256, 7168, 256) counts that and is;
#: (128, 2304, 1024) counts 12.2)
GROUPED_KERNEL_VMEM = 14 * 2 ** 20
#: the same for the weight-gradient kernel, whose count is 8 bytes an
#: element of its [tk, tn] accumulator (float32, and the result tile
#: twice) and 8 an element of its two operand tiles: of 84 tilings
#: compiled for the v5e none that counts under 19.9 MB was refused
#: ((128, 1024, 2048) counts that and is; (128, 2048, 896) counts 17.7)
GROUPED_KERNEL_VMEM_INTO_RHS = 18_000_000


def pick_grouped_tiling(m: int, k: int, n: int, groups: int,
                        rows_a_group: float, backend: str = "",
                        into_rhs: bool = False):
    """The kernel's tiling (tm, tk, tn) for one grouped product, or
    None: the ragged dot.  A pure function of its arguments.  The
    product is lhs [m, k] x [groups, k, n] -> [m, n] (`grouped_matmul`,
    and `grouped_matmul_into_lhs` with the weight's axes read the other
    way round) or, `into_rhs`, [m, k] and [m, n] -> [groups, k, n];
    `rows_a_group` is what an even router sends a group (`groups`
    does not enter today).

    The kernel (`ops/pallas/grouped_matmul.py`) is for the TPU, for
    widths of whole 128-lane tiles, for an m that its row tile divides
    (`grouped_experts` rounds its buffers to one) and for runs of a row
    tile or more; anything else keeps the ragged dot.  What the v5e
    chose (`scripts/expert_product_probe.py --sweep`, PR 50: eight
    calls a program; ms a product of cell 6, 8 x ~1,024 rows in 12,288
    slots at 2,048 x 1,792 | of cell 8, 8 x ~256 rows in 3,072 slots at
    2,304 x 1,024; PERF.md section 7 has the table):

    * tk = k, whole.  A group's [tk, tn] weight tile then stays in fast
      memory while the group's row tiles pass, and no accumulator is
      carried between grid steps: (128, 2048, 896) 0.568 against
      (128, 1024, 896) 0.944 and the ragged dot's 0.821 | (128, 2304,
      1024) 0.122 against (128, 1152, 1024) 0.135 and 0.235.  Among the
      (tk, tn) that fit (`GROUPED_KERNEL_VMEM`) the largest tile wins,
      the larger tk on a tie; tn divides n, so no lane is masked.
    * tm = 128 rows.  A row tile that two runs share is visited once
      for each, so at 8 runs in 8,192 rows 71 visits of 128 multiply
      9,088 rows where 39 of 256 multiply 9,984 and 23 of 512 11,776,
      while each of the matrix unit's weight loads serves fewer rows:
      128 / 256 rows read 0.568 / 0.558 | 0.122 / 0.145 (runs of ~256
      rows touch three tiles of 256 as often as two), and cell 6's step
      161.7 / 161.9 ms.  The runs are NOT aligned to the tiles inside
      the buffers: that pads every run by half a tile on average, 68
      visits of 128 against 71, and the padded slots would have to hold
      zeros.
    * `into_rhs`: tm = 128 too, tk = k and the accumulator's [tk, tn] as
      large as fits (`GROUPED_KERNEL_VMEM_INTO_RHS`): (128, 2048, 896)
      0.741 against (128, 1024, 896) 0.801 and the ragged dot's 1.069 |
      (128, 2304, 512) 0.174 against 0.298.

    One routed layer forward and backward, alone: cell 6's 11.35 ms on
    the ragged dot, 7.81 on the kernel; cell 8's 7.45 and 6.27."""
    tm = GROUPED_KERNEL_ROW_TILE
    if (backend != "tpu" or k % 128 or n % 128 or m % tm
            or rows_a_group < tm):
        return None

    def fits(tk, tn):
        if into_rhs:
            return (8 * tk * tn + 8 * tm * (tk + tn)
                    <= GROUPED_KERNEL_VMEM_INTO_RHS)
        return (4 * (tm * tk + tk * tn + tm * tn) + 4 * tm * tn
                <= GROUPED_KERNEL_VMEM)

    tiles = [(tk * tn, tk, tn)
             for tk in range(128, k + 1, 128) if k % tk == 0
             for tn in range(128, n + 1, 128) if n % tn == 0
             if fits(tk, tn)]
    if not tiles:
        return None
    _, tk, tn = max(tiles)
    return tm, tk, tn


def _tiling(m, k, n, groups, rows_a_group, into_rhs=False):
    return pick_grouped_tiling(m, k, n, groups, rows_a_group,
                               jax.default_backend(), into_rhs)


def _visits(sizes, lhs, tiling, empty_groups=False):
    """The kernel's grid steps over lhs' rows: one jitted call a
    product, which the products of a layer that share (sizes, m, tm)
    trace once and XLA computes once."""
    return kernels.visits(sizes, m=lhs.shape[0], tm=tiling[0],
                          empty_groups=empty_groups)


def _interpret() -> bool:
    """The kernel is compiled by Mosaic on a TPU and interpreted
    anywhere else (where only a test's picker asks for it)."""
    return jax.default_backend() != "tpu"


def grouped_matmul(lhs, rhs, sizes, rows_a_group: float = 0.0):
    """lhs [m, k] whose rows run expert after expert, `sizes[g]` rows
    for expert g (their sum may stay below m); rhs [g, k, n] -> [m, n].
    Rows of lhs past the last group are not read.  Rows of the RESULT
    past the last group are NOT DEFINED: the CPU's lowering gives
    zeros, libtpu's ragged dot and the grouped-matmul kernel leave them
    unwritten, whatever the buffer held before, NaN included (measured
    on the v5e, PRs 39 and 50).  Nothing may read them but another
    grouped product, or a select that drops them.  `rows_a_group`, the
    rows a group expects, is what `pick_grouped_tiling` chooses the
    kernel and its tiling from (0: the ragged dot)."""
    tiling = _tiling(*lhs.shape, rhs.shape[2], rhs.shape[0], rows_a_group)
    if tiling is None:
        return jax.lax.ragged_dot(lhs, rhs, sizes)
    return kernels.product(lhs, rhs, _visits(sizes, lhs, tiling),
                           tiling=tiling, interpret=_interpret())


def grouped_matmul_into_lhs(ct, rhs, sizes, rows_a_group: float = 0.0):
    """`grouped_matmul`'s gradient into its lhs: ct [m, n], rhs
    [g, k, n] -> [m, k], each run of rows times its own expert's weight
    transposed.  The kernel reads the weight as it is stored and
    contracts its last axis; the ragged dot, which contracts rhs'
    middle axis only, is handed a transposed copy.  Its rows past the
    last group: as `grouped_matmul`'s."""
    tiling = _tiling(*ct.shape, rhs.shape[1], rhs.shape[0], rows_a_group)
    if tiling is None:
        return grouped_matmul(ct, jnp.swapaxes(rhs, 1, 2), sizes)
    return kernels.product(ct, rhs, _visits(sizes, ct, tiling),
                           tiling=tiling, transpose_rhs=True,
                           interpret=_interpret())


def grouped_matmul_into_rhs(lhs, ct, sizes, rows_a_group: float = 0.0):
    """`grouped_matmul`'s gradient into its rhs: lhs [m, k], ct [m, n]
    -> [g, k, n], each run of rows contracted into its own expert's
    slice (an empty group's slice is zero).  Rows past the last group
    are not read and enter no slice, on any backend."""
    tiling = _tiling(*lhs.shape, ct.shape[1], sizes.shape[0], rows_a_group,
                     into_rhs=True)
    if tiling is None:
        return jax.lax.ragged_dot_general(
            lhs, ct, sizes, jax.lax.RaggedDotDimensionNumbers(
                dot_dimension_numbers=(((0,), (0,)), ((), ())),
                lhs_ragged_dimensions=[0], rhs_group_dimensions=[]))
    return kernels.product_into_groups(
        lhs, ct, _visits(sizes, lhs, tiling, empty_groups=True),
        tiling=tiling, interpret=_interpret())


def grouped_slots(m_all: int, expected_pairs: float, e: int, f: int,
                  held: int):
    """(m_usual, the kernel's row tile at that size, at the every-pair
    size `m_all`; None: the ragged dot) of a layer whose `held` experts
    [e, f] expect `expected_pairs` pairs a step.  The usual buffers
    hold `GROUPED_SLACK` times those, in whole row tiles of the
    every-pair size's forward product, which then divide both sizes."""
    def row_tile(m):
        tiling = _tiling(m, e, f, held, expected_pairs / held)
        return tiling[0] if tiling else None

    tile = row_tile(m_all) or GROUPED_ROW_TILE
    m_usual = min(m_all, -(-max(1, int(GROUPED_SLACK * expected_pairs))
                           // tile) * tile)
    return m_usual, row_tile(m_usual), row_tile(m_all)


def rows_multiplied(sizes, kernel_row_tile=None):
    """The rows a grouped product multiplies for runs of `sizes` rows.
    The ragged dot: each run rounded up to `GROUPED_ROW_TILE`.  The
    kernel, whose row tiles lie on the buffer's own grid: `tm` a visit,
    one visit for every tile a run touches, so a tile that two runs
    share is counted (and multiplied) twice."""
    if kernel_row_tile is None:
        return jnp.sum(-(-sizes // GROUPED_ROW_TILE)) * GROUPED_ROW_TILE
    ends = jnp.cumsum(sizes)
    visits = -(-ends // kernel_row_tile) - (ends - sizes) // kernel_row_tile
    return jnp.sum(jnp.where(sizes > 0, visits, 0)) * kernel_row_tile


def _activation(gate, up):
    return jax.nn.silu(gate) * up


def _sum_of_slots(rows, slot_of, kept, scale=None):
    """rows [m, e]; slot_of, kept [t, k] -> [t, e]: the rows of a
    token's `kept` slots summed in float32, each times `scale[t, k]`
    (rounded to the rows' precision first) where that is given.  A pair
    not kept adds an exact zero whatever the row its number falls on
    holds: the one mask of the layer, a select inside the sum.  One
    gather of t rows a chosen expert, accumulated in turn: nothing of
    [t, k, e] is written."""
    at = jnp.minimum(slot_of, rows.shape[0] - 1)
    total = None
    for j in range(slot_of.shape[1]):
        # the select first, on the gathered rows as they are: XLA then
        # fuses the widening into the sum (measured the other way round:
        # four [t, e] float32 copies a pass, PR 39)
        term = jnp.where(kept[:, j, None], rows[at[:, j]], 0).astype(
            jnp.float32)
        if scale is not None:
            term = term * scale[:, j, None].astype(rows.dtype).astype(
                jnp.float32)
        total = term if total is None else total + term
    return total.astype(rows.dtype)


def _slot_products(size, h, order, slot_of, sizes, w_gate, w_up, w_down):
    """The experts over the first m slots (`size` = (m, the rows a group
    expects)) -> gate, up [m, f] and ys [m, e], the three products.  No
    mask: a slot past the held runs
    gathers some token's row, which no group reads, and what the
    products leave in such a slot (`grouped_matmul`) only a grouped
    product or `_sum_of_slots`' select meets."""
    m, rows = size
    with scope("dispatch"):
        xs = h[order[:m] // slot_of.shape[1]]
    with scope("products"):
        gate = grouped_matmul(xs, w_gate, sizes, rows)
        up = grouped_matmul(xs, w_up, sizes, rows)
        ys = grouped_matmul(_activation(gate, up), w_down, sizes, rows)
    return gate, up, ys


def _held(slot_of, sizes):
    """[t, k]: the pairs on held experts, whose runs fill the first
    slots."""
    return slot_of < jnp.sum(sizes)


def _slots_forward(size, h, order, slot_of, sizes, weights,
                   w_gate, w_up, w_down):
    """The layer over the first m slots, which hold every held pair
    -> (out [t, e], gate, up, ys)."""
    products = _slot_products(size, h, order, slot_of, sizes,
                              w_gate, w_up, w_down)
    with scope("combine"):
        return (_sum_of_slots(products[-1], slot_of, _held(slot_of, sizes),
                              weights), *products)


def _slots_backward(size, h, order, slot_of, sizes, weights, w_gate, w_up,
                    w_down, gate, up, ys, d_out):
    """`_slots_forward`'s gradient into (h, weights, w_gate, w_up,
    w_down) from its products.  The routing weight of a slot past the
    held runs is zero, so the gradient that enters the slots is zero
    there: the mask of the backward pass is the multiply that makes
    `d_ys`.  What the products leave there afterwards is dropped where
    the sums select the held pairs."""
    m, rows = size
    with scope("dispatch"):
        pair = order[:m]
        token = pair // slot_of.shape[1]
        xs = h[token]
        w_slot = weights.reshape(-1)[pair].astype(d_out.dtype)
        held = _held(slot_of, sizes)
    with scope("combine"):
        d_rows = d_out[token]  # what reached the slot's token
        d_ys = d_rows * w_slot[:, None]
        d_w_slot = jnp.sum(ys.astype(jnp.float32)
                           * d_rows.astype(jnp.float32), axis=-1)
        d_weights = jnp.where(
            held, d_w_slot[jnp.minimum(slot_of, m - 1)], 0).astype(
                weights.dtype)
    with scope("products"):
        act, d_activation = jax.vjp(_activation, gate, up)
        d_w_down = grouped_matmul_into_rhs(act, d_ys, sizes, rows)
        d_gate, d_up = d_activation(
            grouped_matmul_into_lhs(d_ys, w_down, sizes, rows))
        d_w_gate = grouped_matmul_into_rhs(xs, d_gate, sizes, rows)
        d_w_up = grouped_matmul_into_rhs(xs, d_up, sizes, rows)
        d_xs = (grouped_matmul_into_lhs(d_gate, w_gate, sizes, rows)
                + grouped_matmul_into_lhs(d_up, w_up, sizes, rows))
    with scope("dispatch"):
        d_h = _sum_of_slots(d_xs, slot_of, held)
    return (d_h, d_weights, d_w_gate.astype(w_gate.dtype),
            d_w_up.astype(w_up.dtype), d_w_down.astype(w_down.dtype))


def _fits(usual, slot_of, sizes):
    """None where the usual buffers hold every pair whatever the load,
    else whether they hold this step's."""
    m_usual = usual[0]
    if m_usual == slot_of.size:
        return None
    with scope("dispatch"):
        return jnp.sum(sizes) <= m_usual


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def _grouped(usual, h, order, slot_of, sizes, weights,
             w_gate, w_up, w_down):
    """`grouped_experts` after its sort, forward and backward written
    out (`_slots_forward`, `_slots_backward`) at two sizes: the first
    `m_usual` slots or, on a step whose held pairs overflow them, every
    slot (`usual` = (m_usual, the rows a group expects): static).  The
    choice (`lax.cond`) is made inside either rule, so both
    branches give the same shapes and the backward rule's residuals are
    the usual size's alone."""
    return _grouped_fwd(usual, h, order, slot_of, sizes, weights,
                        w_gate, w_up, w_down)[0]


def _grouped_fwd(usual, h, order, slot_of, sizes, weights,
                 w_gate, w_up, w_down):
    args = (h, order, slot_of, sizes, weights, w_gate, w_up, w_down)
    fits = _fits(usual, slot_of, sizes)
    every = (slot_of.size, usual[1])
    forward = functools.partial(_slots_forward, usual)
    if fits is None:
        out, *products = forward(*args)
    else:
        def overflow(*args):
            # nothing of this size is kept: its backward runs it again
            out, *products = _slots_forward(every, *args)
            with scope("dispatch"):
                return (out, *(jnp.zeros(usual[:1] + y.shape[1:], y.dtype)
                               for y in products))

        out, *products = jax.lax.cond(fits, forward, overflow, *args)
    # what a checkpointed segment may hold of this op (the flash
    # kernels name their outputs the same way)
    return out, args + tuple(map(remat_keep, products))


def _grouped_bwd(usual, residuals, d_out):
    fits = _fits(usual, *residuals[2:4])  # slot_of, sizes
    backward = functools.partial(_slots_backward, usual)
    if fits is None:
        grads = backward(*residuals, d_out)
    else:
        def overflow(h, order, slot_of, sizes, weights, w_gate, w_up,
                     w_down, _gate, _up, _ys, d_out):
            every = (slot_of.size, usual[1])
            products = _slot_products(every, h, order, slot_of, sizes,
                                      w_gate, w_up, w_down)
            return _slots_backward(every, h, order, slot_of, sizes, weights,
                                   w_gate, w_up, w_down, *products, d_out)

        grads = jax.lax.cond(fits, backward, overflow, *residuals, d_out)
    d_h, d_weights, *d_experts = grads
    return (d_h, None, None, None, d_weights, *d_experts)


_grouped.defvjp(_grouped_fwd, _grouped_bwd)


def grouped_experts(h, landed_on, w, w_gate, w_up, w_down,
                    expected_pairs: float):
    """The grouped product: h [t, e]; landed_on [t, k] the held expert
    (0 .. held - 1) each chosen pair landed on, or `held` for an expert
    that lives elsewhere; w [t, k] the routing weights; `expected_pairs`
    the pairs an even router sends to the held experts (t * k * held /
    total) -> (out [t, e]; int32 [2]: the rows multiplied, tile padding
    included, and 1 where the step took the every-pair size).

    The step's t * k pairs are sorted by expert, the held experts' runs
    first.  Static shapes and no drop at any load: the buffers keep the
    first m slots, where m is `GROUPED_SLACK` times the pairs the held
    experts expect when they hold that many, and EVERY pair when they
    hold more (`_grouped`: both sizes are compiled, one runs).  The
    products visit the held runs and nothing after."""
    t, k = landed_on.shape
    held = w_gate.shape[0]
    with scope("dispatch"):
        order = jnp.argsort(landed_on.reshape(-1), stable=True)  # slot -> pair
        slot_of = jnp.argsort(order).reshape(t, k).astype(jnp.int32)
        order = order.astype(jnp.int32)
        sizes = jnp.sum(jax.nn.one_hot(landed_on.reshape(-1), held,
                                       dtype=jnp.int32), axis=0)
        weights = jnp.where(landed_on < held, w, 0)
    m_all = t * k
    rows_a_group = expected_pairs / held
    m_usual, tile_usual, tile_all = grouped_slots(
        m_all, expected_pairs, *w_gate.shape[1:], held)
    out = _grouped((m_usual, rows_a_group), h, order, slot_of, sizes,
                   weights, w_gate, w_up, w_down)
    with scope("dispatch"):
        overflow = jnp.sum(sizes) > m_usual
        rows = rows_multiplied(sizes, tile_usual)
        if tile_all != tile_usual:  # the two sizes' products differ
            rows = jnp.where(overflow, rows_multiplied(sizes, tile_all), rows)
        return out, jnp.stack([rows, overflow.astype(jnp.int32)])


class RoutedExperts(Op):
    op_type = OperatorType.ROUTED_EXPERTS
    float32_weights = ("router", "router_bias")
    has_aux_state = True  # weights[num_trainable_weights():] are state
    #: the state entries are counters the forward pass only writes:
    #: a segment that holds this op may be rematerialised
    #: (`GraphExecutor._build_remat_plan`)
    state_is_counters = True
    #: `forward` takes `count_rows`, the rows of the step that are real
    #: tokens, where the caller has them (`GraphExecutor.run_forward`)
    counts_real_rows = True

    def _rows(self) -> int:
        shape = self.inputs[0].shape
        return shape.num_elements() // shape.logical_shape[-1]

    def product_plan(self) -> str:
        """"dense" or "grouped": what a step of this op's declared rows
        takes (`pick_expert_product`)."""
        p: RoutedExpertsParams = self.params
        return pick_expert_product(self._rows(), p.experts_held,
                                   p.router_width, p.top_k,
                                   jax.default_backend())

    def grouped_product_plan(self):
        """("kernel", the tilings `tmxtkxtn` of the usual buffers' six
        products, joined by `+`) or ("ragged", ""): what the grouped
        products of a step of this op's declared rows run on
        (`pick_grouped_tiling`)."""
        p: RoutedExpertsParams = self.params
        e, f = self.inputs[0].shape.logical_shape[-1], p.expert_hidden
        # pairs an even router sends the held experts a step
        pairs = self._rows() * p.top_k * p.experts_held / p.router_width
        m_usual = grouped_slots(self._rows() * p.top_k, pairs, e, f,
                                p.experts_held)[0]
        tilings = [_tiling(m_usual, k, n, p.experts_held,
                           pairs / p.experts_held, into_rhs)
                   for into_rhs in (False, True)
                   for k, n in ((e, f), (f, e))]
        if None in tilings:
            return "ragged", ""
        return "kernel", "+".join(dict.fromkeys(
            "x".join(map(str, t)) for t in tilings))

    def dense_rows_computed(self) -> int:
        """Rows the dense product multiplies a step: every held expert
        over every row."""
        return self.params.experts_held * self._rows()

    def infer_output_shapes(self, input_shapes):
        (ishape,) = input_shapes
        p: RoutedExpertsParams = self.params
        last = [d for d in ishape.dims if not d.is_replica_dim][-1]
        if last.degree != 1 or not self.shard.is_trivial():
            raise ShapeError(
                f"{self.name}: this layer holds one chip's experts; "
                "experts spread over a mesh axis are not built yet")
        if not (0 <= p.first_held
                and p.first_held + p.experts_held <= p.experts_total
                and p.zero_experts >= 0
                and 1 <= p.top_k <= p.router_width):
            raise ShapeError(
                f"{self.name}: held experts [{p.first_held}, "
                f"{p.first_held + p.experts_held}) and top_k {p.top_k} "
                f"do not fit {p.experts_total} experts"
                + (f" + {p.zero_experts} identity experts"
                   if p.zero_experts else ""))
        if p.scoring not in ("sigmoid", "softmax"):
            raise ShapeError(f"{self.name}: scoring {p.scoring!r} is not "
                             "'sigmoid' or 'softmax'")
        if p.shared_expert_gate and not p.shared_hidden:
            raise ShapeError(f"{self.name}: shared_expert_gate without a "
                             "shared expert")
        return [ParallelTensorShape(ishape.dims, p.dtype)]

    def num_trainable_weights(self) -> int:
        p: RoutedExpertsParams = self.params
        return 5 + (3 if p.shared_hidden else 0) + int(p.shared_expert_gate)

    def make_weight_specs(self, input_shapes):
        (ishape,) = input_shapes
        p: RoutedExpertsParams = self.params
        e = ishape.logical_shape[-1]
        rep = ParallelDim(1, ishape.total_degree, is_replica_dim=True)

        def w(*sizes, dtype=p.dtype):
            return ParallelTensorShape(
                tuple(ParallelDim(s) for s in sizes) + (rep,), dtype)

        init, zero = DEFAULT_WEIGHT_INIT, ZeroInitializer()
        n, f = p.experts_held, p.expert_hidden
        specs = [
            WeightSpec("router", w(e, p.router_width), init),
            WeightSpec("router_bias", w(p.router_width), zero),
            WeightSpec("w_gate", w(n, e, f), init),
            WeightSpec("w_up", w(n, e, f), init),
            WeightSpec("w_down", w(n, f, e), init),
        ]
        if p.shared_hidden:
            specs += [
                WeightSpec("shared_gate", w(e, p.shared_hidden), init),
                WeightSpec("shared_up", w(e, p.shared_hidden), init),
                WeightSpec("shared_down", w(p.shared_hidden, e), init),
            ]
        if p.shared_expert_gate:
            specs.append(WeightSpec("shared_expert_gate", w(e), init))
        specs.append(WeightSpec(
            "moe_stats", w(len(MOE_STATS), dtype=DataType.INT32), zero))
        if self.product_plan() == "grouped":
            # the rows multiplied; 1 where the step overflowed
            specs.append(WeightSpec(
                "moe_rows_computed", w(2, dtype=DataType.INT32), zero))
        if p.zero_experts:
            specs.append(WeightSpec(
                "moe_zero", w(len(MOE_ZERO_STATS), dtype=DataType.INT32),
                zero))
        return specs

    def forward(self, inputs, weights, *, training=False, rng=None,
                count_rows=None):
        """`count_rows` (bool, the input's shape less its last axis;
        None: every row) says which rows of the step are real tokens:
        `moe_stats` and `moe_zero` count those alone.  The OUTPUT is
        every row's whatever it says: a pad row multiplies like any
        other (the dense product's cost), only nobody counts it."""
        (x,) = inputs
        p: RoutedExpertsParams = self.params
        router, bias, w_gate, w_up, w_down = weights[:5]
        real_rows = (None if count_rows is None
                     else count_rows.reshape(-1))  # [t]
        with scope("route"):
            h = x.reshape(-1, x.shape[-1])
            chosen, w = route(h, router, bias, p)
        with scope("dispatch"):
            # [t, k, held]: which held expert each chosen pair landed
            # on; a pair for an expert that lives elsewhere, or for an
            # identity expert, is all zeros
            at = chosen - p.first_held
            landed = jax.nn.one_hot(at, p.experts_held, dtype=jnp.float32)
            combine = jnp.einsum("tkx,tk->tx", landed, w)
        grouped = self.product_plan() == "grouped"
        if grouped:
            with scope("dispatch"):
                landed_on = jnp.where((at >= 0) & (at < p.experts_held),
                                      at, p.experts_held)
            out, grouped_counts = grouped_experts(
                h, landed_on, w, w_gate, w_up, w_down,
                h.shape[0] * p.top_k * p.experts_held / p.router_width)
        else:
            out = dense_experts(h, combine, w_gate, w_up, w_down)
        if p.shared_hidden:
            with scope("shared"):
                if p.shared_expert_gate:
                    g = jax.nn.sigmoid(jnp.einsum(
                        "te,e->t", h, weights[8],
                        preferred_element_type=jnp.float32))
                    shared = gated_mlp(h, *weights[5:8]) * g[
                        :, None].astype(out.dtype)
                else:
                    shared = gated_mlp(h, *weights[5:8])
            with scope("combine"):
                out = out + shared
        if p.zero_experts:
            with scope("zero"):
                # the identity experts' term: each row times the sum of
                # its identity picks' weights, on the chip that holds it
                is_zero = chosen >= p.experts_total
                w_zero = jnp.sum(jnp.where(is_zero, w, 0.0), axis=-1)
                out = out + (h.astype(jnp.float32)
                             * w_zero[:, None]).astype(out.dtype)
                real = p.top_k - jnp.sum(is_zero, axis=-1, dtype=jnp.int32)
                if real_rows is None:
                    zero_stats = jnp.stack([
                        h.shape[0] * p.top_k - jnp.sum(real),
                        jnp.min(real), jnp.max(real)])
                else:
                    zero_stats = jnp.stack([
                        jnp.sum(jnp.where(real_rows, p.top_k - real, 0)),
                        jnp.min(jnp.where(real_rows, real, p.top_k)),
                        jnp.max(jnp.where(real_rows, real, 0))])
        with scope("dispatch"):  # the counts of it
            counted, combined = landed, combine != 0
            if real_rows is not None:
                counted = jnp.where(real_rows[:, None, None], landed, 0.0)
                combined = combined & real_rows[:, None]
            rows = jnp.sum(counted, axis=(0, 1)).astype(jnp.int32)  # [held]
            pairs = jnp.sum(rows)
            stats = jnp.stack([
                pairs,
                pairs - jnp.sum(combined).astype(jnp.int32),
                jnp.max(rows),
                jnp.sum(rows > 0).astype(jnp.int32),
            ])
        with scope("combine"):
            out = out.reshape(x.shape).astype(x.dtype)
        return ([out, stats] + ([grouped_counts] if grouped else [])
                + ([zero_stats] if p.zero_experts else []))

    def flops(self):
        """The router's product over its whole width (either scoring
        rule is a few operations a logit on top), the experts' product
        as the chosen one multiplies it (dense: every held expert over
        every row; grouped: the pairs that land on held experts, in
        expectation: an identity pick lands on none and counts
        nothing), the shared expert and its gate's dot product."""
        p: RoutedExpertsParams = self.params
        t = self.inputs[0].shape.num_elements()  # rows x e
        experts = (p.top_k * p.experts_held / p.router_width
                   if self.product_plan() == "grouped" else p.experts_held)
        return t * (2.0 * p.router_width
                    + 6.0 * experts * p.expert_hidden
                    + 6.0 * p.shared_hidden
                    + 2.0 * int(p.shared_expert_gate))
