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
  of rows (`grouped_matmul`: one ragged product a weight, whose groups
  are the runs) and the results go back to their tokens weighted by the
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
[2]: padding of the product's row tiles included; 1 where the layer
took the every-pair size); the dense product's rows are the static
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


def grouped_matmul(lhs, rhs, sizes):
    """lhs [m, k] whose rows run expert after expert, `sizes[g]` rows
    for expert g (their sum may stay below m); rhs [g, k, n] -> [m, n].
    Rows of lhs past the last group are not read.  Rows of the RESULT
    past the last group are NOT DEFINED: the CPU's lowering gives
    zeros, libtpu's kernel leaves them unwritten, whatever the buffer
    held before, NaN included (measured on the v5e, PR 39).  Nothing may
    read them but another grouped product, or a select that drops
    them."""
    return jax.lax.ragged_dot(lhs, rhs, sizes)


def grouped_matmul_into_lhs(ct, rhs, sizes):
    """`grouped_matmul`'s gradient into its lhs: ct [m, n], rhs
    [g, k, n] -> [m, k], each run of rows times its own expert's weight
    transposed (a copy of the weight a call: libtpu's kernel contracts
    rhs' middle axis only).  Its rows past the last group: as
    `grouped_matmul`'s."""
    return grouped_matmul(ct, jnp.swapaxes(rhs, 1, 2), sizes)


def grouped_matmul_into_rhs(lhs, ct, sizes):
    """`grouped_matmul`'s gradient into its rhs: lhs [m, k], ct [m, n]
    -> [g, k, n], each run of rows contracted into its own expert's
    slice.  Rows past the last group are not read and enter no slice,
    on either backend."""
    return jax.lax.ragged_dot_general(
        lhs, ct, sizes, jax.lax.RaggedDotDimensionNumbers(
            dot_dimension_numbers=(((0,), (0,)), ((), ())),
            lhs_ragged_dimensions=[0], rhs_group_dimensions=[]))


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


def _slot_products(m, h, order, slot_of, sizes, w_gate, w_up, w_down):
    """The experts over the first m slots -> gate, up [m, f] and ys
    [m, e], the three products.  No mask: a slot past the held runs
    gathers some token's row, which no group reads, and what the
    products leave in such a slot (`grouped_matmul`) only a grouped
    product or `_sum_of_slots`' select meets."""
    with scope("dispatch"):
        xs = h[order[:m] // slot_of.shape[1]]
    with scope("products"):
        gate = grouped_matmul(xs, w_gate, sizes)
        up = grouped_matmul(xs, w_up, sizes)
        ys = grouped_matmul(_activation(gate, up), w_down, sizes)
    return gate, up, ys


def _held(slot_of, sizes):
    """[t, k]: the pairs on held experts, whose runs fill the first
    slots."""
    return slot_of < jnp.sum(sizes)


def _slots_forward(m, h, order, slot_of, sizes, weights,
                   w_gate, w_up, w_down):
    """The layer over the first m slots, which hold every held pair
    -> (out [t, e], gate, up, ys)."""
    products = _slot_products(m, h, order, slot_of, sizes,
                              w_gate, w_up, w_down)
    with scope("combine"):
        return (_sum_of_slots(products[-1], slot_of, _held(slot_of, sizes),
                              weights), *products)


def _slots_backward(m, h, order, slot_of, sizes, weights, w_gate, w_up,
                    w_down, gate, up, ys, d_out):
    """`_slots_forward`'s gradient into (h, weights, w_gate, w_up,
    w_down) from its products.  The routing weight of a slot past the
    held runs is zero, so the gradient that enters the slots is zero
    there: the mask of the backward pass is the multiply that makes
    `d_ys`.  What the products leave there afterwards is dropped where
    the sums select the held pairs."""
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
        d_w_down = grouped_matmul_into_rhs(act, d_ys, sizes)
        d_gate, d_up = d_activation(
            grouped_matmul_into_lhs(d_ys, w_down, sizes))
        d_w_gate = grouped_matmul_into_rhs(xs, d_gate, sizes)
        d_w_up = grouped_matmul_into_rhs(xs, d_up, sizes)
        d_xs = (grouped_matmul_into_lhs(d_gate, w_gate, sizes)
                + grouped_matmul_into_lhs(d_up, w_up, sizes))
    with scope("dispatch"):
        d_h = _sum_of_slots(d_xs, slot_of, held)
    return (d_h, d_weights, d_w_gate.astype(w_gate.dtype),
            d_w_up.astype(w_up.dtype), d_w_down.astype(w_down.dtype))


def _fits(m_usual, slot_of, sizes):
    """None where the usual buffers hold every pair whatever the load,
    else whether they hold this step's."""
    if m_usual == slot_of.size:
        return None
    with scope("dispatch"):
        return jnp.sum(sizes) <= m_usual


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def _grouped(m_usual, h, order, slot_of, sizes, weights,
             w_gate, w_up, w_down):
    """`grouped_experts` after its sort, forward and backward written
    out (`_slots_forward`, `_slots_backward`) at two sizes: the first
    `m_usual` slots or, on a step whose held pairs overflow them, every
    slot.  The choice (`lax.cond`) is made inside either rule, so both
    branches give the same shapes and the backward rule's residuals are
    the usual size's alone."""
    return _grouped_fwd(m_usual, h, order, slot_of, sizes, weights,
                        w_gate, w_up, w_down)[0]


def _grouped_fwd(m_usual, h, order, slot_of, sizes, weights,
                 w_gate, w_up, w_down):
    args = (h, order, slot_of, sizes, weights, w_gate, w_up, w_down)
    fits = _fits(m_usual, slot_of, sizes)
    usual = functools.partial(_slots_forward, m_usual)
    if fits is None:
        out, *products = usual(*args)
    else:
        def overflow(*args):
            # nothing of this size is kept: its backward runs it again
            out, *products = _slots_forward(slot_of.size, *args)
            with scope("dispatch"):
                return (out, *(jnp.zeros((m_usual,) + y.shape[1:], y.dtype)
                               for y in products))

        out, *products = jax.lax.cond(fits, usual, overflow, *args)
    # what a checkpointed segment may hold of this op (the flash
    # kernels name their outputs the same way)
    return out, args + tuple(map(remat_keep, products))


def _grouped_bwd(m_usual, residuals, d_out):
    fits = _fits(m_usual, *residuals[2:4])  # slot_of, sizes
    usual = functools.partial(_slots_backward, m_usual)
    if fits is None:
        grads = usual(*residuals, d_out)
    else:
        def overflow(h, order, slot_of, sizes, weights, w_gate, w_up,
                     w_down, _gate, _up, _ys, d_out):
            m_all = slot_of.size
            products = _slot_products(m_all, h, order, slot_of, sizes,
                                      w_gate, w_up, w_down)
            return _slots_backward(m_all, h, order, slot_of, sizes, weights,
                                   w_gate, w_up, w_down, *products, d_out)

        grads = jax.lax.cond(fits, usual, overflow, *residuals, d_out)
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
    m_usual = min(m_all, -(-max(1, int(GROUPED_SLACK * expected_pairs))
                           // GROUPED_ROW_TILE) * GROUPED_ROW_TILE)
    out = _grouped(m_usual, h, order, slot_of, sizes, weights,
                   w_gate, w_up, w_down)
    with scope("dispatch"):
        tiles = -(-sizes // GROUPED_ROW_TILE)
        return out, jnp.stack([
            jnp.sum(tiles) * GROUPED_ROW_TILE,
            (jnp.sum(sizes) > m_usual).astype(jnp.int32)])


class RoutedExperts(Op):
    op_type = OperatorType.ROUTED_EXPERTS
    float32_weights = ("router", "router_bias")
    has_aux_state = True  # weights[num_trainable_weights():] are state
    #: the state entries are counters the forward pass only writes:
    #: a segment that holds this op may be rematerialised
    #: (`GraphExecutor._build_remat_plan`)
    state_is_counters = True

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

    def forward(self, inputs, weights, *, training=False, rng=None):
        (x,) = inputs
        p: RoutedExpertsParams = self.params
        router, bias, w_gate, w_up, w_down = weights[:5]
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
                zero_stats = jnp.stack([
                    h.shape[0] * p.top_k - jnp.sum(real),
                    jnp.min(real), jnp.max(real)])
        with scope("dispatch"):  # the counts of it
            rows = jnp.sum(landed, axis=(0, 1)).astype(jnp.int32)  # [held]
            pairs = jnp.sum(rows)
            stats = jnp.stack([
                pairs,
                pairs - jnp.sum(combine != 0).astype(jnp.int32),
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
